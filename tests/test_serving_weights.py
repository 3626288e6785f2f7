"""The dense family's load-time function (`gpt.serving_params`, the
family's `ServingFamily.load`) and the engine's one hook for it: f32
masters in, the tree the compiled steps read out, cast once where the
steps would cast at every use, its q, k and v projections side by side in
one leaf (`wqkv`); at construction and on every swap, on the target and
on a draft model; and nothing run, nothing copied, where a tree is
already fused and in its dtype or the family has no such function."""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.parallel import MeshSpec
from ray_tpu.parallel.sharding import tree_shardings
from ray_tpu.serve.engine import InferenceEngine

BF16, F32, I8 = (jnp.dtype(t) for t in ("bfloat16", "float32", "int8"))


def tiny_cfg(**kw):
    return gpt.GPTConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=64, dtype="bfloat16"), **kw})


@dataclasses.dataclass(frozen=True)
class CastAtUse(gpt.GPTConfig):
    """The same model through a family with no load-time function: the
    engine hands `prefill`, `decode` and `verify` the masters themselves
    and every step casts them at use, as every engine did before."""

    @property
    def family(self):
        return gpt.FAMILY._replace(load=None)


def cast_at_use(cfg):
    return CastAtUse(**dataclasses.asdict(cfg))


def masters(cfg, seed=0):
    return gpt.init_params(jax.random.PRNGKey(seed), cfg)


def make_engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("block_size", 8)
    return InferenceEngine(params, cfg, **kw)


def fused(params, dtype=None):
    """`params` with `wq`, `wk` and `wv` (and their scales) side by side
    in `wqkv` [L, D, 3, H * Dh] (`wqkv_scale` [L, 3, H * Dh]), written
    out here; every other leaf as it is, or cast to `dtype`."""
    if dtype is not None:
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    layers = dict(params["layers"])
    for suffix in ("", "_scale"):
        if "wq" + suffix in layers:
            layers["wqkv" + suffix] = jnp.stack(
                [layers.pop(name + suffix) for name in ("wq", "wk", "wv")],
                axis=-2)
    return {**params, "layers": layers}


def nbytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def dtypes(tree):
    return {leaf.dtype for leaf in jax.tree.leaves(tree)}


def same_buffers(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(x is y for x, y in zip(la, lb))


def streams(eng, prompts, new_tokens=10):
    """Every prompt in flight at once; -> [(tokens, logprobs)] in order."""
    rids = [eng.submit(p, max_new_tokens=new_tokens) for p in prompts]
    eng.run_until_idle()
    out = [list(eng.tokens_for(rid)) for rid in rids]
    eng.check_invariants()
    return [([int(e) for e in evs], [e.logprob for e in evs])
            for evs in out]


def prompts_for(cfg, seed=3):
    rng = np.random.default_rng(seed)
    motif = rng.integers(1, cfg.vocab_size, 4)
    return [np.tile(motif, 5).astype(np.int32),          # two chunks
            rng.integers(1, cfg.vocab_size, 7).astype(np.int32),
            rng.integers(1, cfg.vocab_size, 13).astype(np.int32)]


# ---------------------------------------------------------------------------
# gpt.serving_params
# ---------------------------------------------------------------------------

class TestServingParams:
    def test_every_leaf_takes_the_dtype_a_step_casts_it_to(self):
        """`wqkv` is the three masters' casts side by side; every other
        leaf its master's cast."""
        cfg = tiny_cfg()
        params = masters(cfg)
        served = jax.jit(lambda p: gpt.serving_params(p, cfg))(params)
        want = fused(params)
        assert jax.tree.structure(served) == jax.tree.structure(want)
        assert not {"wq", "wk", "wv"} & set(served["layers"])
        assert served["layers"]["wqkv"].shape == (
            cfg.n_layers, cfg.d_model, 3, cfg.n_heads * cfg.head_dim)
        assert dtypes(params) == {F32} and dtypes(served) == {BF16}
        for got, master in zip(jax.tree.leaves(served),
                               jax.tree.leaves(want)):
            np.testing.assert_array_equal(
                np.asarray(got.astype(F32)),
                np.asarray(master.astype(BF16).astype(F32)))

    def test_int8_keeps_its_scales_f32_and_casts_the_rest(self):
        """`wqkv` / `wqkv_scale` are `quantize_params`' three, side by
        side, bit for bit: a scale an output channel, as before."""
        cfg = tiny_cfg(weight_dtype="int8")
        params = masters(cfg)
        served = gpt.serving_params(params, cfg)
        quantized = fused(gpt.quantize_params(params))
        names = [n for n in gpt.QUANTIZED_WEIGHTS if n not in gpt.QKV]
        assert len(names) == 4 and set(gpt.QKV) < set(gpt.QUANTIZED_WEIGHTS)
        for name in (*names, "wqkv"):
            assert served["layers"][name].dtype == I8
            assert served["layers"][name + "_scale"].dtype == F32
            for leaf in (name, name + "_scale"):
                np.testing.assert_array_equal(
                    np.asarray(served["layers"][leaf]),
                    np.asarray(quantized["layers"][leaf]))
        assert served["layers"]["wqkv_scale"].shape == (
            cfg.n_layers, 3, cfg.n_heads * cfg.head_dim)
        assert set(served["layers"]) == set(quantized["layers"])
        for name in ("embed", "pos_embed", "final_ln_scale"):
            assert served[name].dtype == BF16
        for name in ("ln1_scale", "ln2_scale"):
            assert served["layers"][name].dtype == BF16

    @pytest.mark.parametrize("dtype,tree_dtype", [("float32", F32),
                                                  ("bfloat16", BF16),
                                                  ("int8", I8)])
    def test_a_tree_in_its_dtype_comes_back_leaf_for_leaf(self, dtype,
                                                          tree_dtype):
        """A tree that already holds `wqkv` is a served tree: the very
        leaves come back, the int8 tree's payloads and scales too."""
        if dtype == "int8":
            cfg = tiny_cfg(weight_dtype="int8")
            params = gpt.serving_params(masters(cfg), cfg)
            assert tree_dtype in dtypes(params)
        else:
            cfg = tiny_cfg(dtype=dtype)
            params = fused(masters(cfg), tree_dtype)
        assert same_buffers(gpt.serving_params(params, cfg), params)

    def test_an_unfused_tree_in_its_dtype_is_fused_and_no_more(self):
        """Masters published in the activation dtype: the three
        projections are laid side by side and every other leaf is the
        caller's own buffer."""
        cfg = tiny_cfg()
        params = jax.tree.map(lambda a: a.astype(BF16), masters(cfg))
        served = gpt.serving_params(params, cfg)
        want = fused(params)
        assert jax.tree.structure(served) == jax.tree.structure(want)
        for name, leaf in served["layers"].items():
            if name == "wqkv":
                np.testing.assert_array_equal(
                    np.asarray(leaf.astype(F32)),
                    np.asarray(want["layers"][name].astype(F32)))
            else:
                assert leaf is params["layers"][name]
        assert served["embed"] is params["embed"]
        assert nbytes(served) == nbytes(params)

    @pytest.mark.parametrize("weight_dtype", ["f32", "int8"])
    def test_one_projection_gives_what_the_three_gave(self, weight_dtype):
        """`gpt.layer` over a served layer and over the same numbers held
        apart: q, k and v reach attention equal bit for bit (one dot
        over columns side by side accumulates each column as its own dot
        did), so the layer's output is equal too."""
        cfg = tiny_cfg(weight_dtype=weight_dtype)
        served = gpt.serving_params(masters(cfg), cfg)
        lp = jax.tree.map(lambda a: a[1], served["layers"])
        apart = {k: v for k, v in lp.items() if not k.startswith("wqkv")}
        for i, name in enumerate(gpt.QKV):
            apart[name] = lp["wqkv"][:, i]
            if weight_dtype == "int8":
                apart[name + "_scale"] = lp["wqkv_scale"][i]
        x = jax.random.normal(jax.random.PRNGKey(7), (3, 5, cfg.d_model),
                              BF16)

        def run(lp):
            seen = []

            def attend(q, k, v):
                seen.extend((q, k, v))
                return q + k * v, None
            out, _, _ = gpt.layer(x, lp, cfg, BF16, attend)
            return [np.asarray(a.astype(F32)) for a in (*seen, out)]

        got, want = run(lp), run(apart)
        assert got[0].shape == (3, 5, cfg.n_heads, cfg.head_dim)
        assert np.abs(got[0]).max() > 0
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_training_reads_the_masters_as_it_did(self):
        """`forward` over the masters and over the served tree: the same
        logits bit for bit on the CPU (the operands of every matmul are
        the same numbers, and a column of the one projection accumulates
        as its own dot did), so the cast and the leaf moved and nothing
        else did."""
        cfg = tiny_cfg()
        params = masters(cfg)
        tokens = jnp.asarray(prompts_for(cfg)[0][None])
        want = gpt.forward(params, tokens, cfg)
        got = gpt.forward(gpt.serving_params(params, cfg), tokens, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the engine's hook
# ---------------------------------------------------------------------------

class TestEngineLoadsOnce:
    @pytest.mark.parametrize("ekw", [
        {}, dict(spec="ngram", spec_k=3),
        dict(kv_dtype="int8"), dict(weight_dtype="int8")],
        ids=["decode", "verify", "int8-pool", "int8-weights"])
    def test_streams_what_cast_at_use_streams(self, ekw):
        """Tokens and logprobs of an engine built from f32 masters,
        against the family's own prefill / decode / verify called with
        those masters (an engine whose family has no load-time
        function): equal, bit for bit, chunked prefill, a batch of
        streams and speculative verify included."""
        ekw = dict(ekw)
        cfg = tiny_cfg(**{k: ekw.pop(k) for k in ("kv_dtype", "weight_dtype")
                          if k in ekw})
        params = masters(cfg)
        if cfg.weight_dtype == "int8":      # cast at use: quantized here
            reference = make_engine(cast_at_use(cfg),
                                    gpt.quantize_params(params), **ekw)
        else:
            reference = make_engine(cast_at_use(cfg), params, **ekw)
        assert reference.load_traces == 0
        eng = make_engine(cfg, params, **ekw)
        assert eng.load_traces == 1
        want = streams(reference, prompts_for(cfg))
        got = streams(eng, prompts_for(cfg))
        assert got == want
        assert F32 in dtypes(reference.params)
        assert eng.stats()["decode_traces"] <= 1
        assert eng.stats()["retraces_unexpected"] == 0

    def test_a_swap_takes_f32_masters_and_retraces_nothing(self):
        cfg = tiny_cfg()
        eng = make_engine(cfg, masters(cfg, 0))
        prompts = prompts_for(cfg)
        before = streams(eng, prompts)
        eng.arm_retrace_sentinel()
        published = masters(cfg, 1)
        assert eng.update_params(published) == 1
        after = streams(eng, prompts)
        assert after != before
        assert after == streams(make_engine(cfg, masters(cfg, 1)), prompts)
        s = eng.stats()
        assert s["load_traces"] == 1 and s["decode_traces"] == 1
        assert s["swap_traces"] == 1 and s["retraces_unexpected"] == 0
        assert dtypes(eng.params) == {BF16}
        assert dtypes(published) == {F32}       # the trainer's, untouched
        jax.block_until_ready(published)

    @pytest.mark.parametrize("case", ["float32", "bf16-tree", "no-hook"])
    def test_a_tree_in_its_dtype_is_handed_through(self, case):
        """Nothing runs and nothing is copied: the engine holds the very
        buffers it was given (a `jit` would copy them: 8.4 GB beside
        `brumby-14b`'s 14.4 of 16). For the dense family that is a tree
        with its projections already side by side."""
        cfg = tiny_cfg(dtype="float32" if case == "float32" else "bfloat16")
        params = masters(cfg)
        if case == "bf16-tree":
            params = fused(params, BF16)
        elif case == "float32":
            params = fused(params)
        if case == "no-hook":
            cfg = cast_at_use(cfg)
        eng = make_engine(cfg, params, spec="draft", spec_k=2,
                          draft_params=params, draft_cfg=cfg)
        assert same_buffers(eng.params, params)
        assert same_buffers(eng.draft_params, params)
        assert eng.stats()["load_traces"] == 0
        assert eng.stats()["weight_bytes"] == 2 * nbytes(params)
        eng.generate(prompts_for(cfg)[1], max_new_tokens=4)
        eng.update_params(params, draft_params=params)    # placed as it is
        assert eng.stats()["load_traces"] == 0

    def test_an_unfused_tree_in_its_dtype_is_fused_once(self):
        """Masters published in bf16 with `wq`, `wk` and `wv` apart: the
        load-time function runs, once, at construction and on a swap
        alike, and the tree holds the same bytes; its streams are the
        f32 masters' engine's."""
        cfg = tiny_cfg()
        params = jax.tree.map(lambda a: a.astype(BF16), masters(cfg))
        eng = make_engine(cfg, params)
        assert eng.stats()["load_traces"] == 1
        assert "wqkv" in eng.params["layers"]
        assert not set(gpt.QKV) & set(eng.params["layers"])
        assert eng.stats()["weight_bytes"] == nbytes(params)
        prompts = prompts_for(cfg)
        assert streams(eng, prompts) == streams(
            make_engine(cfg, masters(cfg)), prompts)
        eng.arm_retrace_sentinel()
        eng.update_params(params)
        streams(eng, prompts)
        s = eng.stats()
        assert s["load_traces"] == 1 and s["retraces_unexpected"] == 0

    def test_a_tree_of_another_structure_is_refused_before_the_load(self):
        """The load-time function reads leaves by name, so a swap checks
        the published tree against the one the engine was built from
        before it runs: a dict with other keys, and the served tree
        itself, are the caller's error, nothing is traced and no swap
        counts."""
        cfg = tiny_cfg()
        eng = make_engine(cfg, masters(cfg))
        served = jax.tree.map(jnp.copy, eng.params)
        for tree in ({"nope": jnp.zeros(())}, served):
            with pytest.raises(ValueError, match="structure"):
                eng.update_params(tree)
        s = eng.stats()
        assert s["load_traces"] == 1 and s["swaps"] == 0
        assert eng.update_params(masters(cfg, 1)) == 1

    def test_weight_bytes_halve_against_the_masters(self):
        cfg = tiny_cfg()
        params = masters(cfg)
        assert make_engine(cfg, params).stats()["weight_bytes"] \
            == nbytes(params) // 2
        assert make_engine(tiny_cfg(dtype="float32"), params).stats()[
            "weight_bytes"] == nbytes(params)

    def test_the_engine_keeps_no_reference_to_the_masters(self):
        cfg = tiny_cfg()
        params = masters(cfg)
        eng = make_engine(cfg, params)
        refs = [weakref.ref(leaf) for leaf in jax.tree.leaves(params)]
        del params
        gc.collect()
        assert all(ref() is None for ref in refs)
        eng.generate(prompts_for(cfg)[1], max_new_tokens=3)

    def test_the_int8_engines_other_leaves_are_cast_too(self):
        cfg = tiny_cfg(weight_dtype="int8")
        params = masters(cfg)
        eng = make_engine(cfg, params)
        layers = eng.params["layers"]
        for name in set(gpt.QUANTIZED_WEIGHTS) - set(gpt.QKV) | {"wqkv"}:
            assert layers[name].dtype == I8
            assert layers[name + "_scale"].dtype == F32
        others = [eng.params["embed"], eng.params["pos_embed"],
                  eng.params["final_ln_scale"], layers["ln1_scale"],
                  layers["ln2_scale"]]
        assert {leaf.dtype for leaf in others} == {BF16}
        assert eng.stats()["weight_bytes"] < nbytes(params) // 2
        eng.update_params(params)
        assert eng.stats()["load_traces"] == 1

    def test_a_draft_model_goes_through_the_same_function(self):
        cfg = tiny_cfg()
        dcfg = tiny_cfg(n_layers=1, weight_dtype="int8")
        params, dparams = masters(cfg), masters(dcfg, 5)
        eng = make_engine(cfg, params, spec="draft", spec_k=3,
                          draft_params=dparams, draft_cfg=dcfg)
        assert dtypes(eng.params) == {BF16}
        assert dtypes(eng.draft_params) == {BF16, I8, F32}
        assert eng.draft_params["embed"].dtype == BF16
        assert eng.stats()["load_traces"] == 2
        assert eng.stats()["weight_bytes"] \
            == nbytes(eng.params) + nbytes(eng.draft_params)
        prompts = prompts_for(cfg)
        want = streams(make_engine(cfg, params), prompts)
        assert [t for t, _ in streams(eng, prompts)] == [t for t, _ in want]
        eng.update_params(masters(cfg, 1), draft_params=masters(dcfg, 6))
        eng.update_params(masters(cfg, 2))
        streams(eng, prompts)
        s = eng.stats()
        assert s["load_traces"] == 2 and s["verify_traces"] == 1
        assert s["draft_traces"] == 1 and s["retraces_unexpected"] == 0

    def test_a_sharded_tree_keeps_each_leafs_sharding(self):
        """On a mesh the cast runs on placed arrays: every leaf of the
        tree the steps read lies as its master lay, at construction and
        after a swap; `wqkv` keeps the heads on its last axis, so a
        shard holds its own heads' q, k and v columns."""
        cfg = tiny_cfg()
        mesh = MeshSpec(data=1, tensor=2).build(jax.devices()[:2])
        axes = gpt.param_logical_axes(cfg)
        shardings = tree_shardings(mesh, axes)
        params = jax.device_put(masters(cfg), shardings)
        layers = {name: ax for name, ax in axes["layers"].items()
                  if name not in gpt.QKV}
        layers["wqkv"] = (None, "embed", None, "heads")
        assert axes["layers"]["wq"] == (None, "embed", "heads")
        served = tree_shardings(mesh, {**axes, "layers": layers})
        assert not served["layers"]["wqkv"].is_fully_replicated
        eng = make_engine(cfg, params, mesh=mesh)
        for _ in range(2):
            assert dtypes(eng.params) == {BF16}
            assert jax.tree.structure(eng.params) == jax.tree.structure(
                served)
            for leaf, want in zip(jax.tree.leaves(eng.params),
                                  jax.tree.leaves(served)):
                assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
            eng.update_params(jax.device_put(masters(cfg, 1), shardings))
        assert any(not leaf.sharding.is_fully_replicated
                   for leaf in jax.tree.leaves(eng.params))
        assert eng.stats()["load_traces"] == 1
