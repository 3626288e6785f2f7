"""The window / full layer family on the training path
(`models/window_moe_train.py`, `train.spmd.make_window_moe_trainer`: the
banded and the grouped-head flash kernels, the expert kernels' backward
pass, a chunk of tokens at a time) against its plain reference
(`benchmarks/refs/window_moe_train.py`) at a tiny size on the CPU, seeded
random weights, float32: logits, losses position by position, gradients
leaf by leaf, YaRN's frequencies against the published formula, the four
chips' shares of a layer against the uncut layer, and what each of the
benchmark's wrong programs moves."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import window_moe_train as ref
from ray_tpu.models import window_moe_train as wmt
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import loop, spmd

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
PLAIN = {"rope_type": "default", "rope_theta": 500000}
# `mellum`'s keys at a tiny size: a window of 40 in sequences of 128 (its
# edge inside a 128-wide tile), 8 query heads over 2, YaRN stretched from
# an original 32 positions so that its ramp lies inside the 8 rotary
# pairs, 4 of the router's 16 experts held (experts 4-7), 4 a token
TINY = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=8,
    num_key_value_heads=2, head_dim=16, sliding_window=40,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    rope_parameters={
        "full_attention": {**YARN, "rope_theta": 10000, "factor": 4,
                           "original_max_position_embeddings": 32},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    moe_intermediate_size=32, num_experts=4, published={"num_experts": 16},
    num_experts_per_tok=4, experts_held_from=4, norm_topk_prob=True,
    rms_norm_eps=1e-6, max_position_embeddings=128, layers_from=0,
    vocab_size=512)
TOL = 2e-4      # float32 both sides


def config(**over):
    return wmt.from_published(**{**TINY, **{
        k: over.pop(k) for k in list(over) if k in TINY}},
        dtype="float32", flash_block_q=128, flash_block_kv=128,
        expert_chunk=64, **over)


def batch_of(b=2, t=128, seed=0):
    toks = np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (b, t + 1)).astype(np.int32)
    return {"inputs": toks[:, :-1], "targets": toks[:, 1:]}


@pytest.fixture(scope="module")
def params():
    return wmt.init_params(jax.random.key(0), config())


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def test_the_tree_is_the_reference_s(params):
    lp = params["layers"][0]
    assert sorted(lp) == sorted([
        "attn_norm_scale", "ffn_norm_scale", "w_q", "w_k", "w_v", "w_out",
        "router", "we_gate", "we_up", "we_down"])
    assert lp["w_q"].shape == (64, 8 * 16)
    assert lp["w_k"].shape == lp["w_v"].shape == (64, 2 * 16)
    assert lp["router"].shape == (64, 16)
    assert lp["we_gate"].shape == (4, 32, 64)
    assert params["head"].shape == params["embed"].shape == (512, 64)
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(params))
    cfg = config()
    assert cfg.kinds == ("window", "window", "window", "full")
    assert (cfg.router_width, cfg.held_from, cfg.held_count) == (16, 4, 4)
    assert cfg.rope_window == wmt.RotarySpec(theta=10000.0)
    assert cfg.rope_full.factor == 4 and cfg.rope_full.original == 32


def test_training_forward_matches_the_reference_s_logits(params):
    cfg = config()
    tokens = batch_of()["inputs"]
    x, counts = jax.jit(
        lambda p, t: wmt.forward_features(p, t, cfg))(params, tokens)
    got = jnp.einsum("btd,vd->btv", x, params["head"])
    want = ref.logits(params, tokens, TINY)
    np.testing.assert_allclose(got, want, rtol=0, atol=TOL)
    np.testing.assert_allclose(wmt.forward(params, tokens, cfg), want,
                               rtol=0, atol=TOL)
    assert counts.shape == (4, 2 + 16)
    assert np.all(np.asarray(counts[:, 1]) == 2 * 128 * 4)
    assert np.all(np.asarray(counts[:, 2:]).sum(1) == 2 * 128 * 4)
    assert np.all(np.asarray(counts[:, 0])
                  == np.asarray(counts[:, 2 + 4:2 + 8]).sum(1))


def test_losses_match_the_reference_s_position_by_position(params):
    cfg = config()
    batch = batch_of(seed=2)
    want = ref.token_losses(params, batch["inputs"], batch["targets"], TINY)
    masks = jnp.eye(2 * 128, dtype=jnp.float32).reshape(-1, 2, 128)
    got = jax.jit(jax.vmap(lambda m: spmd.window_moe_loss_fn(
        params, {**batch, "mask": m}, cfg)))(masks)
    np.testing.assert_allclose(np.asarray(got).reshape(2, 128), want,
                               rtol=0, atol=TOL)


@pytest.mark.parametrize("sparse_impl", ["jax", "pallas"])
def test_gradients_match_the_reference_s_leaf_by_leaf(params, sparse_impl):
    """On the plain path of the routed experts and through their three
    kernels (interpret mode), under the banded and the full flash
    kernels, in the whole model."""
    cfg = config(sparse_impl=sparse_impl)
    batch = batch_of(seed=1)
    loss, got = jax.jit(jax.value_and_grad(
        lambda p: spmd.window_moe_loss_fn(p, batch, cfg)))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, batch["inputs"], batch["targets"], TINY)))(
            params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    flat = dict(jax.tree_util.tree_flatten_with_path(got)[0])
    seen = set()
    for path, w in jax.tree_util.tree_flatten_with_path(want)[0]:
        name = jax.tree_util.keystr(path)
        scale = max(float(jnp.max(jnp.abs(w))), 1e-3)
        np.testing.assert_allclose(flat[path], w, rtol=0, atol=2e-3 * scale,
                                   err_msg=name)
        seen.add(name.rsplit("'", 2)[-2])
    assert seen == {"embed", "head", "final_norm_scale", "attn_norm_scale",
                    "ffn_norm_scale", "w_q", "w_k", "w_v", "w_out", "router",
                    "we_gate", "we_up", "we_down"}


def test_yarn_s_frequencies_are_the_published_formula_s():
    """`rope_parameters.full_attention` of the published file: the
    correction range, the first and the last frequency, and every one
    against the formula written out."""
    spec = wmt.RotarySpec.from_published(YARN)
    low, high, ramp = wmt.yarn_ramp(spec, 128)

    def corr(r):
        return 128 * math.log(8192 / (2 * math.pi * r)) / (
            2 * math.log(500000))

    assert (low, high) == (math.floor(corr(32)), math.ceil(corr(1)))
    assert (low, high) == (18, 35)
    got = wmt.inv_freq(spec, 128)
    assert got.shape == (64,) and got[0] == 1.0
    assert got[63] == pytest.approx(500000 ** (-126 / 128) / 16, rel=1e-12)
    for i in range(64):
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        plain = 500000 ** (-2 * i / 128)
        assert ramp[i] == pytest.approx(r)
        assert got[i] == pytest.approx((1 - r) * plain + r * plain / 16,
                                       rel=1e-12)
    np.testing.assert_allclose(ref.inv_freq(YARN, 128), got, rtol=1e-6)
    # factor 1 is plain rotary, and so is a window layer's entry
    np.testing.assert_allclose(
        wmt.inv_freq(spec._replace(factor=1.0), 128),
        wmt.inv_freq(wmt.RotarySpec.from_published(PLAIN), 128), rtol=1e-15)
    np.testing.assert_allclose(ref.inv_freq(PLAIN, 128),
                               wmt.inv_freq(wmt.RotarySpec(500000.0), 128),
                               rtol=1e-6)


def test_rotary_turns_split_halves_by_the_factor():
    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 16))
    pos = jnp.arange(8)
    spec = config().rope_full
    got = wmt.rotary(x, pos, spec)
    np.testing.assert_allclose(
        got[0], ref.rope(x[0], pos, TINY["rope_parameters"][
            "full_attention"]), atol=1e-6)
    # position 0 is the factor alone; a pair's norm is kept but for it
    np.testing.assert_allclose(got[0, 0], x[0, 0] * spec.attention_factor,
                               atol=1e-6)
    np.testing.assert_allclose(
        jnp.linalg.norm(got, axis=-1),
        jnp.linalg.norm(x, axis=-1) * spec.attention_factor, rtol=1e-5)


def test_four_shares_add_up_to_the_uncut_layer(params):
    """The guide's share test: four chips each hold four experts of a
    16-wide router; their routed parts add up to the uncut reference's,
    and what every chip computes alike (the router, the choice, the
    counts over the whole width) is the same on each and counted once."""
    lp = params["layers"][0]
    m = jax.random.normal(jax.random.key(3), (128, 64), jnp.float32)
    whole = {k: jnp.concatenate([lp[k]] * 4) if k.startswith("we_") else lp[k]
             for k in lp}
    whole = {**whole, **{k: jax.random.normal(
        jax.random.key(i), whole[k].shape) * 0.1
        for i, k in enumerate(("we_gate", "we_up", "we_down"))}}
    uncut = ref.routed_part(m, whole, {
        **TINY, "num_experts": 16, "experts_held_from": 0})
    total, every = 0.0, []
    for share in range(4):
        cfg = config(num_experts=4, experts_held_from=4 * share)
        mine = {**whole, **{k: whole[k][4 * share:4 * share + 4]
                            for k in ("we_gate", "we_up", "we_down")}}
        routed, counts = wmt._experts(m, mine, cfg, "experts_grouped")
        np.testing.assert_allclose(routed, ref.routed_part(
            m, mine, {**TINY, "experts_held_from": 4 * share}), atol=TOL)
        total = total + routed
        every.append(np.asarray(counts))
        assert counts[0] == counts[2 + 4 * share:6 + 4 * share].sum()
    np.testing.assert_allclose(total, uncut, atol=TOL)
    assert all(np.array_equal(c[1:], every[0][1:]) for c in every)
    assert sum(int(c[0]) for c in every) == int(every[0][1]) == 128 * 4


def test_a_tied_router_spreads_every_token_evenly_over_the_shares():
    """`router_tied_blocks` 4: a token's 4 experts are its best column's
    copy in each block of the router, so each of the four shares holds
    exactly one of them, whatever the ids; a step parts the copies."""
    cfg = config(router_tied_blocks=4)
    params = wmt.init_params(jax.random.key(5), cfg)
    router = np.asarray(params["layers"][0]["router"])
    assert all(np.array_equal(router[:, :4], router[:, 4 * b:4 * b + 4])
               for b in range(4))
    tokens = batch_of(seed=6)["inputs"]
    _, counts = wmt.forward_features(params, tokens, cfg)
    counts = np.asarray(counts)
    # but for a token whose two best columns score within a rounding
    np.testing.assert_allclose(counts[:, 0] * 4, counts[:, 1], rtol=0.005)
    blocks = counts[:, 2:].reshape(4, 4, 4)
    np.testing.assert_allclose(blocks, np.broadcast_to(
        blocks[:, :1], blocks.shape), atol=2)
    # untied, the share held here is a draw
    _, loose = wmt.forward_features(
        wmt.init_params(jax.random.key(5), config()), tokens, config())
    loose = np.asarray(loose)
    assert np.all(loose[:, 0] * 4 != loose[:, 1])
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    state, step_fn, shard = spmd.make_window_moe_trainer(
        cfg, mesh, rng=jax.random.key(5),
        optimizer=spmd.default_optimizer(warmup_steps=0))
    state, _ = step_fn(state, shard(batch_of(seed=6)))
    router = np.asarray(state.params["layers"][0]["router"])
    assert not np.array_equal(router[:, :4], router[:, 4:8])


def test_a_step_is_dropless_and_every_leaf_is_the_optimizer_s(params):
    cfg = config()
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    state, step_fn, shard = spmd.make_window_moe_trainer(
        cfg, mesh, rng=jax.random.key(0),
        optimizer=spmd.default_optimizer(warmup_steps=0))
    n_params = len(jax.tree.leaves(state.params))
    moments = [a for a in jax.tree.leaves(state.opt_state) if a.ndim]
    assert len(moments) == 2 * n_params
    batch = batch_of(seed=3)
    before = jax.tree.map(np.asarray, state.params)
    _, counts = wmt.forward_features(before, batch["inputs"], cfg)
    state, metrics = step_fn(state, shard(batch))
    held = np.asarray(counts)[:, 2 + 4:2 + 8]
    assert int(metrics["expert_pairs_here"]) == held.sum()
    assert int(metrics["expert_pairs_routed"]) == 4 * 2 * 128 * 4
    assert int(metrics["expert_load_max"]) == held.max()
    assert float(metrics["expert_load_mean"]) == pytest.approx(held.mean())
    moved = jax.tree.map(lambda a, b: bool(np.any(np.asarray(a) != b)),
                         state.params, before)
    assert all(jax.tree.leaves(moved))


def test_the_fused_dispatch_carries_the_counters_into_stats():
    cfg = config()
    mesh = MeshSpec(data=1).build(jax.devices()[:1])
    state, step_fn, _ = spmd.make_window_moe_trainer(
        cfg, mesh, rng=jax.random.key(1),
        optimizer=spmd.default_optimizer(warmup_steps=0))
    train = loop.TrainLoop(step_fn, unroll=2, metrics_interval=2)
    batches = loop.DevicePrefetcher(
        (batch_of(seed=s) for s in range(4)),
        loop.make_placer(mesh, stacked=True), depth=2, group=2)
    state, metrics = train.run(state, batches)
    stats = train.stats()
    assert len(metrics) == 4 and stats["dispatch_traces"] == 1
    assert stats["expert_pairs_routed"] == 4 * 4 * 2 * 128 * 4
    assert stats["expert_pairs_here"] == sum(
        int(m["expert_pairs_here"]) for m in metrics)
    assert stats["expert_load_max"] >= stats["expert_load_mean"] > 0


WRONG = {
    "experts_on_the_float8_grid": dict(expert_round="float8_e4m3fn"),
    "a_window_layer_reads_the_whole_triangle": dict(sliding_window=128),
    "a_band_one_tile_short": dict(sliding_window=24),
    "yarn_left_out": dict(rope_parameters={
        **TINY["rope_parameters"],
        "full_attention": TINY["rope_parameters"]["sliding_attention"]}),
    "one_expert_of_a_token_s_left_out": dict(num_experts_per_tok=3),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_wrong_program_moves_the_losses(params, wrong):
    """Each of the benchmark's five wrong programs, at the tiny size:
    the median over the positions of |its loss - the reference's| is
    many times the sound program's."""
    batch = batch_of(seed=4)
    want = np.asarray(ref.token_losses(
        params, batch["inputs"], batch["targets"], TINY))
    masks = jnp.eye(2 * 128, dtype=jnp.float32).reshape(-1, 2, 128)

    def median_gap(cfg):
        got = jax.jit(jax.vmap(lambda m: spmd.window_moe_loss_fn(
            params, {**batch, "mask": m}, cfg)))(masks)
        return float(np.median(np.abs(
            np.asarray(got).reshape(2, 128) - want)))

    sound = median_gap(config())
    assert sound < 1e-5
    assert median_gap(config(**WRONG[wrong])) > 20 * max(sound, 1e-6)
