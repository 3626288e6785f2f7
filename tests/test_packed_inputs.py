"""A program of the tick takes what the host built for it as one int32
array, transferred once (`serve.engine.pack_rows` / `pack_chunk`, undone
in the jitted wrappers by `unpack_rows` / `unpack_chunk`): the packing
round-trips to the bit, the engine makes one input transfer a program
(`stats()["host_puts"]`), and every stream's tokens and logprobs are what
the four-array path gives. That path (four device arrays and a host
scalar a step, two arrays and four host scalars a chunk, as the engine
ran before) is kept here, in `FourArrayEngine`, as the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.refs import latent_sparse_moe as latent_ref
from benchmarks.refs import retention_decoder as retention_ref
from ray_tpu.models import gpt
from ray_tpu.models import latent_sparse_moe as lsm
from ray_tpu.models import retention
from ray_tpu.parallel import MeshSpec
from ray_tpu.parallel.sharding import engine_io_shardings, tree_shardings
from ray_tpu.serve import engine as engine_mod
from ray_tpu.serve.engine import InferenceEngine
from ray_tpu.util import faults

# 0.0 is greedy's mark; 1e-40 is a denormal, which arithmetic may flush
# to zero and a copy of its bits may not
TEMPS = np.array([0.0, 0.7, 1.0, 1e-40], np.float32)

RETENTION_TINY = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, intermediate_size=128,
    rope_theta=1e6, rms_norm_eps=1e-6, retention_eps=1e-6,
    gate_bias=[4.0, 8.0], max_position_embeddings=128, vocab_size=512)
LATENT_TINY = dict(
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, index_n_heads=16, index_head_dim=16,
    index_topk=12, indexer_types=["full", "shared", "full", "shared"],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse"], layers_from=0,
    intermediate_size=128, moe_intermediate_size=32, n_shared_experts=1,
    published={"n_routed_experts": 8}, num_experts_per_tok=2,
    experts_held_from=2, n_routed_experts=4, routed_scaling_factor=2.5,
    norm_topk_prob=True, rope_theta=8e6, rms_norm_eps=1e-5,
    max_position_embeddings=128, vocab_size=512)


def dense_model():
    cfg = gpt.GPTConfig(vocab_size=512, d_model=32, n_layers=2, n_heads=2,
                        d_ff=64, max_seq_len=128, dtype="float32")
    return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)


def retention_model():
    keys = {k: v for k, v in RETENTION_TINY.items() if k != "gate_bias"}
    cfg = retention.from_published(**keys, dtype="float32",
                                   retention_impl="jax")
    return cfg, retention_ref.init_params(jax.random.key(0),
                                          RETENTION_TINY)


def latent_model():
    cfg = lsm.from_published(**LATENT_TINY, dtype="float32",
                             sparse_impl="jax")
    return cfg, latent_ref.init_params(jax.random.key(0), LATENT_TINY)


MODELS = {"dense": dense_model, "retention": retention_model,
          "latent": latent_model}


@pytest.fixture(scope="module", params=list(MODELS))
def model(request):
    return request.param, *MODELS[request.param]()


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# -- the reference: the four-array path --------------------------------------

class FourArrayEngine(InferenceEngine):
    """The engine with its programs' inputs as they were before the
    packed array: `_dev` leaves the packed array on the host, and each
    program is the old jitted function behind a shim that takes the
    array apart with numpy, puts tokens (or the window), positions,
    tables and temperatures one by one and hands `jit` the rest as host
    scalars. Nothing of `unpack_rows` / `unpack_chunk` runs here, and
    no token stays on the device: a row marked `FROM_STEP` or
    `FROM_CHUNK` gets its value on the host, from the step or the chunk
    read there and then."""

    def __init__(self, params, cfg, **kw):
        super().__init__(params, cfg, **kw)
        fam, mesh = cfg.family, kw.get("mesh")
        slots, blocks, W = self.num_slots, self.max_blocks, self.spec_window
        rep = None if mesh is None else engine_io_shardings(mesh)["inputs"]
        self.separate_puts = 0

        def put(arr):
            self.separate_puts += 1
            return (jnp.asarray(arr) if rep is None
                    else jax.device_put(arr, rep))

        def _sample(logits, temps, key, step):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            k = jax.random.fold_in(key, step)
            safe = jnp.where(temps > 0, temps, 1.0)
            sampled = jax.random.categorical(
                k, logits.astype(jnp.float32) / safe[:, None]
            ).astype(jnp.int32)
            tok = jnp.where(temps > 0, sampled, greedy)
            nat = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            logp = jnp.take_along_axis(nat, tok[:, None], axis=-1)[:, 0]
            return tok, logp

        def _prefill(params, tokens, cache, table, start, length, temp,
                     key, step):
            logits, cache, counts = fam.prefill(
                params, tokens, cache, cfg, mesh, block_table=table,
                start=start, length=length)
            tok, logp = _sample(logits, temp[None], key, step)
            return tok[0], logp[0], cache, counts

        def _decode(params, cache, tokens, pos, tables, temps, key, step):
            logits, cache, counts = fam.decode(
                params, tokens, cache, pos, tables, cfg, mesh)
            tok, logp = _sample(logits, temps, key, step)
            return tok, logp, cache, counts

        def _verify(params, cache, tokens, pos, tables, temps, key, step):
            logits, cache = fam.verify(
                params, tokens, cache, pos, tables, cfg, mesh)
            b, w = tokens.shape
            drafts = tokens[:, 1:]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            k = jax.random.fold_in(key, step)
            safe = jnp.where(temps > 0, temps, 1.0)
            logp = jax.nn.log_softmax(logits / safe[:, None, None], axis=-1)
            p_draft = jnp.exp(jnp.take_along_axis(
                logp[:, :-1], drafts[..., None], axis=-1)[..., 0])
            u = jax.random.uniform(jax.random.fold_in(k, 1), drafts.shape)
            match = jnp.where((temps > 0)[:, None], u < p_draft,
                              drafts == greedy[:, :-1])
            accepted = jnp.sum(
                jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
            res = logp.at[jnp.arange(b)[:, None],
                          jnp.arange(w - 1)[None, :], drafts].set(-1e30)
            corr = jax.random.categorical(
                jax.random.fold_in(k, 2), res, axis=-1).astype(jnp.int32)
            corr = jnp.where((temps > 0)[:, None], corr, greedy)
            drafts_pad = jnp.concatenate(
                [drafts, jnp.zeros_like(drafts[:, :1])], axis=1)
            out = jnp.where(jnp.arange(w)[None, :] < accepted[:, None],
                            drafts_pad, corr)
            nat = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            out_lp = jnp.take_along_axis(nat, out[..., None], axis=-1)[..., 0]
            return out, out_lp, accepted, cache

        prefill = jax.jit(_prefill, donate_argnums=(2,))
        decode = jax.jit(_decode, donate_argnums=(1,))
        verify = jax.jit(_verify, donate_argnums=(1,))

        def rows(packed, w):
            """(tokens, pos, tables, temps, step) of a step's array."""
            r = packed[:-1].reshape(slots, w + 2 + blocks)
            tokens = r[:, 0] if w == 1 else r[:, :w]
            return (put(np.ascontiguousarray(tokens)),
                    put(np.ascontiguousarray(r[:, w])),
                    put(np.ascontiguousarray(r[:, w + 2:])),
                    put(np.ascontiguousarray(r[:, w + 1]).view(np.float32)),
                    np.int32(packed[-1]))

        def decode_fn(params, cache, packed, key, prev, chunk_tok):
            packed = packed.copy()
            tokens = packed[:-1].reshape(slots, 3 + blocks)[:, 0]
            tokens[:] = np.where(
                tokens == engine_mod.FROM_STEP, np.asarray(prev),
                np.where(tokens == engine_mod.FROM_CHUNK,
                         np.asarray(chunk_tok), tokens))
            *arrays, step = rows(packed, 1)
            return decode(params, cache, *arrays, key, step)

        def verify_fn(params, cache, packed, key):
            *arrays, step = rows(packed, W)
            return verify(params, cache, *arrays, key, step)

        def prefill_fn(params, packed, cache, key):
            cap = packed.size - blocks - 4
            start, length, temp, step = packed[cap + blocks:]
            return prefill(
                params, put(packed[None, :cap]), cache,
                put(packed[cap:cap + blocks]), np.int32(start),
                np.int32(length), temp.view(np.float32), key,
                np.int32(step))

        self._decode_fn, self._prefill_fn = decode_fn, prefill_fn
        if self.spec is not None:
            self._verify_fn = verify_fn

    def _dev(self, packed):
        return packed


def prompt(n, seed):
    return np.random.default_rng(seed).integers(1, 512, n).astype(np.int32)


def engines(cfg, params, **kw):
    kw = {"slots": 4, "max_len": 96, "prefill_chunk": 16,
          "prefix_cache": cfg.family.state_blocks == 0, **kw}
    return InferenceEngine(params, cfg, **kw), \
        FourArrayEngine(params, cfg, **kw)


def run(eng, requests):
    """Submit `requests` ((prompt, new tokens, temperature)) at once,
    run to the end; every stream as (token, logprob) pairs."""
    rids = [eng.submit(p, max_new_tokens=n, temperature=t)
            for p, n, t in requests]
    eng.run_until_idle()
    return [[(int(t), float(t.logprob)) for t in eng.tokens_for(rid)]
            for rid in rids]


ALONE = [(prompt(21, 1), 9, 0.0)]
# a sampled and a greedy stream beside three decoders: the prompts' chunks
# (two each at prefill_chunk 16) share their ticks with the others' steps
BESIDE = [(prompt(5, 2), 14, 0.0), (prompt(7, 3), 14, 0.7),
          (prompt(3, 4), 14, 1.0), (prompt(37, 5), 8, 0.7),
          (prompt(29, 6), 8, 0.0)]


# -- the packing -------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 3])
def test_a_steps_array_round_trips_to_the_bit(window):
    rng = np.random.default_rng(0)
    slots, blocks = TEMPS.size, 6
    tokens = rng.integers(0, 2**31 - 1, (slots,) if window is None
                          else (slots, window)).astype(np.int32)
    pos = rng.integers(0, 4096, slots).astype(np.int32)
    tables = rng.integers(0, 999, (slots, blocks)).astype(np.int32)
    step = 2**31 - 2
    packed = engine_mod.pack_rows(tokens, pos, TEMPS, tables, step)
    assert packed.dtype == np.int32 and packed.ndim == 1
    assert packed.size == slots * ((window or 1) + 2 + blocks) + 1
    got = jax.jit(lambda a: engine_mod.unpack_rows(a, slots, window))(
        jnp.asarray(packed))
    g_tokens, g_pos, g_temps, g_tables, g_step = map(np.asarray, got)
    assert g_tokens.shape == tokens.shape and g_temps.dtype == np.float32
    np.testing.assert_array_equal(g_tokens, tokens)
    np.testing.assert_array_equal(g_pos, pos)
    np.testing.assert_array_equal(g_tables, tables)
    np.testing.assert_array_equal(g_temps.view(np.int32),
                                  TEMPS.view(np.int32))
    assert g_temps.view(np.int32)[3] != 0       # the denormal's bits
    assert g_step.dtype == np.int32 and g_step == step


@pytest.mark.parametrize("temp", TEMPS.tolist())
def test_a_chunks_array_round_trips_to_the_bit(temp):
    rng = np.random.default_rng(1)
    cap, blocks = 16, 6
    tokens = rng.integers(1, 2**31 - 1, 11).astype(np.int32)
    table = rng.integers(0, 999, blocks).astype(np.int32)
    packed = engine_mod.pack_chunk(tokens, cap, table, 48, temp, 77)
    assert packed.dtype == np.int32 and packed.shape == (cap + blocks + 4,)
    got = jax.jit(lambda a: engine_mod.unpack_chunk(a, blocks))(
        jnp.asarray(packed))
    g_tokens, g_table, start, length, g_temp, step = map(np.asarray, got)
    assert g_tokens.shape == (1, cap)
    np.testing.assert_array_equal(g_tokens[0, :11], tokens)
    assert not g_tokens[0, 11:].any()           # padded to the bucket
    np.testing.assert_array_equal(g_table, table)
    assert (start, length, step) == (48, 11, 77)
    assert g_temp.dtype == np.float32 and g_temp.shape == ()
    assert g_temp.view(np.int32) == np.float32(temp).view(np.int32)


# -- the streams -------------------------------------------------------------

@pytest.mark.parametrize("requests", [ALONE, BESIDE],
                         ids=["alone", "beside_decoders"])
def test_streams_are_the_four_array_paths(model, requests):
    """Greedy and sampled streams, tokens and logprobs, alone and beside
    three decoders: equal, not close. And the counts: one transfer a
    program here, where the reference makes four a step and two a chunk
    (its four host scalars a chunk and one a step are `jit`'s own)."""
    _, cfg, params = model
    eng, ref = engines(cfg, params)
    assert run(eng, requests) == run(ref, requests)
    s, r = eng.stats(), ref.stats()
    assert s["host_puts"] == s["decode_steps"] + s["prefill_chunks"]
    assert ref.separate_puts == 4 * r["decode_steps"] \
        + 2 * r["prefill_chunks"]
    assert (s["decode_steps"], s["prefill_chunks"],
            s["chunks_overlapped"]) == (r["decode_steps"],
                                        r["prefill_chunks"],
                                        r["chunks_overlapped"])
    if requests is BESIDE:
        assert s["chunks_overlapped"] >= 2
    eng.check_invariants()


@pytest.mark.parametrize("requests", [ALONE, BESIDE],
                         ids=["alone", "beside_decoders"])
def test_ngram_speculation_streams_are_the_four_array_paths(requests):
    """The verify program takes the window in the tokens' place; a tick
    with nothing to speculate on falls back to the decode step with the
    host arrays it built already. One transfer either way."""
    cfg, params = dense_model()
    loop = np.tile(prompt(5, 9), 5)      # a prompt the n-gram lookup hits
    requests = requests + [(loop, 12, 0.0), (loop[:13], 12, 0.7)]
    eng, ref = engines(cfg, params, slots=8, spec="ngram", spec_k=2)
    assert run(eng, requests) == run(ref, requests)
    s = eng.stats()
    assert 0 < s["spec_steps"] < s["decode_steps"]    # both programs ran
    assert s["host_puts"] == s["decode_steps"] + s["prefill_chunks"]
    assert s["verify_traces"] == s["decode_traces"] == 1
    assert s["retraces_unexpected"] == 0


def test_a_draft_models_programs_take_one_array_each():
    """The draft pool's table rides in an array of its own, in the
    step's layout (propose) and the chunk's (the draft pool's prefill):
    one transfer more a program, and the streams of an engine that
    speculates with the target as its own draft are the plain ones."""
    cfg, params = dense_model()
    eng, _ = engines(cfg, params, spec="draft", spec_k=2,
                     draft_cfg=cfg, draft_params=params)
    plain, _ = engines(cfg, params)
    greedy = [r for r in BESIDE if r[2] == 0.0]
    assert [[t for t, _ in s] for s in run(eng, greedy)] \
        == [[t for t, _ in s] for s in run(plain, greedy)]
    s = eng.stats()
    # a propose program runs in the ticks with a slot worth speculating
    # for, and its put is spanned as the step's is
    step_puts = eng._phases.count("engine/decode_put")
    draft_chunks = eng._phases.count("engine/draft_prefill_chunk")
    assert step_puts > s["decode_steps"] and draft_chunks
    assert s["host_puts"] == step_puts + s["prefill_chunks"] + draft_chunks
    assert s["draft_traces"] == s["verify_traces"] == 1
    assert s["retraces_unexpected"] == 0


def test_streams_are_the_four_array_paths_on_a_mesh_of_four():
    """On a mesh the one array is replicated, as the five were."""
    cfg, params = dense_model()
    mesh = MeshSpec(data=2, tensor=2).build(jax.devices()[:4])
    placed = jax.device_put(params, tree_shardings(
        mesh, gpt.param_logical_axes(cfg)))
    eng, ref = engines(cfg, placed, mesh=mesh)
    assert run(eng, BESIDE) == run(ref, BESIDE)
    s = eng.stats()
    assert s["host_puts"] == s["decode_steps"] + s["prefill_chunks"]
    assert set(engine_io_shardings(mesh)) == {"inputs"}
    assert eng._io_sh.is_fully_replicated
    packed = eng._dev(np.zeros(5, np.int32))
    assert len(packed.sharding.device_set) == 4
    # and what the single-device engine gives
    single, _ = engines(cfg, params)
    assert [[t for t, _ in st] for st in run(single, BESIDE[:1])] \
        == [[t for t, _ in st] for st in run(eng, BESIDE[:1])]


# -- the programs compile as they did ----------------------------------------

def test_trace_counts_over_admissions_retirements_and_a_preemption(model):
    """One decode program for the engine's life and one prefill program
    a chunk bucket, through admissions into freed slots, retirements and
    a forced preemption with its re-prefill: what the parent counted
    (the reference engine beside it counts its own, to the same sums)."""
    name, cfg, params = model
    waves = [BESIDE, [(prompt(40, 50), 9, 0.0), (prompt(9, 51), 5, 0.7)]]
    got = {}
    for kind, eng in zip(("packed", "four"),
                         engines(cfg, params, slots=3,
                                 prefill_buckets=(8, 16))):
        streams = run(eng, waves[0])      # five requests on three slots
        faults.install(faults.FaultPlan(seed=3).fail(
            "engine.preempt", at=4, times=1))
        streams += run(eng, waves[1])
        faults.clear()
        eng.check_invariants()
        got[kind] = streams, eng.stats()
    (streams, s), (ref_streams, r) = got["packed"], got["four"]
    assert streams == ref_streams
    assert s["preemptions"] == r["preemptions"] == 1
    assert [len(st) for st in streams] == [n for _, n, _ in sum(waves, [])]
    assert s["decode_traces"] == 1
    assert s["prefill_traces"] == 2       # the buckets 8 and 16, once each
    assert s["retraces_unexpected"] == 0
    assert s["host_puts"] == s["decode_steps"] + s["prefill_chunks"]
