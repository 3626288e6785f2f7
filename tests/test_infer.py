"""Inference-engine tests: the decode-attention reference's position
mask, compile-once semantics, and continuous batching (slot reuse / late
join) through the engine and through Serve streaming. The paged model
path's parity with the full forward is in test_paged_cache.py."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.ops import decode_attention as da


def tiny_cfg(**kw):
    return gpt.GPTConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=64, dtype="float32"), **kw})


def rollout_reference(params, prompt, cfg, steps):
    """Greedy generation via repeated FULL forward passes — the
    O(T^2)-per-token baseline the cache path must match exactly."""
    toks = list(prompt)
    for _ in range(steps):
        logits = gpt.forward(params, jnp.asarray([toks]), cfg)[0, -1]
        toks.append(int(jnp.argmax(logits)))
    return toks[len(prompt):]


# ---------------------------------------------------------------------------
# decode-attention op
# ---------------------------------------------------------------------------

class TestDecodeAttention:
    def _rand(self, b, s, h, d, dtype=jnp.float32):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, h, d), dtype)
        k = jax.random.normal(ks[1], (b, s, h, d), dtype)
        v = jax.random.normal(ks[2], (b, s, h, d), dtype)
        return q, k, v

    def test_reference_masks_positions(self):
        """Entries past pos[b] must not contribute: corrupting them
        leaves the output bit-identical."""
        q, k, v = self._rand(2, 16, 2, 8)
        pos = jnp.array([3, 15], jnp.int32)
        out = da.reference_decode_attention(q, k, v, pos)
        k2 = k.at[0, 4:].set(1e4)
        v2 = v.at[0, 4:].set(-1e4)
        out2 = da.reference_decode_attention(q, k2, v2, pos)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# ---------------------------------------------------------------------------
# continuous-batching engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_setup():
    cfg = tiny_cfg()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def solo(engine_setup):
    """One shared single-request reference engine: its compiled
    prefill/decode are reused by every test that needs 'what would this
    prompt generate alone' (each request runs to completion before the
    next, so runs can't interact)."""
    from ray_tpu.serve.engine import InferenceEngine
    cfg, params = engine_setup
    return InferenceEngine(params, cfg, slots=2, max_len=32,
                           prefill_buckets=(8, 16))


class TestInferenceEngine:
    def _engine(self, cfg, params, **kw):
        from ray_tpu.serve.engine import InferenceEngine
        kw.setdefault("slots", 2)
        kw.setdefault("max_len", 32)
        kw.setdefault("prefill_buckets", (8, 16))
        return InferenceEngine(params, cfg, **kw)

    def test_greedy_matches_full_forward_rollout(self, engine_setup,
                                                 solo):
        cfg, params = engine_setup
        prompt = [5, 9, 3, 7]
        assert solo.generate(prompt, max_new_tokens=6) == \
            rollout_reference(params, prompt, cfg, 6)

    def test_decode_compiles_exactly_once_across_requests(
            self, engine_setup):
        """The acceptance criterion: one decode executable for the
        engine's whole life — across admissions, evictions, bucket
        changes, and temperature/greedy mixes."""
        cfg, params = engine_setup
        eng = self._engine(cfg, params)
        for i, (n, temp) in enumerate([(4, 0.0), (7, 0.0), (3, 1.0),
                                       (12, 0.7), (2, 0.0)]):
            eng.submit([i + 1, i + 2, i + 3], max_new_tokens=n,
                       temperature=temp)
        eng.run_until_idle()
        assert eng.decode_traces == 1
        assert eng.prefill_traces == 1      # every prompt fit bucket 8
        eng.submit(list(range(1, 12)), max_new_tokens=3)  # bucket 16
        eng.run_until_idle()
        assert eng.decode_traces == 1
        assert eng.prefill_traces == 2      # one more bucket, no more

    def test_late_join_does_not_perturb_resident(self, engine_setup,
                                                 solo):
        """A request admitted mid-flight shares decode steps with the
        resident sequence; greedy decode is row-independent, so the
        resident's tokens must be EXACTLY its solo tokens."""
        cfg, params = engine_setup
        want_a = solo.generate([5, 9, 3, 7], max_new_tokens=10)
        want_b = solo.generate([2, 4], max_new_tokens=4)

        eng = self._engine(cfg, params)
        ra = eng.submit([5, 9, 3, 7], max_new_tokens=10)
        ga = eng.tokens_for(ra)
        got_a = [next(ga) for _ in range(3)]      # resident mid-flight
        rb = eng.submit([2, 4], max_new_tokens=4)  # late join
        got_b = list(eng.tokens_for(rb))
        got_a += list(ga)
        assert got_a == want_a
        assert got_b == want_b
        assert eng.decode_traces == 1

    def test_slot_reuse_and_occupancy(self, engine_setup, solo):
        """More requests than slots: retired slots are re-admitted into
        and every request still completes correctly."""
        cfg, params = engine_setup
        eng = self._engine(cfg, params, slots=2)
        prompts = [[i + 1, i + 2] for i in range(5)]
        rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
        eng.run_until_idle()
        for p, rid in zip(prompts, rids):
            assert list(eng.tokens_for(rid)) == \
                solo.generate(p, max_new_tokens=4)
        s = eng.stats()
        assert s["decode_traces"] == 1
        assert 0 < s["slot_occupancy"] <= 1.0
        assert s["active"] == 0 and s["pending"] == 0

    def test_temperature_sampling(self, engine_setup):
        cfg, params = engine_setup
        eng = self._engine(cfg, params)
        out = eng.generate([1, 2, 3], max_new_tokens=8, temperature=1.0)
        assert len(out) == 8
        assert all(0 <= t < cfg.vocab_size for t in out)
        assert eng.decode_traces == 1      # sampling is not a recompile

    def test_eos_stops_early(self, engine_setup, solo):
        cfg, params = engine_setup
        toks = solo.generate([5, 9, 3, 7], max_new_tokens=8)
        eos = toks[2]
        # a greedy rollout may repeat itself: the stream ends at the
        # FIRST occurrence of the end-of-sequence token, wherever that is
        first = toks.index(eos)
        got = solo.generate([5, 9, 3, 7], max_new_tokens=8, eos_id=eos)
        assert got == toks[:first + 1]     # emits eos, then stops
        assert len(got) < len(toks)

    def test_concurrent_consumers(self, engine_setup, solo):
        """N threads each pumping their own request drive one shared
        continuously-batched loop without deadlock or cross-talk."""
        cfg, params = engine_setup
        eng = self._engine(cfg, params, slots=3)
        prompts = {i: [i + 1, i + 2] for i in range(6)}
        want = {i: solo.generate(p, max_new_tokens=5)
                for i, p in prompts.items()}
        got = {}

        def worker(i):
            got[i] = eng.generate(prompts[i], max_new_tokens=5)
        ts = [threading.Thread(target=worker, args=(i,))
              for i in prompts]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert got == want
        assert eng.decode_traces == 1

    def test_submit_validation(self, engine_setup):
        cfg, params = engine_setup
        eng = self._engine(cfg, params)
        with pytest.raises(ValueError, match="empty"):
            eng.submit([])
        # chunked prefill removed the old bucket-length limit: a prompt
        # longer than the largest prefill bucket is fine as long as it
        # fits the cache.
        assert len(eng.generate(list(range(1, 18)),
                                max_new_tokens=4)) == 4
        with pytest.raises(ValueError, match="max_len"):
            eng.submit([1, 2], max_new_tokens=31)
        tiny = self._engine(cfg, params, cache_blocks=1)
        with pytest.raises(ValueError, match="blocks"):
            tiny.submit(list(range(1, 18)), max_new_tokens=4)


# ---------------------------------------------------------------------------
# the tick that holds decoders and a prompt: decode step first, the chunk
# behind it
# ---------------------------------------------------------------------------

LONG = list(range(1, 30))       # four chunks of 8


def dense_family():
    cfg = tiny_cfg()
    return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg), {}


def retention_family():
    """`models/retention.py` at the size of tests/test_retention.py: a
    state block a sequence, no pages, no prefix cache."""
    from benchmarks.refs import retention_decoder as ref
    from ray_tpu.models import retention
    tiny = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, intermediate_size=128,
                rope_theta=1e6, rms_norm_eps=1e-6, retention_eps=1e-6,
                max_position_embeddings=128, vocab_size=128)
    cfg = retention.from_published(**tiny, dtype="float32",
                                   retention_impl="jax")
    params = ref.init_params(jax.random.key(0),
                             {**tiny, "gate_bias": [4.0, 8.0]})
    return cfg, params, {"prefix_cache": False}


FAMILIES = {"dense": dense_family, "retention": retention_family}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return FAMILIES[request.param]()


def overlap_engine(family, **kw):
    from ray_tpu.serve.engine import InferenceEngine
    cfg, params, family_kw = family
    return InferenceEngine(params, cfg, **{
        "slots": 4, "max_len": 64, "prefill_buckets": (8, 16),
        "prefill_chunk": 8, **family_kw, **kw})


def beside_decoders(eng, n=3, new_tokens=30):
    """`n` short streams, each past its first decode step."""
    rids = [eng.submit([3 + i, 5, 7], max_new_tokens=new_tokens)
            for i in range(n)]
    eng.step()
    eng.step()
    assert sum(s.phase == "decode" for s in eng._slots) == n
    return rids


def events(tokens):
    return [(int(t), t.logprob) for t in tokens]


def assert_same_stream(got, want):
    assert [t for t, _ in got] == [t for t, _ in want]
    np.testing.assert_allclose([lp for _, lp in got],
                               [lp for _, lp in want], rtol=0, atol=1e-5)


@pytest.fixture(scope="module")
def alone(family):
    """What LONG and the three short prompts generate with the engine
    to themselves: every chunk waited for, none behind a decode step."""
    eng = overlap_engine(family)
    out = {"long": events(eng.generate(LONG, max_new_tokens=10))}
    for i in range(3):
        out[i] = events(eng.generate([3 + i, 5, 7], max_new_tokens=30))
    st = eng.stats()
    assert st["chunks_overlapped"] == 0 and st["prefill_chunks"] == 4 + 3
    return out


def test_a_greedy_stream_is_the_same_prefilled_alone_or_beside_decoders(
        family, alone):
    eng = overlap_engine(family)
    rids = beside_decoders(eng)
    rid = eng.submit(LONG, max_new_tokens=10)
    assert_same_stream(events(eng.tokens_for(rid)), alone["long"])
    st = eng.stats()
    assert st["chunks_overlapped"] == 4 and st["prefill_chunks"] == 3 + 4
    for i, r in enumerate(rids):        # and the bystanders' are theirs
        assert_same_stream(events(eng.tokens_for(r)), alone[i])
    eng.check_invariants()


def test_chunks_overlapped_counts_the_chunks_behind_a_decode_step(family):
    """Never more than `prefill_chunks`; equal where every chunk met a
    decoder (a closed loop's window); zeroed by `reset_stats`."""
    from ray_tpu.serve.engine import InferenceEngine
    eng = overlap_engine(family)
    beside_decoders(eng)
    st = eng.stats()
    assert st["chunks_overlapped"] == 0 < st["prefill_chunks"]   # the ramp
    eng.reset_stats()
    eng.submit(LONG, max_new_tokens=2)
    for _ in range(4):
        before = eng.stats()
        eng.step()
        st = eng.stats()
        assert st["chunks_overlapped"] - before["chunks_overlapped"] == 1
        assert st["decode_steps"] - before["decode_steps"] == 1
    assert st["chunks_overlapped"] == st["prefill_chunks"] == 4
    assert st["prefill_time_s"] > st["prefill_build_s"] \
        + st["prefill_dispatch_s"] + st["prefill_sync_s"] > 0
    eng.reset_stats()
    st = eng.stats()
    assert st["chunks_overlapped"] == st["prefill_chunks"] == 0
    assert st["prefill_time_s"] == 0.0
    assert "``chunks_overlapped``" in InferenceEngine.stats.__doc__
    eng.run_until_idle()
    eng.check_invariants()


def test_a_prompt_that_ends_behind_a_decode_step_decodes_from_the_next_tick(
        family):
    """Its first token is emitted in the tick of its last chunk; it was
    not in that tick's decode batch, so its second comes a tick later."""
    eng = overlap_engine(family)
    beside_decoders(eng)
    rid = eng.submit(LONG, max_new_tokens=4)

    def slot():
        return next(s for s in eng._slots if s.rid == rid)

    for _ in range(3):
        eng.step()
        assert slot().phase == "prefill" and not eng._out[rid]
    steps = eng.stats()["decode_steps"]
    eng.step()                          # the last chunk, overlapped
    assert slot().phase == "decode" and len(eng._out[rid]) == 1
    assert eng.stats()["decode_steps"] == steps + 1
    assert eng.stats()["chunks_overlapped"] == 4
    eng.step()
    assert len(eng._out[rid]) == 2
    eng.step()
    assert len(eng._out[rid]) == 3


@pytest.mark.parametrize("spec", ["ngram", "draft"])
def test_the_speculative_tick_takes_the_decode_step_s_place(spec):
    """Its programs go first and the chunk behind them, verify or the
    fallback step alike; greedy streams are what the plain engine makes."""
    family = cfg, params, _ = dense_family()
    spec_kw = {"spec": spec, "spec_k": 3}
    if spec == "draft":
        spec_kw.update(draft_params=params, draft_cfg=cfg)
    # a motif the n-gram lookup finds, so verify runs and not only the
    # fallback
    motif = [9, 4, 7] * 9
    streams = []
    for kw in ({}, spec_kw):
        eng = overlap_engine(family, **kw)
        rids = [eng.submit(motif[:6 + i], max_new_tokens=30)
                for i in range(3)]
        eng.step()
        eng.step()
        rids.append(eng.submit(motif + [5, 2], max_new_tokens=10))
        streams.append([events(eng.tokens_for(r)) for r in rids])
        eng.check_invariants()
        st = eng.stats()
        assert st["chunks_overlapped"] == 4 and st["prefill_chunks"] == 7
    assert st["spec_steps"] > 0 and st["verify_traces"] == 1
    plain, speculative = streams
    for got, base in zip(speculative, plain):
        assert_same_stream(got, base)


def _cancel(eng, rids, weights):
    assert eng.cancel(rids[1])
    return [0, 2]


def _preempt(eng, rids, weights):
    from ray_tpu.util import faults
    faults.install(faults.FaultPlan(seed=1).fail(
        "engine.preempt", at=0, times=1))
    return [0, 1, 2]


def _swap(eng, rids, weights):
    # the same weights under a new version: every stream goes on as it was
    assert eng.update_params(weights) == 1
    return [0, 1, 2]


@pytest.mark.parametrize("between", [_cancel, _preempt, _swap],
                         ids=["cancel", "preempt", "hot-swap"])
def test_between_two_overlapped_ticks_the_engine_is_at_rest(
        family, alone, between):
    """`step()` returns with no result unread, so what lands between two
    ticks that each ran a chunk behind a decode step finds the engine as
    it always did: a cancel frees its stream, a forced preemption and a
    hot swap leave every stream token-identical, the books balance."""
    from ray_tpu.util import faults
    cfg, params, family_kw = family
    # a swap donates the tree the engine was built on: it gets its own
    eng = overlap_engine((cfg, jax.tree.map(jnp.copy, params), family_kw))
    faults.clear()
    try:
        rids = beside_decoders(eng)
        rid = eng.submit(LONG, max_new_tokens=10)
        eng.step()
        eng.step()
        assert eng.stats()["chunks_overlapped"] == 2
        eng.check_invariants()
        live = between(eng, rids, params)
        eng.check_invariants()
        eng.step()
        eng.check_invariants()
        assert_same_stream(events(eng.tokens_for(rid)), alone["long"])
        for i in live:
            assert_same_stream(events(eng.tokens_for(rids[i])), alone[i])
    finally:
        faults.clear()
    eng.run_until_idle()
    eng.check_invariants()
    st = eng.stats()
    assert st["active"] == st["pending"] == 0
    assert st["preemptions"] == (between is _preempt)
    assert st["chunks_overlapped"] <= st["prefill_chunks"]


# ---------------------------------------------------------------------------
# through Serve
# ---------------------------------------------------------------------------

@pytest.fixture
def serve_session(ray_session):
    from ray_tpu import serve
    yield serve
    serve.shutdown()


def test_inference_replica_streams_through_serve(serve_session):
    """End-to-end: InferenceReplica deployed through Serve, tokens
    streamed back via the replica's generator/next_chunks machinery, and
    concurrent requests continuously batch into one engine."""
    import concurrent.futures

    from ray_tpu import serve
    from ray_tpu.serve.engine import InferenceReplica

    app = serve.deployment(InferenceReplica).bind(
        dict(vocab_size=128, d_model=32, n_layers=1, n_heads=2,
             d_ff=64, max_seq_len=64, dtype="float32"),
        slots=2, max_len=32)
    h = serve.run(app, name="infer")

    toks = list(h.stream([5, 9, 3], 6))
    assert len(toks) == 6 and all(isinstance(t, int) for t in toks)

    # same prompt, same engine -> same greedy tokens; concurrent
    # requests share the resident engine's slots
    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        outs = list(pool.map(
            lambda _: list(h.stream([5, 9, 3], 6)), range(4)))
    assert all(o == toks for o in outs)

    stats = h.stats.remote()
    import ray_tpu
    s = ray_tpu.get(stats)
    assert s["decode_traces"] == 1
