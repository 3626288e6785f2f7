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
# the tick that holds decoders and a prompt: the chunk among the decode
# steps, which are chained on the device (step t+1 enqueued before step
# t's tokens are read)
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

    def enqueued():                     # read, or in flight
        return eng.stats()["decode_steps"] + (eng._flight is not None)

    for _ in range(4):
        before, steps = eng.stats(), enqueued()
        eng.step()
        st = eng.stats()
        assert st["chunks_overlapped"] - before["chunks_overlapped"] == 1
        assert enqueued() - steps == 1
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
    """`step()` returns with at most one decode step enqueued and unread
    and no other result, and whatever lands between two ticks reads that
    step first (`_rest`), so it finds the engine as it always did: a
    cancel frees its stream, a forced preemption and a hot swap leave
    every stream token-identical, the books balance."""
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
# the decode step chained on the device
# ---------------------------------------------------------------------------

def drained(eng):
    """`eng.step()` followed by the read of what it left in flight: the
    order of the engine before the chain, a step read in its own tick."""
    with eng._lock:
        did = eng.step()
        eng._rest()
    return did


def drive(eng, tick, requests, late=()):
    """Submit `requests` (prompt, new tokens, keywords), tick twice,
    submit `late`, tick to the end: every stream as (token, logprob)
    pairs, and the ticks it took."""
    rids = [eng.submit(p, max_new_tokens=n, **kw) for p, n, kw in requests]
    tick(eng)
    tick(eng)
    rids += [eng.submit(p, max_new_tokens=n, **kw) for p, n, kw in late]
    ticks = 2
    while eng.stats()["active"] or eng.stats()["pending"]:
        tick(eng)
        ticks += 1
    with eng._lock:
        eng._rest()
    return [events(eng._out[r]) for r in rids], ticks


MIXED = ([([3 + i, 5, 7], 12 + 5 * i, {"temperature": 0.9 * (i % 2)})
          for i in range(3)],
         [(LONG, 10, {"temperature": 0.7}), (LONG[:11], 6, {})])


def test_streams_are_the_same_chained_or_drained_every_tick(family):
    """Greedy and sampled streams, to the token and the logprob: a
    chained step has the rows and the key the step would have had with
    its predecessor read first, and every chunk the key of the step
    before it."""
    chained, drained_eng = overlap_engine(family), overlap_engine(family)
    got, _ = drive(chained, lambda e: e.step(), *MIXED)
    want, _ = drive(drained_eng, drained, *MIXED)
    assert got == want
    assert [len(g) for g in got] == [12, 17, 22, 10, 6]
    a, b = chained.stats(), drained_eng.stats()
    assert a["decode_steps"] == b["decode_steps"]
    assert a["steps_chained"] == a["decode_steps"] - 1
    assert a["chain_drains"] == 0
    assert b["steps_chained"] == 0 and \
        b["chain_drains"] == b["decode_steps"]
    # both rows' sources were used: the step's output and a chunk's token
    assert a["chunks_overlapped"] == b["chunks_overlapped"] > 0
    chained.check_invariants()


def test_a_stream_that_ends_on_eos_runs_one_row_step_more(family, alone):
    """The end is known when the token is read, a tick after the step
    behind it was enqueued with the row still in it: nothing past the
    eos is emitted, the slot is free one tick later than a drained
    engine frees it, the row-step's token is thrown away, and the
    request admitted into the freed blocks behind it is untouched."""
    base = [t for t, _ in alone[0]]
    eos = base[6]
    upto = base.index(eos) + 1
    ticks = {}
    for name, tick in (("chained", lambda e: e.step()),
                       ("drained", drained)):
        eng = overlap_engine(family, slots=2)
        rid = eng.submit([3, 5, 7], max_new_tokens=30, eos_id=eos)
        other = eng.submit([4, 5, 7], max_new_tokens=30)
        n, held = 0, set()
        while rid not in eng._done:
            held = {b for s in eng._slots if s.rid == rid for b in s.blocks}
            tick(eng)
            n += 1
        ticks[name] = n
        assert [int(t) for t in eng._out[rid]] == base[:upto]
        idle = next(i for i, s in enumerate(eng._slots) if not s.active)
        if name == "chained":
            # the step behind the eos still holds the row
            assert eng._flight.rows[idle] == rid
        # a new request takes the slot and blocks right behind it
        new = eng.submit([5, 5, 7], max_new_tokens=30)
        tick(eng)
        assert eng._slots[idle].rid == new
        assert held & set(eng._slots[idle].blocks)
        eng.check_invariants()
        assert [int(t) for t in eng._out[rid]] == base[:upto]
        assert_same_stream(events(eng.tokens_for(new)), alone[2])
        assert_same_stream(events(eng.tokens_for(other)), alone[1])
        eng.run_until_idle()
        eng.check_invariants()
        assert eng._flight is None
        st = eng.stats()
        assert st["active"] == st["pending"] == 0
    assert ticks["chained"] == ticks["drained"] + 1


def test_steps_chained_and_chain_drains_count_the_pipeline(family):
    """Every step is chained but the fills, the first and the first
    after each drain; a drain is a read forced by a cancel, a forced
    preemption or a hot swap that met a step in flight, and none where
    there was none."""
    from ray_tpu.util import faults
    cfg, params, family_kw = family
    eng = overlap_engine((cfg, jax.tree.map(jnp.copy, params), family_kw))
    faults.clear()
    try:
        rids = beside_decoders(eng, new_tokens=40)
        eng.submit(LONG, max_new_tokens=8)
        eng.step()
        assert eng._flight is not None and eng.stats()["chain_drains"] == 0
        assert eng.cancel(rids[1])
        assert eng.stats()["chain_drains"] == 1 and eng._flight is None
        assert not eng.cancel(rids[1])          # nothing live: no read
        eng.step()
        eng.step()
        faults.install(faults.FaultPlan(seed=1).fail(
            "engine.preempt", at=0, times=1))
        eng.step()
        assert eng.stats()["chain_drains"] == 2
        assert eng.stats()["preemptions"] == 1
        eng.step()
        assert eng._flight is not None
        assert eng.update_params(params) == 1
        assert eng.stats()["chain_drains"] == 3 and eng._flight is None
        assert eng.update_params(params) == 2   # at rest: no read
        assert eng.stats()["chain_drains"] == 3
    finally:
        faults.clear()
    eng.run_until_idle()
    st = eng.stats()
    assert st["chain_drains"] == 3
    assert st["steps_chained"] == st["decode_steps"] - 1 - 3 > 0
    assert st["host_puts"] == st["decode_steps"] + st["prefill_chunks"]
    assert "``steps_chained``" in type(eng).stats.__doc__
    eng.check_invariants()


def test_an_emit_that_fails_drops_the_step_chained_behind_it():
    """A fault at `engine.emit` leaves the rows after it with the host's
    old token and position, as ever; the step already enqueued behind
    took them for advanced, so it is dropped and they decode on from
    the host, token-identical (the row it struck loses that token, as
    it did before the chain)."""
    from ray_tpu.util import faults
    family = dense_family()
    want = [events(overlap_engine(family).generate(
        [3 + i, 5, 7], max_new_tokens=12)) for i in range(3)]
    eng = overlap_engine(family)
    faults.clear()
    try:
        rids = beside_decoders(eng, new_tokens=12)
        eng.step()
        # three first tokens and two steps' emits so far: the next
        # emit is the first row's, with a step chained behind it
        faults.install(faults.FaultPlan(seed=1).fail(
            "engine.emit", at=0, times=1))
        with pytest.raises(faults.FaultInjected):
            eng.step()
        assert eng._flight is None
    finally:
        faults.clear()
    eng.run_until_idle()
    got = [events(eng._out[r]) for r in rids]
    assert len(got[0]) == len(want[0]) - 1
    assert_same_stream(got[1], want[1])
    assert_same_stream(got[2], want[2])
    eng.check_invariants()


def test_one_decode_program_whatever_feeds_a_row(family):
    """Rows that join from the host, from the step before and from a
    chunk's token run the one compiled step: the marks ride in the packed
    input and the two device operands keep their layout."""
    eng = overlap_engine(family)
    eng.generate([3, 5, 7], max_new_tokens=4)   # from the host, then chained
    eng.generate(LONG, max_new_tokens=3)        # every chunk bucket
    eng.arm_retrace_sentinel()
    marks = set()
    enqueue = eng._enqueue_step

    def spy(behind=None, joining=None, chunk=None, built=None):
        flight = enqueue(behind, joining, chunk, built)
        marks.add((behind is not None, joining is not None))
        return flight

    eng._enqueue_step = spy
    got, _ = drive(eng, lambda e: e.step(), *MIXED)
    assert marks == {(False, False), (True, False), (True, True)}
    assert [len(g) for g in got] == [12, 17, 22, 10, 6]
    st = eng.stats()
    assert st["decode_traces"] == 1 and st["retraces_unexpected"] == 0
    assert eng._decode_fn._cache_size() == 1
    eng.check_invariants()


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
