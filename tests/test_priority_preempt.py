"""Priority-class admission + block-pressure preemption tests: a
preempted greedy stream resumes bitwise token-identical to an
unpreempted run (plain, shared-prefix/COW, and both spec-decode
backends), weighted-share admission ordering with aging (no class ever
starves), class-ordered shedding (lowest queued class evicted first,
same-class behavior unchanged), the seeded engine fault sites
(`engine.alloc` exhaustion drives exactly the planned preemptions;
same seed => identical `fired()` replay), and preempt→resume→cancel
interleavings audited by `check_invariants`."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.exceptions import OverloadedError
from ray_tpu.models import gpt
from ray_tpu.serve.engine import InferenceEngine
from ray_tpu.util import faults


def tiny_cfg(**kw):
    return gpt.GPTConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=64, dtype="float32"), **kw})


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


def make_engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("block_size", 4)
    return InferenceEngine(params, cfg, **kw)


def drain(eng, rid):
    return [int(t) for t in eng.tokens_for(rid)]


def run_all(eng, steps=500):
    for _ in range(steps):
        if not eng.step():
            return
    raise AssertionError("engine did not go idle")


PROMPT = np.arange(1, 9, dtype=np.int32)          # 8 tokens = 2 blocks


# ---------------------------------------------------------------------------
# token-identical resume
# ---------------------------------------------------------------------------

def assert_same_stream(got, base):
    """Tokens exactly; logprobs within 1e-5. A resumed stream re-prefills
    positions the baseline decoded one at a time: two programs that trace
    different matmul shapes and round differently (measured 5e-7 on
    logprobs near -4.6). 1e-5 is twenty times that and far under what a
    wrong mask or a lost block does (1e-2 and up)."""
    assert [t for t, _ in got] == [t for t, _ in base]
    np.testing.assert_allclose([lp for _, lp in got],
                               [lp for _, lp in base], rtol=0, atol=1e-5)


class TestTokenIdenticalResume:
    def _baseline(self, cfg, params, prompt, n, **kw):
        eng = make_engine(cfg, params, **kw)
        rid = eng.submit(prompt, max_new_tokens=n)
        out = [(int(t), t.logprob) for t in eng.tokens_for(rid)]
        return out

    def test_block_pressure_preempt_token_identical(self, setup):
        """Real block pressure: pool sized so a class-2 arrival can only
        be served by evicting the decoding class-0 stream; the class-0
        consumer still sees the exact unpreempted token sequence AND
        logprobs."""
        cfg, params = setup
        base = self._baseline(cfg, params, PROMPT, 6, cache_blocks=32)
        # 4 blocks per request (prompt 8 + new 6 over block 4);
        # cache_blocks=7 leaves 6 usable (block 0 is trash) — one
        # stream fits, two can't.
        eng = make_engine(cfg, params, cache_blocks=7)
        ra = eng.submit(PROMPT, max_new_tokens=6, priority=0)
        for _ in range(4):      # let the low class reach decode
            eng.step()
        rb = eng.submit(np.full(8, 9, np.int32), max_new_tokens=6,
                        priority=2)
        run_all(eng)
        s = eng.stats()
        assert s["preemptions"] >= 1
        assert s["per_class"]["0"]["preemptions"] >= 1
        got = [(int(t), t.logprob) for t in eng.tokens_for(ra)]
        assert_same_stream(got, base)
        assert len(drain(eng, rb)) == 6
        eng.check_invariants()

    def test_forced_preempt_site_token_identical(self, setup):
        """`engine.preempt` fault site: eviction with zero real
        pressure — pure resume-path coverage, no pool math involved."""
        cfg, params = setup
        base = self._baseline(cfg, params, PROMPT, 6, cache_blocks=32)
        faults.install(faults.FaultPlan(seed=3).fail(
            "engine.preempt", at=2, times=1))
        eng = make_engine(cfg, params, cache_blocks=32)
        rid = eng.submit(PROMPT, max_new_tokens=6, priority=0)
        run_all(eng)
        assert eng.stats()["preemptions"] == 1
        assert_same_stream(
            [(int(t), t.logprob) for t in eng.tokens_for(rid)], base)
        eng.check_invariants()

    def test_shared_prefix_cow_preempt_token_identical(self, setup):
        """The victim shares prefix blocks with a sibling stream (radix
        refs + COW on divergence). Preemption must release only the
        victim's non-shared holds, and the resume — which re-admits the
        shared prefix by reference — must stay token-identical while
        the sibling decodes on."""
        cfg, params = setup
        shared = np.arange(1, 9, dtype=np.int32)        # 2 full blocks
        pa = np.concatenate([shared, [20, 21, 22, 23]]).astype(np.int32)
        pb = np.concatenate([shared, [30, 31, 32, 33]]).astype(np.int32)
        base_a = self._baseline(cfg, params, pa, 6, cache_blocks=64)
        base_b = self._baseline(cfg, params, pb, 6, cache_blocks=64)
        eng = make_engine(cfg, params, cache_blocks=64)
        ra = eng.submit(pa, max_new_tokens=6, priority=0)
        rb = eng.submit(pb, max_new_tokens=6, priority=1)
        for _ in range(2):      # both admitted, prefix shared, decoding
            eng.step()
        faults.install(faults.FaultPlan(seed=5).fail(
            "engine.preempt", at=0, times=1))
        run_all(eng)
        assert eng.stats()["preemptions"] == 1
        # the class-0 stream was the victim; both match their baselines
        assert_same_stream(
            [(int(t), t.logprob) for t in eng.tokens_for(ra)], base_a)
        assert_same_stream(
            [(int(t), t.logprob) for t in eng.tokens_for(rb)], base_b)
        eng.check_invariants()

    @pytest.mark.parametrize("spec", ["ngram", "draft"])
    def test_spec_backend_preempt_token_identical(self, setup, spec):
        cfg, params = setup
        kw = {"spec": spec, "spec_k": 3}
        if spec == "draft":
            dcfg = tiny_cfg(n_layers=1)
            kw["draft_cfg"] = dcfg
            kw["draft_params"] = gpt.init_params(
                jax.random.PRNGKey(1), dcfg)
        motif = np.tile([5, 6, 7, 8], 2).astype(np.int32)
        base = self._baseline(cfg, params, motif, 8,
                              cache_blocks=32, **kw)
        faults.install(faults.FaultPlan(seed=9).fail(
            "engine.preempt", at=3, times=1))
        eng = make_engine(cfg, params, cache_blocks=32, **kw)
        rid = eng.submit(motif, max_new_tokens=8, priority=0)
        run_all(eng)
        assert eng.stats()["preemptions"] == 1
        assert_same_stream(
            [(int(t), t.logprob) for t in eng.tokens_for(rid)], base)
        eng.check_invariants()

    def test_mid_prefill_preempt_token_identical(self, setup):
        """Victim caught while still chunk-prefilling (no tokens emitted
        yet): the resume finishes the prefill and the stream is still
        exact."""
        cfg, params = setup
        long_prompt = np.arange(1, 17, dtype=np.int32)
        base = self._baseline(cfg, params, long_prompt, 4,
                              cache_blocks=32, prefill_chunk=4)
        faults.install(faults.FaultPlan(seed=2).fail(
            "engine.preempt", at=1, times=1))
        eng = make_engine(cfg, params, cache_blocks=32, prefill_chunk=4)
        rid = eng.submit(long_prompt, max_new_tokens=4, priority=0)
        run_all(eng)
        assert eng.stats()["preemptions"] == 1
        assert_same_stream(
            [(int(t), t.logprob) for t in eng.tokens_for(rid)], base)
        eng.check_invariants()


# ---------------------------------------------------------------------------
# admission ordering: weighted shares + aging
# ---------------------------------------------------------------------------

class TestAdmissionOrder:
    def _admission_sequence(self, eng, rids_by_class, steps=400):
        """Drive the engine one tick at a time and record the class of
        each newly-admitted rid, in order. With slots=1 a short request
        can be admitted AND retired inside one step() (prefill tick +
        decode tick), so completion order — observed via `_done` — is
        the admission order; still-active slots cover the in-flight
        one."""
        seen, order = set(), []
        for _ in range(steps):
            alive = eng.step()
            for s in eng._slots:
                if s.active and s.rid not in seen:
                    seen.add(s.rid)
                    order.append(rids_by_class[s.rid])
            for rid in eng._done:
                if rid not in seen:
                    seen.add(rid)
                    order.append(rids_by_class[rid])
            if not alive:
                break
        return order

    def test_weighted_shares_stride(self, setup):
        """slots=1, classes 0/1 backlogged together, weight base 2:
        the stride scheduler must interleave ~2 class-1 admissions per
        class-0 (never a starved run), not drain class 1 first."""
        cfg, params = setup
        eng = make_engine(cfg, params, slots=1, cache_blocks=64,
                          priority_classes=2, priority_weight_base=2.0,
                          priority_aging_s=3600.0)   # aging disarmed
        rids = {}
        for i in range(6):
            rids[eng.submit(PROMPT + i, max_new_tokens=2,
                            priority=0)] = 0
            rids[eng.submit(PROMPT + 10 + i, max_new_tokens=2,
                            priority=1)] = 1
        order = self._admission_sequence(eng, rids)
        assert len(order) == 12
        assert sorted(order[:3]) == [0, 1, 1], order
        # every prefix holds the 2:1 share (within one stride step)
        for k in range(1, 13):
            c1 = order[:k].count(1)
            if c1 < 6:
                assert c1 >= (2 * k) // 3 - 1, (k, order)
        eng.check_invariants()

    def test_aging_escalates_past_stride(self, setup):
        """A class-0 request older than its aging bound must be admitted
        AHEAD of fresher high-class traffic, even though stride order
        alone would pick class 1 first."""
        cfg, params = setup
        eng = make_engine(cfg, params, slots=1, cache_blocks=64,
                          priority_classes=2, priority_aging_s=0.01)
        rids = {}
        rids[eng.submit(PROMPT, max_new_tokens=2, priority=0)] = 0
        time.sleep(0.05)        # > (2 - 0) * 0.01 bound
        for i in range(3):
            rids[eng.submit(PROMPT + 10 + i, max_new_tokens=2,
                            priority=1)] = 1
        order = self._admission_sequence(eng, rids)
        assert order[0] == 0, order
        assert eng.stats()["aging_promotions"] >= 1
        eng.check_invariants()

    def test_no_starvation_under_sustained_high_load(self, setup):
        """Low-class requests submitted into a continuous stream of
        high-class traffic all complete, with queue wait bounded by the
        aging escalation (the acceptance criterion's starvation
        bound)."""
        cfg, params = setup
        aging_s = 0.2
        eng = make_engine(cfg, params, slots=1, cache_blocks=64,
                          priority_classes=3, priority_aging_s=aging_s)
        t0 = time.perf_counter()
        low = [eng.submit(PROMPT + i, max_new_tokens=2, priority=0)
               for i in range(3)]
        done_at = {}
        fed = 0
        for _ in range(3000):
            alive = eng.step()
            if fed < 30:        # sustained class-2 pressure
                eng.submit(PROMPT + 40 + (fed % 8), max_new_tokens=2,
                           priority=2)
                fed += 1
            for r in low:
                if r not in done_at and r in eng._done:
                    done_at[r] = time.perf_counter() - t0
            if not alive and fed >= 30:
                break
        assert set(done_at) == set(low), "low-class request starved"
        # worst-case wait is bounded: the aging escalation fires at
        # 3 * aging_s for class 0; generous slack for CPU jitter and
        # the in-flight stream it must still wait out
        bound = 3 * aging_s + 10.0
        assert all(w < bound for w in done_at.values()), done_at
        st = eng.stats()
        assert st["per_class"]["0"]["completed"] == 3
        assert st["per_class"]["2"]["completed"] == 30
        eng.check_invariants()


# ---------------------------------------------------------------------------
# class-ordered shedding
# ---------------------------------------------------------------------------

class TestClassOrderedShedding:
    def test_high_class_evicts_lowest_queued(self, setup):
        """Queue full: a class-2 submit sheds the newest class-0 QUEUED
        request (typed OverloadedError through its tokens_for) and takes
        its place — it does not shed itself."""
        cfg, params = setup
        eng = make_engine(cfg, params, slots=1, cache_blocks=64,
                          priority_classes=3, max_queue=2)
        ra = eng.submit(PROMPT, max_new_tokens=8, priority=0)
        eng.step()              # ra admitted — the queue is for rb/rv
        rb = eng.submit(PROMPT + 1, max_new_tokens=2, priority=0)
        rv = eng.submit(PROMPT + 2, max_new_tokens=2, priority=0)
        rh = eng.submit(PROMPT + 3, max_new_tokens=2, priority=2)
        run_all(eng)
        with pytest.raises(OverloadedError):
            drain(eng, rv)      # newest class-0 was the victim
        assert len(drain(eng, ra)) == 8
        for rid in (rb, rh):
            assert len(drain(eng, rid)) == 2
        s = eng.stats()
        assert s["sheds"] == 1
        assert s["per_class"]["0"]["sheds"] == 1
        eng.check_invariants()

    def test_same_class_sheds_incoming(self, setup):
        """All-one-class traffic keeps PR 12 semantics exactly: nothing
        queued ranks below the incoming request, so the incoming submit
        itself raises."""
        cfg, params = setup
        eng = make_engine(cfg, params, slots=1, cache_blocks=64,
                          max_queue=1)
        ra = eng.submit(PROMPT, max_new_tokens=8)
        eng.step()              # ra admitted
        rb = eng.submit(PROMPT + 1, max_new_tokens=2)
        with pytest.raises(OverloadedError):
            eng.submit(PROMPT + 2, max_new_tokens=2)
        run_all(eng)
        assert len(drain(eng, ra)) == 8 and len(drain(eng, rb)) == 2
        assert eng.stats()["sheds"] == 1
        eng.check_invariants()

    def test_shed_victim_error_is_consumed_once(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, slots=1, cache_blocks=64,
                          priority_classes=2, max_queue=1)
        eng.submit(PROMPT, max_new_tokens=8, priority=0)
        eng.step()              # admitted; queue is for rv
        rv = eng.submit(PROMPT + 1, max_new_tokens=2, priority=0)
        eng.submit(PROMPT + 2, max_new_tokens=2, priority=1)
        with pytest.raises(OverloadedError):
            drain(eng, rv)
        # second poll: rid unknown now (error delivered and cleared) —
        # tokens_for's empty-stream contract, not a second raise
        assert rv not in eng._errors and rv not in eng._out
        assert drain(eng, rv) == []
        run_all(eng)
        eng.check_invariants()


# ---------------------------------------------------------------------------
# seeded engine fault sites
# ---------------------------------------------------------------------------

class TestEngineFaultSites:
    def _chaos_run(self, cfg, params, seed):
        faults.clear()
        faults.install(
            faults.FaultPlan(seed=seed)
            .fail("engine.preempt", p=0.25, times=None)
            .fail("engine.alloc", p=0.2, times=None))
        eng = make_engine(cfg, params, cache_blocks=32,
                          priority_classes=2)
        outs = []
        ra = eng.submit(PROMPT, max_new_tokens=4, priority=0)
        rb = eng.submit(PROMPT + 2, max_new_tokens=4, priority=1)
        run_all(eng, steps=2000)
        outs.append(drain(eng, ra))
        outs.append(drain(eng, rb))
        eng.check_invariants()
        log = faults.fired()
        faults.clear()
        return outs, log, eng.stats()["preemptions"]

    def test_same_seed_identical_fired_log(self, setup):
        """Replay determinism: an identical plan (same seed) fires at
        the identical (site, visit, action) sequence on two independent
        runs, and the engine output is identical too."""
        cfg, params = setup
        outs1, log1, p1 = self._chaos_run(cfg, params, seed=11)
        outs2, log2, p2 = self._chaos_run(cfg, params, seed=11)
        assert log1, "plan never fired — test is vacuous"
        assert log1 == log2
        assert outs1 == outs2 and p1 == p2
        # a different seed produces a different schedule
        _, log3, _ = self._chaos_run(cfg, params, seed=12)
        assert log3 != log1

    def test_alloc_exhaustion_exactly_planned_preemptions(self, setup):
        """The `engine.alloc` site refuses admission exactly where
        planned; each refused high-class admission preempts exactly one
        low-class victim — preemptions == planned failures."""
        cfg, params = setup
        # visits 0,1: the two low-class admissions. The high-class
        # request admits into the third (free) slot: visits 2,3 are the
        # planned failures, each preempting one decoding victim before
        # the retry; the post-preemption retry (visit 4) succeeds.
        faults.install(faults.FaultPlan(seed=1).fail(
            "engine.alloc", at=2, times=2))
        eng = make_engine(cfg, params, slots=3, cache_blocks=64,
                          priority_classes=3)
        ra = eng.submit(PROMPT, max_new_tokens=16, priority=0)
        rb = eng.submit(PROMPT + 1, max_new_tokens=16, priority=0)
        for _ in range(3):      # both low streams mid-decode
            eng.step()
        rh = eng.submit(PROMPT + 2, max_new_tokens=4, priority=2)
        run_all(eng)
        s = eng.stats()
        assert s["preemptions"] == 2, s["preemptions"]
        assert s["per_class"]["0"]["preemptions"] == 2
        assert [v for site, v, a in faults.fired()
                if site == "engine.alloc"] == [2, 3]
        assert len(drain(eng, rh)) == 4
        for rid in (ra, rb):
            assert len(drain(eng, rid)) == 16
        eng.check_invariants()

    def test_alloc_fault_without_victim_defers(self, setup):
        """Exhaustion with no lower-class active stream: the request
        just stays queued for the next tick — no preemption, no error
        to the consumer."""
        cfg, params = setup
        faults.install(faults.FaultPlan(seed=1).fail(
            "engine.alloc", at=0, times=1))
        eng = make_engine(cfg, params, cache_blocks=32)
        rid = eng.submit(PROMPT, max_new_tokens=4)
        run_all(eng)
        assert eng.stats()["preemptions"] == 0
        assert len(drain(eng, rid)) == 4
        eng.check_invariants()

    def test_tick_stall_site_feeds_watchdog(self, setup):
        """The tick-stall chaos site is `engine.tick` with a delay spec:
        the watchdog must count the wedged tick."""
        cfg, params = setup
        faults.install(faults.FaultPlan(seed=1).delay(
            "engine.tick", delay_s=0.25, at=1, times=1))
        eng = make_engine(cfg, params, cache_blocks=32, watchdog_s=0.05)
        rid = eng.submit(PROMPT, max_new_tokens=4)
        run_all(eng)
        assert len(drain(eng, rid)) == 4
        assert eng.stats()["watchdog_stalls"] >= 1
        assert ("engine.tick", 1, "delay") in faults.fired()
        eng.check_invariants()


# ---------------------------------------------------------------------------
# preempt / resume / cancel interleavings
# ---------------------------------------------------------------------------

class TestPreemptCancelInterleavings:
    def _free_blocks(self, eng):
        eng._tree.flush()
        return eng._alloc.free

    def test_cancel_while_resume_pending(self, setup):
        """Cancel lands while the preempted stream sits requeued under
        real block pressure (a forced preempt's resume would be
        re-admitted within the same tick — admission runs after the
        fault consult): everything — blocks, refcounts, _out queue —
        must be released."""
        cfg, params = setup
        # 6 usable blocks, 4 per stream: the class-2 arrival preempts
        # the class-0 stream, whose resume then can't re-admit until
        # the high stream finishes.
        eng = make_engine(cfg, params, cache_blocks=7,
                          priority_classes=3)
        total_free = self._free_blocks(eng)
        rid = eng.submit(PROMPT, max_new_tokens=6, priority=0)
        for _ in range(2):
            eng.step()          # admitted, decoding
        rh = eng.submit(PROMPT + 1, max_new_tokens=6, priority=2)
        eng.step()              # block pressure: rid preempted for rh
        assert eng.stats()["preemptions"] == 1
        assert any(q.rid == rid for q in eng._pending)   # resume queued
        assert eng.cancel(rid)
        run_all(eng)
        eng.check_invariants()
        assert rid not in eng._out
        assert len(drain(eng, rh)) == 6
        assert self._free_blocks(eng) == total_free

    def test_cancel_after_resume_readmitted(self, setup):
        cfg, params = setup
        faults.install(faults.FaultPlan(seed=4).fail(
            "engine.preempt", at=2, times=1))
        eng = make_engine(cfg, params, cache_blocks=32)
        total_free = self._free_blocks(eng)
        rid = eng.submit(PROMPT, max_new_tokens=8)
        for _ in range(5):      # preempt at tick 2, resume re-admitted
            eng.step()
        assert eng.stats()["preemptions"] == 1
        assert eng.cancel(rid)
        run_all(eng)
        eng.check_invariants()
        assert self._free_blocks(eng) == total_free

    def test_repeated_preempt_resume_fuzz(self, setup):
        """Probabilistic forced preemption over a multi-class workload:
        whatever interleaving of preempt/resume/finish happens, streams
        stay token-identical to their baselines, nothing leaks, and
        invariants hold after every tick."""
        cfg, params = setup
        base_eng = make_engine(cfg, params, cache_blocks=64)
        prompts = [(PROMPT + i, 4 + (i % 3)) for i in range(6)]
        base = {}
        for i, (p, n) in enumerate(prompts):
            r = base_eng.submit(p, max_new_tokens=n)
            base[i] = [int(t) for t in base_eng.tokens_for(r)]
        faults.install(faults.FaultPlan(seed=21).fail(
            "engine.preempt", p=0.3, times=None))
        eng = make_engine(cfg, params, cache_blocks=64,
                          priority_classes=3)
        total_free = self._free_blocks(eng)
        rids = {}
        for i, (p, n) in enumerate(prompts):
            rids[i] = eng.submit(p, max_new_tokens=n, priority=i % 3)
        for _ in range(2000):
            alive = eng.step()
            eng.check_invariants()
            if not alive:
                break
        else:
            raise AssertionError("chaos run never went idle")
        assert eng.stats()["preemptions"] >= 1
        for i in rids:
            assert drain(eng, rids[i]) == base[i], i
        eng.check_invariants()
        assert self._free_blocks(eng) == total_free

    def test_preempted_stream_readable_midflight(self, setup):
        """Tokens emitted before the preemption are already in the
        consumer's queue; the post-resume continuation lands in the SAME
        queue — one seamless stream."""
        cfg, params = setup
        faults.install(faults.FaultPlan(seed=6).fail(
            "engine.preempt", at=3, times=1))
        eng = make_engine(cfg, params, cache_blocks=32)
        rid = eng.submit(PROMPT, max_new_tokens=6)
        got = drain(eng, rid)   # pumps step() internally via tokens_for
        assert len(got) == 6
        assert eng.stats()["preemptions"] == 1
        eng.check_invariants()


# ---------------------------------------------------------------------------
# stats / telemetry plumbing
# ---------------------------------------------------------------------------

class TestPriorityStats:
    def test_per_class_counters_and_reset(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, cache_blocks=64,
                          priority_classes=3)
        ra = eng.submit(PROMPT, max_new_tokens=3, priority=0)
        rb = eng.submit(PROMPT + 1, max_new_tokens=3, priority=2)
        run_all(eng)
        drain(eng, ra), drain(eng, rb)
        s = eng.stats()
        assert s["priority_classes"] == 3
        for c in ("0", "2"):
            pc = s["per_class"][c]
            assert pc["submitted"] == pc["completed"] == 1
            assert pc["decode_tokens"] == 3
            assert pc["queue_wait_ms_p99"] >= pc["queue_wait_ms_p50"] >= 0
        eng.reset_stats()
        s2 = eng.stats()
        assert s2["preemptions"] == s2["reprefill_blocks"] == 0
        assert s2["aging_promotions"] == 0
        assert all(v == 0 for pc in s2["per_class"].values()
                   for k, v in pc.items() if k.endswith(("ed", "s"))
                   and k not in ("pending", "active"))
        eng.check_invariants()

    def test_per_class_series_reach_metrics_bridge(self, setup):
        """The nested per_class dict fans out as class-tagged series on
        the Prometheus bridge (engine_per_class_*{class=...})."""
        cfg, params = setup
        from ray_tpu.util import metrics as _metrics
        from ray_tpu.util import telemetry as _telemetry
        eng = make_engine(cfg, params, cache_blocks=64,
                          priority_classes=2)
        name = _telemetry.register_stats_source(
            _telemetry.next_name("prio-test#"), eng, kind="engine")
        try:
            rid = eng.submit(PROMPT, max_new_tokens=3, priority=1)
            run_all(eng)
            drain(eng, rid)
            text = _metrics.render_prometheus(_metrics.snapshot())
            assert "engine_per_class_decode_tokens" in text
            assert 'class="1"' in text
            assert "engine_preemptions" in text
        finally:
            _telemetry.unregister_stats_source(name)

    def test_reprefill_blocks_counts_uncached_resume_blocks(self, setup):
        """With the radix tree publishing the victim's KV at preemption,
        the resume admits those blocks by reference — reprefill_blocks
        counts only what the cache could NOT cover (the not-yet-full
        trailing block)."""
        cfg, params = setup
        faults.install(faults.FaultPlan(seed=3).fail(
            "engine.preempt", at=2, times=1))
        eng = make_engine(cfg, params, cache_blocks=32)
        rid = eng.submit(PROMPT, max_new_tokens=6)
        run_all(eng)
        drain(eng, rid)
        s = eng.stats()
        assert s["preemptions"] == 1
        # resume footprint is 3-4 blocks; the shared prefix covers the
        # full ones, so the uncached tail is at most 2 blocks
        assert 0 <= s["reprefill_blocks"] <= 2
        eng.check_invariants()

    def test_priority_validation(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, priority_classes=2)
        with pytest.raises(ValueError):
            eng.submit(PROMPT, max_new_tokens=2, priority=2)
        with pytest.raises(ValueError):
            eng.submit(PROMPT, max_new_tokens=2, priority=-1)
        with pytest.raises(ValueError):
            make_engine(cfg, params, priority_classes=0)
        with pytest.raises(ValueError):
            make_engine(cfg, params, priority_weight_base=0.5)
