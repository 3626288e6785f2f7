"""Telemetry plane (util/telemetry.py): flight-recorder request tracing,
the stats()->metrics bridge behind the dashboard's /metrics, the train
step-time breakdown, and the runtime retrace sentinel.

Acceptance pins of the observability PR: /metrics serves engine + train
series in parseable Prometheus exposition; /api/timeline interleaves
per-request spans with task events; a forced recompile on a pinned path
after warmup trips `retraces_unexpected` with ONE WARN while armed
same-shape traffic reports zero.
"""

import gc
import json
import logging
import re
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from ray_tpu.models import gpt
from ray_tpu.util import metrics
from ray_tpu.util import telemetry
from ray_tpu.util import tracing


def tiny_cfg(**kw):
    return gpt.GPTConfig(**{**dict(
        vocab_size=128, d_model=32, n_layers=1, n_heads=2, d_ff=64,
        max_seq_len=64, dtype="float32"), **kw})


def assert_prometheus_parses(text):
    """Every non-comment line must match the exposition sample grammar
    with a float-parseable value — the property check_invariants pins."""
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = telemetry._PROM_SAMPLE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        float(m.group(1))


# ---------------------------------------------------------------------------
# prometheus rendering: sanitization + canonical le round-trip
# ---------------------------------------------------------------------------

class TestPrometheusRendering:
    def test_sanitize_name(self):
        assert metrics.sanitize_name("engine0/ttft ms") == \
            "engine0_ttft_ms"
        assert metrics.sanitize_name("0starts_bad") == "_0starts_bad"
        assert metrics.sanitize_name("fine_name:sub") == "fine_name:sub"
        # labels additionally exclude ':'
        assert metrics.sanitize_name("a:b", label=True) == "a_b"

    def test_format_float_canonical(self):
        assert metrics.format_float(2) == "2.0"
        assert metrics.format_float(0.001) == "0.001"
        assert metrics.format_float(float("inf")) == "+Inf"
        assert metrics.format_float(float("-inf")) == "-Inf"
        assert metrics.format_float(np.float32(1.0)) == "1.0"
        # round-trippable with float()
        for v in (2, 0.001, 0.5, 1e-9, 123456.75):
            assert float(metrics.format_float(v)) == float(v)

    def test_histogram_le_labels_roundtrip(self):
        bounds = [0.1, 0.5, 1, 5]
        h = metrics.Histogram("tele_rt_hist", "round trip",
                              boundaries=bounds, tag_keys=("source",))
        for v in (0.05, 0.3, 2.0, 100.0):
            h.observe(v, tags={"source": "t"})
        text = metrics.render_prometheus(metrics.snapshot())
        assert_prometheus_parses(text)
        pat = re.compile(
            r'^ray_tpu_tele_rt_hist_bucket\{.*le="([^"]+)".* (\d+)$')
        les, cums = [], []
        for line in text.splitlines():
            m = pat.match(line)
            if m:
                les.append(float(m.group(1)))   # must round-trip
                cums.append(int(m.group(2)))
        assert les == [0.1, 0.5, 1.0, 5.0, float("inf")]
        assert cums == sorted(cums) and cums[-1] == 4
        assert 'ray_tpu_tele_rt_hist_count{source="t"} 4' in text

    def test_weird_metric_name_renders_parseable(self):
        metrics.Counter("tele weird/name", "d").inc(2)
        text = metrics.render_prometheus(metrics.snapshot())
        assert "ray_tpu_tele_weird_name 2.0" in text
        assert_prometheus_parses(text)


# ---------------------------------------------------------------------------
# tracing ring + context propagation
# ---------------------------------------------------------------------------

class TestTracingRing:
    @pytest.fixture(autouse=True)
    def _enabled(self, monkeypatch):
        monkeypatch.setattr(tracing, "_enabled", True)
        prev_cap = tracing.max_spans()
        tracing.clear_spans()
        yield
        tracing.set_max_spans(prev_cap)
        tracing.clear_spans()

    def test_ring_cap_counts_evictions(self):
        tracing.set_max_spans(4)
        for i in range(10):
            with tracing.span(f"ring{i}"):
                pass
        spans = tracing.get_spans()
        assert len(spans) == 4
        assert [s["name"] for s in spans] == \
            ["ring6", "ring7", "ring8", "ring9"]
        assert tracing.dropped_spans() == 6

    def test_attach_context_across_thread(self):
        got = {}

        def worker(ctx):
            token = tracing.attach_context(ctx)
            try:
                with tracing.span("child") as c:
                    got["child"] = c
            finally:
                tracing.detach_context(token)

        with tracing.span("parent") as p:
            t = threading.Thread(target=worker,
                                 args=(tracing.capture_context(),))
            t.start()
            t.join()
        assert got["child"]["parent_span_id"] == p["span_id"]
        assert got["child"]["trace_id"] == p["trace_id"]
        # without attach, a fresh thread starts a fresh trace
        got.clear()
        t = threading.Thread(target=worker, args=(None,))
        t.start()
        t.join()
        assert got["child"]["parent_span_id"] is None


# ---------------------------------------------------------------------------
# flight recorder (unit: hooks driven directly)
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def _request(self, rec, rid, outcome="finished", tokens=3):
        rec.on_submit(rid, 5)
        rec.on_admit(rid, 4, True)
        rec.on_prefill_chunk(rid, 8, 8, 1e-4)
        rec.on_first_token(rid, 2e-3)
        for _ in range(tokens):
            rec.on_token(rid)
        rec.on_swap_crossing(rid)
        rec.on_finish(rid, outcome)

    def test_lifecycle_spans(self):
        rec = telemetry.FlightRecorder("recunit-a", sample=1.0,
                                       max_spans=64)
        self._request(rec, 1)
        spans = rec.get_spans()
        names = {s["name"] for s in spans}
        assert {"engine.request", "queue_wait", "prefill_chunk",
                "first_token", "swap_crossing", "decode"} <= names
        root = next(s for s in spans if s["name"] == "engine.request")
        assert root["attributes"]["outcome"] == "finished"
        assert root["attributes"]["tokens"] == 3
        assert root["attributes"]["prefix_hit_tokens"] == 4
        assert root["attributes"]["cow"] is True
        # one trace: every span shares the root's trace and parents it
        for s in spans:
            assert s["trace_id"] == root["trace_id"]
            assert s["end_ns"] >= s["start_ns"]
            if s is not root:
                assert s["parent_span_id"] == root["span_id"]
        assert rec.live_requests() == 0
        events = rec.chrome_events()
        # durations render as "X", instants (first_token/swap) as "i"
        assert {e["ph"] for e in events} == {"X", "i"}
        assert all(e["cat"] == "request" for e in events)
        inst = next(e for e in events if e["name"] == "first_token")
        assert inst["s"] == "t" and inst["tid"] == "recunit-a/r1"
        rec.check_invariants()

    def test_ring_bound_and_dropped_counter(self):
        rec = telemetry.FlightRecorder("recunit-b", sample=1.0,
                                       max_spans=8)
        for rid in range(5):
            self._request(rec, rid)
        assert len(rec.get_spans()) == 8
        assert rec.dropped_spans > 0
        rec.check_invariants()
        rec.clear()
        assert rec.get_spans() == [] and rec.dropped_spans == 0

    def test_sampling_zero_records_nothing(self):
        rec = telemetry.FlightRecorder("recunit-c", sample=0.0)
        self._request(rec, 1)
        assert rec.requests_seen == 1
        assert rec.requests_traced == 0
        assert rec.get_spans() == []

    def test_cancel_closes_open_queue_span(self):
        rec = telemetry.FlightRecorder("recunit-d", sample=1.0)
        rec.on_submit(7, 3)
        rec.on_finish(7, "cancelled")   # cancelled while still queued
        spans = rec.get_spans()
        root = next(s for s in spans if s["name"] == "engine.request")
        queue = next(s for s in spans if s["name"] == "queue_wait")
        assert root["attributes"]["outcome"] == "cancelled"
        assert queue["end_ns"] is not None
        assert "decode" not in {s["name"] for s in spans}


# ---------------------------------------------------------------------------
# retrace sentinel (unit: synthetic counters)
# ---------------------------------------------------------------------------

class TestRetraceSentinel:
    def test_cap_watch_warns_once_counts_every_excess(self, caplog):
        count = [1]
        s = telemetry.RetraceSentinel("sentunit-a")
        s.watch("decode", lambda: count[0], cap=1)
        assert s.watching()            # cap watches armed at birth
        assert s.check() == 0
        with caplog.at_level(logging.WARNING,
                             logger="ray_tpu.util.telemetry"):
            count[0] = 3
            assert s.check() == 2
            count[0] = 4
            assert s.check() == 1      # counted again...
        warns = [r for r in caplog.records
                 if "retrace sentinel" in r.message]
        assert len(warns) == 1          # ...but ONE warn per path
        assert "'decode'" in warns[0].message
        assert s.retraces_unexpected == 3
        assert len(s.events) == 2 and s.events[0]["path"] == "decode"

    def test_dynamic_watch_silent_until_armed(self):
        count = [3]
        s = telemetry.RetraceSentinel("sentunit-b")
        s.watch("prefill", lambda: count[0])     # bucket-dependent
        count[0] = 5
        assert s.check() == 0 and not s.watching()   # warmup: no limit
        s.arm()                                   # baseline = 5
        assert s.watching() and s.armed
        assert s.check() == 0
        count[0] = 7
        assert s.check() == 2
        assert s.retraces_unexpected == 2
        s.reset()
        assert s.retraces_unexpected == 0 and not s.watching()


# ---------------------------------------------------------------------------
# stats() -> metrics bridge
# ---------------------------------------------------------------------------

class _Source:
    def __init__(self):
        self.d = {"decode_tokens": 5, "occupancy": 0.5,
                  "spec": "off-string-skipped", "flag": True}

    def stats(self):
        return dict(self.d)


def _series(name):
    for m in metrics.snapshot():
        if m["name"] == name:
            return m["series"]
    return {}


class TestStatsBridge:
    def test_counter_delta_gauge_and_weakref_pruning(self):
        src = _Source()
        name = telemetry.register_stats_source("bridgeunit", src,
                                               kind="bridge")
        try:
            key = (("source", name),)
            # COUNTER_KEYS stat -> delta-tracked counter
            assert _series("bridge_decode_tokens")[key] == 5.0
            src.d["decode_tokens"] = 8
            assert _series("bridge_decode_tokens")[key] == 8.0
            src.d["decode_tokens"] = 2     # upstream reset_stats()
            assert _series("bridge_decode_tokens")[key] == 10.0
            # numeric non-counter stat -> gauge; str/bool skipped
            assert _series("bridge_occupancy")[key] == 0.5
            assert key not in _series("bridge_spec")
            assert key not in _series("bridge_flag")
            assert name in telemetry.summary()["stats_sources"]
        finally:
            del src
            gc.collect()
            metrics.snapshot()             # collect prunes dead weakref
            assert name not in telemetry.summary()["stats_sources"]

    def test_duplicate_name_uniquified(self):
        a, b = _Source(), _Source()
        na = telemetry.register_stats_source("bridgedup", a, kind="bridge")
        nb = telemetry.register_stats_source("bridgedup", b, kind="bridge")
        try:
            assert na == "bridgedup" and nb == "bridgedup-2"
        finally:
            telemetry.unregister_stats_source(na)
            telemetry.unregister_stats_source(nb)


# ---------------------------------------------------------------------------
# engine integration: recorder wiring, stats contract, sentinel
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_setup():
    cfg = tiny_cfg()
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def traced_engine(engine_setup):
    """One engine with a streamed request through it — shared by the
    recorder-wiring, stats-contract, and dashboard-scrape tests."""
    from ray_tpu.serve.engine import InferenceEngine
    cfg, params = engine_setup
    eng = InferenceEngine(params, cfg, slots=2, max_len=32,
                          prefill_buckets=(8, 16))
    rid = eng.submit([5, 9, 3], max_new_tokens=4)
    assert len(list(eng.tokens_for(rid))) == 4   # streamed to completion
    eng.run_until_idle()
    return eng


class TestEngineTelemetry:
    def test_recorder_captures_request_lifecycle(self, traced_engine):
        spans = traced_engine._recorder.get_spans()
        names = {s["name"] for s in spans}
        assert {"engine.request", "queue_wait", "prefill_chunk",
                "first_token", "decode"} <= names
        root = next(s for s in spans if s["name"] == "engine.request")
        assert root["attributes"]["outcome"] == "finished"
        assert root["attributes"]["tokens"] == 4
        st = traced_engine.stats()
        assert st["ttft_ms_p50"] > 0
        assert st["ttft_ms_p50"] <= st["ttft_ms_p99"]
        # the recorder's histograms landed in the module registry
        hist = _series("engine_ttft_ms")
        assert any(dict(k)["source"] == traced_engine.name
                   for k in hist), hist

    def test_stats_docstring_contract(self, traced_engine):
        """Every ``key`` the stats() docstring documents exists in the
        dict, and every dict key is documented — both directions, so the
        contract can't silently rot either way."""
        from ray_tpu.serve.engine import InferenceEngine
        documented = set(re.findall(r"``([a-z0-9_]+)``",
                                    InferenceEngine.stats.__doc__))
        actual = set(traced_engine.stats().keys())
        assert documented - actual == set(), \
            f"documented but not returned: {sorted(documented - actual)}"
        assert actual - documented == set(), \
            f"returned but undocumented: {sorted(actual - documented)}"

    def test_armed_sentinel_reports_zero_on_compile_once_traffic(
            self, engine_setup):
        from ray_tpu.serve.engine import InferenceEngine
        cfg, params = engine_setup
        eng = InferenceEngine(params, cfg, slots=2, max_len=32,
                              prefill_buckets=(8, 16))
        for i, temp in enumerate((0.0, 1.0)):     # warmup: bucket 8
            eng.submit([i + 1, i + 2, i + 3], max_new_tokens=3,
                       temperature=temp)
        eng.run_until_idle()
        eng.arm_retrace_sentinel()
        for i in range(3):                        # same shapes, armed
            eng.submit([i + 2, i + 5], max_new_tokens=4,
                       temperature=0.7 * i)
        eng.run_until_idle()
        st = eng.stats()
        assert st["retraces_unexpected"] == 0
        assert st["decode_traces"] == 1

    def test_sentinel_trips_on_new_bucket_after_arm(self, engine_setup,
                                                    caplog):
        """The forced-recompile acceptance test: a prompt landing in a
        prefill bucket never compiled during warmup re-traces the jitted
        prefill AFTER arm() declared warmup over — the sentinel must
        count it and WARN exactly once."""
        from ray_tpu.serve.engine import InferenceEngine
        cfg, params = engine_setup
        eng = InferenceEngine(params, cfg, slots=2, max_len=40,
                              prefill_buckets=(8, 16, 32))
        eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=2)  # bucket 8
        eng.run_until_idle()
        eng.arm_retrace_sentinel()
        with caplog.at_level(logging.WARNING,
                             logger="ray_tpu.util.telemetry"):
            eng.submit(list(range(1, 31)), max_new_tokens=2)  # bucket 32
            eng.run_until_idle()
        st = eng.stats()
        assert st["retraces_unexpected"] > 0
        warns = [r for r in caplog.records
                 if "retrace sentinel" in r.message]
        assert len(warns) == 1 and "prefill" in warns[0].message
        tripped = st["retraces_unexpected"]
        # traffic in a bucket compiled during warmup adds nothing (the
        # big prompt is NOT re-sent: its blocks are radix-cached now, so
        # a resend would prefill only the tail — a different, smaller
        # chunk bucket, i.e. another legitimate trip)
        eng.submit([7, 8, 9], max_new_tokens=2)   # bucket 8, warmed
        eng.run_until_idle()
        assert eng.stats()["retraces_unexpected"] == tripped
        # the violation is visible in the /api/telemetry summary
        sent = next(s for s in telemetry.summary()["sentinels"]
                    if s["name"] == eng.name)
        assert sent["retraces_unexpected"] == tripped
        assert any(e["path"] == "prefill" for e in sent["events"])

    def test_telemetry_sample_zero_disables_recorder_only(
            self, engine_setup):
        from ray_tpu.serve.engine import InferenceEngine
        cfg, params = engine_setup
        eng = InferenceEngine(params, cfg, slots=2, max_len=32,
                              prefill_buckets=(8, 16),
                              telemetry_sample=0.0)
        eng.submit([4, 2], max_new_tokens=3)
        eng.run_until_idle()
        assert eng._recorder.requests_seen == 1
        assert eng._recorder.requests_traced == 0
        assert eng._recorder.get_spans() == []
        # engine-level latency stats are independent of sampling
        assert eng.stats()["ttft_ms_p50"] > 0


# ---------------------------------------------------------------------------
# train loop: step-time breakdown, MFU/goodput
# ---------------------------------------------------------------------------

class TestTrainLoopTelemetry:
    def test_breakdown_and_goodput(self):
        from ray_tpu.train import loop

        def step_fn(state, batch):
            time.sleep(1e-3)
            return state + 1, {"loss": np.float32(0.5)}

        tl = loop.TrainLoop(step_fn, metrics_interval=2)
        batches = iter([{"x": np.zeros(2)}] * 5)
        state, ms = tl.run(0, batches, num_steps=5)
        assert state == 5 and len(ms) == 5
        bd = tl.last_breakdown
        assert bd["steps"] == 5 and bd["total_s"] > 0
        shares = [bd[f"{k}_share"] for k in
                  ("prefetch", "dispatch", "metrics", "checkpoint",
                   "publish")]
        assert all(0.0 <= s <= 1.0 for s in shares)
        assert sum(shares) <= 1.001
        assert bd["dispatch_s"] >= 5e-3      # five 1ms steps
        assert 0.0 < tl.last_goodput <= 1.0
        st = tl.stats()
        assert st["retraces_unexpected"] == 0
        assert st["unroll"] == 1 and "mfu" not in st
        assert st["dispatch_share"] == bd["dispatch_share"]
        # the loop registered itself: train_* series reach the registry
        key = (("source", tl.name),)
        assert _series("train_goodput")[key] == tl.last_goodput


# ---------------------------------------------------------------------------
# dashboard endpoints: /metrics scrape + merged /api/timeline
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def dashboard_port(ray_session):
    from ray_tpu.dashboard import start_dashboard
    return start_dashboard(0)   # ephemeral port


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        body = r.read().decode()
        if r.headers.get_content_type() == "application/json":
            return json.loads(body)
        return body


class TestDashboardTelemetry:
    def test_metrics_scrape_serves_engine_and_train_series(
            self, ray_session, dashboard_port, traced_engine):
        from ray_tpu.train import loop
        tl = loop.TrainLoop(lambda s, b: (s, {"loss": 0.0}))
        tl.run(0, iter([{"x": np.zeros(1)}] * 2), num_steps=2)
        text = _get(dashboard_port, "/metrics")
        assert_prometheus_parses(text)
        # engine series, tagged by source engine
        line = next(ln for ln in text.splitlines()
                    if ln.startswith("ray_tpu_engine_decode_tokens{"))
        assert f'source="{traced_engine.name}"' in text
        assert float(line.rsplit(" ", 1)[1]) > 0
        # recorder latency histogram made it out as buckets
        assert "ray_tpu_engine_ttft_ms_bucket{" in text
        # train series from the loop that just ran
        assert f'ray_tpu_train_goodput{{source="{tl.name}"}}' in text
        assert "ray_tpu_train_dispatch_s{" in text

    def test_timeline_interleaves_tasks_and_request_spans(
            self, ray_session, dashboard_port, traced_engine):
        import ray_tpu

        @ray_tpu.remote
        def tele_task():
            return 1

        assert ray_tpu.get(tele_task.remote()) == 1
        events = _get(dashboard_port, "/api/timeline")
        cats = {e.get("cat") for e in events}
        assert "task" in cats and "request" in cats
        assert any("tele_task" in e["name"] for e in events
                   if e.get("cat") == "task")
        roots = [e for e in events if e.get("cat") == "request"
                 and e["name"] == "engine.request"]
        assert roots and roots[0]["ph"] == "X"
        assert roots[0]["args"]["outcome"] == "finished"
        # one shared clock: both categories are epoch-µs (dividing by
        # 1e6 gives a unix time near "now"), so request spans sort in
        # among the task events instead of living on a parallel
        # timeline or in different units
        task_ts = [e["ts"] for e in events if e.get("cat") == "task"
                   and "ts" in e]
        now = time.time()
        assert abs(roots[0]["ts"] / 1e6 - now) < 86400
        assert abs(min(task_ts) / 1e6 - now) < 86400

    def test_api_telemetry_summary(self, ray_session, dashboard_port,
                                   traced_engine):
        s = _get(dashboard_port, "/api/telemetry")
        rec = next(r for r in s["recorders"]
                   if r["name"] == traced_engine.name)
        assert rec["requests_traced"] >= 1 and rec["spans"] >= 5
        sent = next(x for x in s["sentinels"]
                    if x["name"] == traced_engine.name)
        assert sent["watching"] is True
        assert s["tracing"]["max_spans"] > 0
        assert s["stats_sources"]


# ---------------------------------------------------------------------------
# self-test
# ---------------------------------------------------------------------------

class TestCheckInvariants:
    def test_passes_after_traffic(self, traced_engine):
        telemetry.check_invariants()

    def test_catches_overflowed_recorder_ring(self):
        rec = telemetry.FlightRecorder("selftest-neg", max_spans=2)
        rec._spans.extend({"name": "x"} for _ in range(5))
        with pytest.raises(AssertionError):
            telemetry.check_invariants()
        del rec
        gc.collect()            # weakset drops it; the plane is clean
        telemetry.check_invariants()
