"""Cluster-wide distributed tracing.

Counterpart of the reference's `ray.util.tracing` integration tests
(test_tracing.py: task/actor spans share one trace across processes)
plus the task-event stage pipeline (`test_task_events.py` timestamp
chains). Covers:

- cross-process propagation: one trace_id spanning >=3 processes in the
  merged `/api/timeline`, for BOTH entry paths (driver -> task -> nested
  task, and HTTP proxy -> ingress replica -> inner replica with a
  flight-recorder request span joining the same trace);
- control-plane stage attribution: per-task timestamp chain
  submitted -> queued -> dispatched -> exec_start -> exec_end ->
  result_put -> got is monotone, and the `task_stage_ms` histogram /
  `stage_breakdown()` read back per-stage quantiles;
- the span ring (deque bound + explicit dropped counter), real
  process/thread chrome lanes, context propagation helpers, and the
  tracing-off overhead probe.

The two e2e tests run subprocess-driven (their own session: tracing is
enabled cluster-wide, which must not leak into the shared fixture) and
are what `make trace-smoke` selects (`-k 'merged or proxy'`).
"""

import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.util import state
from ray_tpu.util import tracing


@pytest.fixture
def cluster(ray_session):
    return ray_session


def _run_e2e(script: str) -> subprocess.CompletedProcess:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", script], cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r


# ---------------------------------------------------------------------------
# e2e: driver -> task -> nested task, one merged trace
# ---------------------------------------------------------------------------

_DRIVER_CHAIN_E2E = r"""
import os
import ray_tpu
from ray_tpu.util import tracing

ray_tpu.init(num_cpus=4)
tracing.enable_tracing()

@ray_tpu.remote
def inner_leaf():
    import os
    return os.getpid()

@ray_tpu.remote
def outer_mid():
    import os
    return (os.getpid(), ray_tpu.get(inner_leaf.remote(), timeout=60))

with tracing.span("e2e.root") as root:
    assert root is not None, "enable_tracing did not arm the driver"
    trace_id = root["trace_id"]
    outer_pid, inner_pid = ray_tpu.get(outer_mid.remote(), timeout=120)
assert len({outer_pid, inner_pid, os.getpid()}) == 3

# TaskDone piggybacks the workers' span rings, so by the time get()
# returned, every task span of this trace is already in the head's ring
# -- no polling needed.
client = ray_tpu._worker.get_client()
events = client.control("timeline", {"trace": trace_id})
assert events and all(
    (e.get("args") or {}).get("trace_id") == trace_id for e in events)
names = [e["name"] for e in events]
assert "e2e.root" in names, names
assert sum(1 for n in names if n == "task.execute") >= 2, names
# ONE trace, >= 3 distinct processes: the driver's root span plus a
# task.execute span from each of the two workers
span_pids = {e["pid"] for e in events if e.get("cat") == "span"}
assert "driver" in span_pids, span_pids
assert len({p for p in span_pids
            if str(p).startswith("worker:")}) >= 2, span_pids
# task events joined the same filtered view (they carry the trace_id)
assert any(e.get("cat") == "task" for e in events), events
# the filter narrows; unfiltered merged view is a superset
assert len(client.control("timeline")) >= len(events)
print("MERGED-TRACE-OK", len(events), sorted(map(str, span_pids)))
ray_tpu.shutdown()
"""


def test_merged_trace_driver_task_nested():
    r = _run_e2e(_DRIVER_CHAIN_E2E)
    assert "MERGED-TRACE-OK" in r.stdout


# ---------------------------------------------------------------------------
# e2e: HTTP proxy -> ingress replica -> inner replica, one merged trace
# ---------------------------------------------------------------------------

_PROXY_E2E = r"""
import json, os, time, urllib.request
os.environ["RAY_TPU_TRACING"] = "1"            # every spawn inherits
os.environ["RAY_TPU_METRICS_FLUSH_PERIOD_S"] = "0.5"
import ray_tpu
from ray_tpu import serve
from ray_tpu.util import tracing

ray_tpu.init(num_cpus=6)

@serve.deployment
class Inner:
    def __init__(self):
        from ray_tpu.util import telemetry
        self.rec = telemetry.FlightRecorder("e2e_inner", sample=1.0)
        self.rid = 0

    def __call__(self, x):
        self.rid += 1
        # flight-recorder request span: parents under the propagated
        # task context, so it shares the HTTP request's trace_id
        self.rec.on_submit(self.rid, prompt_len=1)
        try:
            with tracing.span("inner.work", {"x": x}):
                return x * 2
        finally:
            self.rec.on_finish(self.rid, "finished")

@serve.deployment
class Ingress:
    def __init__(self, inner):
        self.inner = inner

    def __call__(self, req):
        return {"y": self.inner.call(int(req.query["x"]))}

serve.run(Ingress.bind(Inner.bind()), name="t_trace")
proxy = serve.start(http_options={"port": 0})
info = ray_tpu.get(proxy.ready.remote(), timeout=60)
serve.set_route("/trace", "Ingress", "t_trace")

url = f"http://127.0.0.1:{info['port']}/trace?x=21"
resp = urllib.request.urlopen(url, timeout=60)
assert json.loads(resp.read()) == {"y": 42}

# Replica spans rode their tasks' TaskDone; the proxy's own spans
# (http.request / handle.call) arrive on its metrics-flush heartbeat ->
# poll the merged timeline until the trace is complete.
client = ray_tpu._worker.get_client()
deadline = time.time() + 60
events, procs = [], set()
while time.time() < deadline:
    all_events = client.control("timeline")
    roots = [e for e in all_events if e["name"] == "http.request"]
    if roots:
        trace_id = roots[0]["args"]["trace_id"]
        events = [e for e in all_events
                  if (e.get("args") or {}).get("trace_id") == trace_id]
        names = {e["name"] for e in events}
        procs = {e["pid"] for e in events
                 if str(e["pid"]).startswith("worker:")}
        if (len(procs) >= 3 and "inner.work" in names
                and any(e.get("cat") == "request" for e in events)):
            break
    time.sleep(0.3)

names = {e["name"] for e in events}
assert {"http.request", "handle.call", "task.execute",
        "inner.work"} <= names, (names, procs)
# flight-recorder request span joined the same trace
assert any(e.get("cat") == "request" for e in events), names
# ONE trace_id across >= 3 worker processes: proxy, Ingress replica,
# Inner replica
assert len(procs) >= 3, (procs, names)
# the server-side --trace filter returns the same view
filtered = client.control("timeline", {"trace": trace_id})
assert {e["name"] for e in filtered} == names
print("PROXY-TRACE-OK", len(events), sorted(map(str, procs)))
serve.shutdown()
ray_tpu.shutdown()
"""


def test_merged_trace_proxy_to_replicas():
    r = _run_e2e(_PROXY_E2E)
    assert "PROXY-TRACE-OK" in r.stdout


# ---------------------------------------------------------------------------
# stage attribution (shared session: no tracing needed, stages always on)
# ---------------------------------------------------------------------------

def test_stage_timestamps_monotonic(cluster):
    @ray_tpu.remote
    def stage_probe_task():
        time.sleep(0.02)
        return 7

    assert ray_tpu.get(stage_probe_task.remote(), timeout=60) == 7
    recs = [t for t in state.list_tasks()
            if "stage_probe_task" in t["name"]]
    assert recs, "task record missing"
    r = recs[0]
    chain = ("submitted_ts", "queued_ts", "dispatched_ts",
             "exec_start_ts", "exec_end_ts", "result_put_ts", "got_ts")
    vals = [r[k] for k in chain]
    assert all(v is not None for v in vals), r
    for (ka, a), (kb, b) in zip(zip(chain, vals), list(zip(chain, vals))[1:]):
        assert a <= b, f"{ka}={a} > {kb}={b} in {r}"
    # the execute stage really brackets the user function
    assert r["exec_end_ts"] - r["exec_start_ts"] >= 0.02


def test_stage_histogram_and_breakdown(cluster):
    from ray_tpu._private.events import STAGES

    @ray_tpu.remote
    def stage_hist_task(i):
        return i

    assert ray_tpu.get([stage_hist_task.remote(i) for i in range(3)],
                       timeout=60) == [0, 1, 2]

    snap = {m["name"]: m for m in state.get_metrics()}
    assert "task_stage_ms" in snap, sorted(snap)
    hist = snap["task_stage_ms"]
    assert hist["type"] == "histogram"
    # after a full submit -> ... -> get cycle every stage has samples
    assert {(("stage", s),) for s in STAGES} <= set(hist["series"]), \
        sorted(hist["series"])
    for key in hist["series"]:
        buckets, total, count = hist["series"][key]
        assert count >= 1 and total >= 0.0

    text = state.prometheus_metrics()
    assert "ray_tpu_task_stage_ms_bucket" in text
    assert 'stage="execute"' in text and 'stage="got"' in text
    # satellite: the tracing ring's drop counter is scrapeable
    assert "ray_tpu_tracing_dropped_spans" in text

    bd = state.stage_breakdown()
    assert set(bd) == set(STAGES)
    for s in STAGES:
        assert bd[s]["count"] >= 1, (s, bd)
        assert 0.0 <= bd[s]["p50_ms"] <= bd[s]["p99_ms"] <= bd[s]["max_ms"]

    # summary() carries the same breakdown under its reserved key
    summary = state.summarize_tasks()
    assert set(summary["__stages__"]) == set(STAGES)


# ---------------------------------------------------------------------------
# span ring / lanes / context / overhead (pure units)
# ---------------------------------------------------------------------------

def test_span_ring_bound_and_dropped_counter(monkeypatch):
    saved_spans = tracing.get_spans()
    saved_cap = tracing.max_spans()
    monkeypatch.setattr(tracing, "_enabled", True)
    tracing.clear_spans()
    tracing.set_max_spans(4)
    try:
        for i in range(10):
            with tracing.span(f"ring-{i}") as s:
                assert s is not None
        assert len(tracing.get_spans()) == 4          # bound honored
        assert tracing.dropped_spans() == 6           # evictions counted
        assert [s["name"] for s in tracing.get_spans()] == \
            ["ring-6", "ring-7", "ring-8", "ring-9"]  # oldest evicted
        drained = tracing.drain_spans()
        assert len(drained) == 4 and tracing.get_spans() == []
        # ingest() applies the same cap + accounting
        assert tracing.ingest(drained * 3) == 12
        assert len(tracing.get_spans()) == 4
        assert tracing.dropped_spans() == 6 + 8
    finally:
        tracing.clear_spans()
        tracing.set_max_spans(saved_cap)
        tracing.ingest(saved_spans)


def test_chrome_trace_real_lanes():
    spans = [
        {"name": "a", "trace_id": "t1", "span_id": "s1",
         "parent_span_id": None, "start_ns": 1_000, "end_ns": 2_000,
         "attributes": {"k": "v"}, "status": "OK",
         "process": 4242, "proc": "worker:w-7", "thread": "MainThread"},
        {"name": "b", "trace_id": "t1", "span_id": "s2",
         "parent_span_id": "s1", "start_ns": 1_500, "end_ns": None,
         "attributes": {}, "status": "OK",
         "process": 4243, "proc": None, "thread": None,
         "cat": "request", "lane": "engine/r3"},
    ]
    ev = tracing.spans_to_chrome_trace(spans)
    # lanes are real process identities, not trace ids
    assert ev[0]["pid"] == "worker:w-7" and ev[0]["tid"] == "MainThread"
    assert ev[0]["cat"] == "span" and ev[0]["dur"] == 1.0   # us
    assert ev[0]["args"]["trace_id"] == "t1"
    assert ev[0]["args"]["span_id"] == "s1"
    assert ev[0]["args"]["k"] == "v"
    assert ev[1]["pid"] == 4243                  # label fallback: real pid
    assert ev[1]["tid"] == "engine/r3"           # recorder-supplied lane
    assert ev[1]["cat"] == "request"
    assert ev[1]["dur"] > 0                      # open span closed at export


def test_propagation_context_roundtrip():
    assert tracing.propagation_context() is None
    ctx = {"trace_id": "t" * 32, "span_id": "p" * 16}
    s, token = tracing.start_span("child", parent=ctx)
    assert s["trace_id"] == ctx["trace_id"]
    assert s["parent_span_id"] == ctx["span_id"]
    assert tracing.propagation_context() == \
        {"trace_id": s["trace_id"], "span_id": s["span_id"]}
    tracing.end_span(s, token)
    assert tracing.propagation_context() is None
    tok = tracing.attach_context(ctx)
    assert tracing.propagation_context() == ctx
    tracing.detach_context(tok)
    assert tracing.propagation_context() is None
