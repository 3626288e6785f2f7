"""State API, task events, timeline, and metrics.

Counterpart of the reference's `python/ray/tests/test_state_api.py` and
`test_metrics_agent.py` coverage: lifecycle records for tasks/actors,
list_* endpoints, chrome-trace export, and the Counter/Gauge/Histogram
application-metrics pipeline (worker flush → driver aggregation →
prometheus text).
"""

import json
import time

import pytest

import ray_tpu
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import state


@pytest.fixture
def cluster(ray_session):
    return ray_session


def test_list_tasks_lifecycle(cluster):
    @ray_tpu.remote
    def traced(x):
        return x + 1

    refs = [traced.remote(i) for i in range(3)]
    assert ray_tpu.get(refs) == [1, 2, 3]
    tasks = state.list_tasks()
    mine = [t for t in tasks if "traced" in t["name"]]
    assert len(mine) >= 3
    assert all(t["state"] == "FINISHED" for t in mine[:3])
    assert all(t["start_ts"] is not None and t["end_ts"] is not None
               for t in mine[:3])
    assert all(t["worker_id"] for t in mine[:3])


def test_failed_task_recorded(cluster):
    @ray_tpu.remote
    def boom():
        raise ValueError("no")

    ref = boom.remote()
    with pytest.raises(ValueError):
        ray_tpu.get(ref)
    deadline = time.time() + 10
    while time.time() < deadline:
        failed = [t for t in state.list_tasks({"state": "FAILED"})
                  if "boom" in t["name"]]
        if failed:
            break
        time.sleep(0.1)
    assert failed and failed[0]["error"] == "application_error"


def test_list_actors_and_workers(cluster):
    @ray_tpu.remote
    class Stateful:
        def ping(self):
            return "pong"

    a = Stateful.remote()
    assert ray_tpu.get(a.ping.remote()) == "pong"
    actors = state.list_actors()
    mine = [x for x in actors if "Stateful" in x["class_name"]]
    assert mine and mine[0]["state"] == "ALIVE"
    workers = state.list_workers()
    assert any(w["alive"] for w in workers)
    objs = state.list_objects()
    assert isinstance(objs, list)
    nodes = state.list_nodes()
    assert nodes and nodes[0]["resources_total"].get("CPU", 0) > 0
    ray_tpu.kill(a)


def test_summary_and_timeline(cluster, tmp_path):
    @ray_tpu.remote
    def traced2():
        time.sleep(0.05)
        return 1

    ray_tpu.get([traced2.remote() for _ in range(2)])
    summary = state.summarize_tasks()
    key = next(k for k in summary if "traced2" in k)
    assert summary[key].get("FINISHED", 0) >= 2

    out = tmp_path / "timeline.json"
    events = ray_tpu.timeline(str(out))
    assert any("traced2" in e["name"] for e in events)
    loaded = json.loads(out.read_text())
    span = next(e for e in loaded if "traced2" in e["name"])
    assert span["ph"] == "X" and span["dur"] >= 50_000  # >= 50ms in us


def test_metrics_counter_gauge_histogram(cluster):
    c = metrics_mod.Counter("test_requests", "desc", tag_keys=("route",))
    c.inc(2.0, {"route": "/a"})
    c.inc(1.0, {"route": "/b"})
    with pytest.raises(ValueError):
        c.inc(0)
    with pytest.raises(ValueError):
        c.inc(1, {"bogus": "x"})
    g = metrics_mod.Gauge("test_depth", "d")
    g.set(7)
    h = metrics_mod.Histogram("test_lat", "l", boundaries=[0.1, 1.0])
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)

    snap = {m["name"]: m for m in state.get_metrics()}
    assert snap["test_requests"]["series"][(("route", "/a"),)] == 2.0
    assert snap["test_depth"]["series"][()] == 7
    buckets, total, count = snap["test_lat"]["series"][()]
    assert buckets == [1, 1, 1] and count == 3 and abs(total - 5.55) < 1e-9

    text = state.prometheus_metrics()
    assert 'ray_tpu_test_requests{route="/a"} 2.0' in text
    assert "ray_tpu_test_lat_count 3" in text
    assert 'ray_tpu_test_lat_bucket{le="+Inf"} 3' in text


def test_metrics_flow_from_workers(cluster):
    @ray_tpu.remote
    def emit(i):
        from ray_tpu.util import metrics as m
        cnt = m.Counter("test_worker_side", "w")
        cnt.inc(1.0)
        m.flush()
        return i

    assert sorted(ray_tpu.get([emit.remote(i) for i in range(3)])) == [0, 1, 2]
    deadline = time.time() + 10
    total = 0
    while time.time() < deadline:
        snap = {m["name"]: m for m in state.get_metrics()}
        if "test_worker_side" in snap:
            total = sum(snap["test_worker_side"]["series"].values())
            if total >= 1.0:
                break
        time.sleep(0.2)
    # counters sum across the worker processes that pushed
    assert total >= 1.0


def test_merge_snapshots_semantics():
    a = [{"name": "c", "type": "counter", "description": "",
          "series": {(): 1.0}}]
    b = [{"name": "c", "type": "counter", "description": "",
          "series": {(): 2.0}}]
    merged = metrics_mod.merge_snapshots([a, b])
    assert merged[0]["series"][()] == 3.0


# ---------------------------------------------------------------------------
# Log pipeline (reference: _private/log_monitor.py:102 tail-to-driver +
# dashboard/modules/log/): a remote task's print is captured to a per-
# process file, tailed, and reaches (a) a subscribed driver's stderr and
# (b) the head's log ring serving /api/logs. Subprocess-driven: needs its
# own session with a daemon node and a log_to_driver subscription.
# ---------------------------------------------------------------------------

_LOG_E2E = r"""
import sys, time
import ray_tpu
from ray_tpu.cluster_utils import Cluster

c = Cluster(head_resources={"CPU": 2}, log_to_driver=True)
c.add_node({"CPU": 2, "far": 1})

@ray_tpu.remote
def speak_head():
    print("HELLO-FROM-HEAD-WORKER")
    return 1

@ray_tpu.remote(resources={"far": 1})
def speak_node():
    print("HELLO-FROM-NODE-WORKER")
    return 2

assert ray_tpu.get([speak_head.remote(), speak_node.remote()],
                   timeout=120) == [1, 2]

client = ray_tpu._worker.get_client()
deadline = time.time() + 30
found = set()
while time.time() < deadline and len(found) < 2:
    for row in client.control("list_logs"):
        text = "\n".join(client.control(
            "get_log", {"source": row["source"], "lines": 500}))
        if "HELLO-FROM-HEAD-WORKER" in text:
            found.add("head")
        if "HELLO-FROM-NODE-WORKER" in text:
            found.add("node")
    time.sleep(0.3)
assert found == {"head", "node"}, found
# give the subscription fanout a beat to hit our stderr, then exit; the
# parent asserts on captured stderr
time.sleep(1.5)
print("LOGS-RING-OK")
c.shutdown()
"""


def test_log_pipeline_to_driver_and_ring():
    import os
    import subprocess
    import sys as _sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([_sys.executable, "-c", _LOG_E2E], cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, \
        f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "LOGS-RING-OK" in r.stdout
    # tail-to-driver: the remote prints arrived on the DRIVER's stderr,
    # prefixed with their source process
    assert "HELLO-FROM-HEAD-WORKER" in r.stderr
    assert "HELLO-FROM-NODE-WORKER" in r.stderr


# ---------------------------------------------------------------------------
# On-demand stack dumps (reference: `ray stack` scripts.py:1786 + py-spy
# profile_manager.py — workers self-sample via sys._current_frames) and
# general pubsub channels (reference: src/ray/pubsub/publisher.h:307).
# ---------------------------------------------------------------------------

def test_stack_dump_finds_busy_worker(cluster):
    import threading

    @ray_tpu.remote
    def very_recognizable_busy_loop():
        t0 = time.time()
        while time.time() - t0 < 8.0:
            time.sleep(0.05)
        return 1

    ref = very_recognizable_busy_loop.remote()
    time.sleep(1.0)     # let it get scheduled + running
    client = ray_tpu._worker.get_client()
    dumps = client.control("stack", {"worker_id": None, "timeout": 4.0})
    assert dumps, "no stacks collected"
    text = "\n".join(d["stacks"] for d in dumps.values())
    assert "very_recognizable_busy_loop" in text, \
        f"busy function missing from stacks:\n{text[:2000]}"
    assert ray_tpu.get(ref, timeout=60) == 1


def test_pubsub_publish_poll_across_processes(cluster):
    from ray_tpu.util.pubsub import Publisher, Subscriber

    sub = Subscriber("test_chan")

    @ray_tpu.remote
    def announce(i):
        from ray_tpu.util.pubsub import Publisher as P
        return P("test_chan").publish({"i": i})

    seqs = ray_tpu.get([announce.remote(i) for i in range(3)], timeout=60)
    assert len(set(seqs)) == 3
    got = []
    deadline = time.time() + 20
    while len(got) < 3 and time.time() < deadline:
        got.extend(sub.poll(timeout=5.0))
    assert sorted(m["i"] for m in got) == [0, 1, 2]
    # cursor advanced: nothing new -> empty poll, fast
    assert sub.poll(timeout=0.2) == []


def test_pubsub_ring_cap(cluster, monkeypatch):
    # the cap is re-resolved from the environment at publish time, so a
    # small override actually exercises the trim branch
    monkeypatch.setenv("RAY_TPU_PUBSUB_RING_MESSAGES", "10")
    client = ray_tpu._worker.get_client()
    for i in range(25):
        client.control("pubsub_publish",
                       {"channel": "cap_chan", "message": i})
    last, msgs = client.control(
        "pubsub_poll", {"channel": "cap_chan", "after": 0,
                        "timeout": 0.0})
    assert last == 25
    assert len(msgs) == 10 and msgs == list(range(15, 25))


def test_usage_stats_local_and_optin_report(cluster, monkeypatch):
    """Usage stats (reference: _private/usage/usage_lib.py:92): local
    session snapshot always works; network reporting requires BOTH the
    explicit opt-in env AND a configured URL (zero-egress default)."""
    import os
    from ray_tpu._private import usage_stats as us

    us.record_library_usage("unit_test_lib")
    node = ray_tpu._worker.get_client().node
    path = us.write_local(node)
    assert path and os.path.exists(path)
    with open(path) as f:
        payload = json.load(f)
    assert payload["total_num_nodes"] >= 1
    assert "unit_test_lib" in payload["libraries"]
    assert payload["ray_tpu_version"] == ray_tpu.__version__

    # off by default, even with a URL configured
    monkeypatch.delenv("RAY_TPU_USAGE_STATS_ENABLED", raising=False)
    monkeypatch.setenv("RAY_TPU_USAGE_STATS_URL",
                       "http://127.0.0.1:1/nope")
    assert us.maybe_report(node) is False

    # opted in: POSTs the payload to the configured endpoint
    import http.server
    import threading
    got = {}

    class H(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            got["body"] = json.loads(self.rfile.read(n))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=srv.handle_request, daemon=True)
    t.start()
    monkeypatch.setenv("RAY_TPU_USAGE_STATS_ENABLED", "1")
    monkeypatch.setenv(
        "RAY_TPU_USAGE_STATS_URL",
        f"http://127.0.0.1:{srv.server_address[1]}/usage")
    assert us.maybe_report(node) is True
    t.join(timeout=5)
    srv.server_close()
    assert "unit_test_lib" in got["body"]["libraries"]
