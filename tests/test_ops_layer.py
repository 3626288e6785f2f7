"""Dashboard HTTP API, job submission, and CLI session attach.

Counterpart of the reference's `dashboard/modules/job/tests/`,
`python/ray/tests/test_dashboard.py`, and the state-CLI tests: REST
endpoints serve live state; jobs run as managed subprocesses with status
and captured logs; an external process attaches to the session socket.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.job_submission import JobSubmissionClient


@pytest.fixture
def cluster(ray_session):
    return ray_session


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


def test_dashboard_healthz_and_state(cluster, dashboard_port):
    @ray_tpu.remote
    def dash_task():
        return 1

    ray_tpu.get(dash_task.remote())
    assert _get(dashboard_port, "/healthz") == {"status": "ok"}
    nodes = _get(dashboard_port, "/api/nodes")
    assert nodes and nodes[0]["resources_total"]["CPU"] > 0
    tasks = _get(dashboard_port, "/api/tasks")
    assert any("dash_task" in t["name"] for t in tasks)
    assert isinstance(_get(dashboard_port, "/api/workers"), list)
    assert isinstance(_get(dashboard_port, "/api/summary"), dict)
    from ray_tpu.util import metrics as m
    m.Counter("dash_probe", "d").inc(1.0)
    text = _get(dashboard_port, "/metrics")
    assert "ray_tpu_dash_probe 1.0" in text   # prometheus exposition
    # timeseries gauge sample feeding the UI's sparkline charts
    snap = _get(dashboard_port, "/api/metrics_snapshot")
    assert snap["nodes_alive"] >= 1 and snap["workers_alive"] >= 1
    assert snap["ts"] > 0 and "store_used_bytes" in snap
    # the SPA shell + assets serve, and the app covers the reference
    # client's page families (dashboard/client/src/pages/)
    page = _get(dashboard_port, "/")
    assert 'src="/static/app.js"' in page
    app = _get(dashboard_port, "/static/app.js")
    for family in ("overview", "cluster", "jobs", "actors", "tasks",
                   "serve", "logs", "metrics"):
        assert f"pages.{family}" in app, family
    assert "metrics_snapshot" in app
    css = _get(dashboard_port, "/static/style.css")
    assert "--accent" in css
    # path traversal is rejected
    import urllib.error
    with pytest.raises(urllib.error.HTTPError):
        _get(dashboard_port, "/static/../__init__.py")
    # every API the SPA polls responds
    for route in ("/api/nodes", "/api/actors", "/api/tasks",
                  "/api/summary", "/api/jobs", "/api/logs",
                  "/api/serve/applications", "/api/metrics_snapshot"):
        _get(dashboard_port, route)
    # the status route brings the Serve controller up
    from ray_tpu import serve
    serve.shutdown()


def test_job_submit_success_and_logs(cluster):
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"print('job says hi')\"")
    status = client.wait_until_finished(job_id, timeout=60)
    assert status == "SUCCEEDED"
    assert "job says hi" in client.get_job_logs(job_id)
    info = client.get_job_info(job_id)
    assert info["returncode"] == 0
    assert any(j["job_id"] == job_id for j in client.list_jobs())


def test_job_failure_status(cluster):
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"import sys; sys.exit(3)\"")
    assert client.wait_until_finished(job_id, timeout=60) == "FAILED"
    assert client.get_job_info(job_id)["returncode"] == 3


def test_job_stop(cluster):
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=f"{sys.executable} -c \"import time; time.sleep(60)\"")
    time.sleep(0.3)
    assert client.stop_job(job_id)
    assert client.wait_until_finished(job_id, timeout=30) == "STOPPED"


def test_job_env_vars(cluster):
    client = JobSubmissionClient()
    job_id = client.submit_job(
        entrypoint=(f"{sys.executable} -c "
                    "\"import os; print(os.environ['MYVAR'], "
                    "os.environ['RAY_TPU_JOB_ID'])\""),
        runtime_env={"env_vars": {"MYVAR": "tpu42"}})
    assert client.wait_until_finished(job_id, timeout=60) == "SUCCEEDED"
    logs = client.get_job_logs(job_id)
    assert "tpu42" in logs and job_id in logs


def test_dashboard_job_rest(cluster, dashboard_port):
    req = urllib.request.Request(
        f"http://127.0.0.1:{dashboard_port}/api/jobs",
        data=json.dumps({
            "entrypoint": f"{sys.executable} -c \"print('rest job')\""
        }).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as r:
        job_id = json.loads(r.read())["job_id"]
    deadline = time.time() + 60
    while time.time() < deadline:
        info = _get(dashboard_port, f"/api/jobs/{job_id}")
        if info["status"] in ("SUCCEEDED", "FAILED"):
            break
        time.sleep(0.25)
    assert info["status"] == "SUCCEEDED"
    assert "rest job" in _get(dashboard_port, f"/api/jobs/{job_id}/logs")


def test_cli_attach_from_subprocess(cluster):
    """A separate process attaches to this session and reads state —
    the `ray status` path."""
    session_dir = ray_tpu._worker.get_client().node.session_dir
    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli",
         "--session", session_dir, "status"],
        capture_output=True, text=True, timeout=60,
        cwd="/root/repo")
    assert out.returncode == 0, out.stderr
    assert "CPU" in out.stdout and "workers:" in out.stdout

    out = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli",
         "--session", session_dir, "list", "nodes"],
        capture_output=True, text=True, timeout=60, cwd="/root/repo")
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout)[0]["alive"] is True


def test_attach_idle_longpoll_outlives_control_timeout(cluster):
    """An attach client whose default control deadline is SHORTER than a
    long-poll's server-side window must still get the empty batch back,
    not a spurious ConnectionError (ADVICE r3 #3: the transport deadline
    used to equal the server poll timeout exactly)."""
    session_dir = ray_tpu._worker.get_client().node.session_dir
    script = (
        "from ray_tpu._private.attach import AttachClient\n"
        f"c = AttachClient({session_dir!r})\n"
        "last, msgs = c.control('pubsub_poll',"
        " {'channel': 'idle_chan_never_published', 'after': 0,"
        "  'timeout': 4.0})\n"
        "assert msgs == [], msgs\n"
        "c.close()\n"
        "print('POLL_OK')\n")
    env = dict(os.environ)
    # client-side default deadline (2s) < server-side poll window (4s):
    # before the fix this raised ConnectionError at 2s
    env["RAY_TPU_ATTACH_CONTROL_TIMEOUT_S"] = "2.0"
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=60, cwd="/root/repo", env=env)
    assert out.returncode == 0, out.stderr
    assert "POLL_OK" in out.stdout
