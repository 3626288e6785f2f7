"""Remote-driver client mode (reference: Ray Client,
`util/client/worker.py:81` — ray.init("ray://...")): a second process
joins a live session with the full get/put/remote/actor API and leaves it
running on disconnect."""

import os
import subprocess
import sys
import textwrap

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_client(script: str):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)


def test_client_driver_full_api(ray_session):
    @ray_tpu.remote
    class KV:
        def __init__(self):
            self.d = {}

        def put(self, k, v):
            self.d[k] = v
            return True

        def get(self, k):
            return self.d.get(k)

    KV.options(name="client_kv", max_restarts=0).remote()
    # this session's own directory: "auto" is the newest live session on
    # the host, which under `pytest -n` is usually another worker's
    session_dir = ray_tpu._worker.get_client().node.session_dir

    script = textwrap.dedent(f"""
        import sys; sys.path.insert(0, {REPO!r})
        import numpy as np
        import ray_tpu

        client = ray_tpu.init(address={session_dir!r})
        assert client.mode == "worker"

        # tasks
        @ray_tpu.remote
        def double(x):
            return x * 2
        assert ray_tpu.get(double.remote(21), timeout=120) == 42

        # objects (big enough for the shm path)
        ref = ray_tpu.put(np.arange(300000, dtype=np.int32))
        assert int(ray_tpu.get(ref, timeout=60).sum()) == \\
            int(np.arange(300000).sum())

        # named actor created by the PRIMARY driver
        h = ray_tpu.get_actor("client_kv")
        assert ray_tpu.get(h.put.remote("x", 7), timeout=60)
        assert ray_tpu.get(h.get.remote("x"), timeout=60) == 7

        # actors created BY the client
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0
            def inc(self):
                self.n += 1
                return self.n
        c = Counter.remote()
        assert ray_tpu.get(c.inc.remote(), timeout=120) == 1
        ray_tpu.kill(c)

        # cluster state visible
        assert ray_tpu.cluster_resources().get("CPU", 0) > 0
        ray_tpu.shutdown()      # disconnect; session must survive
        print("CLIENT-OK")
    """)
    r = run_client(script)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "CLIENT-OK" in r.stdout

    # the session is still alive and the client's writes persisted
    h = ray_tpu.get_actor("client_kv")
    assert ray_tpu.get(h.get.remote("x"), timeout=60) == 7
    ray_tpu.kill(h)


def test_client_auto_attaches_to_a_live_session(ray_session):
    """`address="auto"` is the newest live session on this host: with
    several on it (other test workers', other tests' own) that says no
    more than that the client attaches to one and sees its resources. A
    session that is starting or leaving while the client looks for it is
    looked for again."""
    script = textwrap.dedent(f"""
        import sys, time; sys.path.insert(0, {REPO!r})
        import ray_tpu

        for attempt in range(20):
            try:
                client = ray_tpu.init(address="auto")
                assert client.mode == "worker"
                assert ray_tpu.cluster_resources().get("CPU", 0) > 0
                break
            except AssertionError:
                raise
            except Exception:
                if ray_tpu.is_initialized():
                    ray_tpu.shutdown()
                time.sleep(0.5)
        else:
            raise SystemExit("no live session could be joined")
        ray_tpu.shutdown()
        print("CLIENT-OK")
    """)
    r = run_client(script)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "CLIENT-OK" in r.stdout
    # this session outlived whatever the client joined and left
    assert ray_session.cluster_resources().get("CPU", 0) > 0
