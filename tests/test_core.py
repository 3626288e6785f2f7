"""Core task/object API tests.

Modeled on the reference's `python/ray/tests/test_basic.py` /
`test_advanced.py` coverage: put/get roundtrips, task graphs, error
propagation, multiple returns, nested tasks, wait semantics.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import GetTimeoutError, TaskCancelledError


def test_put_get_roundtrip(ray_session):
    for value in [1, "x", None, {"a": [1, 2]}, (1, 2), b"bytes", 3.5,
                  {1, 2, 3}]:
        assert ray_tpu.get(ray_tpu.put(value)) == value


def test_put_get_numpy_zero_copy(ray_session):
    arr = np.arange(500_000, dtype=np.float64)
    out = ray_tpu.get(ray_tpu.put(arr))
    np.testing.assert_array_equal(arr, out)
    # Large arrays come back as read-only views over shared memory,
    # like the reference's plasma-backed arrays.
    assert not out.flags.writeable


def test_simple_task(ray_session):
    @ray_tpu.remote
    def f(x):
        return x * 2

    assert ray_tpu.get(f.remote(21)) == 42


def test_task_kwargs_and_defaults(ray_session):
    @ray_tpu.remote
    def f(a, b=10, *, c=100):
        return a + b + c

    assert ray_tpu.get(f.remote(1)) == 111
    assert ray_tpu.get(f.remote(1, b=2, c=3)) == 6


def test_task_dependency_chain(ray_session):
    @ray_tpu.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(10):
        ref = inc.remote(ref)
    assert ray_tpu.get(ref) == 11


def test_task_fanout_fanin(ray_session):
    @ray_tpu.remote
    def sq(x):
        return x * x

    @ray_tpu.remote
    def total(*xs):
        return sum(xs)

    refs = [sq.remote(i) for i in range(10)]
    assert ray_tpu.get(total.remote(*refs)) == sum(i * i for i in range(10))


def test_num_returns(ray_session):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c]) == [1, 2, 3]


def test_error_propagation_type_preserved(ray_session):
    @ray_tpu.remote
    def boom():
        raise KeyError("missing")

    with pytest.raises(KeyError):
        ray_tpu.get(boom.remote())


def test_error_poisons_downstream(ray_session):
    @ray_tpu.remote
    def boom():
        raise ValueError("root cause")

    @ray_tpu.remote
    def consume(x):
        return x

    with pytest.raises(ValueError, match="root cause"):
        ray_tpu.get(consume.remote(boom.remote()))


def test_large_arg_promoted_to_store(ray_session):
    payload = np.random.default_rng(0).standard_normal(300_000)

    @ray_tpu.remote
    def total(x):
        return float(np.sum(x))

    assert ray_tpu.get(total.remote(payload)) == pytest.approx(
        float(np.sum(payload)))


def test_nested_task_submission(ray_session):
    @ray_tpu.remote
    def child(x):
        return x + 1

    @ray_tpu.remote
    def parent(x):
        return ray_tpu.get(child.remote(x)) + 100

    assert ray_tpu.get(parent.remote(1)) == 102


def test_get_timeout(ray_session):
    @ray_tpu.remote
    def slow():
        time.sleep(5)
        return 1

    with pytest.raises(GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.2)


def test_wait_basic(ray_session):
    @ray_tpu.remote
    def fast():
        return 1

    @ray_tpu.remote
    def slow():
        time.sleep(3)
        return 2

    f, s = fast.remote(), slow.remote()
    ready, not_ready = ray_tpu.wait([f, s], num_returns=1, timeout=2)
    assert ready == [f] and not_ready == [s]


def test_wait_rejects_duplicates(ray_session):
    r = ray_tpu.put(1)
    with pytest.raises(ValueError):
        ray_tpu.wait([r, r])


def test_max_retries_on_crash(ray_session):
    import os as _os

    @ray_tpu.remote(max_retries=2)
    def flaky(marker_dir):
        # die the first time, succeed on retry (crash, not exception)
        import os
        marker = os.path.join(marker_dir, "attempted")
        if not os.path.exists(marker):
            open(marker, "w").close()
            os._exit(1)
        return "recovered"

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        assert ray_tpu.get(flaky.remote(d), timeout=60) == "recovered"


def test_retry_exceptions(ray_session):
    @ray_tpu.remote(max_retries=3, retry_exceptions=True)
    def sometimes(marker_dir):
        import os
        marker = os.path.join(marker_dir, "n")
        n = len(os.listdir(marker_dir))
        open(marker + str(n), "w").close()
        if n < 2:
            raise RuntimeError("transient")
        return n

    import tempfile
    with tempfile.TemporaryDirectory() as d:
        assert ray_tpu.get(sometimes.remote(d), timeout=60) == 2


def test_cancel_pending(ray_session):
    @ray_tpu.remote
    def blocked(x):
        return x

    dep = ray_tpu.ObjectRef("obj_never_materializes")
    ref = blocked.remote(dep)
    assert ray_tpu.cancel(ref)
    with pytest.raises(TaskCancelledError):
        ray_tpu.get(ref, timeout=5)


def test_cluster_resources(ray_session):
    total = ray_tpu.cluster_resources()
    assert total["CPU"] == 4.0


def test_tpu_task_gets_chips(ray_session):
    @ray_tpu.remote(num_tpus=1)
    def which_chips():
        import os
        return os.environ.get("TPU_VISIBLE_CHIPS")

    chips = ray_tpu.get(which_chips.remote(), timeout=120)
    assert chips is not None and chips != ""
    # chip + TPU resource return to the pool afterwards
    deadline = time.time() + 30
    while time.time() < deadline:
        if ray_tpu.available_resources().get("TPU") == 2.0:
            break
        time.sleep(0.2)
    assert ray_tpu.available_resources()["TPU"] == 2.0


def test_object_ref_future(ray_session):
    @ray_tpu.remote
    def v():
        return 7

    assert v.remote().future().result(timeout=30) == 7


def test_config_table():
    """Typed option table: every RAY_TPU_ knob is declared once with
    type/default/doc, env overrides parse per type, and the CLI renderer
    sees them (reference: ray_config_def.h + ReadEnv)."""
    import os

    from ray_tpu._private import constants  # noqa: F401  (registers opts)
    from ray_tpu._private.config import OPTIONS, describe, get

    assert len(OPTIONS) >= 15
    rows = describe()
    assert all(r["doc"] for r in rows)
    assert get("SPILL_HIGH_WATER") == constants.SPILL_HIGH_WATER
    os.environ["RAY_TPU_SPILL_HIGH_WATER"] = "0.66"
    try:
        assert get("SPILL_HIGH_WATER") == 0.66
        assert any(r["name"] == "SPILL_HIGH_WATER" and r["overridden"]
                   for r in describe())
    finally:
        del os.environ["RAY_TPU_SPILL_HIGH_WATER"]
    os.environ["RAY_TPU_MAX_WORKERS_CAP"] = "notanint"
    try:
        import pytest
        with pytest.raises(ValueError):
            get("MAX_WORKERS_CAP")
    finally:
        del os.environ["RAY_TPU_MAX_WORKERS_CAP"]


def test_independent_task_not_stalled_by_blocked_backlog(ray_session):
    """A deep backlog of dep-BLOCKED tasks must not delay an
    independent task's dispatch (the pure-enqueue submit path still
    signals the scheduler)."""
    import time
    import ray_tpu

    @ray_tpu.remote
    def slow():
        import time as _t
        _t.sleep(3.0)
        return 1

    @ray_tpu.remote
    def dependent(x):
        return x

    @ray_tpu.remote
    def quick():
        return "now"

    gate = slow.remote()
    blocked = [dependent.remote(gate) for _ in range(64)]
    t0 = time.perf_counter()
    out = ray_tpu.get(quick.remote(), timeout=60)
    dt = time.perf_counter() - t0
    assert out == "now"
    assert dt < 2.0, f"independent task stalled {dt:.2f}s behind a " \
                     "blocked backlog"
    ray_tpu.get(blocked, timeout=120)


def test_blocked_worker_does_not_pin_pool_cap():
    """A worker blocked in get() has released its lease, so it must not
    count against MAX_WORKERS_CAP. With a cap of 1, every level of a
    nested-get chain needs a replacement worker while its parent sits
    blocked — if blocked workers held their pool slot the leaf task
    could never run (regression: push-based shuffle deadlocked once all
    32 slots held reduce tasks blocked on their mergers)."""
    import os
    import subprocess
    import sys
    import textwrap

    child = textwrap.dedent("""
        import ray_tpu
        ray_tpu.init(num_cpus=4)

        @ray_tpu.remote
        def leaf():
            return 1

        @ray_tpu.remote
        def mid():
            return ray_tpu.get(leaf.remote()) + 1

        @ray_tpu.remote
        def top():
            return ray_tpu.get(mid.remote()) + 1

        print("RESULT", ray_tpu.get(top.remote(), timeout=90))

        # replacement workers spawned past the cap while their peers
        # were blocked must retire once the pool goes idle again
        import time
        from ray_tpu._private import worker as worker_mod
        node = worker_mod.get_client().node
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            n = sum(1 for w in node.workers.values()
                    if w.kind == "generic" and w.alive)
            if n <= 1:
                break
            time.sleep(0.5)
        assert n <= 1, f"pool did not shrink back to cap: {n}"
        print("RESULT2", ray_tpu.get(leaf.remote(), timeout=60))
        ray_tpu.shutdown()
    """)
    env = dict(os.environ, RAY_TPU_MAX_WORKERS_CAP="1")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "RESULT 3" in r.stdout
    assert "RESULT2 1" in r.stdout


def test_shutdown_is_bounded_by_a_constant_not_by_what_is_left():
    """`ray_tpu.shutdown()` ends a session of sixteen actors in under 8 s
    and leaves none of their processes: four were killed before (the
    factory's zombies each cost the old teardown a second), one is inside a
    method that sleeps 120 s, one more ignores SIGTERM in such a method,
    one is stopped (it reads its connection no more and takes no SIGTERM).
    The old per-worker terminate / wait(1.0) / kill loop needed 16 s and
    more."""
    import os
    import subprocess
    import sys
    import textwrap

    child = textwrap.dedent("""
        import os, signal, time
        import ray_tpu
        ray_tpu.init(num_cpus=4)

        @ray_tpu.remote(num_cpus=0)
        class A:
            def pid(self):
                return os.getpid()
            def sleep(self, deaf):
                if deaf:
                    signal.signal(signal.SIGTERM, signal.SIG_IGN)
                time.sleep(120)

        actors = [A.remote() for _ in range(16)]
        pids = ray_tpu.get([a.pid.remote() for a in actors], timeout=120)
        for a in actors[3:7]:
            ray_tpu.kill(a)
        actors[0].sleep.remote(False)
        actors[1].sleep.remote(True)
        os.kill(pids[2], signal.SIGSTOP)
        time.sleep(1.0)
        t0 = time.monotonic()
        ray_tpu.shutdown()
        print("SHUTDOWN_S", time.monotonic() - t0)
        def runs(pid):
            try:
                with open(f"/proc/{pid}/stat") as f:
                    return f.read().rsplit(")", 1)[1].split()[0] != "Z"
            except OSError:
                return False
        print("LEFT", [p for p in pids if runs(p)])
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", child], env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    took = float(r.stdout.split("SHUTDOWN_S")[1].split()[0])
    assert took < 8.0, r.stdout
    assert "LEFT []" in r.stdout, r.stdout


def test_a_factory_whose_spawner_died_at_once_does_not_stay():
    """The worker factory watches the pid its spawner gave it. It used to
    ask `os.getppid()` once its imports were done: a driver that had died
    by then had already handed it to another parent, and the factory
    (with the stdio it inherited) lived on; two were left by a whole
    tier-1 run."""
    import os
    import subprocess
    import sys
    import textwrap

    child = textwrap.dedent("""
        import os, threading, time
        from ray_tpu._private import spawn
        threading.Thread(target=spawn._forkserver._ensure,
                         args=(b"k" * 16,), daemon=True).start()
        time.sleep(0.15)        # the factory is still importing
        print(os.getpid(), flush=True)
        os._exit(0)
    """)
    r = subprocess.run([sys.executable, "-c", child], text=True,
                       stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                       timeout=60)
    sock = f"ray_tpu_fs_{int(r.stdout)}.sock"
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        left = []
        for pid in filter(str.isdigit, os.listdir("/proc")):
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as f:
                    if sock.encode() in f.read():
                        left.append(pid)
            except OSError:
                pass
        if not left:
            return
        time.sleep(0.2)
    raise AssertionError(f"factory of a dead spawner still runs: {left}")
