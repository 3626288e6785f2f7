"""Object spilling, memory pressure, and Data byte-budget backpressure.

Counterpart of the reference's `test_object_spilling.py` +
`test_memory_pressure.py` suites: arena overflow and proactive high-water
spilling land objects on real disk (bounded shm), the memory monitor kills
a retriable worker instead of letting the OS OOM, and the Data executor's
byte budget caps in-flight bytes.
"""

import os
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, env_extra: dict) -> str:
    env = dict(os.environ)
    env.update(env_extra)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_arena_overflow_and_proactive_spill(tmp_path):
    """With a 4 MiB arena: overflow puts land on disk, and a spill pass
    drains the arena below the low-water mark while every value stays
    readable; shutdown removes the spill dir."""
    script = textwrap.dedent(f"""
        import sys; sys.path.insert(0, {REPO!r})
        import glob, os
        import numpy as np
        import ray_tpu
        from ray_tpu._private.worker import get_client

        ray_tpu.init(num_cpus=2)
        node = get_client().node
        refs = [ray_tpu.put(np.full(1_000_000, i, np.uint8))
                for i in range(12)]
        node._maybe_spill()
        st = node.store.arena_stats()
        if st is not None:
            assert st["used"] <= 0.5 * st["capacity"] + 1_100_000, st
        spilled = glob.glob(os.path.join(node.store._spill_dir, "obj_*"))
        assert spilled, "expected spill files on disk"
        # tmpfs per-object fallback must stay unused (bounded shm)
        assert not os.listdir(node.store._dir)
        for i, r in enumerate(refs):
            a = ray_tpu.get(r)
            assert int(a[0]) == i and len(a) == 1_000_000
        spill_dir = node.store._spill_dir
        ray_tpu.shutdown()
        assert not os.path.exists(spill_dir)
        print("SPILL-OK")
    """)
    out = _run(script, {
        "RAY_TPU_OBJECT_STORE_BYTES": str(4 * 1024 * 1024),
        "RAY_TPU_OBJECT_SPILL_ROOT": str(tmp_path),
        "RAY_TPU_SPILL_HIGH_WATER": "0.5",
        "RAY_TPU_SPILL_LOW_WATER": "0.2",
    })
    assert "SPILL-OK" in out


def test_data_pipeline_4x_arena_completes(tmp_path):
    """A Data pipeline whose working set is ~4x the arena finishes with
    bounded shm usage: blocks overflow to
    the disk spill dir, never to tmpfs fallback files."""
    script = textwrap.dedent(f"""
        import sys; sys.path.insert(0, {REPO!r})
        import os
        import numpy as np
        import ray_tpu
        from ray_tpu import data as rtd
        from ray_tpu._private.worker import get_client

        ray_tpu.init(num_cpus=2)
        node = get_client().node

        def blow_up(row):
            return {{"z": np.full(1_000_000, row["item"], np.uint8)}}

        ds = rtd.from_items(list(range(16)), parallelism=16).map(blow_up)
        total = 0
        for row in ds.iter_rows():
            total += int(row["z"][0])
        assert total == sum(range(16)), total
        assert not os.listdir(node.store._dir)   # no tmpfs overflow
        ray_tpu.shutdown()
        print("CHURN-OK")
    """)
    out = _run(script, {
        "RAY_TPU_OBJECT_STORE_BYTES": str(4 * 1024 * 1024),
        "RAY_TPU_OBJECT_SPILL_ROOT": str(tmp_path),
    })
    assert "CHURN-OK" in out


def test_memory_monitor_kills_and_task_retries(ray_session):
    """Forced memory pressure kills the newest retriable worker; the task
    retries and completes (worker_killing_policy_retriable_fifo.h)."""
    from ray_tpu._private.memory_monitor import MemoryMonitor
    from ray_tpu._private.worker import get_client

    node = get_client().node

    @ray_tpu.remote(max_retries=2, num_cpus=1)
    def sleepy():
        time.sleep(3.0)
        return "done"

    ref = sleepy.remote()
    deadline = time.time() + 30
    mon = MemoryMonitor(node, threshold=0.5, usage_fn=lambda: 0.99)
    while time.time() < deadline:
        if mon.tick():
            break
        time.sleep(0.2)
    else:
        pytest.fail("monitor never found a busy worker to kill")
    assert mon.kills == 1
    assert ray_tpu.get(ref, timeout=120) == "done"


def test_memory_monitor_noop_below_threshold(ray_session):
    from ray_tpu._private.memory_monitor import MemoryMonitor
    from ray_tpu._private.worker import get_client

    mon = MemoryMonitor(get_client().node, threshold=0.9,
                        usage_fn=lambda: 0.1)
    assert not mon.tick()
    assert mon.kills == 0


def test_data_byte_budget_correctness(ray_session):
    """A 1-byte in-flight budget degrades to serial execution but keeps
    results correct and ordered."""
    from ray_tpu import data as rtd
    from ray_tpu.data.context import DataContext

    ctx = DataContext.get_current()
    old = ctx.max_bytes_in_flight
    ctx.max_bytes_in_flight = 1
    try:
        ds = rtd.from_items(list(range(8))).map(
            lambda r: {"v": r["item"] * 2})
        vals = [r["v"] for r in ds.iter_rows()]
        assert vals == [i * 2 for i in range(8)]
    finally:
        ctx.max_bytes_in_flight = old


def test_inflight_budget_math():
    from ray_tpu.data._internal.execution import _InFlightBudget
    from ray_tpu.data.context import DataContext

    ctx = DataContext.get_current()
    b = _InFlightBudget(ctx, max_tasks=4)
    b.max_bytes = 100
    assert b.admit(60)          # empty window always admits
    b.add(60)
    assert b.admit(40)
    b.add(40)
    assert not b.admit(1)       # byte-capped
    b.remove(60)
    assert b.admit(10)
    b.add(10)
    b.add(10)
    b.add(10)                   # 4 tasks now
    assert not b.admit(1)       # slot-capped
