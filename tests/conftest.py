"""Test harness configuration.

SPMD tests run on a virtual 8-device CPU mesh via
``--xla_force_host_platform_device_count`` — the counterpart of the
reference's one-host multi-raylet ``Cluster`` fixture trick
(`python/ray/cluster_utils.py:99`): fake resources let a laptop test
multi-device logic (SURVEY.md §4.2).
"""

import faulthandler
import os
import signal
import sys
import time

# Must happen before any jax import anywhere in the test session.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from ray_tpu.util.compile_cache import enable_compile_cache  # noqa: E402

# Engines and trainers are rebuilt per test as fresh jit closures over the
# same tiny configs, so most XLA:CPU compiles of a run repeat an earlier
# one that jit's own per-function cache cannot see. JAX's persistent cache
# answers the repeats, from the first run on. It is set in this process
# only (workers and subprocesses inherit no config), at the fixed path
# the chip processes default to; tests that compile for a described TPU
# turn it off around themselves.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# Every test has a limit of its own, and so has the teardown of the shared
# session behind the last test of a process. TEST_LIMIT_S is five times the
# slowest test of a whole tier-1 run: 42.1 s in a run of 611 s here (PR 67,
# `test_latent_sparse_moe.py::test_paged_prefill_and_decode_match_the_reference[pallas]`),
# 54 s at the 784 s the same run has taken elsewhere, 176 s with eight busy
# loops beside six workers on eight cores. TEARDOWN_LIMIT_S is six times the
# 9 s that bound `NodeServer.shutdown` (a lock, a daemon, three graces).
# At the limit a SIGALRM handler fails the test from the main thread with
# every thread's stack in its report, which ends any wait Python can
# interrupt (a lock, a queue, a socket, `get()` without a timeout); a wait it
# cannot (a futex under a C call that holds the GIL) meets faulthandler's
# timer LIMIT_GRACE_S later: stacks to stderr and `_exit`, xdist reports the
# test as failed where its worker died and hands the rest to a new one.
TEST_LIMIT_S = 270.0
TEARDOWN_LIMIT_S = 60.0
LIMIT_GRACE_S = 30.0

_stderr = sys.__stderr__


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (full tuned regressions)")
    # global capture is suspended here, so fd 2 is the process's own stderr:
    # faulthandler's last words must not end in a capture file
    global _stderr
    _stderr = os.fdopen(os.dup(2), "w")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    limit = TEST_LIMIT_S + (TEARDOWN_LIMIT_S if nextitem is None else 0.0)

    def over(signum, frame):
        from ray_tpu._private.worker_main import _format_stacks
        pytest.fail(f"{item.nodeid} passed its limit of {limit:.0f} s; "
                    f"every thread:\n{_format_stacks()}", pytrace=False)

    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + LIMIT_GRACE_S, exit=True,
                                      file=_stderr)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.fixture(scope="session")
def ray_session():
    """One shared local session for all tests (worker spawn is ~2s on the
    1-CPU CI box, so tests share a pool like the reference's
    ray_start_regular fixture, conftest.py:410)."""
    import ray_tpu
    # num_tpus=2 fakes two chips (resources are scheduler numbers, like the
    # reference's Cluster.add_node(num_gpus=8) on a laptop, SURVEY.md §4).
    ray_tpu.init(num_cpus=4, num_tpus=2, ignore_reinit_error=True)
    yield ray_tpu
    # Telemetry-plane self-test before teardown: the whole session's
    # metric registry must still render parseable Prometheus, every
    # span ring must honor its bound, and every retrace sentinel must
    # still be watching its pinned paths.
    from ray_tpu.util import telemetry
    telemetry.check_invariants()
    ray_tpu.shutdown()


def _kill_actors(actors):
    from ray_tpu._private.worker import get_client
    for a in actors:
        get_client().control("kill_actor", {"actor_id": a["actor_id"],
                                            "no_restart": True})


def _actors_since(before):
    from ray_tpu.util import state
    return [a for a in state.list_actors()
            if a["state"] != "DEAD" and a["actor_id"] not in before]


@pytest.fixture
def kills_its_actors(ray_session):
    """For a test that makes actors and only drops their handles (an actor
    lives until it is killed or its session ends): at the test's end, those
    it made are killed."""
    from ray_tpu.util import state
    before = {a["actor_id"] for a in state.list_actors()}
    yield
    _kill_actors(_actors_since(before))


def _left_in_session(actors_before, grace: float = 5.0):
    """What the shared session holds beyond what it held at a file's start,
    once `grace` seconds have not brought it back: live actors the file
    made, placement groups, and CPUs or TPUs that are still taken."""
    import ray_tpu
    from ray_tpu.util import state
    deadline = time.monotonic() + grace
    while True:
        actors = _actors_since(actors_before)
        groups = state.list_placement_groups()
        total, free = ray_tpu.cluster_resources(), ray_tpu.available_resources()
        taken = {k: total[k] - free.get(k, 0) for k in ("CPU", "TPU")
                 if free.get(k, 0) != total.get(k, 0)}
        if not (actors or groups or taken) or time.monotonic() > deadline:
            return actors, groups, taken
        time.sleep(0.05)


@pytest.fixture(scope="module", autouse=True)
def session_given_back(request):
    """A file gives the shared session back as it found it: one session
    serves every file a process runs, in whatever order `--dist loadfile`
    hands them out, so what a file leaves behind is the next file's
    cluster. At the end of a file that used `ray_session`, every CPU and
    TPU is free again and no actor the file made is alive; what is left is
    killed, and the file's last test errors with its names."""
    if not any("ray_session" in getattr(it, "fixturenames", ())
               for it in request.session.items
               if getattr(it, "module", None) is request.module):
        yield
        return
    ray_tpu = request.getfixturevalue("ray_session")
    from ray_tpu._private.worker import get_client
    from ray_tpu.util import state
    before = {a["actor_id"] for a in state.list_actors()}
    yield
    if not ray_tpu.is_initialized():
        return
    actors, groups, taken = _left_in_session(before)
    if not (actors or groups or taken):
        return
    _kill_actors(actors)
    for g in groups:
        get_client().control("remove_pg", g["placement_group_id"])
    _, _, still = _left_in_session(before)
    pytest.fail(
        f"{request.module.__name__} left in the shared session: actors "
        f"{[(a['class_name'], a['name'] or a['actor_id']) for a in actors]}, "
        f"placement groups {[g['placement_group_id'] for g in groups]}, "
        f"taken {taken} (after the kills: {still})", pytrace=False)
