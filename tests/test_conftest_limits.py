"""What `tests/conftest.py` promises every tier-1 run: a test that waits
for ever fails alone and the run goes on, and a file that keeps a piece of
the shared session is told so. Each case runs pytest in a subprocess on
files of its own, with this directory's conftest loaded as a plugin and
its limits (constants, no option sets them) cut to seconds."""

import os
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))

RUNNER = """
import sys
sys.path[:0] = [{here!r}, {repo!r}]
import conftest
conftest.TEST_LIMIT_S, conftest.TEARDOWN_LIMIT_S, conftest.LIMIT_GRACE_S = \\
    {limits}
import pytest
sys.exit(pytest.main([{root!r}, "--rootdir", {root!r}, "-p", "conftest",
                      "-p", "no:cacheprovider", "-q"]))
"""


def _run_under_conftest(root, limits, **files):
    for name, body in files.items():
        (root / f"{name}.py").write_text(textwrap.dedent(body))
    runner = RUNNER.format(here=HERE, repo=os.path.dirname(HERE),
                           limits=limits, root=str(root))
    return subprocess.run([sys.executable, "-c", runner],
                          capture_output=True, text=True, timeout=240)


def test_a_test_that_waits_for_ever_fails_alone(tmp_path):
    r = _run_under_conftest(tmp_path, (2.0, 1.0, 30.0), test_two="""
        import threading

        def test_blocks():
            threading.Event().wait()

        def test_passes():
            pass
        """)
    assert "1 failed, 1 passed" in r.stdout, r.stdout + r.stderr
    assert "test_two.py::test_blocks passed its limit of 2 s" in r.stdout
    # the report holds the stack of the thread that stood
    assert "--- thread MainThread ---" in r.stdout
    assert "threading.Event().wait()" in r.stdout


def test_a_wait_no_signal_ends_takes_the_process_with_its_stacks(tmp_path):
    """Under the GIL in a futex (a mutex locked twice) no Python handler
    runs: faulthandler's timer prints every thread to the process's own
    stderr and exits, which xdist reports as that test's failure."""
    r = _run_under_conftest(tmp_path, (1.0, 1.0, 2.0), test_futex="""
        import ctypes

        def test_blocks_in_c():
            libc = ctypes.PyDLL(None)
            mutex = ctypes.create_string_buffer(64)
            libc.pthread_mutex_lock(mutex)
            libc.pthread_mutex_lock(mutex)
        """)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "Timeout (0:00:04)!" in r.stderr    # limit, teardown, grace
    assert "in test_blocks_in_c" in r.stderr


def test_a_file_that_keeps_a_cpu_is_told_and_the_next_finds_it_free(tmp_path):
    r = _run_under_conftest(tmp_path, (120.0, 60.0, 30.0), test_a_keeps="""
        import ray_tpu

        def test_makes_an_actor(ray_session):
            @ray_tpu.remote(num_cpus=1)
            class Holder:
                def ping(self):
                    return 1
            assert ray_tpu.get(Holder.remote().ping.remote(), timeout=60) == 1

        def test_last_of_the_file(ray_session):
            pass
        """, test_b_finds_it_free="""
        import ray_tpu

        def test_every_cpu_is_free(ray_session):
            assert (ray_tpu.available_resources()["CPU"]
                    == ray_tpu.cluster_resources()["CPU"])
        """)
    assert "3 passed" in r.stdout and "1 error" in r.stdout, \
        r.stdout + r.stderr
    assert "ERROR at teardown of test_last_of_the_file" in r.stdout
    assert "test_a_keeps left in the shared session" in r.stdout
    assert "Holder" in r.stdout and "taken {'CPU': 1.0}" in r.stdout
