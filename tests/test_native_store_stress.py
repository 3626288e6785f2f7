"""Native arena store concurrency stress harness.

Counterpart of the reference's plasma concurrency tests
(`src/ray/object_manager/test/` + TSAN/ASAN CI configs under `ci/`):
N worker PROCESSES hammer one shared arena with create/seal/pin/
acquire/read/delete while the arena stays over-subscribed (forcing the
LRU eviction and boundary-tag coalescing paths), one process gets
SIGKILLed mid-traffic and its pins force-reclaimed (robust-mutex +
release_all crash path), and every surviving read must be consistent
(each object is filled with a one-byte pattern; a torn or reused block
fails the checksum).

Run under sanitizers (separate instrumented .so, never the cached
release build):

    RAY_TPU_SANITIZE=thread  python -m pytest tests/test_native_store_stress.py
    RAY_TPU_SANITIZE=address python -m pytest tests/test_native_store_stress.py
"""

import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r"""
import os, random, sys
sys.path.insert(0, %(repo)r)
from ray_tpu._private.native.arena import Arena

session_dir, wid, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
arena = Arena.open(session_dir, capacity=%(capacity)d)
assert arena is not None, "native arena unavailable"
rng = random.Random(1000 + wid)

import time
# sealed and pinned before the crash: it must read back whatever happens
keep = arena.create(f"keep_{wid}", 4096)
assert keep is not None
keep[:] = bytes([wid + 1]) * 4096
arena.pin(f"keep_{wid}", 1)
arena.seal(f"keep_{wid}")
deadline = time.monotonic() + seconds
mine = []           # (oid, pattern, size) sealed by this worker
ops = sealed = read = evicted_reads = 0
while time.monotonic() < deadline:
    ops += 1
    roll = rng.random()
    if roll < 0.45 or not mine:
        # create -> fill with a pattern -> pin -> seal
        oid = f"obj_{wid}_{ops}"
        size = rng.choice((1 << 10, 16 << 10, 64 << 10, 200 << 10))
        buf = arena.create(oid, size)
        if buf is None:
            # arena full: evict unpinned sealed objects and retry once
            arena.evict(size * 2)
            buf = arena.create(oid, size)
            if buf is None:
                continue
        pattern = (wid * 31 + ops) %% 251 + 1
        buf[:] = bytes([pattern]) * size
        arena.pin(oid, 1)
        arena.seal(oid)
        mine.append((oid, pattern, size))
        sealed += 1
    elif roll < 0.75:
        # read-validate one of ours (we hold the owner pin, so the
        # bytes must NEVER be torn or reused underneath us)
        oid, pattern, size = rng.choice(mine)
        view = arena.acquire(oid)
        if view is None:
            raise AssertionError(f"pinned object {oid} vanished")
        b = view[rng.randrange(size)]
        if b != pattern:
            raise AssertionError(
                f"torn read on {oid}: {b} != {pattern}")
        view.release()
        arena.pin(oid, -1)
        read += 1
    elif roll < 0.9 and mine:
        # release + delete one of ours (frees or condemns)
        oid, pattern, size = mine.pop(rng.randrange(len(mine)))
        arena.pin(oid, -1)
        arena.delete(oid)
    else:
        # cross-worker probe: acquire someone else's object if present;
        # evicted/deleted is fine, torn bytes are not
        other = rng.randrange(%(workers)d)
        oid = f"obj_{other}_{rng.randrange(1, ops + 1)}"
        view = arena.acquire(oid)
        if view is None:
            evicted_reads += 1
        else:
            b0 = view[0]
            ok = all(view[i] == b0 for i in
                     rng.sample(range(len(view)), min(8, len(view))))
            view.release()
            arena.pin(oid, -1)
            if not ok:
                raise AssertionError(f"inconsistent fill in {oid}")
# poisoned or not (store.cc:lock() poisons when the lock's holder died),
# what this worker sealed and pinned stays readable
for oid, pattern, size in mine + [(f"keep_{wid}", wid + 1, 4096)]:
    view = arena.acquire(oid)
    assert view is not None and view[0] == pattern == view[size - 1], oid
    view.release()
    arena.pin(oid, -1)
print(f"worker {wid}: ops={ops} sealed={sealed} read={read} "
      f"missing_probes={evicted_reads} poisoned={int(arena.poisoned())}",
      flush=True)
"""


@pytest.mark.parametrize("n_workers,seconds", [(4, 6.0)])
def test_multiprocess_stress_with_crash(tmp_path, n_workers, seconds):
    capacity = 8 << 20      # 8 MiB arena, deliberately over-subscribed
    session = str(tmp_path)
    script = WORKER % {"repo": REPO, "capacity": capacity,
                       "workers": n_workers}
    procs = [
        subprocess.Popen([sys.executable, "-c", script, session, str(i),
                          str(seconds)],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True)
        for i in range(n_workers)
    ]
    # SIGKILL one worker mid-traffic. If the kill finds it inside the
    # lock, `store.cc:lock()` poisons the arena by contract (the free
    # list may be half written): sealed objects stay readable, nothing
    # is allocated or reused, callers fall back, and the next session's
    # arena is sound. If not, the crash-reclaim path must free its pins
    # so the arena doesn't leak to a halt. Either way no survivor may
    # sleep on the mutex for ever (a lost wake-up did that once in 25
    # loaded runs before lock() timed its waits).
    try:
        time.sleep(seconds / 3)
        procs[0].send_signal(signal.SIGKILL)
        outs = []
        for i, p in enumerate(procs[1:], start=1):
            out, _ = p.communicate(timeout=seconds * 10 + 60)
            outs.append(out)
            assert p.returncode == 0, f"worker {i} failed:\n{out}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.communicate()

    # reclaim every dead process's pins (what a daemon does on each
    # worker death — the SIGKILLed victim is the crash path, the clean
    # exits still hold their owner pins)
    from ray_tpu._private.native.arena import Arena
    arena = Arena.open(session, capacity=capacity)
    assert arena is not None
    for p in procs:
        arena.release_all(p.pid)
    for i in range(1, n_workers):
        view = arena.lookup(f"keep_{i}")
        assert view is not None and view[0] == i + 1 == view[4095]
    if arena.poisoned():
        arena.evict(capacity)
        assert arena.create("post_crash_probe", 1024) is None
        arena.close()
        fresh = tmp_path / "fresh"
        fresh.mkdir()
        arena = Arena.open(str(fresh), capacity=capacity)
    else:
        arena.evict(capacity)
    # after reclaim + eviction (or in the next session's arena) a fresh
    # create of half the capacity works
    buf = arena.create("post_crash_probe", capacity // 2)
    assert buf is not None, "arena leaked to death after crash reclaim"
    buf[:] = b"\x42" * (capacity // 2)
    arena.seal("post_crash_probe")
    view = arena.lookup("post_crash_probe")
    assert view is not None and view[0] == 0x42
    arena.close()
    assert any("sealed=" in o for o in outs)


def test_stress_under_sanitizer_smoke(tmp_path):
    """Build + run a short burst against the TSAN-instrumented library
    when a sanitizer build is requested (or as a plain smoke otherwise).
    Sanitizer findings abort the worker -> nonzero exit -> failure."""
    sanitize = os.environ.get("RAY_TPU_SANITIZE", "")
    env = dict(os.environ)
    if sanitize in ("thread", "address"):
        # a sanitized .so can only dlopen into a process with the
        # sanitizer runtime already mapped (static TLS); preload it
        lib = {"thread": "libtsan.so", "address": "libasan.so"}[sanitize]
        path = subprocess.run(["gcc", f"-print-file-name={lib}"],
                              capture_output=True, text=True,
                              check=True).stdout.strip()
        env["LD_PRELOAD"] = path
        # TSAN flags: fail loudly, but don't die on the expected
        # inter-process shared mapping (it only sees one process)
        env.setdefault("TSAN_OPTIONS", "halt_on_error=1")
        # leak detection off: LSan reports CPython's own interpreter
        # allocations; heap-overflow/UAF detection (the part that can
        # implicate store.cc) stays on
        env.setdefault("ASAN_OPTIONS",
                       "detect_leaks=0:halt_on_error=1")
    script = WORKER % {"repo": REPO, "capacity": 4 << 20, "workers": 2}
    procs = [
        subprocess.Popen([sys.executable, "-c", script, str(tmp_path),
                          str(i), "2.0"],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, env=env)
        for i in range(2)
    ]
    for i, p in enumerate(procs):
        out, _ = p.communicate(timeout=180)
        assert p.returncode == 0, \
            f"worker {i} failed under {sanitize or 'release'}:\n{out}"
