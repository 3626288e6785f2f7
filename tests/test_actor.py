"""Actor tests, modeled on the reference's `python/ray/tests/test_actor.py`:
lifecycle, ordering, named actors, restarts, kill, concurrency."""

import time

import pytest

import ray_tpu
from ray_tpu.actor import wait_for_actor_ready
from ray_tpu.exceptions import ActorDiedError

# these tests make actors and drop the handles
pytestmark = pytest.mark.usefixtures("kills_its_actors")


@ray_tpu.remote
class Counter:
    def __init__(self, start=0):
        self.x = start

    def incr(self, n=1):
        self.x += n
        return self.x

    def value(self):
        return self.x

    def crash(self):
        import os
        os._exit(1)


def test_actor_basic(ray_session):
    c = Counter.remote(10)
    assert ray_tpu.get(c.incr.remote()) == 11
    assert ray_tpu.get(c.incr.remote(5)) == 16
    assert ray_tpu.get(c.value.remote()) == 16


def test_actor_method_ordering(ray_session):
    c = Counter.remote(0)
    refs = [c.incr.remote() for _ in range(20)]
    assert ray_tpu.get(refs) == list(range(1, 21))


def test_actor_handle_passed_to_task(ray_session):
    c = Counter.remote(0)

    @ray_tpu.remote
    def bump(counter, n):
        return ray_tpu.get(counter.incr.remote(n))

    assert ray_tpu.get(bump.remote(c, 42)) == 42


def test_named_actor(ray_session):
    Counter.options(name="the-counter").remote(5)
    h = ray_tpu.get_actor("the-counter")
    assert ray_tpu.get(h.incr.remote()) == 6


def test_get_actor_missing(ray_session):
    with pytest.raises(ValueError):
        ray_tpu.get_actor("no-such-actor")


def test_duplicate_actor_name_rejected(ray_session):
    Counter.options(name="dup-name").remote()
    with pytest.raises(Exception):
        h = Counter.options(name="dup-name").remote()
        wait_for_actor_ready(h, timeout=30)


def test_actor_constructor_error(ray_session):
    @ray_tpu.remote
    class Bad:
        def __init__(self):
            raise RuntimeError("ctor fails")

        def ping(self):
            return "pong"

    b = Bad.remote()
    with pytest.raises((RuntimeError, ActorDiedError)):
        ray_tpu.get(b.ping.remote(), timeout=60)


def test_actor_death_fails_pending(ray_session):
    c = Counter.remote(0)
    assert ray_tpu.get(c.incr.remote()) == 1
    c.crash.remote()
    with pytest.raises(ActorDiedError):
        ray_tpu.get(c.value.remote(), timeout=60)


def test_actor_restart(ray_session):
    # max_task_retries stays 0 so the crashing call itself is NOT replayed
    # on the restarted instance (replaying it would crash-loop, same as the
    # reference).
    c = Counter.options(max_restarts=2).remote(0)
    assert ray_tpu.get(c.incr.remote()) == 1
    c.crash.remote()
    # After restart state resets to the constructor args (reference
    # semantics: restarted actors rerun __init__).
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            assert ray_tpu.get(c.incr.remote(), timeout=30) >= 1
            break
        except ActorDiedError:
            time.sleep(0.5)
    else:
        pytest.fail("actor never came back")


def test_kill_actor(ray_session):
    c = Counter.remote(0)
    assert ray_tpu.get(c.incr.remote()) == 1
    ray_tpu.kill(c)
    with pytest.raises(ActorDiedError):
        ray_tpu.get(c.incr.remote(), timeout=60)


def test_actor_max_concurrency(ray_session):
    @ray_tpu.remote
    class Sleeper:
        def nap(self, t):
            time.sleep(t)
            return t

    s = Sleeper.options(max_concurrency=4).remote()
    t0 = time.time()
    refs = [s.nap.remote(1.0) for _ in range(4)]
    ray_tpu.get(refs, timeout=60)
    # 4 overlapping 1s naps should take well under 4s.
    assert time.time() - t0 < 3.5


def test_method_num_returns(ray_session):
    @ray_tpu.remote
    class Splitter:
        @ray_tpu.method(num_returns=2)
        def pair(self):
            return "a", "b"

    s = Splitter.remote()
    a, b = s.pair.remote()
    assert ray_tpu.get([a, b]) == ["a", "b"]


# ---------------------------------------------------------------------------
# asyncio actors (reference: async actor execution, _private/async_compat.py
# + async execute_task in _raylet.pyx — any `async def` method switches the
# actor onto a per-actor event loop with max_concurrency as a semaphore)
# ---------------------------------------------------------------------------

def test_async_actor_overlapping_awaits(ray_session):
    @ray_tpu.remote
    class Signal:
        def __init__(self):
            import asyncio
            self.event = asyncio.Event()

        async def wait(self):
            await self.event.wait()
            return "released"

        async def release(self):
            self.event.set()
            return True

    s = Signal.remote()
    # wait() blocks on an asyncio.Event only a SECOND concurrently
    # running method can set: deadlocks unless calls overlap on one loop
    r1 = s.wait.remote()
    time.sleep(0.3)
    r2 = s.release.remote()
    assert ray_tpu.get(r2, timeout=30) is True
    assert ray_tpu.get(r1, timeout=30) == "released"


def test_async_actor_default_high_concurrency(ray_session):
    @ray_tpu.remote
    class Napper:
        async def nap(self, i):
            import asyncio
            await asyncio.sleep(0.5)
            return i

    n = Napper.remote()
    t0 = time.time()
    out = ray_tpu.get([n.nap.remote(i) for i in range(20)], timeout=60)
    # async actors default to max_concurrency=1000: 20 naps overlap
    assert time.time() - t0 < 4.0
    assert sorted(out) == list(range(20))


def test_async_actor_semaphore_limit(ray_session):
    @ray_tpu.remote(max_concurrency=2)
    class Two:
        async def nap(self):
            import asyncio
            await asyncio.sleep(0.4)
            return 1

    t = Two.remote()
    t0 = time.time()
    ray_tpu.get([t.nap.remote() for _ in range(6)], timeout=60)
    dt = time.time() - t0
    # 6 naps through a 2-permit semaphore: 3 serialized waves
    assert dt > 1.0, f"semaphore not enforced ({dt:.2f}s)"


def test_async_actor_sync_methods_and_errors(ray_session):
    @ray_tpu.remote
    class Mixed:
        async def boom(self):
            raise ValueError("async boom")

        def plain(self):
            return "sync-ok"

    m = Mixed.remote()
    assert ray_tpu.get(m.plain.remote(), timeout=30) == "sync-ok"
    with pytest.raises(Exception, match="async boom"):
        ray_tpu.get(m.boom.remote(), timeout=30)


def test_failed_constructor_recycles_pooled_worker(ray_session):
    """A pooled worker converted into an actor host goes back to the
    pool when the user constructor raises — repeated creation failures
    must not strand healthy workers."""
    import ray_tpu
    from ray_tpu import exceptions as exc

    @ray_tpu.remote(num_cpus=0)
    class Broken:
        def __init__(self):
            raise RuntimeError("nope")

        def ping(self):
            return 1

    @ray_tpu.remote(num_cpus=0)
    class Fine:
        def ping(self):
            return 1

    for _ in range(6):
        b = Broken.remote()
        with pytest.raises(exc.RayTpuError):
            ray_tpu.get(b.ping.remote(), timeout=60)
    # the pool is intact: a healthy actor still comes up quickly
    f = Fine.remote()
    assert ray_tpu.get(f.ping.remote(), timeout=60) == 1
    ray_tpu.kill(f)
