"""Serve-equivalent tests (modeled on the reference's `serve/tests/`:
test_api, test_deploy, test_autoscaling_policy, test_batching)."""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_session(ray_session):
    yield serve
    serve.shutdown()


@serve.deployment(num_replicas=2)
class Doubler:
    def __call__(self, x):
        return x * 2


def test_deploy_and_call(serve_session):
    handle = serve.run(Doubler.bind(), name="t_basic")
    assert handle.call(21) == 42
    refs = [handle.remote(i) for i in range(10)]
    assert ray_tpu.get(refs, timeout=60) == [i * 2 for i in range(10)]


def test_composition_handles(serve_session):
    @serve.deployment
    class Ingress:
        def __init__(self, doubler):
            self.doubler = doubler

        def __call__(self, x):
            return self.doubler.call(x) + 1

    h = serve.run(Ingress.bind(Doubler.bind()), name="t_comp")
    assert h.call(10) == 21
    st = serve.status()
    assert st["t_comp:Ingress"]["status"] == "RUNNING"
    assert st["t_comp:Doubler"]["replicas"] == 2


def test_method_calls_and_function_deployment(serve_session):
    @serve.deployment
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self, by):
            self.n += by
            return self.n

        def __call__(self, x):
            return x

    h = serve.run(Counter.bind(), name="t_method")
    assert h.incr.call(5) == 5
    assert h.incr.call(3) == 8

    @serve.deployment
    def square(x):
        return x * x

    hf = serve.run(square.bind(), name="t_fn")
    assert hf.call(7) == 49


def test_http_proxy(serve_session):
    @serve.deployment
    class Echo:
        def __call__(self, req):
            return {"path": req.path, "q": req.query,
                    "body": req.json()}

    serve.run(Echo.bind(), name="t_http")
    proxy = serve.start(http_options={"port": 0})
    info = ray_tpu.get(proxy.ready.remote(), timeout=30)
    serve.set_route("/echo", "Echo", "t_http")
    url = f"http://127.0.0.1:{info['port']}/echo?a=1"
    resp = urllib.request.urlopen(urllib.request.Request(
        url, data=json.dumps({"hi": 5}).encode()))
    out = json.loads(resp.read())
    assert out == {"path": "/echo", "q": {"a": "1"}, "body": {"hi": 5}}
    # 404 for unknown route
    try:
        urllib.request.urlopen(
            f"http://127.0.0.1:{info['port']}/nope")
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404


def test_priority_rides_the_serve_path(serve_session):
    """A request's priority class travels handle -> replica contextvar
    (and proxy header -> handle.options), with the deployment's
    `default_priority` as the fallback — the serve-side plumbing of the
    engine's priority classes."""
    @serve.deployment(default_priority=1)
    class WhatClass:
        def __call__(self, req):
            return serve.get_request_priority()

    h = serve.run(WhatClass.bind(), name="t_prio")
    assert h.call(0) == 1                       # deployment default
    assert h.options(priority=3).call(0) == 3   # per-call override
    assert h.call(0) == 1                       # options() didn't stick

    proxy = serve.start(http_options={"port": 0})
    info = ray_tpu.get(proxy.ready.remote(), timeout=30)
    serve.set_route("/prio", "WhatClass", "t_prio")
    base = f"http://127.0.0.1:{info['port']}/prio"
    req = urllib.request.Request(base, data=b"{}")
    req.add_header("X-Serve-Priority", "2")
    assert json.loads(urllib.request.urlopen(req).read()) == 2
    assert json.loads(urllib.request.urlopen(
        urllib.request.Request(f"{base}?priority=4",
                               data=b"{}")).read()) == 4
    try:
        urllib.request.urlopen(urllib.request.Request(
            base, data=b"{}", headers={"X-Serve-Priority": "nope"}))
        assert False, "expected 400"
    except urllib.error.HTTPError as e:
        assert e.code == 400


def test_batching(serve_session):
    @serve.deployment(max_concurrent_queries=16)
    class Batched:
        def __init__(self):
            self.sizes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
        def handle(self, xs):
            self.sizes.append(len(xs))
            return [x + 100 for x in xs]

        def __call__(self, x):
            return self.handle(x)

        def get_sizes(self):
            return self.sizes

    h = serve.run(Batched.bind(), name="t_batch")
    outs = ray_tpu.get([h.remote(i) for i in range(8)], timeout=60)
    assert sorted(outs) == [100 + i for i in range(8)]
    assert max(h.get_sizes.call()) > 1      # actually batched


def test_autoscaling_up(serve_session):
    @serve.deployment(autoscaling_config={
        "min_replicas": 1, "max_replicas": 3,
        "target_num_ongoing_requests_per_replica": 1,
        "downscale_delay_s": 300})
    class Slow:
        def __call__(self, x):
            time.sleep(1.5)
            return x

    h = serve.run(Slow.bind(), name="t_auto")
    refs = [h.remote(i) for i in range(12)]
    grew = False
    for _ in range(8):
        time.sleep(0.5)
        st = serve.status()["t_auto:Slow"]
        if st["target_replicas"] >= 2:
            grew = True
            break
    ray_tpu.get(refs, timeout=120)
    assert grew, serve.status()


def test_replica_restart_on_death(serve_session):
    @serve.deployment(num_replicas=1)
    class Svc:
        def __call__(self, x):
            return x + 1

    h = serve.run(Svc.bind(), name="t_restart")
    assert h.call(1) == 2
    # kill the replica behind the controller's back
    from ray_tpu.serve.controller import get_controller
    c = get_controller()
    _, replicas = ray_tpu.get(
        c.get_replicas.remote("Svc", "t_restart", -1), timeout=30)
    ray_tpu.kill(replicas[0])
    # controller health check replaces it; handle retries through death
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            assert h.call(5, timeout=10) == 6
            break
        except Exception:
            time.sleep(0.5)
    else:
        pytest.fail("replica never recovered")


def test_redeploy_updates_code(serve_session):
    @serve.deployment
    class V:
        def __call__(self, x):
            return "v1"

    h = serve.run(V.bind(), name="t_upgrade")
    assert h.call(0) == "v1"

    @serve.deployment(name="V")
    class V2:
        def __call__(self, x):
            return "v2"

    h2 = serve.run(V2.bind(), name="t_upgrade")
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            if h2.call(0) == "v2":
                break
        except Exception:
            pass
        time.sleep(0.3)
    else:
        pytest.fail("redeploy never took effect")

    serve.delete("t_upgrade")
    assert "t_upgrade:V" not in serve.status()


def test_streaming_response_http(serve_session):
    """A generator deployment streams chunked bytes through the proxy —
    the response arrives incrementally, not as one buffered body
    (reference: streaming replies, _private/replica.py:249)."""
    @serve.deployment
    class Streamer:
        def __call__(self, req):
            def gen():
                for i in range(40):
                    yield f"chunk-{i};"
            return serve.StreamingResponse(gen(), content_type="text/plain")

    serve.run(Streamer.bind(), name="streamapp")
    proxy = serve.start(http_options={"port": 0})
    info = ray_tpu.get(proxy.ready.remote(), timeout=30)
    serve.set_route("/stream", "Streamer", "streamapp")
    url = f"http://127.0.0.1:{info['port']}/stream"
    resp = urllib.request.urlopen(url, timeout=60)
    assert resp.headers.get("Transfer-Encoding") == "chunked"
    body = resp.read().decode()
    assert body == "".join(f"chunk-{i};" for i in range(40))


def test_streaming_via_handle(serve_session):
    """Python-side streaming consumption without HTTP."""
    @serve.deployment
    class Gen:
        def __call__(self, n):
            def producer():
                for i in range(n):
                    yield i * i
            return producer()

    serve.run(Gen.bind(), name="genapp")
    h = serve.get_deployment_handle("Gen", "genapp")
    got = list(h.stream(5))
    assert got == [0, 1, 4, 9, 16]


def test_proxy_concurrent_requests(serve_session):
    """Slow replicas must not serialize the proxy: 8 concurrent requests
    against 2 replicas of a 0.4s deployment finish in ~4 batch rounds,
    far under the 3.2s serial floor."""
    import concurrent.futures

    @serve.deployment(num_replicas=2, max_concurrent_queries=4)
    class Slow:
        def __call__(self, req):
            time.sleep(0.4)
            return "ok"

    serve.run(Slow.bind(), name="slowapp")
    proxy = serve.start(http_options={"port": 0})
    info = ray_tpu.get(proxy.ready.remote(), timeout=30)
    serve.set_route("/slow", "Slow", "slowapp")
    url = f"http://127.0.0.1:{info['port']}/slow"

    def one(_):
        return urllib.request.urlopen(url, timeout=60).read()

    t0 = time.time()
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as pool:
        results = list(pool.map(one, range(8)))
    elapsed = time.time() - t0
    assert all(r == b"ok" for r in results)
    assert elapsed < 2.4, f"proxy serialized requests: {elapsed:.2f}s"


def test_async_replica_soak_1k_concurrent(serve_session):
    """1000 concurrent slow requests overlap on ONE replica's event loop
    (reference: serve's async replica, `serve/_private/replica.py:429`).
    Thread-per-call would need 1000 threads; serialized execution would
    take ~1000s. The async replica holds them all on awaits."""
    @serve.deployment(max_concurrent_queries=1000)
    class Slow:
        async def __call__(self, i):
            import asyncio
            await asyncio.sleep(1.0)
            return i

    h = serve.run(Slow.bind(), name="t_soak")
    assert ray_tpu.get(h.remote(-1), timeout=60) == -1   # warm
    t0 = time.time()
    out = ray_tpu.get([h.remote(i) for i in range(1000)], timeout=240)
    dt = time.time() - t0
    assert out == list(range(1000))
    assert dt < 60, f"requests serialized: {dt:.1f}s for 1000x1s"
