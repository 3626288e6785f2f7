"""Core-runtime scalability benchmark -> SCALE.json.

Counterpart of the reference's `python/ray/_private/ray_perf.py:93`
microbenchmark suites + the release scalability envelope
(`release/benchmarks/README.md:8-31`: 1M queued tasks, 10k concurrent,
40k actors, 1 GiB broadcast). Suites here measure the same axes at a
scale one machine can hold, and record the machine shape next to every
number so the envelope is honest:

  queued_tasks        submit 100k no-op tasks before draining any
  task_throughput     no-op tasks/s through the pool (warm workers)
  actor_creation      actor processes created/s (modest N; process-per-
                      actor on this box)
  actor_call_rate     pipelined method calls/s on one actor
  small_put_get       1 KiB put+get round trips/s
  store_bandwidth     25 MiB put+get GB/s through the shm arena
  broadcast_1gib      one 1 GiB object read by tasks on N daemon nodes

Run: python scale_bench.py [--queued 100000] [--actors 200] [--out SCALE.json]
The reference package is not installed in this container (zero-egress
image), so `ray_comparison` records the published envelope instead of a
same-container measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import time


def bench_queued_tasks(ray_tpu, n: int) -> dict:
    @ray_tpu.remote
    def nop():
        return None

    # warm one worker so drain isn't dominated by first-spawn
    ray_tpu.get(nop.remote())
    t0 = time.perf_counter()
    refs = [nop.remote() for _ in range(n)]
    t_submit = time.perf_counter() - t0
    t1 = time.perf_counter()
    ray_tpu.get(refs)
    t_drain = time.perf_counter() - t1
    # absorb the 100k-ObjectRef release storm HERE: the batched decref
    # flood (and the head's free processing) otherwise lands in the
    # middle of the next suite's window (the same isolation _settle
    # exists for)
    del refs
    ray_tpu.get(ray_tpu.put(1))
    time.sleep(3.0)
    return {
        "queued": n,
        "submit_per_s": round(n / t_submit, 1),
        "drain_per_s": round(n / t_drain, 1),
        # submit is now a pure enqueue (no inline dispatch when the
        # backlog is deep), so dispatch work that used to overlap the
        # submit window lands in the drain window; the end-to-end rate
        # is the number the two split views can't misrepresent
        "end_to_end_per_s": round(n / (t_submit + t_drain), 1),
        "submit_s": round(t_submit, 2),
        "drain_s": round(t_drain, 2),
    }


def bench_task_throughput(ray_tpu, n: int = 2000) -> dict:
    @ray_tpu.remote
    def nop():
        return None

    ray_tpu.get([nop.remote() for _ in range(20)])
    t0 = time.perf_counter()
    ray_tpu.get([nop.remote() for _ in range(n)])
    dt = time.perf_counter() - t0
    return {"tasks": n, "tasks_per_s": round(n / dt, 1)}


def _settle(ray_tpu, timeout: float = 120.0) -> None:
    """Wait until dying worker processes are reaped, so one suite's
    teardown storm (e.g. 200 actor exits) can't pollute the next
    suite's numbers on a small box."""
    client = ray_tpu._worker.get_client()
    deadline = time.time() + timeout
    while time.time() < deadline:
        workers = client.control("list_workers")
        if sum(1 for w in workers if w.get("alive")) <= 4:
            return
        time.sleep(0.5)


def bench_actor_creation(ray_tpu, n: int) -> dict:
    @ray_tpu.remote(num_cpus=0)
    class A:
        def ping(self):
            return 1

    t0 = time.perf_counter()
    actors = [A.remote() for _ in range(n)]
    ray_tpu.get([a.ping.remote() for a in actors])
    dt = time.perf_counter() - t0
    for a in actors:
        ray_tpu.kill(a)
    _settle(ray_tpu)
    return {"actors": n, "created_per_s": round(n / dt, 2),
            "total_s": round(dt, 1)}


def bench_actor_calls(ray_tpu, n: int = 2000) -> dict:
    @ray_tpu.remote(num_cpus=0)
    class Counter:
        def __init__(self):
            self.i = 0

        def inc(self):
            self.i += 1
            return self.i

    a = Counter.remote()
    ray_tpu.get(a.inc.remote())
    t0 = time.perf_counter()
    out = ray_tpu.get([a.inc.remote() for _ in range(n)])
    dt = time.perf_counter() - t0
    assert out[-1] == n + 1
    ray_tpu.kill(a)
    return {"calls": n, "calls_per_s": round(n / dt, 1)}


def bench_small_put_get(ray_tpu, n: int = 500) -> dict:
    import numpy as np
    arr = np.zeros(256, np.float32)   # 1 KiB
    for _ in range(20):   # warm the path (same courtesy the task suites get)
        ray_tpu.get(ray_tpu.put(arr))
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(ray_tpu.put(arr))
    dt = time.perf_counter() - t0
    return {"round_trips": n, "per_s": round(n / dt, 1)}


def bench_small_put_get_zero_copy(ray_tpu, n: int = 300) -> dict:
    """The two small-object fast paths the zero-copy rework targets:
    1 KiB values ride inline in the descriptor (no store file at all);
    256 KiB values land in the shm arena and `get` must hand back an
    arena-backed read-only view, not an intermediate bytes copy."""
    import numpy as np
    small = np.zeros(256, np.float32)          # 1 KiB -> inline
    big = np.zeros(64 * 1024, np.float32)      # 256 KiB -> arena
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(ray_tpu.put(small))
    dt_small = time.perf_counter() - t0
    t1 = time.perf_counter()
    for _ in range(n):
        out = ray_tpu.get(ray_tpu.put(big))
    dt_big = time.perf_counter() - t1
    # zero-copy evidence: the array is a view over store memory (has a
    # base buffer and is read-only), not a freshly-owned copy
    zero_copy = bool(out.base is not None and not out.flags.writeable)
    return {
        "round_trips": n,
        "inline_1kib_per_s": round(n / dt_small, 1),
        "arena_256kib_per_s": round(n / dt_big, 1),
        "arena_gb_per_s": round(n * big.nbytes / dt_big / 1e9, 3),
        "arena_zero_copy_view": zero_copy,
    }


def parity_workload(n_tasks: int = 2000, n_puts: int = 200) -> dict:
    """One self-contained session: pipelined-submit n_tasks, drain, then
    n_puts put/get round trips — returning rates AND output digests so
    two runs with different channel settings can be checked for
    bit-identical results (batching must change timing, never values).
    Run via `scale_bench.py --parity-child N M` so the framing/pipeline
    env flags are construction-time fresh."""
    import hashlib

    import numpy as np

    import ray_tpu
    from ray_tpu._private import config

    ray_tpu.init(num_cpus=4)

    @ray_tpu.remote
    def affine(i):
        return i * 3 + 1

    ray_tpu.get(affine.remote(0))    # warm one worker
    t0 = time.perf_counter()
    refs = [affine.remote(i) for i in range(n_tasks)]
    t_submit = time.perf_counter() - t0
    t1 = time.perf_counter()
    out = ray_tpu.get(refs)
    t_drain = time.perf_counter() - t1

    arr = np.arange(256, dtype=np.float32)    # 1 KiB
    t2 = time.perf_counter()
    for _ in range(n_puts):
        got = ray_tpu.get(ray_tpu.put(arr))
    t_put = time.perf_counter() - t2
    digest = hashlib.sha256(np.asarray(got).tobytes()).hexdigest()
    doc = {
        "channel_batching": bool(config.get("CHANNEL_BATCHING")),
        "submit_pipeline": bool(config.get("SUBMIT_PIPELINE")),
        "tasks": n_tasks,
        "submit_per_s": round(n_tasks / t_submit, 1),
        "drain_per_s": round(n_tasks / t_drain, 1),
        "end_to_end_per_s": round(n_tasks / (t_submit + t_drain), 1),
        "put_get_per_s": round(n_puts / t_put, 1),
        # parity evidence: every task result and the round-tripped
        # object bytes, reduced to comparable values
        "task_checksum": sum(out),
        "object_digest": digest,
    }
    ray_tpu.shutdown()
    return doc


def bench_batched_vs_unbatched(n_tasks: int = 20_000,
                               n_puts: int = 500) -> dict:
    """Before/after envelope for the batched control plane: the same
    parity workload in two fresh processes — framing + pipelined
    submission ON (the default) vs the legacy per-message/per-ack wire
    — with output parity asserted, not assumed."""
    import subprocess
    import sys

    out = {}
    for label, flag in (("batched", "1"), ("unbatched", "0")):
        env = dict(os.environ,
                   RAY_TPU_CHANNEL_BATCHING=flag,
                   RAY_TPU_SUBMIT_PIPELINE=flag)
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--parity-child",
             str(n_tasks), str(n_puts)],
            env=env, capture_output=True, text=True, timeout=1200)
        if r.returncode != 0:
            raise RuntimeError(f"{label} parity child failed:\n"
                               f"{r.stdout}\n{r.stderr}")
        out[label] = json.loads(r.stdout.strip().splitlines()[-1])
    b, u = out["batched"], out["unbatched"]
    if (b["task_checksum"] != u["task_checksum"]
            or b["object_digest"] != u["object_digest"]):
        raise AssertionError(
            f"batching changed RESULTS, not just timing: {b} vs {u}")
    out["output_parity"] = True
    out["speedup_end_to_end"] = round(
        b["end_to_end_per_s"] / u["end_to_end_per_s"], 2)
    out["speedup_submit"] = round(b["submit_per_s"] / u["submit_per_s"], 2)
    out["speedup_put_get"] = round(b["put_get_per_s"] / u["put_get_per_s"],
                                   2)
    return out


def bench_store_bandwidth(ray_tpu, n: int = 40) -> dict:
    import numpy as np
    big = np.zeros(25_000_000 // 4, np.float32)   # 25 MiB
    t0 = time.perf_counter()
    for _ in range(n):
        ray_tpu.get(ray_tpu.put(big))
    dt = time.perf_counter() - t0
    return {"mib": 25, "reps": n,
            "gb_per_s": round(n * big.nbytes / dt / 1e9, 2)}


def bench_broadcast(ray_tpu, cluster, gib: float = 1.0,
                    n_nodes: int = 2) -> dict:
    import numpy as np
    node_ids = [cluster.add_node({"CPU": 1, f"bx{i}": 1})
                for i in range(n_nodes)]

    payload = np.ones(int(gib * (1 << 30) // 4), np.float32)

    @ray_tpu.remote
    def reduce_sum(a):
        return float(a[::4096].sum())

    def fanout():
        t_put0 = time.perf_counter()
        ref = ray_tpu.put(payload)
        t_put = time.perf_counter() - t_put0
        t0 = time.perf_counter()
        refs = [reduce_sum.options(resources={f"bx{i}": 1}).remote(ref)
                for i in range(n_nodes)]
        out = ray_tpu.get(refs, timeout=600)
        dt = time.perf_counter() - t0
        assert all(abs(v - out[0]) < 1e-3 for v in out)
        del refs, ref
        ray_tpu.get(ray_tpu.put(1))   # drain the decref batch promptly
        return t_put, dt

    # Steady state, not first touch: this box is a microVM with lazy
    # host memory — the FIRST write of any page costs a hypervisor
    # fault (~0.26 GB/s); recycled arena blocks run at memory speed.
    # A real cluster streams through warm, recycled blocks, so the
    # steady-state number is the framework's throughput and the cold
    # pass would measure the hypervisor. Two warm passes to converge.
    fanout()
    time.sleep(3)
    fanout()
    time.sleep(3)
    t_put, dt = fanout()
    for nid in node_ids:
        cluster.kill_node(nid)
    total_bytes = payload.nbytes * n_nodes
    return {"gib": gib, "nodes": n_nodes, "put_s": round(t_put, 2),
            "fanout_s": round(dt, 2),
            "aggregate_gb_per_s": round(total_bytes / dt / 1e9, 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--queued", type=int, default=100_000)
    ap.add_argument("--actors", type=int, default=200)
    ap.add_argument("--broadcast-gib", type=float, default=1.0)
    ap.add_argument("--broadcast-nodes", type=int, default=2)
    ap.add_argument("--out", default="SCALE.json")
    ap.add_argument("--parity-child", nargs=2, type=int, metavar=("N", "M"),
                    help="internal: run the parity workload (N tasks, M "
                         "put/gets) in THIS process and print JSON")
    args = ap.parse_args()

    if args.parity_child:
        print(json.dumps(parity_workload(*args.parity_child)))
        return

    os.environ.setdefault("RAY_TPU_OBJECT_STORE_BYTES",
                          str(4 * (1 << 30)))   # 1 GiB payloads fit
    import ray_tpu
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(head_resources={"CPU": max(4, os.cpu_count() or 1)})

    results = {}
    # queued_tasks runs LAST among the task suites: its 100k-ObjectRef
    # release storm drains for a long tail and was bleeding into the
    # suites measured after it
    results["task_throughput"] = bench_task_throughput(ray_tpu)
    results["actor_call_rate"] = bench_actor_calls(ray_tpu)
    results["actor_creation"] = bench_actor_creation(ray_tpu, args.actors)
    results["small_put_get"] = bench_small_put_get(ray_tpu)
    results["small_put_get_zero_copy"] = bench_small_put_get_zero_copy(
        ray_tpu)
    results["store_bandwidth"] = bench_store_bandwidth(ray_tpu)
    results["queued_tasks"] = bench_queued_tasks(ray_tpu, args.queued)
    _settle(ray_tpu)
    results["broadcast_1gib"] = bench_broadcast(
        ray_tpu, cluster, args.broadcast_gib, args.broadcast_nodes)
    # last: spawns its own fresh sessions in subprocesses, so the
    # parent cluster must be idle while they run
    results["batched_vs_unbatched"] = bench_batched_vs_unbatched()

    # Per-stage control-plane attribution over everything this run
    # submitted (submit→queue→dispatch→execute→result_put→got): the
    # before/after ledger each scheduler-throughput PR is judged by.
    client = ray_tpu._worker.get_client()
    stage_breakdown = client.control("stage_breakdown")

    doc = {
        "machine": {
            "cpus": os.cpu_count(),
            "platform": platform.platform(),
            "variance_note": "single-run numbers on a shared-core "
                             "microVM: repeated full runs observed "
                             "±25% on queued_tasks and up to 4x on the "
                             "put/get suites — compare envelopes across "
                             "machine classes, not runs",
        },
        "results": results,
        "stage_breakdown": stage_breakdown,
        "ray_comparison": {
            "same_container": None,
            "note": "reference ray package not installed in this "
                    "zero-egress container; published envelope for "
                    "context (release/benchmarks/README.md:8-31): 1M+ "
                    "tasks queued on one m4.16xlarge (64 cores), 10k+ "
                    "concurrent tasks / 40k+ actors on a 64-node "
                    "cluster, 1 GiB broadcast to 50+ nodes. This box "
                    "has 1 core; numbers above are per-core envelope "
                    "points, not cluster ceilings.",
        },
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
    print(json.dumps(doc["results"], indent=2))
    cluster.shutdown()


if __name__ == "__main__":
    main()
