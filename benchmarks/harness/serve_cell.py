"""Runs one serving cell once: `ray_tpu.init()` -> `serve.run` of one
`BenchReplica` (an `InferenceReplica`, weights from `--seed`, the sizes of
the configuration's `program.serve` block) -> client threads that call
`DeploymentHandle.stream`. This process never touches JAX: the replica's
process holds the chip, and `device`, the peak memory, the profiler's
trace and the comparison with the reference all come from it.

A run, in order (every part but the window is set-up, and shown in
`setup_parts`):

  replica   `serve.run` until the replica answers: imports, the TPU
            runtime, the weights, the pool
  warm      one request per prefill chunk bucket, one of several chunks
            and one that shares a block and a half with it, drained
            inside the replica; the reference's program
  ramp      the load starts and runs until the population in flight is
            level: in an open loop for the mix's `ramp_s`; in a closed
            loop, whose clock is the system's own, until `ramp_requests`
            have been sent, so that the window opens at the same place
            in the work however long the ramp took
  window    `--seconds` (with `--trace 1` the mix's `trace_s`) inside the
            load; rates, gaps and first tokens are taken over it from
            the clients' clocks, the engine's counts are zeroed at its
            start and read at its end
  end       the load runs on until the requests that are compared
            (`traffic.check_plan`: the mix's, the same in every run) have
            streamed the tokens compared of them, `CHECK_WAIT_S` at the
            most; every other open request is cancelled (a drain of the
            longest would last two minutes and show nothing the window's
            own requests do not); the replica compares those requests
            with the reference; everything is shut down

An open-loop request is timed from when it was due, a closed-loop one
from when its client sent it.
"""

from __future__ import annotations

import glob
import os
import signal
import threading
import time

import numpy as np

from benchmarks.harness import trace as trace_mod
from benchmarks.harness import traffic as traffic_mod
from benchmarks.harness.common import BenchFailure, program_seed

CALL_TIMEOUT_S = 600
CHECK_WAIT_S = 60       # past the window's close, for a compared request


# ---------------------------------------------------------------------------
# the load
# ---------------------------------------------------------------------------

class Load:
    """Client threads over one handle. Closed loop: `clients` threads,
    each sending its next request when the last one has ended. Open
    loop: one scheduler that starts a thread per request when it is due.
    Every token's time at its client is kept."""

    def __init__(self, handle, mix: dict, requests):
        self.handle, self.mix, self.requests = handle, mix, requests
        self.records: list = []
        self.lateness_ms: list = []
        self.stopping = threading.Event()
        self._lock = threading.Lock()
        self._threads: list = []

    def start(self) -> None:
        self.t_load = time.perf_counter()
        if self.mix["loop"] == "closed":
            self._threads = [threading.Thread(target=self._closed_client,
                                              daemon=True)
                             for _ in range(self.mix["clients"])]
        else:
            self._threads = [threading.Thread(target=self._schedule,
                                              daemon=True)]
        for t in list(self._threads):
            t.start()

    def _closed_client(self) -> None:
        while not self.stopping.is_set():
            with self._lock:
                req = next(self.requests)
            self._serve_one(req, time.perf_counter())

    def _schedule(self) -> None:
        for req in self.requests:
            due = self.t_load + req["due_s"]
            if self.stopping.wait(max(0.0, due - time.perf_counter())):
                return
            self.lateness_ms.append((time.perf_counter() - due) * 1e3)
            t = threading.Thread(target=self._serve_one, args=(req, due),
                                 daemon=True)
            with self._lock:
                self._threads.append(t)
            t.start()

    def _serve_one(self, req: dict, t_ref: float) -> None:
        rec = {"index": req["index"], "t_ref": t_ref,
               "prompt": req["prompt"],
               "prompt_tokens": len(req["prompt"]),
               "asked": req["max_new_tokens"], "arrivals": [],
               "tokens": [], "logprobs": [], "ended": None}
        with self._lock:
            self.records.append(rec)
        try:
            stream = self.handle.stream(
                req["prompt"], req["max_new_tokens"],
                timeout=self.mix["request_timeout_s"])
            try:
                for tok in stream:
                    rec["arrivals"].append(time.perf_counter())
                    rec["tokens"].append(int(tok))
                    rec["logprobs"].append(float(tok.logprob))
                    if self.stopping.is_set():
                        break
            finally:
                stream.close()
            rec["ended"] = ("complete" if len(rec["tokens"]) == rec["asked"]
                            else "short")
        except Exception as e:              # counted as a failed request
            rec["ended"] = f"error: {e!r}"[:200]
        if rec["ended"] != "complete" and self.stopping.is_set():
            rec["ended"] = "cut"            # by the end of the load

    def await_streamed(self, plan: list, deadline: float) -> None:
        """Until every request of `plan` ([(index, tokens)]) has streamed
        so many tokens or has ended without them, `deadline` (on
        `time.perf_counter`) at the latest. The load runs on meanwhile:
        a request that is compared is served beside as many others as
        in the window."""
        def ready() -> bool:
            with self._lock:
                by_index = {r["index"]: r for r in self.records}
            return all(index in by_index
                       and (len(by_index[index]["tokens"]) >= n
                            or by_index[index]["ended"] is not None)
                       for index, n in plan)

        while not ready() and time.perf_counter() < deadline:
            time.sleep(0.05)

    def stop(self, timeout_s: float = 60.0) -> None:
        self.stopping.set()
        deadline = time.monotonic() + timeout_s
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        alive = sum(t.is_alive() for t in threads)
        if alive:
            raise BenchFailure(f"{alive} client threads did not end")


def check_sample(records: list, plan: list) -> tuple:
    """(what the replica compares, problems): of each request of `plan`
    ([(index, tokens)], `traffic.check_plan`) its prompt and its first
    `tokens` streamed tokens with their logprobs. A request of the plan
    that streamed fewer is a problem of its own and nothing takes its
    place: another request would make another sample, and the mean is
    weighted by tokens. `records` in any order (a closed loop's clients
    append theirs after they let the generator's lock go)."""
    by_index = {r["index"]: r for r in records}
    samples, problems = [], []
    for index, n in plan:
        r = by_index.get(index)
        if r is None or len(r["tokens"]) < n:
            problems.append(
                f"request {index} of the mix's first block is one of the "
                f"{len(plan)} compared with the reference and "
                + ("was never sent" if r is None else
                   f"streamed {len(r['tokens'])} of the {n} tokens "
                   f"compared of it ({r['ended']})")
                + ": nothing is compared in its place")
        else:
            samples.append({"index": index, "prompt": r["prompt"],
                            "tokens": r["tokens"][:n],
                            "logprobs": r["logprobs"][:n]})
    return samples, problems


def warm_prompts(info: dict, rng) -> list:
    """One prompt per chunk bucket (each a program), one of three chunks
    and a bit, and one that shares a block and a half with that one (the
    block-copy program)."""
    vocab = info["vocab_size"]
    prompts = [rng.integers(0, vocab, n, dtype=np.int32)
               for n in info["chunk_buckets"]]
    long = rng.integers(0, vocab, min(3 * info["prefill_chunk"] + 5,
                                      info["max_len"] // 2), dtype=np.int32)
    shared = long.copy()
    shared[info["block_size"] * 3 // 2:] = rng.integers(
        0, vocab, len(long) - info["block_size"] * 3 // 2, dtype=np.int32)
    return prompts + [long, shared]


# ---------------------------------------------------------------------------
# processes
# ---------------------------------------------------------------------------

def _ray_tpu_processes():
    """(pid, parent pid, command line) of every worker-side process of
    ray_tpu on this host."""
    for path in glob.glob("/proc/[0-9]*"):
        try:
            with open(path + "/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            with open(path + "/stat", "rb") as f:
                ppid = int(f.read().rsplit(b")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        if ("ray_tpu._private.worker_main" in cmd
                or "ray_tpu._private.forkserver" in cmd):
            yield int(os.path.basename(path)), ppid, cmd.strip()


def stop_workers() -> list:
    """After `ray_tpu.shutdown()`: wait for the workers to go, stop the
    fork factory (this process's own child, kept warm by design) and
    wait for it. Returns the workers that were left."""
    mine = os.getpid()
    deadline = time.monotonic() + 20
    while True:
        procs = list(_ray_tpu_processes())
        factories = [pid for pid, ppid, cmd in procs
                     if ppid == mine and "forkserver" in cmd]
        workers = [f"{pid}: {cmd}" for pid, ppid, cmd in procs
                   if pid not in factories and ppid in [mine] + factories]
        if not workers or time.monotonic() > deadline:
            break
        time.sleep(0.2)
    for pid in factories:
        os.kill(pid, signal.SIGTERM)
        os.waitpid(pid, 0)
    return workers


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------

def run(cell: dict, config: dict, mix: dict, *, seed: int, seconds: float,
        trace: bool, platform: str, scratch: str) -> dict:
    parts, last = {}, [time.perf_counter()]

    def mark(name: str) -> None:
        now = time.perf_counter()
        parts[name], last[0] = now - last[0], now

    import ray_tpu
    from benchmarks.harness.serve_replica import BenchReplica
    from ray_tpu import serve
    mark("imports")
    if ray_tpu._detect_tpu_chips() < cell["chips"]:
        raise BenchFailure(f"this machine shows {ray_tpu._detect_tpu_chips()}"
                           f" chip(s), the cell needs {cell['chips']}")
    block = config["program"]["serve"]
    window_s = min(seconds, float(mix["trace_s"])) if trace else seconds
    trace_dir = os.path.join(scratch, "trace") if trace else None
    ray_tpu.init()
    mark("init")
    failure = None
    try:
        app = serve.deployment(
            BenchReplica, num_replicas=1,
            ray_actor_options={"num_tpus": 1},
            max_concurrent_queries=block["max_concurrent_queries"],
        ).bind(config, seed=program_seed(seed))
        handle = serve.run(app, name=cell["name"].replace(".", "-"))
        handle._refresh(force=True)
        replica = handle._replicas[0]

        def in_replica(method: str, *args):
            return ray_tpu.get(
                replica.handle_method.remote(method, args, {}),
                timeout=CALL_TIMEOUT_S)

        info = in_replica("describe")
        if info["platform"] != platform:
            raise BenchFailure(f"the replica runs on {info['platform']}, "
                               f"the cell needs a {platform} chip")
        mark("replica")
        warmed = in_replica(
            "warm", warm_prompts(info, np.random.default_rng([seed, 11])),
            mix["warm_new_tokens"])
        mark("warm")
        load = Load(handle, mix, traffic_mod.serve_requests(
            mix, seed, info["vocab_size"]))
        load.start()
        if mix["loop"] == "closed":
            while len(load.records) < mix["ramp_requests"]:
                if time.perf_counter() - load.t_load > mix["ramp_s"]:
                    raise BenchFailure(
                        f"only {len(load.records)} of {mix['ramp_requests']}"
                        f" ramp requests sent in {mix['ramp_s']} s")
                time.sleep(0.01)
        else:
            time.sleep(mix["ramp_s"])
        in_replica("window_start", trace_dir)
        mark("ramp")
        setup_done = w0 = time.perf_counter()
        time.sleep(window_s)
        w1 = time.perf_counter()
        window = in_replica("window_stop")
        plan = traffic_mod.check_plan(mix)
        stopped = time.perf_counter()     # a traced window's stop: 40-50 s
        load.await_streamed(plan, w1 + CHECK_WAIT_S)
        waited_s = time.perf_counter() - stopped
        load.stopping.set()
        cancelled = in_replica("end_load")
        load.stop()
        samples, problems = check_sample(load.records, plan)
        verdict = in_replica("check", samples)
    except BenchFailure as e:
        failure = e
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
        left = stop_workers()
    if failure or left:
        raise failure or BenchFailure(f"workers left after shutdown: {left}")

    summary = (trace_mod.reduce(trace_mod.find_xplane(trace_dir))
               if trace else None)
    records = load.records
    ws = traffic_mod.window_stats(records, w0, w1)
    pct = traffic_mod.percentile
    engine = window["engine"]
    ended = [r for r in records if r["ended"] != "cut"]
    failed = [r for r in ended if r["ended"] != "complete"]
    tick_ms = (engine["tick_s"] / engine["ticks"] * 1e3
               if engine["ticks"] else 0.0)
    # p90: a generator short of CPU is late all the time; a machine that
    # stood still for seconds (one run in ten did, engine and all) makes
    # the few requests due meanwhile late, and the run is still sound
    late_p90 = pct(load.lateness_ms, 90) or 0.0
    late_limit = mix.get("late_limit_ms", tick_ms)      # one tick
    tol = config["tolerances"]
    checks = [
        ["logprob_max_abs", verdict["logprob_max_abs"],
         tol["logprob_max_abs"]],
        ["logprob_mean_abs", verdict["logprob_mean_abs"],
         tol["logprob_mean_abs"]],
        ["requests_compared", verdict["requests"], f"== {len(plan)}"],
        ["logprob_tokens_compared", verdict["tokens"],
         f"== {sum(n for _, n in plan)}"],
        ["failed_requests", len(failed), 0],
        ["programs_in_window", window["programs_in_window"], 0],
        ["retraces_unexpected", engine["retraces_unexpected"], 0],
        ["generator_late_ms_p90", late_p90, late_limit],
    ]
    if verdict["tokens"] and (
            verdict["logprob_max_abs"] > tol["logprob_max_abs"]
            or verdict["logprob_mean_abs"] > tol["logprob_mean_abs"]):
        problems.append(
            f"streamed logprobs are {verdict['logprob_max_abs']} (max) / "
            f"{verdict['logprob_mean_abs']} (mean) from the reference's, "
            f"limits {tol['logprob_max_abs']} / {tol['logprob_mean_abs']}")
    if failed:
        problems.append(f"{len(failed)} requests did not get every token "
                        f"they asked for: {failed[0]['ended']}")
    if window["programs_in_window"]:
        problems.append(f"{window['programs_in_window']} programs were "
                        f"compiled or loaded inside the window: "
                        f"{window['compiled']}")
    if engine["retraces_unexpected"]:
        problems.append(f"{engine['retraces_unexpected']} unexpected "
                        f"retraces")
    if late_p90 > late_limit:
        problems.append(f"the generator ran {late_p90:.1f} ms late (p90), "
                        f"more than {late_limit:.1f} ms (one tick, unless "
                        f"the mix says otherwise)")
    if not ws["tpot_ms"]:
        problems.append("no token arrived inside the window")

    end_to_end = {"serve_tokens_per_s": ws["tokens_per_s"]}
    for name, sample in (("ttft", ws["ttft_ms"]), ("tpot", ws["tpot_ms"])):
        for p in (50, 90):
            value = pct(sample, p)
            if value is not None:
                end_to_end[f"{name}_p{p}_ms"] = value
    every = np.sort(np.concatenate(
        [np.asarray(r["arrivals"], np.float64) for r in records] or [[]]))
    edges = np.arange(load.t_load, w1 + 5.0, 5.0)
    streams = sum(1 for r in records if r["arrivals"]
                  and r["arrivals"][0] < w1 and r["arrivals"][-1] >= w0)
    return {
        "correct": not problems, "problems": problems, "checks": checks,
        "attempted": len(ended), "failed": len(failed),
        "setup_end": setup_done, "t0": w0,
        "device": verdict["device"],
        "stats": {
            "end_to_end": end_to_end,
            "serve": {**end_to_end, "window_s": w1 - w0,
                      "tokens_in_window": ws["tokens"],
                      "ttft_samples": len(ws["ttft_ms"]),
                      "tpot_samples": len(ws["tpot_ms"]),
                      "ttft_p99_ms": pct(ws["ttft_ms"], 99),
                      "tpot_p99_ms": pct(ws["tpot_ms"], 99),
                      "decoding_context_tokens":
                          ws["decoding_context_tokens"],
                      "streams_in_window": streams,
                      "window_starts_s_into_load": w0 - load.t_load,
                      "tokens_per_s_by_5s_of_load": [
                          float(n) / 5.0 for n in np.histogram(
                              every, edges)[0]] if len(edges) > 1 else [],
                      "in_flight_at_window_start_end": [
                          sum(1 for r in records if r["t_ref"] <= t and not (
                              r["ended"] == "complete"
                              and r["arrivals"][-1] <= t))
                          for t in (w0, w1)],
                      "requests_sent": len(records),
                      "requests_cut_by_the_end": len(records) - len(ended),
                      "cancelled_at_end": cancelled,
                      "generator_late_ms_p90": late_p90,
                      "generator_late_ms_p99": pct(load.lateness_ms, 99)
                      or 0.0,
                      "generator_late_ms_max": max(load.lateness_ms,
                                                   default=0.0),
                      "tick_ms_mean": tick_ms,
                      "logprob_max_abs": verdict["logprob_max_abs"],
                      "logprob_mean_abs": verdict["logprob_mean_abs"],
                      "logprob_tokens_compared": verdict["tokens"],
                      "logprob_per_request_max":
                          verdict["per_request_max"],
                      "compared_index_prompt_tokens": [
                          [s["index"], len(s["prompt"]), len(s["tokens"])]
                          for s in samples],
                      "waited_for_compared_s": waited_s},
            "engine": {**engine,
                       "slot_occupancy_pct": 100 * engine["slot_occupancy"]},
            "compile": {**verdict["compile"], "warm": warmed},
            "setup_parts": {**parts, **{f"replica_{k}": v for k, v in
                                        info["setup_parts"].items()}},
            "traced_s": window_s if trace else None},
        "trace": summary,
    }
