"""Runs one training cell once, in this process: `spmd.make_gpt_trainer` +
`loop.TrainLoop` with the prefetcher and the fused multi-step dispatch, on
a mesh over the chips the cell asks for. Fresh seeded batches every step.

The window: dispatches are issued until `seconds` have passed, then the
loop drains; the rate is all trained tokens over all of that wall time
(first timed dispatch to the last one's results on the host).
"""

from __future__ import annotations

import importlib
import itertools
import os
import time

import numpy as np

from benchmarks.harness import trace as trace_mod
from benchmarks.harness.common import (BenchFailure, CompileWatch,
                                       device_report, gpt_kwargs,
                                       program_seed)


def run(cell: dict, config: dict, mix: dict, *, seed: int, seconds: float,
        trace: bool, platform: str, scratch: str) -> dict:
    parts, last = {}, [time.perf_counter()]

    def mark(name: str) -> None:
        """Where set-up goes: seconds since the last mark."""
        now = time.perf_counter()
        parts[name], last[0] = now - last[0], now

    import jax

    from benchmarks.harness import traffic as traffic_mod
    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import loop, spmd

    mark("imports")
    devices = jax.devices()
    mark("backend")
    if devices[0].platform != platform or len(devices) < cell["chips"]:
        raise BenchFailure(f"JAX found {len(devices)} {devices[0].platform} "
                           f"device(s), the cell needs {cell['chips']} "
                           f"{platform} chip(s)")
    devices = devices[:cell["chips"]]
    watch = CompileWatch()
    cfg = gpt.GPTConfig(**gpt_kwargs(config), **config["program"]["model"],
                        **config["program"]["train"])
    mesh = MeshSpec(**mix["mesh"]).build(devices)
    state, step_fn, shard = spmd.make_gpt_trainer(
        cfg, mesh, rng=jax.random.key(program_seed(seed)),
        optimizer=spmd.default_optimizer(**config["program"]["optimizer"]))
    jax.block_until_ready(state.params)
    mark("state")
    unroll = mix["unroll"]
    host = traffic_mod.train_batches(mix, seed, cfg.vocab_size)
    first = [next(host) for _ in range(unroll)]

    # Reference first, while the state is still the seeded one: the plain
    # float32 loss over a seeded sample of the first batch's sequences,
    # against the program's own loss function on the same sample.
    ref = importlib.import_module(f"benchmarks.refs.{config['reference']}")
    pick = np.random.default_rng([seed, 5]).choice(
        mix["batch"], mix["check_sequences"], replace=False)
    sample = {k: v[np.sort(pick)] for k, v in first[0].items()}
    with jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(
            lambda p, b: ref.loss(p, b["inputs"], b["targets"], cfg.n_heads)
        )(state.params, shard(sample)))
    program_loss = float(jax.jit(
        lambda p, b: spmd.gpt_loss_fn(p, b, cfg, mesh)
    )(state.params, shard(sample)))
    mark("reference")

    batches = loop.DevicePrefetcher(
        itertools.chain(first, host), loop.make_placer(mesh, stacked=True),
        depth=mix["prefetch_depth"], group=unroll)
    train = loop.TrainLoop(step_fn, unroll=unroll, metrics_interval=unroll)
    warm_steps = unroll * mix["warm_dispatches"]
    state, warm = train.run(state, batches, num_steps=warm_steps)
    mark("warm_dispatches")
    programs_before = watch.programs()

    def timed(limit_s: float):
        """Dispatch batches until `limit_s` have passed since the first."""
        t_first = None
        for batch in batches:
            now = time.perf_counter()
            if t_first is None:
                t_first = now
            elif now - t_first >= limit_s:
                return
            yield batch

    summary = traced_s = None
    if trace:
        traced_s = min(seconds, float(mix.get("trace_s", 10.0)))
        trace_dir = os.path.join(scratch, "trace")
        trace_mod.start(trace_dir)
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            state, traced = train.run(state, timed(traced_s))
        trace_mod.stop()
    setup_done = t0 = time.perf_counter()
    state, metrics = train.run(
        state, timed(seconds - traced_s if trace else seconds))
    jax.block_until_ready(state.params)
    wall_s = time.perf_counter() - t0
    breakdown = dict(train.last_breakdown)
    stats = train.stats()
    programs_in_window = watch.programs() - programs_before
    if trace:
        summary = trace_mod.reduce(trace_mod.find_xplane(trace_dir))
        metrics = traced + metrics

    losses = [float(m["loss"]) for m in warm + metrics]
    steps = breakdown["steps"]
    tokens = steps * mix["batch"] * mix["seq_len"]
    tol = config["tolerances"]["loss_abs"]
    quarter = max(1, len(losses) // 4)
    problems = []
    if abs(program_loss - ref_loss) > tol:
        problems.append(f"the program's loss on the sample is {program_loss},"
                        f" the reference's {ref_loss}")
    if abs(losses[0] - ref_loss) > 0.1:
        problems.append(f"the first step's loss {losses[0]} is far from the "
                        f"reference's {ref_loss} on a sample of its batch")
    if not np.all(np.isfinite(losses)):
        problems.append("a loss is not finite")
    if not np.mean(losses[-quarter:]) < np.mean(losses[:quarter]):
        problems.append("the loss did not fall")
    if stats["dispatch_traces"] != 1 or stats["retraces_unexpected"]:
        problems.append(f"the dispatch traced {stats['dispatch_traces']} "
                        f"times")
    if programs_in_window:
        problems.append(f"{programs_in_window} programs were compiled or "
                        f"loaded inside the window")
    return {
        "correct": not problems, "problems": problems,
        "attempted": steps, "failed": 0,
        "setup_end": setup_done, "t0": t0,
        "device": device_report(),
        "stats": {
            "end_to_end": {"train_tokens_per_s": tokens / wall_s},
            "train": {"train_tokens_per_s": tokens / wall_s,
                      "wall_s": wall_s, "steps": steps, "tokens": tokens,
                      "step_ms": wall_s / steps * 1e3,
                      "batch": mix["batch"], "seq_len": mix["seq_len"],
                      "unroll": unroll, "chips": cell["chips"],
                      "first_loss": losses[0], "last_loss": losses[-1],
                      "reference_loss": ref_loss,
                      "program_loss_on_sample": program_loss},
            "loop": {**breakdown, **stats},
            "compile": {"compiles": watch.compiles,
                        "compile_s": watch.compile_s,
                        "cache_hits": watch.cache_hits},
            "setup_parts": parts, "traced_s": traced_s},
        "trace": summary,
    }
