"""Runs one training cell once, in this process: `spmd.make_gpt_trainer` +
`loop.TrainLoop` with the prefetcher and the fused multi-step dispatch, on
a mesh over the chips the cell asks for. Fresh seeded batches every step.

The window: dispatches are issued until `seconds` (or the mix's
`window_s`, where that is less) have passed, then the loop drains; the
rate is all trained tokens over all of that wall time (first timed
dispatch to the last one's results on the host).

What `models/gpt.py` cannot run is refused here, where its `GPTConfig` is
built (`common.model_config`), as this driver's `BenchFailure`. A
configuration that trains through other entry points than
`models.gpt:GPTConfig`, `train.spmd:make_gpt_trainer` and
`train.spmd:gpt_loss_fn` names them under `program.entry`.
"""

from __future__ import annotations

import importlib
import itertools
import os
import time

import numpy as np

from benchmarks.harness import trace as trace_mod
from benchmarks.harness.common import (BenchFailure, CompileWatch,
                                       device_report, entry_point,
                                       model_config, program_seed)


def position_losses(loss_fn, cfg, mesh, shape, block: int = 8192):
    """-> f(params, batch) = the program's loss at every position of a
    batch of `shape` [k, T], float64 [k, T], through its own loss
    function: `jax.vmap` over one-hot `mask`s, `block` positions a call.
    Nothing of the forward depends on the mask, so a call runs it once."""
    import jax
    import jax.numpy as jnp

    n = shape[0] * shape[1]
    block = min(block, n)

    @jax.jit
    def some(params, batch, start):
        masks = (jnp.arange(n)[None, :] == start + jnp.arange(block)[:, None]
                 ).astype(jnp.float32).reshape(block, *shape)
        return jax.vmap(
            lambda m: loss_fn(params, {**batch, "mask": m}, cfg, mesh))(masks)

    def every(params, batch):
        return np.concatenate([
            np.asarray(some(params, batch, i), np.float64)
            for i in range(0, n, block)])[:n].reshape(shape)

    return every


def run(cell: dict, config: dict, mix: dict, *, seed: int, seconds: float,
        trace: bool, platform: str, scratch: str) -> dict:
    parts, last = {}, [time.perf_counter()]

    def mark(name: str) -> None:
        """Where set-up goes: seconds since the last mark."""
        now = time.perf_counter()
        parts[name], last[0] = now - last[0], now

    import jax

    from benchmarks.harness import traffic as traffic_mod
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import loop, spmd

    seconds = min(seconds, float(mix.get("window_s", seconds)))
    mark("imports")
    devices = jax.devices()
    mark("backend")
    if devices[0].platform != platform or len(devices) < cell["chips"]:
        raise BenchFailure(f"JAX found {len(devices)} {devices[0].platform} "
                           f"device(s), the cell needs {cell['chips']} "
                           f"{platform} chip(s)")
    devices = devices[:cell["chips"]]
    watch = CompileWatch()
    cfg = model_config(config, "train", **config["program"]["train"])
    if mix["batch"] % mix["check_sequences"]:
        raise BenchFailure("check_sequences has to divide the batch")
    mesh = MeshSpec(**mix["mesh"]).build(devices)
    state, step_fn, shard = entry_point(config, "trainer")(
        cfg, mesh, rng=jax.random.key(program_seed(seed)),
        optimizer=spmd.default_optimizer(**config["program"]["optimizer"]))
    jax.block_until_ready(state.params)
    mark("state")
    unroll = mix["unroll"]
    host = traffic_mod.train_batches(mix, seed, cfg.vocab_size)
    first = [next(host) for _ in range(unroll)]

    # Reference first, while the state is still the seeded one: the plain
    # float32 loss over a seeded sample of the first batch's sequences,
    # against the program's own loss function on the same sample: the two
    # means, or, where the mix says `check_by` "position", the median over
    # the sample's positions of |the program's loss there - the
    # reference's|, in which gaps of either sign cannot cancel and which a
    # common id that rounding sends to another expert does not move. Then
    # the program's loss on the whole first batch, slice by slice in the
    # sample's shape (one program for both): what the first step's loss
    # has to be, on the same sequences.
    ref = importlib.import_module(f"benchmarks.refs.{config['reference']}")
    k = mix["check_sequences"]
    pick = np.sort(np.random.default_rng([seed, 5]).choice(
        mix["batch"], k, replace=False))
    sample = {name: v[pick] for name, v in first[0].items()}
    by_position = mix.get("check_by") == "position"
    loss_fn = entry_point(config, "loss")
    with jax.default_matmul_precision("highest"):
        ref_losses = np.asarray(jax.jit(
            lambda p, b: (ref.token_losses if by_position else ref.loss)(
                p, b["inputs"], b["targets"], config)
        )(state.params, shard(sample)), np.float64)
    ref_loss = float(ref_losses.mean())
    if by_position:
        every = position_losses(loss_fn, cfg, mesh, sample["inputs"].shape)

        def program_mean(batch):
            return float(every(state.params, shard(batch)).mean())

        program_losses = every(state.params, shard(sample))
        program_loss = float(program_losses.mean())
        sample_check = ["program_loss_minus_reference_median_by_position",
                        float(np.median(np.abs(program_losses - ref_losses))),
                        config["tolerances"]["loss_position_abs"]]
    else:
        program_loss_fn = jax.jit(lambda p, b: loss_fn(p, b, cfg, mesh))

        def program_mean(batch):
            return float(program_loss_fn(state.params, shard(batch)))

        program_loss = program_mean(sample)
        sample_check = ["program_loss_minus_reference_on_sample",
                        abs(program_loss - ref_loss),
                        config["tolerances"]["loss_abs"]]
    first_batch_loss = float(np.mean([
        program_mean({name: v[i:i + k] for name, v in first[0].items()})
        for i in range(0, mix["batch"], k)]))
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
    if not sample_check[1] <= sample_check[2]:
        problems.append(f"the program's loss on the sample is {program_loss},"
                        f" the reference's {ref_loss}: {sample_check[0]} "
                        f"{sample_check[1]}, limit {sample_check[2]}")
    if abs(losses[0] - first_batch_loss) > tol:
        problems.append(f"the first step's loss is {losses[0]}, the "
                        f"program's loss function gives {first_batch_loss}"
                        f" on the same batch")
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
    checks = [
        sample_check,
        ["first_step_loss_minus_loss_fn_on_first_batch",
         abs(losses[0] - first_batch_loss), tol],
        ["last_quarter_mean_loss", float(np.mean(losses[-quarter:])),
         f"< {float(np.mean(losses[:quarter]))}"],
        ["dispatch_traces", stats["dispatch_traces"], 1],
        ["programs_in_window", programs_in_window, 0],
    ]
    return {
        "correct": not problems, "problems": problems, "checks": checks,
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
                      "program_loss_on_sample": program_loss,
                      "program_loss_on_first_batch": first_batch_loss},
            "loop": {**breakdown, **stats},
            "compile": {"compiles": watch.compiles,
                        "compile_s": watch.compile_s,
                        "cache_hits": watch.cache_hits},
            "setup_parts": parts, "traced_s": traced_s},
        "trace": summary,
    }
