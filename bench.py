"""Flagship benchmark: GPT train throughput, streaming fresh host batches
through the overlapped training loop (ray_tpu/train/loop.py).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} plus
"checkpoint_overhead_pct", "mfu", "step_breakdown" (host step-time
shares from TrainLoop.last_breakdown: prefetch / dispatch / metrics /
checkpoint / publish), "retraces_unexpected" (retrace-sentinel
violations of the fused dispatch's compile-once pin — must be 0), and
the device it ran on: "platform", "device_kind", "device_count". Off a
TPU it runs a toy model so tier-1 can check the contract; "mfu" and
"vs_baseline" are then 0.0 (not measured).

Methodology (changed in PR 2): earlier rounds re-dispatched one jitted
step per Python iteration on a single pre-sharded device batch, so the
number excluded host→device transfer and dispatch overhead. The loop now
generates a FRESH host batch every step and streams it through the
double-buffered prefetcher with fused multi-step dispatch, so tokens/s is
an honest end-to-end figure — host feed, transfer, dispatch, compute and
the (ring-buffered, every-K-steps) metrics fetch all inside the timed
region. On this round's chip: not measured.

Knobs (env vars, platform-tuned defaults below):
  RAY_TPU_BENCH_ACCUM     gradient-accumulation microbatches per step
                          (spmd.make_train_step(accum=k); k splits the
                          batch, so tokens/step is unchanged)
  RAY_TPU_BENCH_UNROLL    steps fused into one jitted dispatch
                          (loop.TrainLoop(unroll=u))
  RAY_TPU_BENCH_PREFETCH  host→device transfers kept in flight
                          (loop.DevicePrefetcher(depth=d))
  RAY_TPU_BENCH_INTERVAL  steps between host metric fetches
                          (loop.MetricsRing(interval=K))
  RAY_TPU_BENCH_BATCH / RAY_TPU_BENCH_STEPS  shape of the timed region
  RAY_TPU_BENCH_CKPT_EVERY  async-snapshot cadence for the
                          checkpoint-overhead region (ft.AsyncCheckpointer)

The reference publishes no committed throughput numbers (BASELINE.md —
"harness only"); its north star is "ResNet-50 / GPT wall-clock at >= NCCL
DDP parity". DDP-over-NCCL training of dense transformers lands at ~40% MFU
on A100-class setups, so `vs_baseline` reports measured MFU / 0.40: >= 1.0
means the TPU path beats the reference's realistic efficiency envelope.
"""

from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

_BASELINE_MFU = 0.40


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


def main():
    from ray_tpu.models import gpt
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import loop, spmd
    from ray_tpu.util import telemetry
    from ray_tpu.util.compile_cache import enable_compile_cache

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if on_tpu:
        enable_compile_cache()
        peak = telemetry.device_peak_flops(devices[0])  # unknown: raise
        cfg = gpt.GPTConfig(vocab_size=50304, d_model=1024, n_layers=12,
                            n_heads=16, d_ff=4096, max_seq_len=1024,
                            attn_impl="flash", logits_dtype="bfloat16",
                            remat_policy="dots", loss_impl="fused")
        # None of these choices has been measured on this round's chip
        # (bf16 unembed output, remat_policy="dots", the batch).
        # loss_impl="fused" (ops/fused_xent.py) streams the unembed in
        # vocab chunks so the [B, T, V] logits tensor never exists.
        # B=24: the compiler's memory analysis puts the step alone at
        # 15.75 GiB of the 16 GB chip (B=16: 9.27 GiB of temporaries),
        # so expect to lower RAY_TPU_BENCH_BATCH or raise
        # RAY_TPU_BENCH_ACCUM. unroll=4 amortizes one Python dispatch
        # over 4 steps; prefetch=2 double-buffers the host feed.
        batch_size, steps, warmup = 24, 20, 4
        accum, unroll, prefetch, interval = 1, 4, 2, 10
    else:   # CPU smoke mode so the benchmark is runnable anywhere.
        # Exercises the full overlap path end-to-end: fused loss (scan
        # fallback), accum=2 microbatching, unroll=2 fused dispatch,
        # depth-2 prefetch, ring-buffered metrics. XLA:CPU compile of the
        # nested scans dominates wall-clock, so the model is as small as
        # the path allows — the number only matters on silicon.
        cfg = gpt.small(loss_impl="fused", n_layers=1, max_seq_len=64,
                        d_model=64, d_ff=256, n_heads=2, vocab_size=256)
        steps, warmup = 8, 2
        accum, unroll, prefetch, interval = 2, 2, 2, 4
        # microbatches shard over the data axes, so the batch must hold
        # accum * n_devices rows (tests force an 8-device CPU mesh)
        grain = accum * len(devices)
        batch_size = grain * max(1, 4 // grain)

    batch_size = _env_int("RAY_TPU_BENCH_BATCH", batch_size)
    steps = _env_int("RAY_TPU_BENCH_STEPS", steps)
    accum = _env_int("RAY_TPU_BENCH_ACCUM", accum)
    unroll = _env_int("RAY_TPU_BENCH_UNROLL", unroll)
    prefetch = _env_int("RAY_TPU_BENCH_PREFETCH", prefetch)
    interval = _env_int("RAY_TPU_BENCH_INTERVAL", interval)
    warmup = max(unroll * ((warmup + unroll - 1) // unroll), unroll)
    steps = max(unroll * (steps // unroll), unroll)

    mesh = MeshSpec(data=-1).build(devices)
    state, step_fn, _ = spmd.make_gpt_trainer(cfg, mesh, accum=accum)

    # Fresh host batch every step — the data plane the loop must hide.
    def host_batches():
        rng = np.random.default_rng(0)
        while True:
            toks = rng.integers(0, cfg.vocab_size,
                                (batch_size, cfg.max_seq_len + 1),
                                np.int32)
            yield {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    place = loop.make_placer(mesh, stacked=unroll > 1)
    batches = loop.DevicePrefetcher(host_batches(), place,
                                    depth=prefetch, group=unroll)
    tokens_per_step = batch_size * cfg.max_seq_len
    flops_tok = spmd.train_flops_per_token(cfg, cfg.max_seq_len)
    train = loop.TrainLoop(step_fn, unroll=unroll,
                           metrics_interval=interval,
                           flops_per_step=flops_tok * tokens_per_step)

    # Warmup compiles the fused dispatch and fills the prefetch ring;
    # drain() inside run() blocks until the device finishes, so the
    # timed region starts on an idle device with transfers in flight.
    state, metrics = train.run(state, batches, num_steps=warmup)
    assert np.isfinite(metrics[-1]["loss"])

    t0 = time.perf_counter()
    state, metrics = train.run(state, batches, num_steps=steps)
    # run() already drained the ring (a device_get of every pending
    # dispatch), so execution — not just dispatch — is inside dt.
    dt = time.perf_counter() - t0
    assert np.isfinite(metrics[-1]["loss"])

    # Checkpoint-overhead region: the SAME compiled loop reruns with an
    # async checkpointer attached (device-side copies + background
    # host fetch/commit — train/ft.py), so the delta vs the clean region
    # is exactly what fault tolerance costs per step, end-of-run flush
    # included.
    import shutil
    import tempfile

    from ray_tpu.train import ft

    ckpt_every = _env_int("RAY_TPU_BENCH_CKPT_EVERY",
                          max(unroll, steps // 2))
    ckpt_dir = tempfile.mkdtemp(prefix="ray_tpu_bench_ckpt_")
    try:
        ckpt = ft.AsyncCheckpointer(ckpt_dir, every=ckpt_every,
                                    max_in_flight=2, keep=1)
        train.checkpointer = ckpt
        t0 = time.perf_counter()
        state, metrics = train.run(state, batches, num_steps=steps)
        dt_ckpt = time.perf_counter() - t0
        train.checkpointer = None
        assert np.isfinite(metrics[-1]["loss"])
        assert ckpt.commits > 0, "checkpoint region committed nothing"
        ckpt.close()
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    checkpoint_overhead_pct = (dt_ckpt - dt) / dt * 100.0
    # Step-time breakdown from the checkpoint region — the run where all
    # the host activities the loop is supposed to hide (data feed,
    # metrics plumbing, checkpoint snapshots) are actually live.
    bd = train.last_breakdown
    step_breakdown = {
        k: round(bd.get(f"{k}_share", 0.0), 4)
        for k in ("prefetch", "dispatch", "metrics", "checkpoint",
                  "publish")}

    tok_s = tokens_per_step * steps / dt
    # a CPU rate over a chip's peak is not a utilization: 0.0 off-TPU
    mfu = tok_s * flops_tok / (peak * len(devices)) if on_tpu else 0.0
    vs_baseline = mfu / _BASELINE_MFU

    print(json.dumps({
        "metric": "gpt_train_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 3),
        "checkpoint_overhead_pct": round(checkpoint_overhead_pct, 2),
        "mfu": round(mfu, 4),
        "step_breakdown": step_breakdown,
        "retraces_unexpected": train.sentinel.retraces_unexpected,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }))


if __name__ == "__main__":
    main()
