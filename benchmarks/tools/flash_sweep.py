"""Sweeps `ops.flash_attention`'s blocks and tiles at one shape on the
device this machine has: forward (with its logsumexp), dQ and dK/dV in one
jitted gradient, five traced calls a plan, device ms a call by kernel name
from the profiler's trace. The table in `flash_attention`'s docstring and
PERF.md's are made with it. Not part of a benchmark run.

    python3 benchmarks/tools/flash_sweep.py --bh 64 --t 8192 --dqk 192 \
        --dv 128 --plans 1024x1024x512 512x512x512 2048x1024x512

A plan is `block_q x block_kv x tile`; one the chip's compiler refuses is
printed with its refusal.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import spans, trace  # noqa: E402

CALLS = 5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--bh", type=int, default=64)
    ap.add_argument("--t", type=int, default=8192)
    ap.add_argument("--dqk", type=int, default=192)
    ap.add_argument("--dv", type=int, default=128)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--plans", nargs="+", required=True)
    args = ap.parse_args()
    bench_run.use_checkout()
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa

    b = args.bh // args.heads
    keys = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (b, args.t, args.heads, d),
                                 jnp.bfloat16)
               for kk, d in zip(keys, (args.dqk, args.dqk, args.dv)))
    with bench_run.scratch_dir() as scratch:
        for plan in args.plans:
            bq, bkv, tile = (int(n) for n in plan.split("x"))
            fa._SUB = tile
            step = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fa.flash_attention(
                    q, k, v, True, bq, bkv).astype(jnp.float32)),
                (0, 1, 2)))
            try:
                jax.block_until_ready(step(q, k, v))
            except Exception as e:      # the compiler's refusal
                print(json.dumps({"plan": plan,
                                  "refused": str(e)[:300]}), flush=True)
                continue
            where = os.path.join(scratch, plan)
            trace.start(where)
            for _ in range(CALLS):
                out = step(q, k, v)
            jax.block_until_ready(out)
            trace.stop()
            kernels = spans.reduce(trace.find_xplane(where))["kernels"]
            print(json.dumps({"plan": plan, "device": str(jax.devices()[0]),
                              **{n: round(s * 1e3 / max(c, 1), 3)
                                 for n, (c, s) in kernels.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
