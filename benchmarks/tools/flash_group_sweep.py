"""`flash_sweep.py` for grouped heads and a window: sweeps
`ops.flash_attention`'s blocks and tiles at one shape of `hq` query heads
over `hkv` key-value heads, banded (`--window`) or full, on the device
this machine has: forward (with its logsumexp), dQ and dK/dV in one jitted
gradient, five traced calls a plan, device ms a call by kernel name from
the profiler's trace, and the share of the square each plan's walks
compute. The table in `flash_attention`'s docstring (PR 57) is made with
it. Not part of a benchmark run.

    python3 benchmarks/tools/flash_group_sweep.py --hq 32 --hkv 4 \
        --t 32768 --d 128 --window 1024 --plans 1024x2048x512 512x1024x256

A plan is `block_q x block_kv x tile`; one the chip's compiler refuses is
printed with its refusal.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import spans, trace  # noqa: E402

CALLS = 5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--hq", type=int, default=32)
    ap.add_argument("--hkv", type=int, default=4)
    ap.add_argument("--t", type=int, default=32768)
    ap.add_argument("--d", type=int, default=128)
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--plans", nargs="+", required=True)
    ap.add_argument("--timeout", type=int, default=1500)
    args = ap.parse_args()
    signal.alarm(args.timeout)
    bench_run.use_checkout()
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import flash_attention as fa

    keys = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (1, args.t, h, args.d), jnp.bfloat16)
               for kk, h in zip(keys, (args.hq, args.hkv, args.hkv)))
    with bench_run.scratch_dir() as scratch:
        for plan in args.plans:
            bq, bkv, tile = (int(n) for n in plan.split("x"))
            fa._SUB = tile
            step = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(fa.flash_attention(
                    q, k, v, True, bq, bkv, args.window
                ).astype(jnp.float32)), (0, 1, 2)))
            try:
                jax.block_until_ready(step(q, k, v))
            except Exception as e:      # the compiler's refusal
                print(json.dumps({"plan": plan, "window": args.window,
                                  "refused": str(e)[:300]}), flush=True)
                continue
            where = os.path.join(scratch, plan)
            trace.start(where)
            for _ in range(CALLS):
                out = step(q, k, v)
            jax.block_until_ready(out)
            trace.stop()
            kernels = spans.reduce(trace.find_xplane(where))["kernels"]
            share = fa.executed_share(
                fa._plan_blocks(args.t, bq, bkv), args.t, True, args.window)
            print(json.dumps({"plan": plan, "window": args.window,
                              "executed_share": round(share, 5),
                              **{n: round(s * 1e3 / max(c, 1), 3)
                                 for n, (c, s) in kernels.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
