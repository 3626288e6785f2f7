"""Times `ops.grouped_experts`' three training kernels at one shape with
the width slice `_width_slice` picks and with slices forced to other
sizes, on the device this machine has: five traced calls of one jitted
gradient, device ms a call by kernel name. What
`grouped_experts._width_slice`'s rule for a width of whole lane tiles is
held to (PERF.md, section 6, PR 57). Not part of a benchmark run.

    python3 benchmarks/tools/experts_slice_sweep.py --tokens 8192 --d 2304 \
        --f 896 --held 16 --router 64 --k 8 --slices 0 128
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
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--d", type=int, default=2304)
    ap.add_argument("--f", type=int, default=896)
    ap.add_argument("--held", type=int, default=16)
    ap.add_argument("--router", type=int, default=64)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--slices", type=int, nargs="+", default=[0],
                    help="rows of the width a grid step; 0: the rule's own")
    ap.add_argument("--timeout", type=int, default=900)
    args = ap.parse_args()
    signal.alarm(args.timeout)
    bench_run.use_checkout()
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import grouped_experts as ge

    keys = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(keys[0], (args.tokens, args.d), jnp.bfloat16)
    # k distinct experts a token, uniform over the router's width
    chosen = jnp.argsort(jax.random.uniform(
        keys[1], (args.tokens, args.router)), -1)[:, :args.k].astype(
            jnp.int32)
    weights = jnp.full((args.tokens, args.k), 1.0 / args.k, jnp.float32)
    mats = [jax.random.normal(kk, (args.held, args.f, args.d), jnp.float32)
            * 0.02 for kk in keys[2:5]]
    rule = ge._width_slice
    with bench_run.scratch_dir() as scratch:
        for fs in args.slices:
            ge._width_slice = rule if fs == 0 else (
                lambda width, fs=fs: (fs, width // fs))
            step = jax.jit(jax.grad(
                lambda x, w, g, u, d: jnp.sum(ge.experts_grouped(
                    x, chosen, w, g, u, d, held_from=0, impl="pallas",
                    name=ge.EXPERTS_GROUPED_TRAIN)[0]), (0, 1, 2, 3, 4)))
            picked = ge._width_slice(args.f)
            try:
                jax.block_until_ready(step(x, weights, *mats))
            except Exception as e:      # the compiler's refusal
                print(json.dumps({"slice": picked[0],
                                  "refused": str(e)[:300]}), flush=True)
                continue
            where = os.path.join(scratch, str(fs))
            trace.start(where)
            for _ in range(CALLS):
                out = step(x, weights, *mats)
            jax.block_until_ready(out)
            trace.stop()
            kernels = spans.reduce(trace.find_xplane(where))["kernels"]
            print(json.dumps({"slice": picked[0], "steps": picked[1],
                              **{n: round(s * 1e3 / max(c, 1), 3)
                                 for n, (c, s) in kernels.items()}}),
                  flush=True)
    ge._width_slice = rule
    return 0


if __name__ == "__main__":
    sys.exit(main())
