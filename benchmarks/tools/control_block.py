"""Runs a cell once with a named block of its configuration laid over it,
as `control.py` does with the block named `control`, for a configuration
that brings more than one control (`command-a-plus.json`: `control`, the
cache in the nearest lower precision, and `control_window`, the full
layer cut at the window). Prints every number the cell's `correct`
compares beside its limit; a control has to come out as not correct. Not
part of a benchmark run. One seed a call (the chip is one process's):

    python3 benchmarks/tools/control_block.py --block control_window \
        --workload command-a-plus.mixed-closed24 --seed 5 --seconds 51
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import common  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--block", default="control")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    bench_run.use_checkout()
    _, cell, config, mix = bench_run.load_cell(args.workload)
    with bench_run.scratch_dir() as scratch:
        out = bench_run.drive(
            cell, common.merged(config, config[args.block]), mix,
            seed=args.seed, seconds=args.seconds, trace=False,
            platform=args.platform, scratch=scratch)
    print(json.dumps({"control_of": args.workload, "block": args.block,
                      "seed": args.seed, "correct": out["correct"],
                      "checks": out["checks"],
                      "problems": out["problems"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
