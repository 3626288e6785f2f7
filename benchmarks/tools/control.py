"""Runs a cell's control once: the same cell with the configuration's
`control` block laid over it (the program's own path in the nearest
precision below the one the configuration states), and prints every
number its `correct` compares beside its limit. The control has to come
out as not correct; the limits in the configuration file stand between
what sound runs read and what this reads (PERF.md, section 4). Not part
of a benchmark run; `benchmarks/tests` holds the same control at a tiny
size. One seed a call (the chip is one process's):

    for s in 5 6 7; do python3 benchmarks/tools/control.py \
        --workload olmo-1b.chat-closed64 --seed $s --seconds 10; done
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--platform", default="tpu")
    args = ap.parse_args()
    bench_run.use_checkout()
    _, cell, config, mix = bench_run.load_cell(args.workload)
    with bench_run.scratch_dir() as scratch:
        out = bench_run.drive(
            cell, common.merged(config, config["control"]), mix,
            seed=args.seed, seconds=args.seconds, trace=False,
            platform=args.platform, scratch=scratch)
    print(json.dumps({"control_of": args.workload, "seed": args.seed,
                      "correct": out["correct"], "checks": out["checks"],
                      "problems": out["problems"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
