"""Records the small trace `benchmarks/tests` reads its readers against:
one cell's driver at the size of the `tiny` blocks of its configuration
and its mix, traced, on whatever device this machine has (the chip, for
a trace worth keeping), and the `.xplane.pb` copied to `--out`.

    python3 benchmarks/tools/record_trace.py --workload olmo-1b.chat-closed64 \
        --out chiprun_out/v5e_serve.xplane.pb [--platform tpu]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import common, trace  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=0.08,
                    help="the traced window: a trace grows by megabytes "
                    "a second")
    args = ap.parse_args()
    bench_run.use_checkout()
    _, cell, config, mix = bench_run.load_cell(args.workload)
    with bench_run.scratch_dir() as scratch:
        out = bench_run.drive(
            cell, common.merged(config, config["tiny"]),
            common.merged(mix, mix["tiny"]), seed=args.seed,
            seconds=args.seconds, trace=True, platform=args.platform,
            scratch=scratch)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        shutil.copy(trace.find_xplane(os.path.join(scratch, "trace")),
                    args.out)
    print(json.dumps({"correct": out["correct"], "problems": out["problems"],
                      "bytes": os.path.getsize(args.out),
                      "modules": out["trace"]["modules"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
