"""Finds the highest arrival rate a serving cell's replica sustains, once,
when the cell is defined: the cell's open-loop mix at one fixed rate a
call (the chip is one process's), and what tells a growing backlog from
a level one: requests in flight at the window's start and end, the
engine's queue at its end, tokens per second by 5 s of load, the tails.

    for r in 0.4 0.5 0.6 0.7; do python3 benchmarks/tools/serve_sweep.py \
        --workload olmo-1b.chat-steady --rate $r --seconds 40 --seed 7; done

The mix then holds four fifths of the highest rate whose backlog stayed
level; the sweep is not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402

SERVE = ("serve_tokens_per_s", "ttft_p50_ms", "ttft_p90_ms", "tpot_p50_ms",
         "tpot_p90_ms", "ttft_samples", "tpot_samples",
         "in_flight_at_window_start_end", "tokens_per_s_by_5s_of_load",
         "generator_late_ms_p90", "generator_late_ms_p99")
ENGINE = ("queue_depth", "pending", "active", "slot_occupancy",
          "queue_wait_ms_p50", "queue_wait_ms_p99", "ticks")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    bench_run.use_checkout()
    _, cell, config, mix = bench_run.load_cell(args.workload)
    with bench_run.scratch_dir() as scratch:
        out = bench_run.drive(
            cell, config, dict(mix, rate_per_s=args.rate), seed=args.seed,
            seconds=args.seconds, trace=False, platform="tpu",
            scratch=scratch)
    serve, engine = out["stats"]["serve"], out["stats"]["engine"]
    print(json.dumps({
        "rate_per_s": args.rate, "correct": out["correct"],
        "problems": out["problems"], "attempted": out["attempted"],
        "failed": out["failed"],
        **{k: serve[k] for k in SERVE if k in serve},
        **{k: engine[k] for k in ENGINE}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
