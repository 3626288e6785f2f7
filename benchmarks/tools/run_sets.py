"""Runs a cell the way a bound is set from: sets of runs, each run of a set
with another seed, the same seeds in every set, all in one call; then the
spread of every metric as the contract defines it (distance between the
first and third quartile of `statistics.quantiles(values, n=4)`, as a share
of the median), per set and the wider of the sets.

    python3 benchmarks/tools/run_sets.py --workload <cell> --seeds 1,2,3,4,5,6 \
        --sets 2 --out chiprun_out/sets [--trace 1]

Each run is the benchmark's own command in a process of its own. The
first run of a cell in a checkout compiles, so its `setup_s` is shown
apart and left out of the spreads.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.trace{args.trace}.jsonl")
    runs = []
    for k in range(args.sets):
        for seed in seeds:
            t = time.time()
            proc = subprocess.run(
                bench["command"] + ["--workload", args.workload, "--seed",
                                    str(seed), "--seconds", str(seconds),
                                    "--trace", str(args.trace)],
                cwd=ROOT, stdout=subprocess.PIPE, text=True)
            took = time.time() - t
            line = (proc.stdout.strip().splitlines() or [""])[-1]
            try:
                result = json.loads(line)
            except ValueError:
                result = None
            rec = {"set": k, "seed": seed, "rc": proc.returncode,
                   "wall_s": took, "result": result}
            runs.append(rec)
            with open(path, "a") as f:
                f.write(json.dumps(rec) + "\n")
            print(f"set {k} seed {seed} rc {proc.returncode} "
                  f"wall {took:.1f}s "
                  + (json.dumps({n: m["value"] for n, m in
                                 result["metrics"].items()})
                     + f" correct={result['correct']} "
                     f"attempted={result['attempted']} "
                     f"failed={result['failed']}" if result else line[-200:]),
                  flush=True)
    good = [r for r in runs if r["result"]]
    names = sorted({n for r in good for n in r["result"]["metrics"]})
    summary = {}
    for name in names:
        per_set = []
        for k in range(args.sets):
            vals = [r["result"]["metrics"][name]["value"] for r in good
                    if r["set"] == k and name in r["result"]["metrics"]
                    and not (name == "setup_s" and r is good[0])]
            if len(vals) >= 2:
                per_set.append({"median": statistics.median(vals),
                                "spread": spread(vals), "n": len(vals)})
        if per_set:
            summary[name] = {"sets": per_set,
                             "widest_spread": max(s["spread"]
                                                  for s in per_set)}
    print(json.dumps({"workload": args.workload, "summary": summary},
                     indent=1), flush=True)
    with open(os.path.join(args.out,
                           f"{args.workload}.trace{args.trace}.summary.json"),
              "w") as f:
        json.dump(summary, f, indent=1)
    return 0 if len(good) == len(runs) else 1


if __name__ == "__main__":
    sys.exit(main())
