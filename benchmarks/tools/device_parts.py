"""Whose the device's time is, as a table: program x part x direction in
milliseconds a run and per cent of the program, the largest ops with
their part and the tail of their `op_name`, and the ops without an
`op_name` (`compiler`) summed by opcode and result shape
(`benchmarks/layer_metrics/device_parts.py` is the reader).

    python3 benchmarks/tools/device_parts.py <trace.xplane.pb>
    python3 benchmarks/tools/device_parts.py <cell> [--seed N] [--seconds S]
        [--json chiprun_out/<cell>.parts.json]

A cell is run once, traced, as `run.py --trace 1` runs it (on the chip,
so through `chiprun`); its per-layer metrics are printed before the
table and the seconds the reader took to parse the trace after it.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import peaks, trace  # noqa: E402
from benchmarks.layer_metrics import device_parts  # noqa: E402

DIRECTIONS = (device_parts.FWD, device_parts.BWD, device_parts.RECOMPUTE)
CLASSES = (*device_parts.PARTS, device_parts.UNSCOPED, device_parts.COMPILER)


def tail(op_name: str | None, n: int = 4) -> str:
    return "/".join((op_name or "-").split(";", 1)[0].split("/")[-n:])


def table(summary: dict, top: int = 20) -> dict:
    """The reduction as plain data (ms a run of each program): what the
    text below prints and `--json` writes."""
    out = {"programs": {}, "ops": [], "compiler": {}, "unscoped": {}}
    runs = {}
    for name, p in sorted(summary["programs"].items(),
                          key=lambda kv: -kv[1]["self_ns"]):
        if not p["runs"] or not p["self_ns"]:
            continue
        runs[name] = p["runs"]
        ms = {f"{part}.{d}": v * 1e-6 / p["runs"]
              for (part, d), v in p["parts"].items()}
        out["programs"][name] = {
            "runs": p["runs"], "table": p["table"],
            "self_ms": p["self_ns"] * 1e-6 / p["runs"], "parts_ms": ms,
            "inherited_ms": {part: v * 1e-6 / p["runs"]
                             for part, v in p["inherited"].items()}}
    by_kind = collections.defaultdict(float)
    loose = collections.defaultdict(float)
    for (program, instruction, label, op_name, part, direction, inherited,
         calls, ns) in summary["ops"]:
        if program not in runs:
            continue
        ms = ns * 1e-6 / runs[program]
        if len(out["ops"]) < top:
            out["ops"].append({
                "program": program, "instruction": instruction,
                "label": label, "part": part, "direction": direction,
                "inherited": inherited, "op_name": tail(op_name),
                "calls_a_run": calls / runs[program], "ms": ms})
        if part == device_parts.COMPILER:
            by_kind[program, " ".join(label.split(" ")[1:])] += ms
        elif part == device_parts.UNSCOPED:
            loose[program, tail(op_name, 3)] += ms
    for name, rows in (("compiler", by_kind), ("unscoped", loose)):
        for (program, kind), ms in sorted(rows.items(), key=lambda kv: -kv[1]):
            out[name].setdefault(program, []).append([kind, ms])
    return out


def show(data: dict, each: int = 12) -> None:
    for name, p in data["programs"].items():
        print(f"\n{name}: {p['runs']} runs on chip 0 in the window, "
              f"{p['self_ms']:.4f} ms of op self time a run"
              + ("" if p["table"] else "  (no HLO in the trace)"))
        print(f"  {'part':<10}" + "".join(f"{d:>11}" for d in DIRECTIONS)
              + f"{'all':>11}{'%':>8}{'inherited':>11}")
        for part in CLASSES:
            row = [p["parts_ms"].get(f"{part}.{d}", 0.0) for d in DIRECTIONS]
            if not any(row):
                continue
            print(f"  {part:<10}" + "".join(f"{v:>11.4f}" for v in row)
                  + f"{sum(row):>11.4f}{100 * sum(row) / p['self_ms']:>8.2f}"
                  + f"{p['inherited_ms'].get(part, 0.0):>11.4f}")
    print("\nlargest ops (ms a run of their program):")
    for op in data["ops"]:
        print(f"  {op['ms']:>9.4f}  {op['program']:<14}{op['part']:<9}"
              f"{op['direction']:<10}{'*' if op['inherited'] else ' '} "
              f"{op['label'][:60]:<60}  {op['op_name']}")
    for what in ("compiler", "unscoped"):
        for program, rows in data[what].items():
            print(f"\n{what} ops of {program}, by "
                  + ("opcode and result" if what == "compiler"
                     else "op_name") + " (ms a run):")
            for kind, ms in rows[:each]:
                print(f"  {ms:>9.4f}  {kind}")


def run_cell(workload: str, seed: int, seconds: float):
    """One traced run as `run.py --trace 1` makes it: (the per-layer
    values, the reduction, the seconds the reduction's parse took)."""
    bench_run.use_checkout()
    bench, cell, config, mix = bench_run.load_cell(workload)
    with bench_run.scratch_dir() as scratch:
        out = bench_run.drive(cell, config, mix, seed=seed, seconds=seconds,
                              trace=True, platform="tpu", scratch=scratch)
        path = trace.find_xplane(os.path.join(scratch, "trace"))
        size = os.path.getsize(path)
        t0 = time.perf_counter()
        summary = device_parts.reduce(path)
        parse_s = time.perf_counter() - t0
        device_parts._cache[path] = summary
        values = bench_run.collect(
            bench, cell, config, mix, out, seconds=seconds, trace=True,
            peak=peaks.peaks_for(out["device"]["kind"]), setup_s=0.0)
    return values, summary, {"parse_s": parse_s, "trace_bytes": size,
                             "correct": out["correct"],
                             "busy_s": out["trace"]["busy_s"],
                             "window_s": out["trace"]["window_s"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", help="a cell's name or a trace file")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--json", help="write the table as JSON here too")
    args = ap.parse_args()
    extra = {}
    if os.path.isfile(args.what):
        t0 = time.perf_counter()
        summary = device_parts.reduce(args.what)
        extra = {"parse_s": time.perf_counter() - t0,
                 "trace_bytes": os.path.getsize(args.what)}
    else:
        values, summary, extra = run_cell(args.what, args.seed, args.seconds)
        print(json.dumps({"workload": args.what, "seed": args.seed,
                          "metrics": values, **extra}), flush=True)
    if summary is None:
        print("no /host:metadata plane or no device op in the trace")
        return 1
    data = {**table(summary), **extra}
    show(data)
    print(f"\nthe reader's parse: {extra['parse_s']:.2f} s over "
          f"{extra['trace_bytes'] / 1e6:.1f} MB", flush=True)
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(data, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
