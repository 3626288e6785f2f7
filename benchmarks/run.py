"""Runs one cell of the benchmark once and prints its result as one JSON
object on the last line of standard output.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell in
`BENCHMARK.json`, its configuration in `benchmarks/configs/<config>.json`,
its traffic mix in `benchmarks/traffic/<traffic>.json` (whose `driver`
names the module `benchmarks/harness/<driver>_cell.py` that runs it), and each
per-layer metric in `benchmarks/layer_metrics/<name>.json`. The
configuration names its reference (`benchmarks/refs/<reference>.py`) and
its arithmetic (`benchmarks/<arith>.py`, whose `widths(config)` reads the
file's own keys). Adding a configuration, a mix or a metric adds files and
`BENCHMARK.json` entries and edits nothing here.

Exit code 0 and a result line, or another code and no result line: no
accelerator, too few chips, or a checkout without the program.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"run.py: no {what} named {name!r} in BENCHMARK.json")
    return found[0]


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def read_layer_metric(name: str, ctx: dict):
    """A per-layer metric is a file of its own: either a path into the
    run's stats, or the reducer module beside it. Nothing to read ->
    None, and the metric is left out of the line."""
    spec = load_json("benchmarks", "layer_metrics", f"{name}.json")
    if "stat" in spec:
        from benchmarks.layer_metrics._stats import lookup
        value = lookup(ctx, spec["stat"])
        return None if value is None else value * spec.get("scale", 1.0)
    reducer = importlib.import_module(
        f"benchmarks.layer_metrics.{spec['reducer']}")
    return reducer.read(ctx, **spec.get("args", {}))


def use_checkout() -> None:
    """This checkout first on the import path of this process and of
    every worker it starts, and the compile cache inside it."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p and p != ROOT])
    from benchmarks.harness import common
    common.use_compile_cache()


@contextlib.contextmanager
def scratch_dir():
    """`<checkout>/.bench_scratch/<pid>`, where a traced run's trace is
    written and `spans.summary` looks for it; gone afterwards."""
    path = os.path.join(ROOT, ".bench_scratch", f"{os.getpid()}")
    os.makedirs(path, exist_ok=True)
    try:
        yield path
    finally:
        shutil.rmtree(path, ignore_errors=True)


def drive(cell: dict, config: dict, mix: dict, **how) -> dict:
    """One run of the cell's driver (the mix names it)."""
    return importlib.import_module(
        f"benchmarks.harness.{mix['driver']}_cell").run(
            cell, config, mix, **how)


def load_cell(workload: str):
    """(BENCHMARK.json, the cell's entry, its configuration, its mix)."""
    bench = load_json("BENCHMARK.json")
    cell = by_name(bench["workloads"], workload, "workload")
    entry = by_name(bench["configs"], cell["config"], "config")
    mix = load_json("benchmarks", "traffic", f"{cell['traffic']}.json")
    return bench, cell, load_json(entry["file"]), mix


def collect(bench: dict, cell: dict, config: dict, mix: dict, out: dict, *,
            seconds: float, trace: bool, peak: dict, setup_s: float):
    """From a driver's output to {metric: value}: the cell's end-to-end
    metrics, or with `trace` its per-layer ones."""
    arith = importlib.import_module(f"benchmarks.{config['arith']}")
    ctx = {"stats": out["stats"], "trace": out["trace"], "cell": cell,
           "config": config, "traffic": mix, "peaks": peak,
           "arith": arith, "widths": arith.widths(config),
           "seconds": seconds}
    flat = {**out["stats"]["end_to_end"], "setup_s": setup_s}
    values = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if applies(m, cell["name"]):
            value = (read_layer_metric(m["name"], ctx) if trace
                     else flat.get(m["name"]))
            if value is not None:
                values[m["name"]] = float(value)
    return values


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    use_checkout()
    from benchmarks.harness import common, peaks, spans

    bench, cell, config, mix = load_cell(args.workload)
    t_driver = time.perf_counter()
    try:
        with scratch_dir() as scratch:
            out = drive(cell, config, mix, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        platform="tpu", scratch=scratch)
            values = collect(
                bench, cell, config, mix, out, seconds=args.seconds,
                trace=bool(args.trace),
                peak=peaks.peaks_for(out["device"]["kind"]),
                setup_s=out["setup_end"] - T_START)
            idle_gaps = spans.idle_gaps({"trace": out["trace"]})
        units = {m["name"]: m["unit"]
                 for m in bench["end_to_end"] + bench["per_layer"]}
        metrics = {n: {"value": v, "unit": units[n]}
                   for n, v in values.items()}
        missing = [m["name"] for m in bench["end_to_end"]
                   if applies(m, cell["name"]) and m["name"] not in metrics]
        if not args.trace and missing:
            raise common.BenchFailure(
                f"the run gave no value for {missing}")
    except common.BenchFailure as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2

    device = dict(out["device"])
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device,
              "workload": cell["name"], "seed": args.seed,
              "seconds": args.seconds, "problems": out["problems"],
              "checks": out["checks"],
              "stats": {k: v for k, v in out["stats"].items()
                        if k not in ("end_to_end", "setup_parts")},
              "setup_parts": {"before_driver": t_driver - T_START,
                              **out["stats"].get("setup_parts", {})}}
    if args.trace:
        summary = out["trace"]
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": idle_gaps}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
