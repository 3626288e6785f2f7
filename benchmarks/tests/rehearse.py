"""Runs one cell's driver on the CPU backend with the sizes a test hands it
(see test_benchmark.py); prints what `run.py` would have computed, under
names that cannot be mistaken for a chip's result."""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("RAY_TPU_NUM_TPUS", "1")      # a serving cell's chip
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

from benchmarks import run as bench_run  # noqa: E402


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    out = bench_run.drive(cell, config, mix, seed=2**31 + 5, seconds=4.0,
                          trace=spec["trace"], platform="cpu",
                          scratch=spec["scratch"])
    metrics = bench_run.collect(
        spec["bench"], cell, config, mix, out, seconds=4.0,
        trace=spec["trace"], setup_s=1.0,
        peak={"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0})
    result = {k: out[k] for k in ("correct", "attempted", "failed",
                                  "device", "problems", "checks")}
    result["stats"] = {k: v for k, v in out["stats"].items()
                       if k != "setup_parts"}
    print(json.dumps({"result": result, "metrics": metrics}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
