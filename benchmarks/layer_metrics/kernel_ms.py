"""Self time of named Pallas kernels per optimizer step per chip, on the
basis of `train_step_device_ms`: the kernels' seconds on all chips over
the fused dispatch's runs on all chips x steps a dispatch."""

from benchmarks.harness import spans
from benchmarks.layer_metrics._stats import lookup


def read(ctx, kernels: list, module: str = "jit_multi",
         steps: str = "traffic.unroll"):
    s = spans.summary(ctx)
    if not s or module not in ctx["trace"]["modules"]:
        return None
    found = spans.kernel_seconds(s, kernels)
    if found is None:
        return None
    runs = ctx["trace"]["modules"][module][0]
    return found[1] * 1e3 / (runs * lookup(ctx, steps))
