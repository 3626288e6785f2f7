"""Self time of named Pallas kernels per run of a program per chip: the
kernels' seconds on all chips over the program's runs on all chips x
steps a run (`train_step_device_ms`'s basis for the fused train
dispatch; a decode step for `jit__decode`). Whichever of the listed
kernels ran are summed: a fused kernel that stands for two of the names
still gives the metric; nothing only where none of them ran."""

from benchmarks.harness import spans
from benchmarks.layer_metrics._stats import lookup


def read(ctx, kernels: list, module: str = "jit_multi",
         steps: str | int = "traffic.unroll"):
    s = spans.summary(ctx)
    if not s or module not in ctx["trace"]["modules"]:
        return None
    found = spans.kernel_seconds(s, kernels)
    if found is None:
        return None
    runs = ctx["trace"]["modules"][module][0]
    per_run = lookup(ctx, steps) if isinstance(steps, str) else steps
    return found[1] * 1e3 / (runs * per_run)
