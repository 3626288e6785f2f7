"""Median device duration of one jitted program, from the trace's
`XLA Modules` line, optionally per step of a fused dispatch."""

from benchmarks.layer_metrics._stats import lookup


def read(ctx, module: str, divide_by: str | None = None):
    trace = ctx.get("trace")
    if not trace or module not in trace["modules"]:
        return None
    _, _, median_s = trace["modules"][module]
    per = lookup(ctx, divide_by) if divide_by else 1
    return median_s * 1e3 / per
