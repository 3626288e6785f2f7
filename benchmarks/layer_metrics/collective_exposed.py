"""Collective time not hidden behind compute, as a share of the fused
dispatch's device time (per chip)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or "jit_multi" not in trace["modules"]:
        return None
    per_chip = trace["modules"]["jit_multi"][1] / trace["chips"]
    return 100.0 * trace["collective_exposed_s"] / per_chip
