"""A named kernel's share of its byte roofline: the bytes a run of a named
program needs of it (a named function of the configuration's `arith`, of
the file's widths and, where `of` says so, of a number from the run's
stats, optionally over another) over the chip's memory bandwidth, over
the kernel's self time a run of that program. Nothing where the kernel
or the program is not in the trace (a program without them)."""

from benchmarks.harness import spans
from benchmarks.layer_metrics._stats import lookup


def read(ctx, kernel: str, module: str, bytes: str, of: str | None = None,
         per: str | None = None):
    s = spans.summary(ctx)
    if not s or module not in ctx["trace"]["modules"]:
        return None
    found = spans.kernel_seconds(s, [kernel])
    if found is None or found[1] <= 0:
        return None
    args = []
    if of is not None:
        value, over = lookup(ctx, of), lookup(ctx, per) if per else 1
        if value is None or not over:
            return None
        args.append(value / over)
    need = getattr(ctx["arith"], bytes)(ctx["widths"], *args)
    run_s = found[1] / ctx["trace"]["modules"][module][0]
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / run_s
