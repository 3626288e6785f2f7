"""Several named kernels' share, together, of their compute roofline, a
step: the operations a step needs of them (a named function of the
configuration's `arith`, of the file's widths and of a count from the
run's stats a step) over the chip's peak, over the kernels' self time a
step (`kernel_ms.py`'s basis: their seconds on all chips over the
program's runs on all chips x steps a run). `kernel_flops_roofline.py`
reads one kernel's time a run; a forward and its two backward kernels are
three names and one piece of work. Nothing where none of the kernels, the
program or the count is there (a program without them)."""

from benchmarks.harness import spans
from benchmarks.layer_metrics._stats import lookup


def read(ctx, kernels: list, ops: str, of: str, per: str,
         module: str = "jit_multi", steps: str = "traffic.unroll"):
    s = spans.summary(ctx)
    if not s or module not in ctx["trace"]["modules"]:
        return None
    found = spans.kernel_seconds(s, kernels)
    count, over = lookup(ctx, of), lookup(ctx, per)
    if found is None or found[1] <= 0 or count is None or not over:
        return None
    need = getattr(ctx["arith"], ops)(ctx["widths"], count / over)
    step_s = found[1] / (ctx["trace"]["modules"][module][0]
                         * lookup(ctx, steps))
    return 100.0 * need / ctx["peaks"]["flops_per_s"] / step_s
