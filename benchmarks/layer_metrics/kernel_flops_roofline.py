"""A named kernel's share of its compute roofline: the operations a run of
a named program needs of it (a named function of the configuration's
`arith`, of the file's widths and of a number from the run's stats over
another) over the chip's peak, over the kernel's self time a run of that
program. It is `kernel_roofline.py`'s share with operations for bytes and
the other peak under it, so that reader does the reading. Nothing where
the kernel or the program is not in the trace (a program without them)."""

from benchmarks.layer_metrics import kernel_roofline


def read(ctx, kernel: str, module: str, ops: str, of: str, per: str):
    share = kernel_roofline.read(ctx, kernel, module, ops, of, per)
    if share is None:
        return None
    peaks = ctx["peaks"]
    return share * peaks["hbm_bytes_per_s"] / peaks["flops_per_s"]
