"""A program span's total time as a share of the traced window."""

from benchmarks.harness import spans


def read(ctx, span: str):
    s = spans.summary(ctx)
    if not s or span not in s["spans"] or s["window_s"] <= 0:
        return None
    return 100.0 * s["spans"][span][1] / s["window_s"]
