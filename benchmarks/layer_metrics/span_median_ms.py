"""Median duration of a program span in the traced window."""

from benchmarks.harness import spans


def read(ctx, span: str):
    s = spans.summary(ctx)
    if not s or span not in s["spans"]:
        return None
    return s["spans"][span][2] * 1e3
