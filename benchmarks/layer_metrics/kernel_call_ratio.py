"""Calls of one named kernel over calls of another: an exact count."""

from benchmarks.harness import spans


def read(ctx, calls_of: str, per_call_of: str):
    s = spans.summary(ctx)
    if not s:
        return None
    num = spans.kernel_seconds(s, [calls_of])
    den = spans.kernel_seconds(s, [per_call_of])
    if num is None or den is None:
        return None
    return num[0] / den[0]
