"""Milliseconds of host time a span: the total seconds of the program
spans `plus`, less those of `minus`, over the count of the span `per`,
in the traced window. Nothing where any of the named spans is missing:
a trace of a program that does not open them all would read a
subtraction with a term left out."""

from benchmarks.harness import spans


def read(ctx, plus: list, per: str, minus: list = ()):
    s = spans.summary(ctx)
    if not s or any(n not in s["spans"] for n in (*plus, *minus, per)):
        return None
    count = s["spans"][per][0]
    if count <= 0:
        return None
    seconds = (sum(s["spans"][n][1] for n in plus)
               - sum(s["spans"][n][1] for n in minus))
    return 1e3 * seconds / count
