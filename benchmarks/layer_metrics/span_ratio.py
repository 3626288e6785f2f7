"""Program spans `of` over the program span `over`, in per cent: by their
counts or by their total seconds in the traced window; `complement`
gives 100 minus it (the part of `over` outside `of`)."""

from benchmarks.harness import spans


def read(ctx, of: list, over: str, by: str = "seconds",
         complement: bool = False):
    s = spans.summary(ctx)
    if not s or over not in s["spans"]:
        return None
    field = {"count": 0, "seconds": 1}[by]
    whole = s["spans"][over][field]
    if whole <= 0:
        return None
    part = sum(s["spans"][n][field] for n in of if n in s["spans"])
    share = 100.0 * part / whole
    return 100.0 - share if complement else share
