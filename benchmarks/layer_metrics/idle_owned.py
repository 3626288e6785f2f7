"""Share of chip 0's idle time, in gaps of 20 us and more, that lies
under a program span: how much of the idle time the program's own spans
explain without the Python tracer."""

from benchmarks.harness import spans


def read(ctx):
    s = spans.summary(ctx)
    if not s or not s["spans"] or not s["chips"]:
        return None
    owners = s["idle_owners"]
    unowned = owners.get(spans.NO_SPAN, 0.0)
    owned = s["idle_s"] - unowned - owners.get(spans.SHORT, 0.0)
    if owned + unowned <= 0:
        return None
    return 100.0 * owned / (owned + unowned)
