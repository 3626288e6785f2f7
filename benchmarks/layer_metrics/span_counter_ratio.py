"""What the program itself counted over the window, read off the trace:
`InferenceEngine.stats()` under an open profiler session writes one
`engine/counters` span that carries every int and float entry of the dict
it returns (the engine's tallies and `ServingFamily.counts`' entries)
under the entry's own key. The replica resets the counts before the
session opens and reads `stats()` once before it closes, so the LAST such
span of the trace holds the window's totals; a profile of a live replica,
whose controller polls `stats()`, holds a series, and the last is still
the longest window.

The attributes named by `of` (a name or a list, summed) over those named
by `per` (absent: the numerator alone, for a ratio the program made).
Both totals are of one snapshot, so the reading does not depend on how
long after the reset it was taken.

Nothing where there is no trace, the trace holds no such span (a program
before it: the parent of the PR that brought it), a name is missing from
the span, or the divisor is zero. Parsed once a path.
"""

from __future__ import annotations

import functools

from benchmarks.layer_metrics import tick_events

SPAN = "engine/counters"


@functools.lru_cache(maxsize=1)
def profile(path: str):
    """The parsed trace at `path`: one parse a path, for this reader and
    whichever next reads a span's attributes."""
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


@functools.lru_cache(maxsize=1)
def last_snapshot(path: str) -> dict | None:
    """The attributes of the latest `SPAN` event of `/host:CPU`."""
    start, attrs = None, None
    for plane in profile(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN and (start is None
                                        or ev.start_ns >= start):
                    start, attrs = ev.start_ns, dict(ev.stats)
    return attrs


def read(ctx, of, per=None):
    path = tick_events.find(ctx)
    if path is None:
        return None
    attrs = last_snapshot(path)
    if attrs is None:
        return None

    def total(names):
        names = [names] if isinstance(names, str) else names
        if any(n not in attrs for n in names):
            return None
        return sum(float(attrs[n]) for n in names)

    top = total(of)
    if per is None or top is None:
        return top
    bottom = total(per)
    return top / bottom if bottom else None
