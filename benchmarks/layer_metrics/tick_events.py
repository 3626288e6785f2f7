"""The `engine/tick` events of the traced run with the two attributes the
engine writes on each (`gap_us`: the time since the previous tick ended;
`carried`: whether that tick left work behind), and chip 0's idle time
split between "inside a tick" and "between two ticks" by overlap.

`spans.idle_owners` gives a whole gap to the innermost span that covers
its midpoint, so a decode-only tick's one gap (the end of the decode
program, over the token's return, the emit, the hand-off, admission and
the next tick's build, to the next dispatch) changes owner with a few
hundred microseconds. Here a gap is cut at the ticks' own ends: what
lies inside an `engine/tick` is the tick's, what lies inside
`[start - gap_us, start]` of a tick with `carried` 1 is the hand-off's,
and what lies before a tick that carried nothing (demand that was not
there) is neither's. Every gap counts, the ones under 20 us too.

    what                   reads
    tick_gap_ms            median `gap_us` / 1000 of the carried ticks
    idle_in_tick_ms        chip 0's idle time inside a tick, a tick
    idle_between_ticks_ms  chip 0's idle time inside a carried gap, a tick

The window and the count of ticks are `spans.reduce`'s: `bench/window`,
else first to last device op; a tick counts where it lies wholly inside.
A trace whose ticks carry no `gap_us` (a program before the attribute)
gives nothing, and so does one with no device plane for the idle two.
"""

from __future__ import annotations

import statistics

from benchmarks.harness import spans, trace

TICK = "engine/tick"
_cache: dict[str, dict | None] = {}


def split(idle, ticks) -> tuple[float, float]:
    """(inside a tick, between two ticks) of the idle intervals `idle`,
    by overlap. `ticks`: `(start, end, gap, carried)` in `idle`'s unit;
    a tick's gap is `[start - gap, start]` and counts only where
    `carried`."""
    whole = sum(e - s for s, e in idle)
    inside = [(s, e) for s, e, _, _ in ticks]
    between = [(s - gap, s) for s, _, gap, carried in ticks if carried]
    return (whole - trace._minus(idle, inside),
            whole - trace._minus(idle, between))


def reduce(path: str) -> dict | None:
    """Seconds throughout; None where no tick carries `gap_us`.

    ticks           `engine/tick` events wholly inside the window
    gaps_s          `gap_us` of those with `carried` 1
    chips           device planes in the trace
    idle_s          chip 0's idle time in the window
    idle_in_tick_s, idle_between_ticks_s   its two parts, by overlap
    """
    from jax.profiler import ProfileData
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    ticks, window = [], None
    for line in planes["/host:CPU"].lines if "/host:CPU" in planes else ():
        for ev in line.events:
            if ev.name == trace.WINDOW_SPAN:
                window = (ev.start_ns, ev.start_ns + ev.duration_ns)
            elif ev.name == TICK:
                attrs = dict(ev.stats)
                if "gap_us" not in attrs:
                    return None
                ticks.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                              int(attrs["gap_us"]) * 1000,
                              int(attrs["carried"])))
    if not ticks:
        return None
    chips = [[(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
              for ln in planes[name].lines if ln.name == trace.OPS_LINE
              for ev in ln.events]
             for name in sorted(planes, key=lambda n: (len(n), n))
             if trace.DEVICE_PLANE.match(name)]
    if window is None and any(chips):
        window = (min(s for ops in chips for s, _, _ in ops),
                  max(e for ops in chips for _, e, _ in ops))
    ticks.sort()
    # a CPU trace: no device op to open the window, every tick is in it
    lo, hi = window or (ticks[0][0], max(t[1] for t in ticks))
    whole = [t for t in ticks if t[0] >= lo and t[1] <= hi]
    ns = 1e-9
    out = {"ticks": len(whole), "chips": len(chips),
           "gaps_s": [gap * ns for _, _, gap, carried in whole if carried],
           "idle_s": None, "idle_in_tick_s": None,
           "idle_between_ticks_s": None}
    if chips:
        ops0 = [(s, e) for s, e, _ in trace._clip(chips[0], lo, hi)]
        idle = trace._gaps(ops0, lo, hi)
        inside, between = split(idle, ticks)
        out.update(idle_s=sum(e - s for s, e in idle) * ns,
                   idle_in_tick_s=inside * ns,
                   idle_between_ticks_s=between * ns)
    return out


def find(ctx: dict) -> str | None:
    """This run's trace, where `spans.summary` looks for it."""
    if not ctx.get("trace"):
        return None
    try:
        return trace.find_xplane(spans.trace_dir())
    except FileNotFoundError:
        return None


def summary(ctx: dict) -> dict | None:
    path = find(ctx)
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = reduce(path)
    return _cache[path]


def read(ctx, what: str):
    """`tick_gap_ms`, `idle_in_tick_ms` or `idle_between_ticks_ms`."""
    s = summary(ctx)
    if not s:
        return None
    if what == "tick_gap_ms":
        return statistics.median(s["gaps_s"]) * 1e3 if s["gaps_s"] else None
    idle = s[what.removesuffix("_ms") + "_s"]
    if idle is None or not s["ticks"]:
        return None
    return idle * 1e3 / s["ticks"]
