"""A named kernel's share of a roofline whose need no sum over the window
gives: the need of each run of a program is a function of attributes the
engine wrote on that run's own span, and the share is the mean need a
run over the chip's peak, over the kernels' self time a run.

    gqa_window_decode_roofline   `engine/decode_dispatch` carries
        `bounded_rows`, the sum over the decoding streams of min(context,
        window); the clients' sum of contexts cannot give a minimum a
        stream. Bound by bytes.
    gqa_chunk_roofline           `engine/prefill_chunk` carries `start`
        and `tokens`; the keys a query may see depend on where its chunk
        starts, which tokens a chunk alone do not give. Bound by
        operations.

`need` names the function of the configuration's `arith` (of the file's
widths and the span's attributes `attrs`, in order), `peak` the entry of
the peaks' table the need is divided by. The spans are those wholly
inside the window (`bench/window`, else first to last device op), as
`tick_events.py` takes its ticks. Nothing where the kernel or the program
is not in the trace, or no span carries the attributes (a program before
them).
"""

from __future__ import annotations

from benchmarks.harness import spans, trace
from benchmarks.layer_metrics import tick_events

_cache: dict[tuple, list | None] = {}


def span_attrs(path: str, span: str, attrs: tuple) -> list | None:
    """[(attribute values, in `attrs`' order)] of the `span` events
    wholly inside the window; None where one lacks an attribute."""
    from jax.profiler import ProfileData
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    found, window = [], None
    for line in planes["/host:CPU"].lines if "/host:CPU" in planes else ():
        for ev in line.events:
            if ev.name == trace.WINDOW_SPAN:
                window = (ev.start_ns, ev.start_ns + ev.duration_ns)
            elif ev.name == span:
                stats = dict(ev.stats)
                if any(a not in stats for a in attrs):
                    return None
                found.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                              tuple(float(stats[a]) for a in attrs)))
    if window is None:
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
               for name, p in planes.items() if trace.DEVICE_PLANE.match(name)
               for ln in p.lines if ln.name == trace.OPS_LINE
               for ev in ln.events]
        if ops:
            window = (min(s for s, _ in ops), max(e for _, e in ops))
    lo, hi = window or (float("-inf"), float("inf"))
    return [v for s, e, v in found if s >= lo and e <= hi]


def read(ctx, kernels: list, module: str, span: str, attrs: list,
         need: str, peak: str):
    s = spans.summary(ctx)
    path = tick_events.find(ctx)
    if not s or path is None or module not in ctx["trace"]["modules"]:
        return None
    ran = spans.kernel_seconds(s, kernels)
    if ran is None or ran[1] <= 0:
        return None
    key = (path, span, tuple(attrs))
    if key not in _cache:
        _cache[key] = span_attrs(*key)
    values = _cache[key]
    if not values:
        return None
    fn = getattr(ctx["arith"], need)
    mean_need = sum(fn(ctx["widths"], *v) for v in values) / len(values)
    run_s = ran[1] / ctx["trace"]["modules"][module][0]
    return 100.0 * mean_need / ctx["peaks"][peak] / run_s
