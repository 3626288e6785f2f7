"""A second reduction of the traced run's `.xplane.pb`, by name: what the
program itself wrote into the profiler's trace (PERF.md, section 3).

  * kernels: every Pallas kernel has a name of its own
    (`pl.pallas_call(name=...)`), which is its instruction's name in the
    `XLA Ops` line: `%flash_fwd.12 = ... custom-call(...),
    custom_call_target="tpu_custom_call"` is a call of `flash_fwd`.
  * program spans: `ray_tpu.util.telemetry.Phases` opens a
    `jax.profiler.TraceAnnotation` per span, so `/host:CPU` holds
    `train/...`, `engine/...` and `stream/...` events on the clock of the
    device planes, with or without the Python tracer.
  * idle owners: each gap of chip 0 of 20 us and more belongs to the
    innermost program span covering its midpoint.

`trace.reduce` keeps its job (busy time, ops by shape, modules,
collectives); the readers of the named metrics call `summary(ctx)` here.
The trace is found where the drivers write it (`train_cell.py` in this
process, `serve_cell.py`'s replica in its own):
`<checkout>/.bench_scratch/<pid of run.py>/trace`, which `run.py` keeps
until every metric is read. Parsed once a process.

A trace of a program without names or spans (the parent of the PR that
added them) gives empty tables, and the readers then return nothing.
"""

from __future__ import annotations

import collections
import os
import re
import statistics

from benchmarks.harness import common, trace

SPAN = re.compile(r"^(train|engine|stream)/")
KERNEL = re.compile(r"^%?([\w\-]+?)(?:\.\d+)? = ")
NO_SPAN = "no program span"
SHORT = "between queued ops (<20us)"

_cache: dict[str, dict] = {}


def reduce(path: str) -> dict:
    """Seconds throughout.

    chips      device planes in the trace
    window_s   the `bench/window` annotation, else first to last device op
    kernels    {name: [calls, self seconds]}, all chips, inside the window
    spans      {name: [count, total seconds, median seconds]} of the
               program spans that end inside the window
    idle_s     chip 0's idle time in the window
    idle_owners {owner: seconds}: a program span's name, `no program
               span`, or `between queued ops (<20us)`; sums to idle_s
    """
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {p.name: p for p in data.planes}
    spans, window = [], None
    if "/host:CPU" in planes:
        for line in planes["/host:CPU"].lines:
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif SPAN.match(ev.name):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    chips = []
    for name in sorted(planes, key=lambda n: (len(n), n)):
        if not trace.DEVICE_PLANE.match(name):
            continue
        ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
               for ln in planes[name].lines if ln.name == trace.OPS_LINE
               for ev in ln.events]
        chips.append(ops)
    if window is None and any(chips):
        window = (min(s for ops in chips for s, _, _ in ops),
                  max(e for ops in chips for _, e, _ in ops))
    out = {"chips": len(chips), "window_s": 0.0, "kernels": {}, "spans": {},
           "idle_s": 0.0, "idle_owners": {}}
    if window is None:
        if spans:       # a CPU trace: host spans and no device plane
            window = (min(s for s, _, _ in spans),
                      max(e for _, e, _ in spans))
        else:
            return out
    lo, hi = window
    ns = 1e-9
    out["window_s"] = (hi - lo) * ns

    durations = collections.defaultdict(list)
    for s, e, name in spans:
        if s >= lo and e <= hi:
            durations[name].append((e - s) * ns)
    out["spans"] = {n: [len(v), sum(v), statistics.median(v)]
                    for n, v in durations.items()}

    kernels = collections.defaultdict(lambda: [0, 0.0])
    for ops in chips:
        for name, dur in trace._self_times(trace._clip(ops, lo, hi)):
            if 'custom_call_target="tpu_custom_call"' not in name:
                continue
            m = KERNEL.match(name)
            tot = kernels[m.group(1) if m else name[:48]]
            tot[0] += 1
            tot[1] += dur * ns
    out["kernels"] = dict(kernels)

    if chips:
        owners = collections.defaultdict(float)
        inner = sorted(spans, key=lambda t: t[1] - t[0])   # innermost first
        ops0 = [(s, e) for s, e, _ in trace._clip(chips[0], lo, hi)]
        for s, e in trace._gaps(ops0, lo, hi):
            if e - s < trace.SHORT_GAP_NS:
                owners[SHORT] += (e - s) * ns
                continue
            mid = (s + e) / 2
            owner = next((n for a, b, n in inner if a <= mid < b), NO_SPAN)
            owners[owner] += (e - s) * ns
        out["idle_owners"] = dict(owners)
        out["idle_s"] = sum(owners.values())
    return out


def trace_dir() -> str:
    """Where the drivers write this run's trace."""
    return os.path.join(common.ROOT, ".bench_scratch", str(os.getpid()),
                        "trace")


def summary(ctx: dict) -> dict | None:
    """The reduction of this run's trace, or None where there is none:
    an untraced run, or a driver that keeps its trace elsewhere."""
    if not ctx.get("trace"):
        return None
    try:
        path = trace.find_xplane(trace_dir())
    except FileNotFoundError:
        return None
    if path not in _cache:
        _cache[path] = reduce(path)
    return _cache[path]


def idle_gaps(ctx: dict, top: int = 10) -> list:
    """`breakdown.idle_gaps`: chip 0's idle seconds by the program span
    that owned them, the largest first."""
    s = summary(ctx)
    if not s:
        return []
    return [[n, v] for n, v in sorted(s["idle_owners"].items(),
                                      key=lambda kv: -kv[1])[:top]]


def kernel_seconds(s: dict, names) -> tuple[int, float] | None:
    """(calls, self seconds) over those of the named kernels that ran;
    None where none of them did (a fused kernel may stand for two of the
    names: whichever ran are the whole)."""
    ran = [s["kernels"][n] for n in names if n in s["kernels"]]
    if not ran:
        return None
    return sum(k[0] for k in ran), sum(k[1] for k in ran)
