"""From a profiler trace (`.xplane.pb`) to numbers: device busy time, time
per operation and per program, collectives. Kept with the benchmark so
every PR reduces its trace the same way; checked against small recorded
v5e traces in `benchmarks/tests/`. Who owned the idle gaps is
`spans.py`'s: the program's own spans name them.

What a v5e trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`. Its line `XLA Modules` has one event per executed
program, named `jit_<fn>(<fingerprint>)`. Its line `XLA Ops` has one
event per executed HLO instruction, named by the instruction's whole
text (`%fusion.3 = bf16[..]{layout} fusion(...)`); a `while` or a
conditional spans the instructions of its body, so times here are *self*
times. A Pallas kernel is a `custom-call` with
`custom_call_target="tpu_custom_call"`; the trace does not carry the
kernel's Python name, so a kernel is known by its shapes:
`pallas <results> <- <operands>`. `Async XLA Ops` holds copies and
collectives that overlap compute. The plane `/host:CPU` has one line per
host thread holding every `TraceAnnotation` (and, were the Python tracer
on, spans `$file.py:line function`). One clock, nanoseconds.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re
import shutil


DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench/window"
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
SHORT_GAP_NS = 20_000
_SHAPE = re.compile(r"(\w+\[[\d,]*\])")


def start(trace_dir: str) -> None:
    """Python tracer off: the program's own spans own every idle gap
    (`idle_owned_share` 100 % in both training cells, ledger PR 24-29),
    and the tracer slows the host it watches."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} traces under {trace_dir}")
    return found[0]


def _split_result(rest: str) -> tuple[str, str]:
    """`<result type> <opcode>(...` -> (result type, remainder)."""
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                return rest[:i + 1], rest[i + 1:].lstrip()
    head, _, tail = rest.partition(" ")
    return head, tail


_label_cache: dict[str, tuple[str, str]] = {}


def op_label(event_name: str) -> tuple[str, str]:
    """(instruction name, short label) of an `XLA Ops` event. The label
    is `<instruction> <opcode> <result shapes>`, and for a Pallas kernel
    `pallas <result shapes> <- <operand shapes>`."""
    hit = _label_cache.get(event_name)
    if hit:
        return hit
    m = re.match(r"%?([\w.\-]+) = (.*)", event_name, re.S)
    if not m:
        out = (event_name, event_name[:96])
    else:
        instr = m.group(1)
        result, tail = _split_result(m.group(2))
        opcode = tail.split("(", 1)[0]
        shapes = ",".join(_SHAPE.findall(result))
        if 'custom_call_target="tpu_custom_call"' in tail:
            args = tail.split("(", 1)[1].split("), custom_call_target")[0]
            label = f"pallas {shapes} <- {','.join(_SHAPE.findall(args))}"
        else:
            label = f"{instr} {opcode} {shapes}"[:96]
        out = (instr, label)
    _label_cache[event_name] = out
    return out


def _merge(intervals) -> list[list[float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _union(intervals) -> float:
    return sum(e - s for s, e in _merge(intervals))


def _gaps(intervals, lo: float, hi: float):
    out, at = [], lo
    for s, e in _merge(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def _minus(intervals, cover) -> float:
    """Total length of `intervals` (merged) outside the union of `cover`."""
    merged = _merge(cover)
    starts = [m[0] for m in merged]
    total = 0.0
    for s, e in _merge(intervals):
        left = e - s
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        while i < len(merged) and merged[i][0] < e:
            left -= max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
            i += 1
        total += max(left, 0.0)
    return total


def _self_times(events):
    """events: (start, end, key) properly nested on one line. Yields
    (key, self duration): an instruction's time minus its body's."""
    events = sorted(events, key=lambda t: (t[0], -(t[1] - t[0])))
    stack = []           # [end, key, self]
    for s, e, key in events:
        while stack and stack[-1][0] <= s:
            done = stack.pop()
            yield done[1], done[2]
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, key, e - s])
    while stack:
        done = stack.pop()
        yield done[1], done[2]


def _clip(events, lo, hi):
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def reduce(path: str, top: int = 10) -> dict:
    """The summary every trace-sourced metric reads. Seconds throughout.

    window_s       the traced window: the `bench/window` annotation where
                   the benchmark wrote one, else first to last device op
    busy_s         union of device-op intervals in the window, per chip
    ops            {"<program>/<label>": [calls, self seconds]}, all chips
    modules        {program: [runs, seconds, median seconds]}, all chips
    collective_s, collective_exposed_s   per chip: collective time, and the
                   part of it during which no other op ran on that chip
    device_ops     the `top` ops by self seconds: [[name, seconds], ...]
    """
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {p.name: p for p in data.planes}
    chips = sorted((int(DEVICE_PLANE.match(n).group(1)), p)
                   for n, p in planes.items() if DEVICE_PLANE.match(n))
    window = None
    if "/host:CPU" in planes:
        for line in planes["/host:CPU"].lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)

    per_chip, inventory = [], {}
    for _, plane in chips:
        lines = {}
        for ln in plane.lines:
            if ln.name not in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                continue
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in ln.events]
            lines.setdefault(ln.name, []).extend(evs)
            if evs:
                inventory[f"{plane.name}/{ln.name}"] = [
                    len(evs), min(e[0] for e in evs) * 1e-9,
                    max(e[1] for e in evs) * 1e-9]
        per_chip.append((lines.get(OPS_LINE, []), lines.get(ASYNC_LINE, []),
                         lines.get(MODULES_LINE, [])))
    empty = {"chips": len(per_chip), "lines": inventory,
             "window_s": 0.0, "busy_s": 0.0,
             "collective_s": 0.0, "collective_exposed_s": 0.0, "ops": {},
             "modules": {}, "device_ops": []}
    if not any(ops for ops, _, _ in per_chip):
        return empty
    if window is None:
        window = (min(min(s for s, _, _ in o) for o, _, _ in per_chip if o),
                  max(max(e for _, e, _ in o) for o, _, _ in per_chip if o))
    lo, hi = window
    ns = 1e-9
    op_tot = collections.defaultdict(lambda: [0, 0.0])
    mod_runs = collections.defaultdict(list)
    busy = coll = exposed = 0.0
    for ops, asyncs, modules in per_chip:
        ops, asyncs = _clip(ops, lo, hi), _clip(asyncs, lo, hi)
        busy += _union([(s, e) for s, e, _ in ops])
        modules = sorted((s, e, re.sub(r"\(\d+\)$", "", n))
                         for s, e, n in modules)
        mod_starts = [m[0] for m in modules]

        def program(at, modules=modules, mod_starts=mod_starts):
            i = bisect.bisect_right(mod_starts, at) - 1
            return modules[i][2] if i >= 0 and at < modules[i][1] else ""

        for (s, name), dur in _self_times(
                [(s, e, (s, n)) for s, e, n in ops]):
            tot = op_tot[f"{program(s)}/{op_label(name)[1]}"]
            tot[0] += 1
            tot[1] += dur * ns
        is_coll = [bool(COLLECTIVE.match(op_label(n)[0]))
                   for _, _, n in ops]
        cs = [(s, e) for (s, e, n) in asyncs
              if COLLECTIVE.match(op_label(n)[0])]
        cs += [(s, e) for (s, e, _), c in zip(ops, is_coll) if c]
        # compute = leaf ops that are not collectives (a `while` spans
        # its body, so containers say nothing about overlap)
        leaves = [(s, e) for (s, e, n), c in zip(ops, is_coll) if not c
                  and op_label(n)[1].split(" ")[1:2]
                  not in (["while"], ["conditional"], ["call"])]
        coll += _union(cs)
        exposed += _minus(cs, leaves)
        for s, e, n in modules:
            if s >= lo and e <= hi:
                mod_runs[n].append((e - s) * ns)
    n_chips = len(per_chip)

    def med(xs):
        return sorted(xs)[len(xs) // 2]

    ranked = sorted(op_tot.items(), key=lambda kv: -kv[1][1])
    return {
        "chips": n_chips,
        "lines": inventory,      # {plane/line: [events, first s, last s]}
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n_chips,
        "collective_s": coll * ns / n_chips,
        "collective_exposed_s": exposed * ns / n_chips,
        "ops": dict(op_tot),
        "modules": {n: [len(v), sum(v), med(v)]
                    for n, v in mod_runs.items()},
        "device_ops": [[n, v[1]] for n, v in ranked[:top]],
    }


def op_seconds(summary: dict, pattern: str) -> tuple[int, float]:
    """(calls, self seconds) over the ops whose key matches `pattern`."""
    rx = re.compile(pattern)
    calls, secs = 0, 0.0
    for name, (n, s) in summary.get("ops", {}).items():
        if rx.search(name):
            calls += n
            secs += s
    return calls, secs
