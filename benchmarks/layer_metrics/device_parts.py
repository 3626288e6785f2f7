"""Whose a device op is: chip 0's `XLA Ops` self times of the traced run,
each op given the part of the model that its HLO instruction's `op_name`
names, by program.

The program opens one vocabulary of `jax.named_scope`s where its work is
traced (`ray_tpu/models/family.py:PARTS`: `embed`, `mixer`, `ffn`,
`head`, `optimizer`), and a scope is metadata on the HLO: every
instruction of the optimized module carries the path of scopes and
transforms it was traced under as `metadata.op_name`
(`jit(_decode)/while/body/closed_call/mixer/dot_general`). The trace
holds those modules itself: the plane `/host:metadata`, which has no
lines and which `jax.profiler.ProfileData` therefore shows as empty, has
one `XEventMetadata` a program that ran, named like its `XLA Modules`
events (`jit__decode(6206349698961270391)`), with one stat `Hlo Proto`.
So the join needs no second compile and no file beside the trace:

    op event `%fusion.171 = ...`  ->  instruction `fusion.171`
    -> `op_name` in the table of the program that ran at that time
    -> part (the first component that is one of the five, also inside
       `transpose(jvp(..))`), else `unscoped` (an `op_name` with none of
       them), else `compiler` (no `op_name`: copies, bitcasts and
       whatever else the compiler put in; or no such instruction)
    -> direction: `recompute` under a checkpoint's
       `rematted_computation`, else `bwd` under a `transpose(..)`, else
       `fwd`

A fusion is one instruction and goes to its own `op_name`, which is its
root's. What a `lax.scan` does itself carries the scan's path and no
scope of the body's (`.../while/body/dynamic_slice`: a layer's weights
cut out of the stack, and on a v5e laid out anew for the matmul that
reads them; `.../while/body/dynamic_update_slice`: a layer's gradient
put into the stack): such an instruction takes the part of the nearest
instruction it feeds that has one, else of the nearest that feeds it
(`inherited`), and only what no part is near stays `unscoped`. An
instruction without `op_name` inherits nothing: `compiler` is a class of
its own. Times are self times (a `while` less its body), clipped to the
window (`bench/window`, else first to last device op), chip 0 alone.

The plane is read by a walker over the protobuf wire format: the five
message types it needs are a few fields each, tensorflow's classes take
15 s to import and the chip's image need not have them. A trace without
the plane, or a program without a table, gives None, never 0.
"""

from __future__ import annotations

import bisect
import collections
import re

from benchmarks.harness import trace
from benchmarks.layer_metrics import tick_events

PARTS = ("embed", "mixer", "ffn", "head", "optimizer")
UNSCOPED, COMPILER = "unscoped", "compiler"
FWD, BWD, RECOMPUTE = "fwd", "bwd", "recompute"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
_WRAPPED = re.compile(r"^(?:(?:transpose|jvp|vmap)\()+([^()]*)\)+$")
_cache: dict[str, dict | None] = {}


# ---------------------------------------------------------------------------
# the protobuf wire format, as far as the tables need it
# ---------------------------------------------------------------------------

def fields(buf):
    """(field number, value) of every field of one message: an int for
    a varint or a fixed-width field, a memoryview for a length-delimited
    one (a string, bytes or a nested message: the caller knows which)."""
    buf = memoryview(buf)
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, kind = key >> 3, key & 7
        if kind == 0:
            value, at = _varint(buf, at)
        elif kind == 2:
            size, at = _varint(buf, at)
            value, at = buf[at:at + size], at + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value = int.from_bytes(buf[at:at + size], "little")
            at += size
        else:
            raise ValueError(f"wire type {kind} at byte {at}")
        yield number, value


def _varint(buf, at):
    value = shift = 0
    while True:
        byte = buf[at]
        at += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, at
        shift += 7


def _first(buf, number):
    return next((v for n, v in fields(buf) if n == number), None)


def _text(view) -> str:
    return "" if view is None else bytes(view).decode("utf-8", "replace")


def hlo_instructions(hlo_proto) -> list:
    """[(name, opcode, op_name ("" where it has none), id, operand ids)]
    over every computation of a serialized `HloProto` (hlo_module 1 >
    computations 3 > instructions 2 > name 1, opcode 2, metadata 7 >
    op_name 2, id 35, operand_ids 36, packed or not)."""
    out = []
    module = _first(hlo_proto, 1)
    for n, computation in fields(module) if module is not None else ():
        if n != 3:
            continue
        for m, instruction in fields(computation):
            if m != 2:
                continue
            name = opcode = meta = ident = None
            operands = []
            for k, v in fields(instruction):
                if k == 1:
                    name = v
                elif k == 2:
                    opcode = v
                elif k == 7:
                    meta = v
                elif k == 35:
                    ident = v
                elif k == 36 and isinstance(v, int):
                    operands.append(v)
                elif k == 36:
                    at = 0
                    while at < len(v):
                        one, at = _varint(v, at)
                        operands.append(one)
            out.append((_text(name), _text(opcode),
                        _text(_first(meta, 2)) if meta else "", ident,
                        operands))
    return out


CONTROL = ("while", "conditional", "call")
NEAR = 8        # instructions between one without a part and its part


def hlo_parts(instructions) -> dict:
    """{instruction name: (op_name, part, direction, inherited)} of
    `hlo_instructions`' rows: the part its own `op_name` names, else,
    for one that has an `op_name` and is no `while`, `conditional` or
    `call`, the part of the nearest instruction that it feeds, else
    that feeds it, walking through instructions without a part (never
    through one of those three: a loop's inputs are not its results'),
    at most `NEAR` of them."""
    own = {ident: part_of(op_name)
           for _, _, op_name, ident, _ in instructions}
    control = {ident for _, opcode, _, ident, _ in instructions
               if opcode in CONTROL}
    feeds = collections.defaultdict(list)
    fed_by = {}
    for _, _, _, ident, operands in instructions:
        fed_by[ident] = operands
        for operand in operands:
            feeds[operand].append(ident)

    def nearest(start, edges):
        seen, front = {start}, [start]
        for _ in range(NEAR):
            front = sorted({n for at in front for n in edges.get(at, ())
                            if n not in seen and n not in control})
            found = [own[n] for n in front if own.get(n) in PARTS]
            if found:
                return found[0]
            if not front:
                return None
            seen.update(front)
        return None

    out = {}
    for name, opcode, op_name, ident, _ in instructions:
        part, inherited = own[ident], False
        if part == UNSCOPED and opcode not in CONTROL:
            near = nearest(ident, feeds) or nearest(ident, fed_by)
            part, inherited = near or part, near is not None
        out[name] = (op_name, part, direction_of(op_name), inherited)
    return out


def tables(path: str) -> dict | None:
    """{program as its `XLA Modules` events name it: `hlo_parts` of
    its module} from the trace file's `/host:metadata` plane (XSpace.planes
    1; XPlane.name 2, .event_metadata 4 and .stat_metadata 5, both maps
    of key 1 to value 2; XEventMetadata.name 2, .stats 5;
    XStatMetadata.name 2; XStat.metadata_id 1, .bytes_value 6). None
    where the file has no such plane."""
    with open(path, "rb") as f:
        space = f.read()
    for n, plane in fields(space):
        if n != 1 or _text(_first(plane, 2)) != METADATA_PLANE:
            continue
        stat_names, events = {}, []
        for m, entry in fields(plane):
            if m == 5:
                meta = _first(entry, 2)
                stat_names[_first(entry, 1)] = _text(_first(meta, 2))
            elif m == 4:
                events.append(_first(entry, 2))
        out = {}
        for event in events:
            name, protos = None, []
            for k, v in fields(event):
                if k == 2:
                    name = _text(v)
                elif k == 5:
                    stat = dict(fields(v))
                    if stat_names.get(stat.get(1)) == HLO_STAT and 6 in stat:
                        protos.append(stat[6])
            if name and protos:
                out[name] = hlo_parts(hlo_instructions(protos[0]))
        return out
    return None


# ---------------------------------------------------------------------------
# an op_name's part and direction
# ---------------------------------------------------------------------------

def _components(op_name: str):
    """The scopes of the first of an instruction's `;`-joined paths, the
    transforms around a scope taken off: `transpose(jvp(mixer))` ->
    `mixer`."""
    for component in op_name.split(";", 1)[0].split("/"):
        wrapped = _WRAPPED.match(component)
        yield wrapped.group(1) if wrapped else component


def part_of(op_name: str | None, parts=PARTS) -> str:
    if not op_name:
        return COMPILER
    return next((c for c in _components(op_name) if c in parts), UNSCOPED)


def direction_of(op_name: str | None) -> str:
    path = (op_name or "").split(";", 1)[0]
    if "rematted_computation" in path.split("/"):
        return RECOMPUTE
    return BWD if "transpose(" in path else FWD


# ---------------------------------------------------------------------------
# the trace's ops, each with its part
# ---------------------------------------------------------------------------

def reduce(path: str) -> dict | None:
    """Nanoseconds throughout; None where the trace has no
    `/host:metadata` plane or no device op.

    programs  {program, its fingerprint taken off: {"runs": chip 0's runs
              wholly inside the window, "self_ns": its ops' self time,
              "table": whether the plane holds its module,
              "parts": {(part, direction): self ns},
              "inherited": {part: self ns of it that was inherited}}}
    ops       [(program, instruction, label, op_name or None, part,
              direction, inherited, calls, self ns)], the largest first
    """
    found = tables(path)
    if found is None:
        return None
    from jax.profiler import ProfileData
    planes = {p.name: p for p in ProfileData.from_file(path).planes}
    chip0 = min((n for n in planes if trace.DEVICE_PLANE.match(n)),
                key=lambda n: (len(n), n), default=None)
    if chip0 is None:
        return None
    lines = {ln.name: [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                       for ev in ln.events]
             for ln in planes[chip0].lines
             if ln.name in (trace.OPS_LINE, trace.MODULES_LINE)}
    ops = lines.get(trace.OPS_LINE, [])
    if not ops:
        return None
    window = None
    for line in planes["/host:CPU"].lines if "/host:CPU" in planes else ():
        for ev in line.events:
            if ev.name == trace.WINDOW_SPAN:
                window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    lo, hi = window or (min(s for s, _, _ in ops), max(e for _, e, _ in ops))
    modules = sorted(lines.get(trace.MODULES_LINE, []))
    starts = [m[0] for m in modules]

    def short(name):
        return re.sub(r"\(\d+\)$", "", name)

    def running(at):
        i = bisect.bisect_right(starts, at) - 1
        return modules[i][2] if i >= 0 and at < modules[i][1] else ""

    programs = {}
    for s, e, name in modules:
        p = programs.setdefault(short(name), {
            "runs": 0, "self_ns": 0.0, "table": False,
            "parts": collections.defaultdict(float),
            "inherited": collections.defaultdict(float)})
        p["runs"] += s >= lo and e <= hi
        p["table"] = p["table"] or name in found
    per_op = collections.defaultdict(lambda: [0, 0.0])
    for (s, name), dur in trace._self_times(
            [(s, e, (s, n)) for s, e, n in trace._clip(ops, lo, hi)]):
        program = running(s)
        instruction, label = trace.op_label(name)
        row = found.get(program, {}).get(
            instruction, (None, COMPILER, FWD, False))
        tot = per_op[(short(program), instruction, label, *row)]
        tot[0] += 1
        tot[1] += dur
    rows = []
    for key, (calls, ns) in per_op.items():
        program, _, _, _, part, direction, inherited = key
        p = programs.get(program)
        if p is not None:
            p["self_ns"] += ns
            p["parts"][part, direction] += ns
            if inherited:
                p["inherited"][part] += ns
        rows.append((*key, calls, ns))
    rows.sort(key=lambda r: -r[-1])
    return {"programs": programs, "ops": rows, "window_ns": hi - lo}


def summary(ctx: dict) -> dict | None:
    """This run's reduction, parsed once however many metrics read it;
    None for an untraced run or a trace kept elsewhere."""
    path = tick_events.find(ctx)
    if path is None:
        return None
    if path not in _cache:
        _cache[path] = reduce(path)
    return _cache[path]


def read(ctx, module: str, part: str | None = None, per: str | int = 1,
         direction: str | None = None):
    """Milliseconds a run of `module` on one chip (over `per`: the steps
    of a fused dispatch, a dotted path into the run's data or a number)
    that chip 0 spent in the ops of `part` (every part where None) in
    `direction` (every direction where None). The runs are
    `ctx["trace"]["modules"]`'s, all chips', over the chips. A program
    that names no part anywhere (one from before the scopes, or one a
    compile cache answered with an executable from before them: a scope
    is no part of a cache key) has `compiler` to read and nothing else."""
    s = summary(ctx)
    if not s or module not in ctx["trace"]["modules"]:
        return None
    program = s["programs"].get(module)
    if program is None or not program["table"]:
        return None
    if part != COMPILER and not any(p in PARTS for p, _ in program["parts"]):
        return None
    from benchmarks.layer_metrics._stats import lookup
    ns = sum(v for (p, d), v in program["parts"].items()
             if part in (None, p) and direction in (None, d))
    runs = ctx["trace"]["modules"][module][0] / ctx["trace"]["chips"]
    steps = lookup(ctx, per) if isinstance(per, str) else per
    return ns * 1e-6 / (runs * steps)
