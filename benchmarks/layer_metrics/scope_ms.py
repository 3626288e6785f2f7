"""Device time under a scope below a part: milliseconds a run of a
program that chip 0 spent in the ops whose HLO instruction's `op_name`
names `scope` as one of its components (`jit(_decode)/ffn/
shortcut_experts/dot_general`), wherever under `family.PARTS` it lies.
`device_parts.py` gives every op its part and reads nothing below one;
this reads the same reduction (`device_parts.summary`: the trace's
`/host:metadata` tables joined to chip 0's op self times) by a scope's
name. Nothing where the trace has no such table, the program did not run,
or no op of it names the scope (a program from before the scope)."""

from benchmarks.layer_metrics import device_parts
from benchmarks.layer_metrics._stats import lookup


def read(ctx, module: str, scope: str, per: str | int = 1):
    s = device_parts.summary(ctx)
    if not s or module not in ctx["trace"]["modules"]:
        return None
    program = s["programs"].get(module)
    if program is None or not program["table"]:
        return None
    ns = sum(row[-1] for row in s["ops"]
             if row[0] == module and row[3]
             and scope in row[3].split(";", 1)[0].split("/"))
    if not ns:
        return None
    runs = ctx["trace"]["modules"][module][0] / ctx["trace"]["chips"]
    steps = lookup(ctx, per) if isinstance(per, str) else per
    return ns * 1e-6 / (runs * steps)
