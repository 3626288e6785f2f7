"""Shared by the readers: look a dotted path up in the run's context."""

from __future__ import annotations


def lookup(ctx: dict, path: str):
    """`loop.prefetch_share` is read from the run's stats; a path that
    starts with `traffic.`, `config.` or `cell.` from that file's data.
    A path that leads nowhere gives None."""
    head, _, rest = path.partition(".")
    if head in ("traffic", "config", "cell"):
        value, path = ctx[head], rest
    else:
        value = ctx["stats"]
    for key in path.split("."):
        if not isinstance(value, dict) or key not in value:
            return None
        value = value[key]
    return value
