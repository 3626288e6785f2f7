"""Opt-in tracing spans (OpenTelemetry-style, dependency-free).

Counterpart of the reference's `ray.util.tracing`
(`util/tracing/tracing_helper.py`: lazy OpenTelemetry proxy, spans around
task submit/execute, enabled via `ray.init(_tracing_startup_hook=...)`).
OpenTelemetry isn't in this image, so spans are recorded in-process with
the OTel span shape (name, trace/span ids, start/end ns, attributes,
parent) and exported as JSON — loadable by OTel collectors' file receiver
or converted to chrome://tracing.

Cross-process propagation is explicit, not ambient: the submitting
client stamps `propagation_context()` — a minimal `{trace_id, span_id}`
dict — onto `TaskSpec.trace_ctx` (`_private/worker.py` submit paths);
the executing worker `attach_context`s it and opens a `task.execute`
span (`_private/worker_main.py`), so spans opened inside the task nest
under the submitter's. The serve plane rides the same rails: the HTTP
proxy opens a root span per request and attaches it around the handle
call, handle→replica is an actor-method task (stamped like any other),
and the replica's context flows into the engine caller thread via
`contextvars` (`Replica._invoke` copies the context), where the
`FlightRecorder` parents its request spans under it. Workers drain
their span rings back to the head — piggybacked on `TaskDone` and on
the periodic metrics flush — and the head `ingest()`s them into its own
ring, so the node's "timeline" verb emits ONE merged
cluster trace instead of per-process fragments.

The active-span slot is a `contextvars.ContextVar`: it flows into
asyncio tasks and (via `contextvars.copy_context().run`) into executor
threads, which a `threading.local` cannot do.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import os
import threading
import time
import uuid
from typing import Dict, List, Optional

# Enablement is a cached process-local flag, refreshed only by
# enable_tracing()/_enable_local() (the SetTracing broadcast) and read
# from the RAY_TPU_TRACING env var once at import — spawned workers
# inherit the driver's env, and live ones get the broadcast. The off
# path of span() must stay a couple of attribute reads; an os.environ
# lookup per call is already too expensive for the <1% task-overhead
# contract scale_bench enforces.
_enabled = os.environ.get("RAY_TPU_TRACING") == "1"
_lock = threading.Lock()
_current: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_active_span", default=None)

# Retention: the span store is a ring — a long-running engine must not
# grow driver memory without bound. Overflow evictions are counted so a
# truncated export is observable, never silent. The ring is a deque so
# eviction is O(1) (a list's pop(0) made every overflowing record O(n)).
DEFAULT_MAX_SPANS = 10_000
_max_spans = int(os.environ.get("RAY_TPU_TRACING_MAX_SPANS",
                                DEFAULT_MAX_SPANS))
_spans: "collections.deque[dict]" = collections.deque(maxlen=_max_spans)
_dropped = 0

# Human-readable lane for this process in merged chrome traces
# ("driver", "worker:<id>", ...); falls back to the pid.
_proc_label: Optional[str] = None


def set_process_label(label: str) -> None:
    """Name this process's lane in merged chrome-trace exports."""
    global _proc_label
    _proc_label = label


def process_label() -> str:
    return _proc_label or f"pid-{os.getpid()}"


def set_max_spans(cap: int) -> None:
    """Configure the span ring's capacity (evicting oldest if needed)."""
    global _max_spans, _spans, _dropped
    with _lock:
        _max_spans = max(1, int(cap))
        old = _spans
        _spans = collections.deque(maxlen=_max_spans)
        while len(old) > _max_spans:
            old.popleft()
            _dropped += 1
        _spans.extend(old)


def max_spans() -> int:
    return _max_spans


def dropped_spans() -> int:
    """Spans evicted from the ring since process start (or clear)."""
    return _dropped


def _record(s: dict) -> None:
    global _dropped
    with _lock:
        if len(_spans) == _max_spans:
            _dropped += 1        # deque(maxlen) evicts silently; count it
        _spans.append(s)


def enable_tracing() -> None:
    """Turn span recording on cluster-wide: in this process, in workers
    spawned later (they inherit the RAY_TPU_TRACING env var), and — when
    a session is live — in already-running workers via a control-plane
    broadcast (protocol.SetTracing)."""
    global _enabled
    _enabled = True
    os.environ["RAY_TPU_TRACING"] = "1"
    try:
        from ray_tpu._private import worker as _worker
        if _worker.is_initialized():
            _worker._global_client.control("enable_tracing")
    except Exception:
        pass   # no session yet: env inheritance covers future workers


def tracing_enabled() -> bool:
    """True when span recording is on in this process — set by
    `enable_tracing()`, the SetTracing broadcast, or the inherited
    RAY_TPU_TRACING env var (read once at import)."""
    return _enabled


def _enable_local() -> None:
    """Process-local enable (the receiving end of the broadcast)."""
    global _enabled
    _enabled = True
    os.environ["RAY_TPU_TRACING"] = "1"


def _new_id(nbytes: int) -> str:
    return uuid.uuid4().hex[:nbytes * 2]


def _make_span(name: str, parent: Optional[dict],
               attributes: Optional[Dict]) -> dict:
    return {
        "name": name,
        "trace_id": parent["trace_id"] if parent else _new_id(16),
        "span_id": _new_id(8),
        "parent_span_id": parent["span_id"] if parent else None,
        "start_ns": time.time_ns(),
        "end_ns": None,
        "attributes": dict(attributes or {}),
        "status": "OK",
        "process": os.getpid(),
        "proc": process_label(),
        "thread": threading.current_thread().name,
    }


class _NullSpan:
    """Reusable no-op context manager: the tracing-off fast path of
    `span()`. A contextlib generator costs microseconds per call even
    when it yields immediately; this is two slotted method calls."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, attributes: Optional[Dict] = None):
    """Record one span; nests under the active span of this context.
    With tracing off this is a flag read + a shared null context."""
    if not _enabled:
        return _NULL_SPAN
    return _live_span(name, attributes)


@contextlib.contextmanager
def _live_span(name: str, attributes: Optional[Dict]):
    parent = _current.get()
    s = _make_span(name, parent, attributes)
    _current.set(s)
    try:
        yield s
    except BaseException as e:
        s["status"] = "ERROR"
        s["attributes"]["exception"] = repr(e)
        raise
    finally:
        s["end_ns"] = time.time_ns()
        _current.set(parent)
        _record(s)


def start_span(name: str, attributes: Optional[Dict] = None,
               parent: Optional[dict] = None):
    """Manual span start for code that can't wrap its body in a `with`
    (async request handlers, cross-thread hops). Unlike `span()` this
    does NOT gate on `tracing_enabled()` — callers open one exactly when
    a propagated context proves the trace is live (or they checked
    themselves). Returns (span, token) for `end_span`."""
    s = _make_span(name, parent if parent is not None else _current.get(),
                   attributes)
    token = _current.get()
    _current.set(s)
    return s, token


def end_span(s: dict, token, error: Optional[str] = None) -> None:
    """Close a span from `start_span` and restore the prior context."""
    if error:
        s["status"] = "ERROR"
        s["attributes"]["exception"] = error
    s["end_ns"] = time.time_ns()
    _current.set(token)
    _record(s)


def capture_context() -> Optional[dict]:
    """The active span (or attached remote context) of this execution
    context, for handing to another thread/task explicitly."""
    return _current.get()


def propagation_context(span_dict: Optional[dict] = None) -> Optional[dict]:
    """Minimal wire-format context — `{"trace_id", "span_id"}` — for
    stamping onto a TaskSpec. Reads the active span when `span_dict` is
    not given; returns None when no trace is active (nothing is stamped,
    nothing is recorded: the disabled path stays one ContextVar read)."""
    s = span_dict if span_dict is not None else _current.get()
    if s is None:
        return None
    return {"trace_id": s["trace_id"], "span_id": s["span_id"]}


def attach_context(ctx: Optional[dict]):
    """Make `ctx` (a span or a `propagation_context()` dict from the
    submitter) the calling context's active span, so spans opened here
    nest under the submitter's. Returns a token for `detach_context`."""
    prev = _current.get()
    _current.set(ctx)
    return prev


def detach_context(token) -> None:
    """Restore the context that was active before `attach_context`."""
    _current.set(token)


def get_spans() -> List[dict]:
    with _lock:
        return list(_spans)


def drain_spans() -> List[dict]:
    """Atomically remove and return all buffered spans (the worker→head
    collection hop: drained spans ride TaskDone / the metrics flush up
    to the head, which `ingest()`s them)."""
    with _lock:
        if not _spans:
            return []
        out = list(_spans)
        _spans.clear()
        return out


def ingest(spans: List[dict]) -> int:
    """Head side of the drain: append spans produced by another process
    into this ring (same cap + dropped accounting). Returns the count."""
    global _dropped
    if not spans:
        return 0
    with _lock:
        for s in spans:
            if isinstance(s, dict):
                if len(_spans) == _max_spans:
                    _dropped += 1
                _spans.append(s)
    return len(spans)


def clear_spans() -> None:
    global _dropped
    with _lock:
        _spans.clear()
        _dropped = 0


def spans_to_chrome_trace(spans: Optional[List[dict]] = None) -> List[dict]:
    """Convert to chrome://tracing 'X' events (merge with ray_tpu.timeline
    output for one combined view). Lanes are real process identities —
    pid = the producing process's label ("driver", "worker:<id>"), tid =
    the producing thread (or a span-supplied lane) — so a merged
    multi-process trace separates correctly instead of scattering one
    lane per trace id. The trace id rides in args for filtering."""
    out = []
    for s in (spans if spans is not None else get_spans()):
        end = s["end_ns"] or time.time_ns()
        out.append({
            "name": s["name"], "cat": s.get("cat", "span"), "ph": "X",
            "ts": s["start_ns"] / 1e3, "dur": (end - s["start_ns"]) / 1e3,
            "pid": s.get("proc") or s["process"],
            "tid": s.get("lane") or s.get("thread") or "main",
            "args": {**s["attributes"], "trace_id": s["trace_id"],
                     "span_id": s["span_id"]},
        })
    return out
