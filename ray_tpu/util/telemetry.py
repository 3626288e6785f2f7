"""Hot-path flight recorder: one observability plane over the engine,
trainer, and flywheel telemetry islands.

The serving engine, training loop, and RL flywheel each keep rich
private telemetry (`InferenceEngine.stats()`, `MetricsRing`,
`weight_swap_ms`), none of which reached the plane the core ships — the
`util.metrics` Prometheus registry, `util.tracing` spans, the
dashboard's `/metrics` and `/api/timeline`. This module is the bridge,
built from five pieces:

  * `Phases` — program spans on the profiler's clock: an owner's
    `phase(name, **attrs)` is a `jax.profiler.TraceAnnotation` plus a
    `{name: [count, seconds]}` total the owner hands out in `stats()`.
    The train loop, the prefetcher, the engine's tick and pump and the
    serve replica's replies time themselves with it, so a profiler
    trace shows what the host did on the clock of the device planes
    without the Python tracer. The recorder below keeps the epoch's
    clock (the merged timeline's) and joins a profile by tick number.

  * `FlightRecorder` — per-request lifecycle tracing for an engine:
    submit → queue wait → each prefill chunk (prefix-hit/COW annotated)
    → decode → first token → first yield to the stream's consumer →
    finish/cancel/swap-crossing, recorded as `util.tracing`-shaped span
    dicts in a bounded ring (evictions counted, never silent), each
    tagged with the engine tick it fell in. Sampled per request
    (`RAY_TPU_TELEMETRY_SAMPLE`, default 1.0) and cheap enough to leave
    on: the per-token hook is one dict lookup + an int increment, and an
    unsampled request costs a single failed lookup per hook.
    Distills TTFT and queue-wait into `util.metrics` histograms.

  * stats-dict metrics bridge — `register_stats_source(name, obj)`
    holds a weakref to anything with a `stats() -> dict` (engines,
    replicas, train loops, flywheels) and a collect hook
    (`metrics.add_collect_hook`) republishes every numeric stat as a
    Gauge — or, for the monotone keys in `COUNTER_KEYS`, a delta-tracked
    Counter that treats a decrease as `reset_stats()` — tagged by
    source, so the dashboard's `/metrics` serves engine / replica /
    paged-cache / spec-decode / flywheel-staleness series to Prometheus
    with no per-step push anywhere on the hot path.

  * `RetraceSentinel` — runtime watcher over compile-once counters
    (`decode_traces`, `verify_traces`, `swap_traces`, the fused train
    dispatch). Pinned paths carry a hard cap from construction; bucket-
    dependent paths (prefill) are baselined by `arm()` after warmup.
    The moment any watched counter exceeds its allowance the sentinel
    increments `retraces_unexpected` and emits ONE WARN per path — the
    property the compile-once tests pin only at test time, enforced in
    production.

  * `chrome_trace_events()` / `summary()` / `check_invariants()` —
    exports: recorder spans + `util.tracing` spans as chrome://tracing
    events (the node's "timeline" verb merges them with task events into
    one view), a JSON health summary for `/api/telemetry`, and the
    self-test the shared test-session fixture runs at teardown.

Everything here is driver/host-side: no device syncs, no jax import at
module load.
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import random
import re
import sys
import threading
import time
import uuid
import weakref

from ray_tpu.util import metrics as _metrics
from ray_tpu.util import tracing as _tracing

logger = logging.getLogger(__name__)

DEFAULT_SAMPLE = float(os.environ.get("RAY_TPU_TELEMETRY_SAMPLE", "1.0"))
DEFAULT_MAX_SPANS = int(os.environ.get("RAY_TPU_TELEMETRY_MAX_SPANS",
                                       "4096"))
# Per-request chunk-span bound: a pathological prompt chunked a thousand
# times must not make one live trace unbounded.
MAX_CHUNKS_PER_REQUEST = 256

_lock = threading.Lock()
_ids: dict[str, itertools.count] = {}
_recorders: "weakref.WeakSet[FlightRecorder]" = weakref.WeakSet()
_sentinels: "weakref.WeakSet[RetraceSentinel]" = weakref.WeakSet()


def next_name(kind: str) -> str:
    """Process-unique instance name per kind: engine0, engine1, train0…
    Used to tag each source's metric series."""
    with _lock:
        counter = _ids.setdefault(kind, itertools.count())
        return f"{kind}{next(counter)}"


def _now_ns() -> int:
    return time.time_ns()


# ---------------------------------------------------------------------------
# program spans on the profiler's clock
# ---------------------------------------------------------------------------

class _HostPhase:
    """The span in a process that has not loaded jax (a serve replica of
    a plain callable): no profiler can be tracing it, so it is the
    total alone."""

    __slots__ = ("_total", "_t0", "seconds")

    def __init__(self, total: list, name: str, attrs: dict):
        self._total = total
        self.seconds = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def set(self, **attrs) -> None:
        pass

    def __exit__(self, *exc):
        self.seconds = dt = time.perf_counter() - self._t0
        total = self._total
        total[0] += 1
        total[1] += dt
        return False


_phase_type = None


def _phase_class():
    """`jax.profiler.TraceAnnotation` that also times itself into its
    owner's totals. Built once jax is loaded: this module imports no
    jax, and a process that never loaded it is not made to."""
    global _phase_type
    if _phase_type is None:
        if "jax" not in sys.modules:
            return _HostPhase
        from jax.profiler import TraceAnnotation

        class Phase(TraceAnnotation):
            __slots__ = ("_total", "_t0", "seconds")

            def __init__(self, total: list, name: str, attrs: dict):
                super().__init__(name, **attrs)
                self._total = total
                self.seconds = 0.0

            def __enter__(self):
                super().__enter__()
                self._t0 = time.perf_counter()
                return self

            def set(self, **attrs) -> None:
                """Attributes known only at the end (tokens emitted,
                requests admitted); dropped when no session is on."""
                if self.is_enabled():
                    self.set_metadata(**attrs)

            def __exit__(self, *exc):
                self.seconds = dt = time.perf_counter() - self._t0
                total = self._total
                total[0] += 1
                total[1] += dt
                return super().__exit__(*exc)

        _phase_type = Phase
    return _phase_type


class Phases:
    """One owner's program spans: `phase(name, **attrs)` is a context
    manager that is a `jax.profiler.TraceAnnotation` — so the span lies
    in a profiler trace's `/host:CPU` plane on the clock of the device
    planes, and with no session on is a flag test in C++ — and adds
    `(1, elapsed perf_counter seconds)` to `totals[name]`, which the
    owner hands out in its `stats()`. Nothing else: no ring, no export.
    After exit the span's `seconds` is what it added.

    Each update is a list item's `+=` with no Python call between load
    and store, so threads that share an owner lose no count under the
    GIL. Names: PERF.md, section 3.
    """

    __slots__ = ("totals",)

    def __init__(self):
        self.totals: dict[str, list] = {}    # name -> [count, seconds]

    def phase(self, name: str, **attrs):
        total = self.totals.get(name)
        if total is None:
            total = self.totals.setdefault(name, [0, 0.0])
        return _phase_class()(total, name, attrs)

    def count(self, name: str) -> int:
        return self.totals.get(name, (0, 0.0))[0]

    def seconds(self, *names: str) -> float:
        return sum(self.totals.get(n, (0, 0.0))[1] for n in names)

    def clear(self) -> None:
        """Zero in place: a span open across the call still lands."""
        for total in self.totals.values():
            total[0], total[1] = 0, 0.0


# ---------------------------------------------------------------------------
# latency histograms (module-level, tagged by source)
# ---------------------------------------------------------------------------

_MS_BOUNDARIES = [0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000, 5000]
_metric_cache: dict[tuple[type, str], "_metrics.Metric"] = {}


def _metric(cls, name: str, desc: str = "", boundaries=None,
            tag_keys=("source",)):
    """Lazily create/reuse one tagged metric; returns None when the name
    is already registered as a conflicting type (the scrape must not
    break because two subsystems picked one name)."""
    key = (cls, name)
    with _lock:
        m = _metric_cache.get(key)
        if m is not None:
            return m
        try:
            if cls is _metrics.Histogram:
                m = cls(name, desc, boundaries=boundaries,
                        tag_keys=tag_keys)
            else:
                m = cls(name, desc, tag_keys=tag_keys)
        except (ValueError, TypeError):
            return None
        _metric_cache[key] = m
        return m


# ---------------------------------------------------------------------------
# flight recorder: per-request engine tracing
# ---------------------------------------------------------------------------

class FlightRecorder:
    """Sampled per-request lifecycle tracer for one engine.

    The engine calls the `on_*` hooks from inside its scheduler, under
    its scheduler lock, but for two that its request side calls under
    its delivery lock: `on_submit` and `on_first_yield`. Those two meet
    the scheduler's hooks in `_live`, `_await_yield` and the ring only,
    one atomic dict or deque operation at a time. Every hook for an
    unsampled request is one dict miss. Spans use the `util.tracing`
    dict shape (epoch-ns timestamps, so they interleave with task events
    on the merged timeline) and land in a bounded ring on finish —
    `dropped_spans` counts ring evictions so truncation is observable.
    """

    def __init__(self, name: str | None = None, *,
                 sample: float | None = None,
                 max_spans: int | None = None):
        self.name = name or next_name("recorder")
        self.sample = (DEFAULT_SAMPLE if sample is None
                       else max(0.0, min(1.0, float(sample))))
        self.max_spans = max(1, int(DEFAULT_MAX_SPANS if max_spans is None
                                    else max_spans))
        self._spans: collections.deque = collections.deque()
        self._live: dict[int, dict] = {}
        self._rng = random.Random(0x5EED ^ hash(self.name))
        # The engine's tick number, set by `InferenceEngine.step`: every
        # event carries it, and it is what joins a request's events to
        # the `engine/tick` annotations of a profiler trace (this
        # recorder's clock is the epoch's, the profiler's its own).
        self.tick = 0
        # rid -> (first-token ns, trace id, root span id) of requests
        # whose first token exists and has not reached its consumer;
        # bounded like the ring, oldest first out (a request that is
        # never consumed through `tokens_for` would stay).
        self._await_yield: collections.OrderedDict[int, tuple] = \
            collections.OrderedDict()
        # first token made -> first token yielded, ms (engine stats)
        self.deliver_waits: collections.deque = collections.deque(
            maxlen=512)
        self.dropped_spans = 0
        self.requests_seen = 0
        self.requests_traced = 0
        _recorders.add(self)

    # -- engine hooks (hot path) --------------------------------------

    def on_submit(self, rid: int, prompt_len: int) -> None:
        self.requests_seen += 1
        if self.sample <= 0.0 or (self.sample < 1.0
                                  and self._rng.random() >= self.sample):
            return
        now = _now_ns()
        # Join the distributed trace when the submitting context carries
        # one (proxy → replica → engine: the replica's context flows into
        # this caller thread via contextvars), else start a fresh trace.
        parent = _tracing.capture_context()
        if parent is not None:
            trace_id = parent["trace_id"]
            parent_sid = parent["span_id"]
        else:
            trace_id = uuid.uuid4().hex
            parent_sid = None
        root = self._span("engine.request", trace_id, parent_sid, now,
                          {"rid": rid, "engine": self.name,
                           "prompt_len": int(prompt_len)})
        queue = self._span("queue_wait", trace_id, root["span_id"], now,
                           {"rid": rid})
        self._live[rid] = {"root": root, "queue": queue, "extra": [],
                           "first_ns": None, "tokens": 0}
        self.requests_traced += 1

    def on_admit(self, rid: int, prefix_hit_tokens: int,
                 cow: bool) -> None:
        tr = self._live.get(rid)
        if tr is None:
            return
        now = _now_ns()
        tr["queue"]["end_ns"] = now
        tr["root"]["attributes"].update(
            prefix_hit_tokens=int(prefix_hit_tokens), cow=bool(cow))
        h = _metric(_metrics.Histogram, "engine_queue_wait_ms",
                    "submit -> slot admission, ms",
                    boundaries=_MS_BOUNDARIES)
        if h is not None:
            h.observe((now - tr["queue"]["start_ns"]) / 1e6,
                      tags={"source": self.name})

    def on_prefill_chunk(self, rid: int, tokens: int, bucket: int,
                         dur_s: float) -> None:
        tr = self._live.get(rid)
        if tr is None or len(tr["extra"]) >= MAX_CHUNKS_PER_REQUEST:
            return
        end = _now_ns()
        root = tr["root"]
        s = self._span("prefill_chunk", root["trace_id"],
                       root["span_id"], end - int(dur_s * 1e9),
                       {"rid": rid, "tokens": int(tokens),
                        "bucket": int(bucket)})
        s["end_ns"] = end
        tr["extra"].append(s)

    def on_first_token(self, rid: int, wait_s: float) -> None:
        tr = self._live.get(rid)
        if tr is None:
            return
        tr["first_ns"] = _now_ns()
        tr["extra"].append(self._instant(tr, "first_token", rid))
        if len(self._await_yield) >= self.max_spans:
            try:        # one atomic step: a consumer may pop beside it
                self._await_yield.popitem(last=False)
            except KeyError:
                pass
        root = tr["root"]
        self._await_yield[rid] = (tr["first_ns"], root["trace_id"],
                                  root["span_id"])
        h = _metric(_metrics.Histogram, "engine_ttft_ms",
                    "submit -> first token, ms",
                    boundaries=_MS_BOUNDARIES)
        if h is not None:
            h.observe(wait_s * 1e3, tags={"source": self.name})

    def on_first_yield(self, rid: int) -> None:
        """`tokens_for` is handing the request's first token to its
        consumer (under the engine's delivery lock; a tick may be
        running): the instant past the engine's edge. It goes to the
        ring at once, ahead of the request's other spans if the request
        is still live: the tick that finishes the request may be
        collecting those right now."""
        made = self._await_yield.pop(rid, None)
        if made is None:
            return
        first_ns, trace_id, root_sid = made
        now = _now_ns()
        self.deliver_waits.append((now - first_ns) / 1e6)
        s = self._span("first_yield", trace_id, root_sid, now,
                       {"rid": rid})
        s["end_ns"] = now
        self._push(s)

    def on_token(self, rid: int) -> None:
        tr = self._live.get(rid)
        if tr is not None:
            tr["tokens"] += 1

    def on_swap_crossing(self, rid: int) -> None:
        tr = self._live.get(rid)
        if tr is not None:
            tr["extra"].append(self._instant(tr, "swap_crossing", rid))

    def _on_kv_transfer(self, name: str, metric: str, rid: int,
                        blocks: int, nbytes: int, dur_s: float) -> None:
        """Shared body for the disaggregation transfer hooks: one
        `kv_export`/`kv_import` span under the request root plus a
        tagged latency histogram — `kv_transfer_ms` on the merged
        timeline is the pair's union."""
        h = _metric(_metrics.Histogram, metric,
                    "paged KV block transfer (one handoff side), ms",
                    boundaries=_MS_BOUNDARIES)
        if h is not None:
            h.observe(dur_s * 1e3, tags={"source": self.name})
        tr = self._live.get(rid)
        if tr is None or len(tr["extra"]) >= MAX_CHUNKS_PER_REQUEST:
            return
        end = _now_ns()
        root = tr["root"]
        s = self._span(name, root["trace_id"], root["span_id"],
                       end - int(dur_s * 1e9),
                       {"rid": rid, "blocks": int(blocks),
                        "bytes": int(nbytes)})
        s["end_ns"] = end
        tr["extra"].append(s)

    def on_kv_export(self, rid: int, blocks: int, nbytes: int,
                     dur_s: float) -> None:
        """Prefill-role engine gathered `blocks` KV blocks to host for
        a handoff (device->host side of kv_transfer_ms)."""
        self._on_kv_transfer("kv_export", "engine_kv_export_ms", rid,
                             blocks, nbytes, dur_s)

    def on_kv_import(self, rid: int, blocks: int, nbytes: int,
                     dur_s: float) -> None:
        """Decode-role engine scattered a handoff's blocks into its
        pool (host->device side of kv_transfer_ms)."""
        self._on_kv_transfer("kv_import", "engine_kv_import_ms", rid,
                             blocks, nbytes, dur_s)

    def on_handoff(self, rid: int, dur_s: float) -> None:
        """End-to-end prefill->decode handoff latency (export + wire +
        import), recorded by whichever layer drove the transfer — the
        serve DisaggHandle or an engine-level test harness."""
        h = _metric(_metrics.Histogram, "serve_handoff_ms",
                    "prefill->decode handoff, end to end, ms",
                    boundaries=_MS_BOUNDARIES)
        if h is not None:
            h.observe(dur_s * 1e3, tags={"source": self.name})
        tr = self._live.get(rid)
        if tr is not None:
            tr["extra"].append(self._instant(tr, "handoff", rid))

    def on_finish(self, rid: int, outcome: str) -> None:
        tr = self._live.pop(rid, None)
        if outcome in ("cancelled", "handoff"):   # nobody to yield to
            self._await_yield.pop(rid, None)
        if tr is None:
            return
        now = _now_ns()
        root, queue = tr["root"], tr["queue"]
        if queue["end_ns"] is None:     # cancelled while still pending
            queue["end_ns"] = now
        root["end_ns"] = now
        root["attributes"]["outcome"] = outcome
        root["attributes"]["tokens"] = tr["tokens"]
        spans = [root, queue] + tr["extra"]
        first = tr["first_ns"]
        if first is not None:
            dec = self._span("decode", root["trace_id"],
                             root["span_id"], first,
                             {"rid": rid, "tokens": tr["tokens"]})
            dec["end_ns"] = now
            spans.append(dec)
        for s in spans:
            self._push(s)

    # -- internals ----------------------------------------------------

    def _push(self, s: dict) -> None:
        if len(self._spans) >= self.max_spans:
            self._spans.popleft()
            self.dropped_spans += 1
        self._spans.append(s)

    def _span(self, name, trace_id, parent, start_ns, attrs) -> dict:
        attrs["tick"] = self.tick
        return {"name": name, "trace_id": trace_id,
                "span_id": uuid.uuid4().hex[:16],
                "parent_span_id": parent, "start_ns": start_ns,
                "end_ns": None, "attributes": attrs, "status": "OK",
                "process": os.getpid()}

    def _instant(self, tr, name, rid) -> dict:
        now = _now_ns()
        root = tr["root"]
        s = self._span(name, root["trace_id"], root["span_id"], now,
                       {"rid": rid})
        s["end_ns"] = now
        return s

    # -- export -------------------------------------------------------

    def get_spans(self) -> list[dict]:
        return list(self._spans)

    def drain_spans(self) -> list[dict]:
        """Atomically pop the ring (worker side of cluster-wide span
        collection: drained spans ride the TaskDone / metrics-flush hop
        to the head's tracing ring). Spans are tagged with this
        recorder's category/lane/process so the head's merged chrome
        view keeps the per-request lanes."""
        out = []
        while True:
            try:
                s = self._spans.popleft()
            except IndexError:
                break
            rid = s["attributes"].get("rid", 0)
            s.setdefault("cat", "request")
            s.setdefault("lane", f"{self.name}/r{rid}")
            s.setdefault("proc", _tracing.process_label())
            out.append(s)
        return out

    def live_requests(self) -> int:
        return len(self._live)

    def chrome_events(self) -> list[dict]:
        """Recorder spans as chrome://tracing events, cat="request" so
        they are distinguishable from task events (cat="task") and
        application spans (cat="span") on the merged timeline. Instant
        markers (first_token / swap_crossing) become "i" events."""
        out = []
        for s in self.get_spans():
            rid = s["attributes"].get("rid", 0)
            base = {"name": s["name"], "cat": "request",
                    "pid": s["process"], "tid": f"{self.name}/r{rid}",
                    "args": s["attributes"]}
            end = s["end_ns"] or _now_ns()
            if end == s["start_ns"]:
                out.append({**base, "ph": "i", "ts": s["start_ns"] / 1e3,
                            "s": "t"})
            else:
                out.append({**base, "ph": "X", "ts": s["start_ns"] / 1e3,
                            "dur": (end - s["start_ns"]) / 1e3})
        return out

    def clear(self) -> None:
        self._spans.clear()
        self.dropped_spans = 0

    def check_invariants(self) -> None:
        assert len(self._spans) <= self.max_spans, \
            f"{self.name}: span ring {len(self._spans)} > cap " \
            f"{self.max_spans}"
        assert 0.0 <= self.sample <= 1.0, self.sample
        assert self.requests_traced <= self.requests_seen
        for tr in self._live.values():
            assert len(tr["extra"]) <= MAX_CHUNKS_PER_REQUEST + 8
        assert len(self._await_yield) <= self.max_spans


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------

class RetraceSentinel:
    """Runtime watcher over compile-once trace counters.

    Two watch flavors: a `cap` watch is armed from construction with a
    hard allowance (decode must trace exactly once, ever — caps hold for
    any workload, so the existing compile-once suites run fully watched
    and report zero); a dynamic watch (cap=None) has no allowance until
    `arm()` snapshots its current count as the baseline — the shape for
    bucket-dependent paths like chunked prefill, where "warmed up" is
    workload-defined. `check()` is a handful of int compares, cheap
    enough for every scheduler tick; the first violation per path logs
    ONE WARN and every excess trace increments `retraces_unexpected`.
    """

    def __init__(self, name: str | None = None):
        self.name = name or next_name("sentinel")
        self._watches: dict[str, dict] = {}
        self.retraces_unexpected = 0
        self.armed = False
        self.events: collections.deque = collections.deque(maxlen=64)
        _sentinels.add(self)

    def watch(self, path: str, getter, cap: int | None = None,
              *, registered: bool = False) -> None:
        """`registered=True` asserts `path` is in graftlint's
        compile-once inventory (scopes.RETRACE_WATCHES) — the repo's
        jitted hot paths arm their watches through this, so the static
        R003 registry and the runtime sentinel can never drift apart.
        Ad-hoc/test watches keep the default."""
        if registered:
            from ray_tpu.tools.graftlint import scopes as _scopes
            if path not in _scopes.RETRACE_WATCHES:
                raise ValueError(
                    f"sentinel watch {path!r} is not a registered "
                    "compile-once path — add it to COMPILE_ONCE_JITS in "
                    "ray_tpu/tools/graftlint/scopes.py (R003) so lint "
                    "and runtime agree on the inventory")
        self._watches[path] = {
            "getter": getter,
            "cap": None if cap is None else int(cap),
            "limit": None if cap is None else int(cap),
            "counted": 0, "warned": False}

    def arm(self) -> None:
        """Declare warmup over: baseline every dynamic watch at its
        current count, so any further trace on it is unexpected. Cap
        watches are unaffected (they were armed from construction)."""
        self.armed = True
        for w in self._watches.values():
            if w["cap"] is None:
                try:
                    w["limit"] = int(w["getter"]())
                except Exception:
                    continue
                w["counted"] = w["limit"]

    def check(self) -> int:
        """Compare every watched counter against its allowance; count
        and WARN on new excess traces. Returns newly-counted excess."""
        new = 0
        for path, w in self._watches.items():
            limit = w["limit"]
            if limit is None:
                continue
            try:
                cur = int(w["getter"]())
            except Exception:
                continue
            base = max(limit, w["counted"])
            if cur > base:
                delta = cur - base
                w["counted"] = cur
                self.retraces_unexpected += delta
                new += delta
                self.events.append({
                    "ts": time.time(), "sentinel": self.name,
                    "path": path, "traces": cur, "allowed": limit})
                if not w["warned"]:
                    w["warned"] = True
                    logger.warning(
                        "retrace sentinel [%s]: pinned path %r "
                        "re-traced at runtime (traces=%d, allowed=%d) — "
                        "a compile-once guarantee broke; expect a "
                        "latency spike and check for changing input "
                        "shapes/dtypes", self.name, path, cur, limit)
        if new:
            c = _metric(_metrics.Counter, "retraces_unexpected",
                        "traces of pinned compile-once paths beyond "
                        "their allowance")
            if c is not None:
                c.inc(new, tags={"source": self.name})
        return new

    def watching(self) -> bool:
        return any(w["limit"] is not None
                   for w in self._watches.values())

    def reset(self) -> None:
        self.retraces_unexpected = 0
        self.events.clear()
        for w in self._watches.items():
            pass
        for w in self._watches.values():
            w["counted"] = 0
            w["warned"] = False
            if w["cap"] is None:
                w["limit"] = None
        self.armed = False


# ---------------------------------------------------------------------------
# stats-dict -> metrics bridge
# ---------------------------------------------------------------------------

# Monotone-while-not-reset stats keys published as Counters with delta
# tracking (a decrease means reset_stats(); the post-reset count re-adds
# from zero). Everything else numeric is a Gauge.
COUNTER_KEYS = frozenset({
    "decode_steps", "prefill_tokens", "decode_tokens", "prefill_chunks",
    "prefix_hit_tokens", "cow_copies", "evicted_blocks", "cancelled",
    "swaps", "spec_steps", "total", "snapshots", "commits", "stalls",
    "fetches", "iterations",
    # serve-plane fault tolerance (handle/engine/controller stats)
    "retries", "failovers", "sheds", "watchdog_stalls",
    "breaker_trips", "replicas_restarted", "health_check_failures",
    # task-event recorder (stage-attribution observations)
    "stage_samples",
    # priority/preemption plane (engine + per_class sub-dicts)
    "preemptions", "reprefill_blocks", "aging_promotions",
    "submitted", "completed",
    # disaggregated prefill/decode (engine handoff plane + the proxy's
    # SLO admission verdicts)
    "handoffs", "imports", "handoffs_abandoned",
    "kv_blocks_exported", "kv_blocks_imported",
    "kv_export_bytes", "kv_import_bytes",
    "slo_sheds", "slo_queued",
})

_sources: dict[str, tuple] = {}          # name -> (weakref, kind)
# (name, metric) or (name, metric, class_tag) -> last published count
_last_counts: dict[tuple, float] = {}
_hook_installed = False


def register_stats_source(name: str, obj, kind: str = "engine") -> str:
    """Publish `obj.stats()` into the metrics registry at every scrape/
    flush, as `<kind>_<key>` series tagged source=<name>. Holds only a
    weakref — a garbage-collected source silently drops out (its gauges
    keep their last value for the session). Returns the (possibly
    uniquified) registered name."""
    global _hook_installed
    with _lock:
        final = name
        i = 2
        while final in _sources and _sources[final][0]() is not None \
                and _sources[final][0]() is not obj:
            final = f"{name}-{i}"
            i += 1
        _sources[final] = (weakref.ref(obj), kind)
        if not _hook_installed:
            _metrics.add_collect_hook(_collect)
            _hook_installed = True
    # In a worker process the hook only runs when the flusher snapshots;
    # make sure one is running even if no Metric exists here yet.
    _metrics.ensure_flusher()
    return final


def unregister_stats_source(name: str) -> None:
    with _lock:
        _sources.pop(name, None)
        for key in [k for k in _last_counts if k[0] == name]:
            del _last_counts[key]


def _collect() -> None:
    """The metrics collect hook: refresh every live source's series.
    Runs BEFORE the registry lock (metrics.snapshot contract), so it may
    freely create metrics; a broken source never breaks the scrape."""
    with _lock:
        items = list(_sources.items())
    dead = []
    for name, (ref, kind) in items:
        obj = ref()
        if obj is None:
            dead.append(name)
            continue
        try:
            stats = obj.stats()
        except Exception:
            continue
        if isinstance(stats, dict):
            _publish_stats(kind, name, stats)
    for name in dead:
        unregister_stats_source(name)


def _publish_stats(kind: str, name: str, stats: dict) -> None:
    for key, val in stats.items():
        if isinstance(val, bool) or isinstance(val, str):
            continue
        if isinstance(val, dict):
            # One level of nesting fans out as tagged series: a stats key
            # like ``per_class: {"0": {"sheds": 2, ...}, ...}`` becomes
            # `<kind>_<key>_<metric>{source=..., class="0"}` — the
            # fairness/usage-by-class view without N distinct sources.
            for tag, sub in val.items():
                if not isinstance(sub, dict):
                    continue
                for skey, sval in sub.items():
                    if isinstance(sval, (bool, str)):
                        continue
                    try:
                        num = float(sval)
                    except (TypeError, ValueError):
                        continue
                    _publish_one(name, f"{kind}_{key}_{skey}", skey, num,
                                 {"source": name, "class": str(tag)},
                                 (name, f"{kind}_{key}_{skey}", str(tag)))
            continue
        try:
            num = float(val)
        except (TypeError, ValueError):
            continue
        mname = f"{kind}_{key}"
        _publish_one(name, mname, key, num, {"source": name},
                     (name, mname))


def _publish_one(name: str, mname: str, key: str, num: float,
                 tags: dict, ckey: tuple) -> None:
    """Publish one numeric sample: delta-tracked Counter when `key` is in
    COUNTER_KEYS, Gauge otherwise. `ckey` keys the delta state (2-tuple
    for flat stats, 3-tuple with the class tag for nested ones); the
    metric's tag_keys come from `tags` so class-tagged series declare
    both labels."""
    tag_keys = tuple(tags)
    if key in COUNTER_KEYS:
        c = _metric(_metrics.Counter, mname, tag_keys=tag_keys)
        if c is None:
            return
        last = _last_counts.get(ckey, 0.0)
        if num < last:          # stats reset upstream
            last = 0.0
        if num > last:
            c.inc(num - last, tags=tags)
        _last_counts[ckey] = num
    else:
        g = _metric(_metrics.Gauge, mname, tag_keys=tag_keys)
        if g is not None:
            g.set(num, tags=tags)


# ---------------------------------------------------------------------------
# exports / self-test
# ---------------------------------------------------------------------------

def chrome_trace_events() -> list[dict]:
    """This process's recorder spans + application tracing spans as
    chrome://tracing events. The node's "timeline" control verb merges
    these with the task-event trace, so `GET /api/timeline` and
    `ray_tpu timeline` serve one combined view (cat = task | request |
    span)."""
    out = []
    for rec in list(_recorders):
        out.extend(rec.chrome_events())
    out.extend(_tracing.spans_to_chrome_trace())
    return out


def drain_recorder_spans() -> list[dict]:
    """Pop every live recorder's span ring — the worker side of cluster
    span collection (`worker_main._drain_spans_for_push` and the metrics
    flusher call this). Head-resident recorders are never drained: their
    rings are read in place by `chrome_trace_events()`, and draining
    them too would double-count once the head ingests its own ring."""
    out = []
    for rec in list(_recorders):
        out.extend(rec.drain_spans())
    return out


def _tracing_gauges() -> None:
    """Collect hook: surface the tracing ring's drop counter on /metrics
    so a truncated cluster trace is observable at scrape time."""
    g = _metric(_metrics.Gauge, "tracing_dropped_spans",
                "spans evicted from the in-process tracing ring")
    if g is not None:
        g.set(_tracing.dropped_spans(), tags={"source": "tracing"})


_metrics.add_collect_hook(_tracing_gauges)


def summary() -> dict:
    """JSON health summary for `/api/telemetry`."""
    return {
        "recorders": [{
            "name": r.name, "sample": r.sample,
            "requests_seen": r.requests_seen,
            "requests_traced": r.requests_traced,
            "live_requests": r.live_requests(),
            "spans": len(r.get_spans()),
            "dropped_spans": r.dropped_spans,
        } for r in list(_recorders)],
        "sentinels": [{
            "name": s.name, "armed": s.armed,
            "watching": s.watching(),
            "retraces_unexpected": s.retraces_unexpected,
            "events": list(s.events),
        } for s in list(_sentinels)],
        "tracing": {
            "enabled": _tracing.tracing_enabled(),
            "spans": len(_tracing.get_spans()),
            "max_spans": _tracing.max_spans(),
            "dropped_spans": _tracing.dropped_spans(),
        },
        "stats_sources": sorted(_sources.keys()),
    }


_PROM_SAMPLE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(?:\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (\S+)$')


def check_invariants() -> None:
    """Telemetry-plane self-test (tests/conftest.py runs it at session
    teardown, mirroring the engine's check_invariants pattern): every
    rendered metric sample parses under the Prometheus exposition
    grammar, the tracing and recorder rings honor their bounds, and
    every sentinel still watches its pinned paths."""
    text = _metrics.render_prometheus(_metrics.snapshot())
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _PROM_SAMPLE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        float(m.group(1))           # value must be a number
    assert len(_tracing.get_spans()) <= _tracing.max_spans(), \
        "tracing span ring exceeded its cap"
    for rec in list(_recorders):
        rec.check_invariants()
    for s in list(_sentinels):
        assert s.watching() or not s._watches, \
            f"sentinel {s.name} has watches but none armed"
