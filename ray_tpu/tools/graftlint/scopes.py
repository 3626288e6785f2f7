"""Scope registries: the repo's declared hot paths, compile-once jits,
locks, and lock-order graph.

This module is the single source of truth shared by the static rules and
by the runtime: `RetraceSentinel.watch(..., registered=True)` validates
its watch name against RETRACE_WATCHES, so adding a new jitted hot path
without registering it here fails loudly at engine construction — and
adding a jit assignment to a registered file without an inventory entry
fails R003 at lint time. Paths are repo-relative posix.

Deliberately dependency-free: importable from ray_tpu.util.telemetry
without dragging the linter (or jax) in.
"""

from __future__ import annotations

ENGINE = "ray_tpu/serve/engine.py"
LOOP = "ray_tpu/train/loop.py"
FT = "ray_tpu/train/ft.py"
FLYWHEEL = "ray_tpu/rl/flywheel.py"
SPMD = "ray_tpu/train/spmd.py"
PREDICTOR = "ray_tpu/train/predictor.py"
CONTROLLER = "ray_tpu/serve/controller.py"
REPLICA = "ray_tpu/serve/replica.py"
HANDLE = "ray_tpu/serve/handle.py"
DISAGG = "ray_tpu/serve/disagg.py"
TELEMETRY = "ray_tpu/util/telemetry.py"
METRICS = "ray_tpu/util/metrics.py"
FAULTS = "ray_tpu/util/faults.py"
TRACING = "ray_tpu/util/tracing.py"
EVENTS = "ray_tpu/_private/events.py"
WORKER_MAIN = "ray_tpu/_private/worker_main.py"
NETADDR = "ray_tpu/_private/netaddr.py"

# --- R001: functions whose bodies are latency-critical host code. A
# host sync here stalls the device queue (or the scheduler tick).
HOT_SCOPES: dict[str, frozenset[str]] = {
    ENGINE: frozenset({
        "InferenceEngine.step",
        "InferenceEngine.tokens_for",
        # the one wait-or-pump loop `tokens_for` and `handoff_for`
        # block in: a host sync here holds up whoever pumps next
        "InferenceEngine._await",
        "InferenceEngine._try_admit",
        "InferenceEngine._admit_pending",
        "InferenceEngine._batch_arrays",
        # a program's host-built input, packed into one int32 array and
        # put once (`pack_rows` / `pack_chunk`, then `_dev`): a sync on
        # the way would hold the tick's first program back
        "pack_rows",
        "pack_chunk",
        "InferenceEngine._dev",
        "InferenceEngine._decode_inputs",
        "InferenceEngine._put_rows",
        # the chained decode step: enqueued behind a step whose tokens
        # are unread (`_chain_tick`, `_enqueue_step`), which `_read_step`
        # waits for a tick later, or `_rest` for whoever needs the
        # engine at rest
        "InferenceEngine._goes_on",
        "InferenceEngine._enqueue_step",
        "InferenceEngine._chain_tick",
        "InferenceEngine._read_step",
        "InferenceEngine._rest",
        "InferenceEngine._start_chunk",
        "InferenceEngine._read_chunk_token",
        "InferenceEngine._finish_chunk",
        "InferenceEngine._prefill_tick",
        "InferenceEngine._decode_with_chunk",
        "InferenceEngine._decode_tick",
        "InferenceEngine._spec_tick",
        "InferenceEngine._emit",
        # priority/preemption plane — all run inside the scheduler tick
        # under engine.scheduler (the admission queue shares self._lock;
        # no new lock, so no new LOCK_ORDER edges)
        "InferenceEngine._admission_order",
        "InferenceEngine._pick_victim",
        "InferenceEngine._preempt",
        "InferenceEngine._force_preempt",
        "InferenceEngine._admit_or_preempt",
        "InferenceEngine._shed_lowest_below",
        # disaggregated prefill/decode handoff plane — export runs in
        # the prefill-completion tick, import admission inside step();
        # both under engine.scheduler (no new lock, no new LOCK_ORDER
        # edges)
        "InferenceEngine._export_handoff",
        "InferenceEngine._admit_imports",
        "InferenceEngine._try_import",
        "InferenceEngine.handoff_for",
    }),
    LOOP: frozenset({
        "TrainLoop.run",
        "MetricsRing.push",
        "MetricsRing._sync",
        "DevicePrefetcher.__next__",
    }),
    FT: frozenset({
        "AsyncCheckpointer.maybe_snapshot",
        "AsyncCheckpointer.flush",
    }),
    FLYWHEEL: frozenset({
        "FlywheelLoop._publish",
    }),
    # span-drain path: runs on every TaskDone seal / metrics flush, and
    # _record sits inside span() on every traced hot-path operation
    TRACING: frozenset({
        "_record",
        "drain_spans",
        "ingest",
    }),
    EVENTS: frozenset({
        "TaskEventRecorder._collect_stages_locked",
    }),
    WORKER_MAIN: frozenset({
        "WorkerRuntime._drain_spans_for_push",
    }),
}

# --- R003: compile-once inventory. For each registered file, every
# `<anchor> = jax.jit(...)` assignment (or factory returning a jit) must
# appear here; the value is the RetraceSentinel watch name guarding it,
# or None for jits that are deliberately unwatched (cheap, cold, or
# traced a bounded number of times by construction).
COMPILE_ONCE_JITS: dict[str, dict[str, str | None]] = {
    ENGINE: {
        "self._prefill_fn": "prefill",
        "self._decode_fn": "decode",
        "tick_jit": "tick",     # a step and a chunk, one program;
        # compiled ahead of its first use (`_compile_ahead`), so that no
        # tick traces it
        "self._copy_fn": None,          # COW block copy; shapes fixed
        "self._verify_fn": "verify",
        "self._propose_fn": "draft",
        "self._draft_prefill_fn": "draft_prefill",
        "self._swap_fn": "swap",
        "_loader": "load",              # factory: the family's load-time fn
        # disaggregated prefill/decode block transport (one trace per
        # pool geometry: target + optional draft pool)
        "self._gather_fn": "kv_gather",
        "self._scatter_block_fn": "kv_scatter",
    },
    LOOP: {
        "fuse_steps": "dispatch",       # factory: returns the fused jit
    },
    FT: {
        "self._copy": None,             # device-side snapshot clone
    },
    FLYWHEEL: {
        "self._step": None,             # watched via TrainLoop dispatch
    },
    SPMD: {
        "make_train_step": None,        # factory; callers own the watch
    },
    PREDICTOR: {
        "self._apply": None,            # one bucket set, traced per shape
    },
}

# The sentinel watch names that must be armed with registered=True.
RETRACE_WATCHES: frozenset[str] = frozenset(
    name
    for per_file in COMPILE_ONCE_JITS.values()
    for name in per_file.values()
    if name is not None
)

# --- R002: factories whose *returned* callable donates these argnums.
# Keyed by bare factory name; matched at call sites of the assigned
# target (e.g. `self._dispatch = fuse_steps(...)`).
DONATING_FACTORIES: dict[str, tuple[int, ...]] = {
    "fuse_steps": (0,),
    "make_train_step": (0,),
}


class LockSpec:
    """A declared lock. `blocking_ok` marks locks that exist to
    serialize an inherently blocking operation (e.g. the engine swap
    mutex, whose whole job is to hold device placement away from the
    scheduler lock); R004 skips the blocking-call check under them but
    still tracks them in the lock-order graph."""

    __slots__ = ("name", "blocking_ok")

    def __init__(self, name: str, blocking_ok: bool = False):
        self.name = name
        self.blocking_ok = blocking_ok


# --- R004: declared locks, keyed by file -> {with-expr dotted name}.
LOCKS: dict[str, dict[str, LockSpec]] = {
    ENGINE: {
        "self._lock": LockSpec("engine.scheduler"),
        "self._swap_mutex": LockSpec("engine.swap", blocking_ok=True),
        # delivery condition: rids, the inbox and what a tick hands to
        # its streams; consumers wait under it for a tick they do not
        # run. Held for dict/deque operations only, never device work.
        "self._delivery": LockSpec("engine.delivery", blocking_ok=True),
        # held by the one consumer running the next tick (`_await`);
        # tried without blocking and never waited for, so it adds no
        # edge: its holder goes on to take engine.scheduler in step()
        "self._pump": LockSpec("engine.pump", blocking_ok=True),
    },
    CONTROLLER: {
        "self._lock": LockSpec("serve.controller"),
    },
    REPLICA: {
        "self._lock": LockSpec("serve.replica"),
    },
    HANDLE: {
        # router lock brackets routing state only — the failover/retry
        # work (controller RPCs, backoff sleeps) must never run under it
        "self._lock": LockSpec("serve.handle.router"),
        "self._router.lock": LockSpec("serve.handle.router"),
        "self._router.refresh_lock": LockSpec(
            "serve.handle.refresh", blocking_ok=True),
        "self._mu": LockSpec("serve.handle.stats"),
    },
    DISAGG: {
        # parked-handoff map / pull-stats state on both replica roles
        "self._lock": LockSpec("serve.disagg.state"),
        # serializes pull exchanges on the shared netaddr connection;
        # its whole job is to hold blocking wire recvs away from state
        "self._pull_mu": LockSpec("serve.disagg.pull", blocking_ok=True),
    },
    TELEMETRY: {
        "_lock": LockSpec("telemetry.registry"),
    },
    METRICS: {
        "self.lock": LockSpec("metrics.registry"),
        "self._lock": LockSpec("metrics.series"),
    },
    FAULTS: {
        "_lock": LockSpec("faults.registry"),
    },
    TRACING: {
        "_lock": LockSpec("tracing.ring"),
    },
    EVENTS: {
        # stage histograms are observed OUTSIDE this lock (durations are
        # collected under it, fed to metrics after release) — keep it
        # leaf-level: no metrics/tracing edges
        "self._lock": LockSpec("events.recorder"),
    },
    NETADDR: {
        # outbound-queue condition: senders wait under it for
        # backpressure credit, the flusher waits under it for work
        "self._qcv": LockSpec("netaddr.batch.queue", blocking_ok=True),
        # serializes wire writes; its whole job is to hold a (blocking)
        # socket send away from the queue state
        "self._wire_lock": LockSpec("netaddr.batch.wire",
                                    blocking_ok=True),
        # one-shot UDP interface probe memo
        "_advertise_lock": LockSpec("netaddr.advertise",
                                    blocking_ok=True),
    },
    WORKER_MAIN: {
        # pipelined-submission window: submitters wait under it when
        # the credit window is exhausted
        "self._sub_cv": LockSpec("worker.submit_window",
                                 blocking_ok=True),
    },
}

# Declared lock-order edges (may-acquire-while-holding). Observed
# nestings in registered files must be a subset; cycles in the union of
# declared and observed edges are findings.
LOCK_ORDER: frozenset[tuple[str, str]] = frozenset({
    ("engine.swap", "engine.scheduler"),
    # a tick hands over tokens, ends and errors; never the other way
    ("engine.scheduler", "engine.delivery"),
    # update_params counts itself among the threads a pump lets go
    # first (`_before_pump`) while it holds the swap mutex
    ("engine.swap", "engine.delivery"),
    ("engine.scheduler", "telemetry.registry"),
    ("telemetry.registry", "metrics.registry"),
    ("metrics.registry", "metrics.series"),
    # handle refresh: controller RPC under the blocking-ok refresh lock,
    # snapshot/commit under the router lock
    ("serve.handle.refresh", "serve.handle.router"),
    # frame flusher / send_bytes: pop the outbound queue while holding
    # the wire (send()'s opposite-direction wire probe is a
    # non-blocking try-acquire, so it adds no queue->wire edge)
    ("netaddr.batch.wire", "netaddr.batch.queue"),
})
