"""Worker process entry point.

Counterpart of the reference's worker main + Cython `execute_task` callback
(`python/ray/_private/workers/default_worker.py` + `_raylet.pyx:1245`): a
process that registers with its node, receives pushed tasks, resolves
dependencies from the shared-memory store, runs user code, and seals results.

The same process hosts either a pool ("generic") worker or a dedicated
actor. Actor concurrency has two modes, mirroring the reference: classes
with any `async def` method run every call as a coroutine on a per-actor
event loop (max_concurrency = an asyncio.Semaphore; reference:
`_private/async_compat.py:19` + async execute_task in `_raylet.pyx`),
and plain classes with `max_concurrency > 1` use a thread pool (threaded
concurrency groups).
"""

from __future__ import annotations

import os
import queue
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from multiprocessing import connection

from ray_tpu._private import netaddr, protocol, serialization
from ray_tpu._private.object_store import ObjectStore
from ray_tpu.exceptions import RayTpuError, TaskError
from ray_tpu.util import tracing as _tracing

import contextvars

_ASYNC_TASK_ID: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_async_task_id", default=None)


class WorkerRuntime:
    """Per-worker state + the client channel back to the node server.

    `exit_on_disconnect` is True for real pool/actor workers (their whole
    purpose dies with the session) and False for client drivers embedded
    in a USER process (ray_tpu.init(address=...)) — killing the user's
    script on disconnect would be hostile."""

    def __init__(self, address: str, worker_id: str, authkey: bytes,
                 exit_on_disconnect: bool = True):
        self.worker_id = worker_id
        self.exit_on_disconnect = exit_on_disconnect
        self.conn = netaddr.client(address, authkey)
        if netaddr.is_tcp(address):
            # cross-machine client driver: no shared memory with the head —
            # object payloads ride inline both ways (the head inlines
            # GetReply locations for remote conns and re-materializes
            # oversized inline puts into its own store)
            self.store = None
        else:
            session_dir = os.path.dirname(address)
            self.store = ObjectStore(session_dir)
        self.functions: dict[str, object] = {}
        self.actor_instance = None
        self.actor_id: str | None = None
        self.task_queue: queue.Queue = queue.Queue()
        self._req_id = 0
        self._req_lock = threading.Lock()
        self._replies: dict[int, object] = {}
        self._reply_cv = threading.Condition()
        self._send_lock = threading.Lock()
        self._executor: ThreadPoolExecutor | None = None
        self._loop = None                # asyncio actors: per-actor loop
        self._async_sem = None
        self._io_executor: ThreadPoolExecutor | None = None
        self._current_task_ids = threading.local()
        self.shutdown = False
        # batched refcount events -> driver (hold/release/escape), flushed
        # by a timer so __del__ storms don't become a message storm. An
        # ORDERED (kind, oid) list: bucketing by kind would replay a
        # release-then-re-hold pair inside one flush window in the wrong
        # order and free an object with a live ref.
        self._ref_lock = threading.Lock()
        self._ref_pending: list[tuple[str, str]] = []
        # Pipelined submission state (credit window + replay ring).
        # Submissions stream without per-task acks; `_sub_ring` retains
        # every spec past the last credit so a SubmitNack (the head saw
        # a seq gap) or the resync timer can replay it. Guarded by
        # `_sub_cv`'s lock; `_sub_next` is the next seq to assign,
        # `_sub_acked` the highest credited seq.
        from ray_tpu._private import config as _config
        self._sub_pipelined = bool(_config.get("SUBMIT_PIPELINE"))
        self._sub_cv = threading.Condition()
        self._sub_ring: dict[int, object] = {}
        self._sub_next = 0
        self._sub_acked = -1
        self._sub_last_progress = time.monotonic()
        threading.Thread(target=self._ref_flush_loop,
                         name="ref-flush", daemon=True).start()

    # ---- channel ----------------------------------------------------------

    def send(self, msg):
        with self._send_lock:
            self.conn.send(msg)

    def _next_req_id(self) -> int:
        with self._req_lock:
            self._req_id += 1
            return self._req_id

    def request(self, make_msg):
        """Send a request carrying a fresh req_id; block for the reply."""
        req_id = self._next_req_id()
        self.send(make_msg(req_id))
        with self._reply_cv:
            while req_id not in self._replies:
                self._reply_cv.wait(1.0)
                if self.shutdown:
                    raise RuntimeError("worker shutting down")
            reply = self._replies.pop(req_id)
        if isinstance(reply, protocol.ErrorReply):
            raise RayTpuError(reply.error)
        return reply

    def reader_loop(self):
        while True:
            try:
                msg = self.conn.recv()
            except (EOFError, OSError, TypeError):
                if self.exit_on_disconnect:
                    os._exit(0)
                self.shutdown = True
                with self._reply_cv:
                    self._reply_cv.notify_all()
                return
            if isinstance(msg, protocol.PushTask):
                self.task_queue.put(msg)
            elif isinstance(msg, protocol.FreeObject):
                # all refs gone cluster-wide: drop this process's owner pin
                # so the arena block can actually be reclaimed
                try:
                    if self.store is not None:
                        self.store.delete(msg.desc)
                except Exception:
                    pass
            elif isinstance(msg, protocol.DumpStack):
                self.send(protocol.StackDumpReply(
                    msg.req_id, self.worker_id, os.getpid(),
                    _format_stacks()))
            elif isinstance(msg, protocol.LogBatch):
                # log_to_driver subscription: another process's output,
                # prefixed so interleaved sources stay attributable
                nid = msg.node_id or "head"
                for ln in msg.lines or ():
                    print(f"({msg.source}, node={nid}) {ln}",
                          file=sys.stderr)
            elif isinstance(msg, protocol.SetTracing):
                # driver enabled tracing after this worker spawned
                if msg.enabled:
                    _tracing._enable_local()
            elif isinstance(msg, protocol.KillWorker):
                self.shutdown = True
                self.task_queue.put(None)
                with self._reply_cv:
                    self._reply_cv.notify_all()
            elif isinstance(msg, protocol.SubmitCredit):
                self._on_submit_credit(msg.ack_seq)
            elif isinstance(msg, protocol.SubmitNack):
                with self._sub_cv:
                    self._replay_submits_locked(msg.expected_seq)
            elif isinstance(msg, (protocol.GetReply, protocol.WaitReply,
                                  protocol.SubmitReply,
                                  protocol.ActorCallReply,
                                  protocol.ErrorReply)):
                with self._reply_cv:
                    self._replies[msg.req_id] = msg
                    self._reply_cv.notify_all()

    # ---- object access (used by the ray_tpu client API in worker mode) ----

    def get_objects(self, object_ids, timeout=None):
        reply = self.request(lambda rid: protocol.GetRequest(
            rid, list(object_ids), timeout))
        if reply.timed_out:
            from ray_tpu.exceptions import GetTimeoutError
            raise GetTimeoutError(f"get() timed out: {object_ids[:3]}")
        if getattr(reply, "error", None):
            from ray_tpu.exceptions import ObjectFreedError, ObjectLostError
            cls_name, _, detail = reply.error.partition(": ")
            cls = (ObjectFreedError if cls_name == "ObjectFreedError"
                   else ObjectLostError)
            raise cls(detail or reply.error)
        out = []
        for oid in object_ids:
            out.append(self._read_with_refresh(oid, reply.locations[oid]))
        return out

    def _read_with_refresh(self, oid, desc, retries: int = 2):
        """Read a descriptor, re-fetching the location on a miss: a spill
        or copy-promotion may have moved the bytes after this descriptor
        was handed out (the spiller swaps the directory entry first, so a
        fresh location always resolves)."""
        from ray_tpu.exceptions import ObjectLostError
        if self.store is None:
            if desc.inline is None:
                raise ObjectLostError(
                    f"object {oid} arrived without inline payload on a "
                    "remote client connection")
            return serialization.loads(desc.inline)
        for attempt in range(retries + 1):
            try:
                return self.store.get(desc)
            except ObjectLostError:
                if attempt == retries:
                    raise
                reply = self.request(lambda rid: protocol.GetRequest(
                    rid, [oid], 30.0))
                if reply.timed_out or getattr(reply, "error", None) \
                        or oid not in reply.locations:
                    raise
                desc = reply.locations[oid]

    def put_object(self, value) -> str:
        from ray_tpu._private import ids
        oid = ids.new_object_id()
        if self.store is None:
            from ray_tpu._private.object_store import inline_descriptor
            desc = inline_descriptor(oid, value)
        else:
            desc = self.store.put(oid, value)
        self.send(protocol.PutRequest(oid, desc))
        return oid

    def wait_objects(self, object_ids, num_returns, timeout, fetch_local):
        reply = self.request(lambda rid: protocol.WaitRequest(
            rid, list(object_ids), num_returns, timeout, fetch_local))
        return reply.ready, reply.not_ready

    def submit_spec(self, spec):
        if not self._sub_pipelined:
            reply = self.request(
                lambda rid: protocol.SubmitRequest(rid, spec))
            if not reply.ok:
                raise RayTpuError(f"submit failed: {reply.error}")
            return
        # Pipelined: assign the next seq, retain the spec for replay,
        # block only when the credit window is exhausted. No reply is
        # awaited — submit failures surface as error objects stored
        # under the spec's return ids (matching how the reference's
        # async task submission reports scheduling errors).
        from ray_tpu._private.constants import (SUBMIT_RESYNC_S,
                                                SUBMIT_WINDOW)
        with self._sub_cv:
            while (self._sub_next - self._sub_acked > SUBMIT_WINDOW
                   and not self.shutdown):
                progressed = self._sub_cv.wait(SUBMIT_RESYNC_S)
                if not progressed:
                    self._replay_submits_locked(self._sub_acked + 1)
            if self.shutdown:
                raise RuntimeError("worker shutting down")
            seq = self._sub_next
            self._sub_next = seq + 1
            self._sub_ring[seq] = spec
        self.send(protocol.SubmitRequest(-1, spec, seq=seq))

    def _replay_submits_locked(self, from_seq: int) -> None:
        """Re-send every retained spec with seq >= from_seq in order
        (caller holds _sub_cv). Duplicates are dropped by the receiver's
        seq dedupe, which re-credits — so replay is idempotent and also
        recovers a lost credit."""
        for seq in sorted(self._sub_ring):
            if seq >= from_seq:
                self.send(protocol.SubmitRequest(
                    -1, self._sub_ring[seq], seq=seq))
        self._sub_last_progress = time.monotonic()

    def _on_submit_credit(self, ack_seq: int) -> None:
        with self._sub_cv:
            if ack_seq > self._sub_acked:
                self._sub_acked = ack_seq
                for seq in [s for s in self._sub_ring if s <= ack_seq]:
                    del self._sub_ring[seq]
                self._sub_last_progress = time.monotonic()
                self._sub_cv.notify_all()

    def _submit_resync(self) -> None:
        """Periodic (ref-flush cadence): with unacked submissions and no
        credit progress for SUBMIT_RESYNC_S, replay the ring — covers a
        lost tail message that no later gap would ever reveal."""
        from ray_tpu._private.constants import SUBMIT_RESYNC_S
        with self._sub_cv:
            if (self._sub_ring
                    and time.monotonic() - self._sub_last_progress
                    > SUBMIT_RESYNC_S):
                self._replay_submits_locked(self._sub_acked + 1)

    def control(self, method, payload=None):
        reply = self.request(lambda rid: protocol.ActorCallRequest(
            rid, method, payload))
        if reply.error is not None:
            raise RayTpuError(reply.error)
        return reply.result

    # ---- refcount event batching -----------------------------------------

    def enqueue_ref_event(self, kind: str, oid: str) -> None:
        with self._ref_lock:
            self._ref_pending.append((kind, oid))

    def _flush_ref_events(self) -> None:
        with self._ref_lock:
            if not self._ref_pending:
                return
            batch, self._ref_pending = self._ref_pending, []
        try:
            self.control("ref_update",
                         {"holder": self.worker_id, "events": batch})
        except Exception:
            pass  # driver gone; session over

    def _ref_flush_loop(self) -> None:
        from ray_tpu._private import worker as _worker_mod
        from ray_tpu._private.constants import REF_FLUSH_INTERVAL_S
        while not self.shutdown:
            time.sleep(REF_FLUSH_INTERVAL_S)
            _worker_mod._drain_decs()
            self._flush_ref_events()
            self._submit_resync()

    # ---- execution --------------------------------------------------------

    def current_task_id(self):
        # async actor methods record their id in a ContextVar (one per
        # asyncio task); sync paths use the thread-local
        tid = _ASYNC_TASK_ID.get()
        if tid is not None:
            return tid
        return getattr(self._current_task_ids, "task_id", None)

    def _resolve_fn(self, spec: protocol.TaskSpec):
        fn = self.functions.get(spec.function_id)
        if fn is None:
            if spec.function_blob is None:
                raise RayTpuError(
                    f"function {spec.function_desc} not cached and no blob")
            fn = serialization.loads_message(spec.function_blob)
            self.functions[spec.function_id] = fn
        return fn

    def _resolve_args(self, spec, arg_locations):
        def one(kind, v):
            if kind == "ref":
                loc = arg_locations.get(v)
                if loc is None:
                    # directory hole at push time (object lost mid-flight):
                    # fetch a fresh location — it resolves once the object
                    # is reconstructed or raises the terminal error
                    value = self.get_objects([v])[0]
                else:
                    value = self._read_with_refresh(v, loc)
            else:
                value = serialization.loads(v)
            return value
        args = [one(k, v) for k, v in spec.args]
        kwargs = {name: one(k, v) for name, (k, v) in spec.kwargs.items()}
        # Error propagation: a dependency that failed short-circuits this
        # task, surfacing the ORIGINAL error (reference: RayTaskError values
        # poison downstream tasks).
        for v in list(args) + list(kwargs.values()):
            if isinstance(v, (TaskError, RayTpuError)):
                raise _DepFailed(v)
        return args, kwargs

    def _start_task_span(self, spec: protocol.TaskSpec):
        """Attach the submitter's trace context and open `task.execute`.
        Gated on the stamped ctx, not on local enablement: a stamped spec
        proves the trace is live even if this worker predates the
        driver's enable_tracing() broadcast. Returns (span, token)."""
        if spec.trace_ctx is None:
            return None
        return _tracing.start_span(
            "task.execute",
            {"task_id": spec.task_id,
             "name": spec.name or spec.function_desc,
             "worker_id": self.worker_id},
            parent=spec.trace_ctx)

    def run_task(self, push: protocol.PushTask):
        spec = push.spec
        chips = os.environ.get("TPU_VISIBLE_CHIPS")
        self._current_task_ids.task_id = spec.task_id
        sp = self._start_task_span(spec)
        exec_start = time.time()
        try:
            is_actor_method = (spec.actor_id is not None
                               and not spec.actor_creation)
            fn = None if is_actor_method else self._resolve_fn(spec)
            args, kwargs = self._resolve_args(spec, push.arg_locations)
            if spec.actor_creation:
                cls = fn
                self.actor_instance = cls(*args, **kwargs)
                self.actor_id = spec.actor_id
                result = None
                values = [None] * spec.num_returns
            elif spec.actor_id is not None:
                method = getattr(self.actor_instance, spec.method_name)
                result = method(*args, **kwargs)
                values = self._split_returns(result, spec.num_returns)
            else:
                result = fn(*args, **kwargs)
                values = self._split_returns(result, spec.num_returns)
            error = False
        except _DepFailed as df:
            values = [df.cause] * spec.num_returns
            error = True
        except BaseException as e:
            tb = traceback.format_exc()
            te = TaskError(type(e).__name__, str(e), tb, cause=e)
            values = [te] * spec.num_returns
            error = True
        finally:
            self._current_task_ids.task_id = None
        exec_end = time.time()
        if sp is not None:
            _tracing.end_span(sp[0], sp[1],
                              error="task_error" if error else None)
        self._seal_and_send(spec, values, error, exec_start, exec_end)

    def _drain_spans_for_push(self):
        """This process's buffered tracing spans (plus any worker-resident
        FlightRecorder spans), to piggyback on the next TaskDone. Cheap
        when tracing never ran: one deque emptiness check."""
        spans = _tracing.drain_spans()
        if "ray_tpu.util.telemetry" in sys.modules:
            from ray_tpu.util import telemetry as _telemetry
            spans += _telemetry.drain_recorder_spans()
        return spans or None

    def _seal_and_send(self, spec, values, error,
                       exec_start=None, exec_end=None):
        descs = []
        for oid, value in zip(spec.return_ids, values):
            try:
                descs.append(self.store.put(oid, value))
            except BaseException as e:   # unpicklable return, etc.
                tb = traceback.format_exc()
                te = TaskError(type(e).__name__,
                               f"failed to serialize result: {e}", tb)
                descs.append(self.store.put(oid, te))
                error = True
        self.send(protocol.TaskDone(
            task_id=spec.task_id, return_descs=descs, error=error,
            actor_ready=spec.actor_creation and not error,
            exec_start_ts=exec_start, exec_end_ts=exec_end,
            spans=self._drain_spans_for_push()))

    @staticmethod
    def _split_returns(result, num_returns):
        if num_returns == 1:
            return [result]
        out = list(result)
        if len(out) != num_returns:
            raise ValueError(
                f"task declared num_returns={num_returns} but returned "
                f"{len(out)} values")
        return out

    # ---- asyncio actor runtime -------------------------------------------
    # Async actors (any `async def` method) run their methods as
    # coroutines on ONE per-actor event loop with max_concurrency as an
    # asyncio.Semaphore — thousands of concurrent slow requests overlap
    # on awaits instead of burning a thread each (reference:
    # `_private/async_compat.py:19` get_new_event_loop + async task
    # execution in `_raylet.pyx` execute_task; Serve's replica relies on
    # exactly this).

    def _start_actor_event_loop(self, max_concurrency: int):
        import asyncio
        self._loop = asyncio.new_event_loop()
        self._async_sem = None
        # blocking work (dependency resolution via store/network, result
        # sealing) leaves the loop for this pool so awaits keep flowing
        self._io_executor = ThreadPoolExecutor(
            max_workers=4, thread_name_prefix="actor-io")

        def run():
            asyncio.set_event_loop(self._loop)
            self._async_sem = asyncio.Semaphore(max_concurrency)
            self._loop.run_forever()
        t = threading.Thread(target=run, daemon=True,
                             name="actor-eventloop")
        t.start()
        while self._async_sem is None:   # loop thread publishing the sem
            time.sleep(0.001)

    async def _run_task_async(self, push: protocol.PushTask):
        import asyncio
        import contextlib
        import inspect as _inspect
        spec = push.spec
        loop = asyncio.get_running_loop()
        # Control-plane exemption (reference: Ray's concurrency groups —
        # actor classes route health/stats RPCs through a group that
        # data-plane calls cannot saturate). A class may declare
        # `_control_plane_methods`: those methods skip the
        # max_concurrency semaphore, so a scrape or health ping is never
        # queued behind a full window of long-blocking data calls.
        # (Observed: serve replicas with max_concurrency streams all
        # parked in next_chunks starved the controller's stats fan-out.)
        gate = self._async_sem
        if spec.method_name in getattr(type(self.actor_instance),
                                       "_control_plane_methods", ()):
            gate = contextlib.nullcontext()
        async with gate:
            # each asyncio task has its own context, so the current-task
            # id — and the attached trace context — survive interleaving
            # (a thread-local cannot)
            _ASYNC_TASK_ID.set(spec.task_id)
            sp = self._start_task_span(spec)
            exec_start = time.time()
            try:
                args, kwargs = await loop.run_in_executor(
                    self._io_executor, self._resolve_args, spec,
                    push.arg_locations)
                method = getattr(self.actor_instance, spec.method_name)
                result = method(*args, **kwargs)
                if _inspect.isawaitable(result):
                    result = await result
                values = self._split_returns(result, spec.num_returns)
                error = False
            except _DepFailed as df:
                values = [df.cause] * spec.num_returns
                error = True
            except BaseException as e:
                tb = traceback.format_exc()
                te = TaskError(type(e).__name__, str(e), tb, cause=e)
                values = [te] * spec.num_returns
                error = True
            exec_end = time.time()
            if sp is not None:
                _tracing.end_span(sp[0], sp[1],
                                  error="task_error" if error else None)
            await loop.run_in_executor(
                self._io_executor, self._seal_and_send, spec, values,
                error, exec_start, exec_end)

    def main_loop(self):
        import asyncio
        while not self.shutdown:
            push = self.task_queue.get()
            if push is None:
                break
            spec = push.spec
            if spec.actor_creation:
                max_concurrency = (spec.actor_options or {}).get(
                    "max_concurrency", 1)
                self.run_task(push)      # constructs the instance
                # async-ness is decided from the CLASS with the same
                # predicate the driver uses (actor.py _is_async_class):
                # instance-level getattr would execute property getters,
                # and dunder filtering would miss `async def __call__`
                from ray_tpu.actor import _is_async_class
                if self.actor_instance is not None and \
                        _is_async_class(type(self.actor_instance)):
                    self._start_actor_event_loop(max_concurrency)
                elif max_concurrency > 1:
                    self._executor = ThreadPoolExecutor(
                        max_workers=max_concurrency,
                        thread_name_prefix="actor-method")
            elif self._loop is not None:
                asyncio.run_coroutine_threadsafe(
                    self._run_task_async(push), self._loop)
            elif self._executor is not None:
                self._executor.submit(self.run_task, push)
            else:
                self.run_task(push)
        os._exit(0)


def _format_stacks() -> str:
    """Every thread's Python stack, named (the `ray stack` payload)."""
    import traceback
    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in sys._current_frames().items():
        out.append(f"--- thread {names.get(tid, tid)} ---")
        out.extend(ln.rstrip()
                   for ln in traceback.format_stack(frame))
    return "\n".join(out)


class _DepFailed(Exception):
    def __init__(self, cause):
        self.cause = cause


def run(address: str, worker_id: str):
    """Worker entry, callable both from exec (main) and from a
    forkserver child (forkserver.py) — the child passes args directly
    instead of re-parsing argv."""
    authkey = bytes.fromhex(os.environ["RAY_TPU_AUTHKEY"])
    if os.environ.get("TPU_VISIBLE_CHIPS"):
        # a chip worker compiles for its chip: keep what it compiles
        from ray_tpu.util.compile_cache import enable_compile_cache
        enable_compile_cache()
    rt = WorkerRuntime(address, worker_id, authkey)
    _tracing.set_process_label(f"worker:{worker_id}")
    rt.send(protocol.RegisterWorker(worker_id, os.getpid()))

    # Install this runtime as the process-global client so user code can call
    # ray_tpu.get/put/remote/... inside tasks (nested submission).
    from ray_tpu._private import worker as worker_mod
    worker_mod.connect_worker_mode(rt)

    # Span drain must not depend on the process ever registering a
    # metric (the proxy records spans but owns no counters).
    from ray_tpu.util import metrics as _metrics
    _metrics.ensure_flusher()

    threading.Thread(target=rt.reader_loop, daemon=True,
                     name="ray_tpu-worker-reader").start()
    rt.main_loop()


def main():
    run(sys.argv[1], sys.argv[2])


if __name__ == "__main__":
    main()
