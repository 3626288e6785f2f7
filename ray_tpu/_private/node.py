"""Head node server: cluster store, cluster scheduler, object directory,
and the head host's own worker pool.

The head process plays the reference's GCS (gcs_server.h:78: named actors,
KV, job table, node membership, placement groups) plus the head host's
raylet (node_manager.h:117: worker leasing, dependency management, local
dispatch) plus the ownership-based object directory
(reference_count.h:61 + ownership_based_object_directory.h).

Additional hosts run a `HostDaemon` each (`daemon.py` — the raylet
equivalent owning that host's object store and worker pool). The head's
cluster scheduler (`_pick_node`: affinity → SPREAD → locality → pack, the
hybrid_scheduling_policy.h:50 counterpart) assigns tasks to nodes and
leases them over the node channel; object bytes move node-to-node through
chunked pulls (object_manager.h:130,139). `cluster_utils.Cluster` spins up
N daemons on one machine with fake resources — the reference's
one-host multi-raylet test fixture (python/ray/cluster_utils.py:99).

Worker processes connect over a UNIX socket; the message set is
`protocol.py`.
"""

from __future__ import annotations

import atexit
import itertools
import logging
import os
import shutil
import subprocess
import sys
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field, replace
from multiprocessing import connection

from ray_tpu._private import config, constants, ids, netaddr, protocol
from ray_tpu._private.object_store import Descriptor, ObjectStore
from ray_tpu._private.serialization import dumps
from ray_tpu.exceptions import (
    ActorDiedError,
    GetTimeoutError,
    ObjectFreedError,
    ObjectLostError,
    PlacementGroupError,
    SchedulingError,
    RayTpuError,
    TaskCancelledError,
    WorkerCrashedError,
)

logger = logging.getLogger("ray_tpu")

_EPS = 1e-9


def _lineage_size(spec) -> int:
    """Approximate retained bytes of one lineage entry (blob + inline
    args + fixed overhead)."""
    n = len(spec.function_blob or b"") + 256
    for kind, v in list(spec.args) + list(spec.kwargs.values()):
        if kind == "v":
            n += len(v)
    return n


def _fits(avail: dict, req: dict) -> bool:
    return all(avail.get(k, 0.0) + _EPS >= v for k, v in req.items())


def _sub(avail: dict, req: dict) -> None:
    for k, v in req.items():
        avail[k] = avail.get(k, 0.0) - v


def _add(avail: dict, req: dict) -> None:
    for k, v in req.items():
        avail[k] = avail.get(k, 0.0) + v


def plan_gang_placement(pools, bundles, strategy, *, links=None,
                        link_load=None, bandwidth=0.0):
    """Pure bundle-placement planner (no NodeServer state): pick a pool
    for every bundle under `strategy`, contention-aware for bandwidth-
    tagged gangs.

    pools      ordered [(pool_id, available_resources)] — first entry is
               the preferred pool (the head's own ledger).
    links      pool_id -> iterable of interconnect link-group ids the
               pool hangs off (ICI ring / DCN pod, RAY_TPU_LINK_GROUPS).
    link_load  link id -> number of bandwidth-tagged gangs already
               placed on that link.
    bandwidth  this gang's declared appetite (GB/s); 0 keeps the legacy
               ordering exactly (contention never enters the sort key).

    Scoring follows the contention model of 2207.07817: a pool's cost is
    the number of bandwidth-hungry gangs sharing any of its links, so a
    tagged gang gets anti-affinity from links other tagged gangs load.
    SPREAD ranks fitting pools by (bundle count so far, contention,
    arrival order); PACK/STRICT_PACK rank by (contention, arrival
    order). All keys are integers and the sort is stable, so placement
    is deterministic for a given pool order and load map.

    Returns a list of pool ids aligned with `bundles`, or None if the
    gang is infeasible on the current free pools.
    """
    links = links or {}
    link_load = link_load or {}
    sim = {pid: dict(av) for pid, av in pools}
    order = [pid for pid, _ in pools]
    idx = {pid: i for i, pid in enumerate(order)}

    if bandwidth:
        cost = {pid: sum(link_load.get(l, 0) for l in links.get(pid, ()))
                for pid in order}
    else:
        cost = dict.fromkeys(order, 0)

    if strategy == "STRICT_PACK":
        # every bundle on ONE pool; tagged gangs try quiet pools first
        for pid in sorted(order, key=lambda p: (cost[p], idx[p])):
            s = dict(sim[pid])
            if all(_fits(s, b) and (_sub(s, b) or True) for b in bundles):
                return [pid] * len(bundles)
        return None
    assignment = []
    if strategy == "STRICT_SPREAD":
        used = set()
        for b in bundles:
            ranked = sorted(order, key=lambda p: (cost[p], idx[p]))
            pid = next((p for p in ranked
                        if p not in used and _fits(sim[p], b)), None)
            if pid is None:
                return None
            _sub(sim[pid], b)
            used.add(pid)
            assignment.append(pid)
        return assignment
    if strategy == "SPREAD":
        # best-effort distinct: prefer the fitting pool with the fewest
        # bundles so far, quietest links breaking the tie
        counts = dict.fromkeys(order, 0)
        for b in bundles:
            ranked = sorted(order,
                            key=lambda p: (counts[p], cost[p], idx[p]))
            pid = next((p for p in ranked if _fits(sim[p], b)), None)
            if pid is None:
                return None
            _sub(sim[pid], b)
            counts[pid] += 1
            assignment.append(pid)
        return assignment
    # PACK (default): first-fit in (contention, arrival) order — with no
    # bandwidth tag that is exactly the legacy head-first scan
    ranked = sorted(order, key=lambda p: (cost[p], idx[p]))
    for b in bundles:
        pid = next((p for p in ranked if _fits(sim[p], b)), None)
        if pid is None:
            return None
        _sub(sim[pid], b)
        assignment.append(pid)
    return assignment


@dataclass
class _TaskState:
    spec: protocol.TaskSpec
    deps: set = field(default_factory=set)   # unresolved object ids
    submitter: object = None                 # _WorkerConn for nested submits
    retries_left: int = 0
    retry_exceptions: bool = False
    cancelled: bool = False
    node: str | None = None                  # node leased to (None = head)
    node_released: bool = False              # resources released (blocked)
    tpu_chips: list = field(default_factory=list)
    localizing: bool = False                 # remote-arg pull in flight
    dep_failures: int = 0                    # free requeues on dep pulls


@dataclass
class _WorkerConn:
    worker_id: str
    conn: connection.Connection
    proc: object = None                      # mp.Process | subprocess.Popen
    # "generic" (pool) | "actor" | "dedicated" (TPU / runtime-env tasks,
    # retire after one task) | "attach" (external CLI/job connections)
    kind: str = "generic"
    idle: bool = True
    current: _TaskState | None = None
    known_functions: set = field(default_factory=set)
    # resources temporarily released while the worker blocks in get()
    released: dict = field(default_factory=dict)
    alive: bool = True
    # True for conns accepted on the TCP listener from another machine:
    # they can't mmap this host's store, so get/put payloads ride inline
    remote: bool = False
    # set exactly once when RegisterWorker lands: spawn waiters block on
    # THIS, not the global cv (a notify_all herd under creation bursts)
    reg_event: threading.Event = field(default_factory=threading.Event)
    # True while a pool worker is converted into an actor host; lets a
    # failed constructor hand the (still healthy) worker back to the pool
    pooled_actor: bool = False
    # Pipelined-submission receive state (only the per-worker reader
    # thread touches these): next expected SubmitRequest.seq, and
    # whether a nack for the current gap is already outstanding.
    sub_next: int = 0
    sub_nacked: bool = False

    def send(self, msg) -> bool:
        """False on a dead or absent peer. No lock around the channel's own
        (`BatchedConnection.send` is thread-safe): held across a write that
        waits for the worker to read, it kept this worker's reader thread,
        which sends credits and replies, from reading what the worker was
        itself blocked writing (`test_gbdt_trainer_multiworker_parity`
        stood so)."""
        conn = self.conn        # None between spawn and registration
        if conn is None:
            return False
        try:
            conn.send(msg)
            return True
        except (OSError, ValueError):
            return False


@dataclass
class _ActorState:
    actor_id: str
    creation_spec: protocol.TaskSpec
    worker: _WorkerConn | None = None
    ready: bool = False
    dead: bool = False
    death_cause: str = ""
    queue: list = field(default_factory=list)    # pending _TaskState, FIFO
    inflight: list = field(default_factory=list)
    max_concurrency: int = 1
    max_restarts: int = 0
    restarts_used: int = 0
    max_task_retries: int = 0
    name: str | None = None
    resources: dict = field(default_factory=dict)
    tpu_chips: list = field(default_factory=list)
    method_meta: dict = field(default_factory=dict)  # for get_actor handles
    pending_restart: bool = False
    node: str | None = None      # node hosting the actor (None = head)


@dataclass
class _PlacementGroup:
    pg_id: str
    bundles: list            # list[dict]
    strategy: str
    available: list = None   # per-bundle remaining resources
    bundle_nodes: list = None  # per-bundle node id (None = head)
    # Declared interconnect appetite (GB/s, 0 = indifferent). Bandwidth-
    # tagged gangs count toward per-link contention in the placement
    # model (2207.07817): later tagged gangs steer away from links these
    # bundles already load.
    bandwidth: float = 0.0

    def __post_init__(self):
        if self.available is None:
            self.available = [dict(b) for b in self.bundles]
        if self.bundle_nodes is None:
            self.bundle_nodes = [None] * len(self.bundles)


@dataclass
class _RemoteNode:
    """Head-side record of a registered HostDaemon (the GCS's view of one
    raylet: gcs_node_manager + per-node resource bookkeeping)."""
    node_id: str
    conn: connection.Connection
    address: str                              # daemon listener (peer pulls)
    pid: int = 0
    proc: object = None                       # Popen if the head spawned it
    total: dict = field(default_factory=dict)
    available: dict = field(default_factory=dict)
    free_tpu_chips: list = field(default_factory=list)
    # interconnect link-group ids this host hangs off (RegisterNode
    # .link_groups, from RAY_TPU_LINK_GROUPS on the daemon's machine)
    links: list = field(default_factory=list)
    alive: bool = True
    inflight: dict = field(default_factory=dict)  # task_id -> _TaskState
    last_seq: int = 0   # highest NodeSeq seen (dedupe for blip replays)
    send_lock: threading.Lock = field(default_factory=threading.Lock)
    # duck-typing so the shared get/wait request handlers accept a node
    # channel in place of a _WorkerConn
    kind: str = "node"
    worker_id: str = ""
    current: object = None
    released: dict = field(default_factory=dict)
    # daemons localize via the pull plane, never inline (see _WorkerConn)
    remote: bool = False

    def send(self, msg) -> bool:
        return protocol.safe_send(self.conn, self.send_lock, msg)


class NodeServer:
    """One per session; lives in the driver process."""

    def __init__(self, resources: dict, session_dir: str, num_tpu_chips: int,
                 standalone: bool = False):
        self.session_dir = session_dir
        self.standalone = standalone
        self.node_id = ids.new_node_id()
        self.store = ObjectStore(session_dir)
        self.total_resources = dict(resources)
        self.available = dict(resources)
        self.free_tpu_chips = list(range(num_tpu_chips))

        self.lock = threading.RLock()
        self.cv = threading.Condition(self.lock)   # object-ready notification

        self.directory: dict[str, Descriptor] = {}
        self.obj_waiting_tasks: dict[str, list[_TaskState]] = {}
        # counter-based get() waiters: oid -> [waiter dicts]; each
        # registration decrements instead of every blocked get()
        # rescanning its whole id list per wakeup (O(ids^2) for a
        # 100k-ref ray.get otherwise)
        self._get_waiters: dict[str, list] = {}

        # Distributed refcount state (reference: ReferenceCounter,
        # reference_count.h:61). An object is freed when: no process holds
        # a live ObjectRef (ref_holders empty), no queued/running task will
        # consume it (task_arg_refs 0), and it never escaped via pickle.
        self.ref_holders: dict[str, set] = {}     # oid -> holder ids
        self.escaped_refs: set = set()
        self.task_arg_refs: dict[str, int] = {}   # oid -> pending consumers
        self.obj_origin: dict[str, str] = {}      # oid -> worker_id|driver
        self.dead_pending: set = set()            # released pre-registration
        # ids freed by refcounting: tombstones so a racing get/wait/submit
        # fails fast instead of waiting forever for a re-registration that
        # can never come (bounded FIFO)
        self.freed_refs: "OrderedDict[str, bool]" = OrderedDict()
        # task_ids whose args were already released (exactly-once guard);
        # bounded FIFO so a long session doesn't grow it forever
        self._args_released: "OrderedDict[str, bool]" = OrderedDict()

        self.pending: "deque[_TaskState]" = deque()
        self.workers: dict[str, _WorkerConn] = {}
        self.actors: dict[str, _ActorState] = {}
        self.named_actors: dict[str, str] = {}
        self.placement_groups: dict[str, _PlacementGroup] = {}
        self.kv: dict[tuple, bytes] = {}

        # Multi-node state (the GCS side of the split, gcs_server.h:78):
        # registered HostDaemons, head-local cached copies of remote
        # objects, which nodes cached copies of what (for promotion on
        # owner-node death, object_recovery_manager.h:41), and objects
        # whose every copy died with a node.
        self.nodes: dict[str, _RemoteNode] = {}
        self.local_copies: dict[str, Descriptor] = {}
        # oid -> {node_id: that node's OWN copy descriptor} (backing can
        # differ from the primary's, so promotion must use it verbatim)
        self.copy_nodes: dict[str, dict] = {}
        self.lost_objects: dict[str, str] = {}    # oid -> cause
        # Lineage: producing TaskSpec per live task-returned object, so a
        # copy lost with its node can be rebuilt by re-executing the task
        # (reference: lineage pinning in ReferenceCounter + resubmission,
        # task_manager.h:173, object_recovery_manager.h:41). Entries drop
        # when the object is freed or the FIFO cap evicts them.
        self.lineage: "OrderedDict[str, protocol.TaskSpec]" = OrderedDict()
        self._lineage_bytes = 0                    # accumulated spec bytes
        self.reconstructions: dict[str, int] = {}  # oid -> rebuild count
        self.reconstructing: set = set()           # oids being rebuilt
        self._spread_rr = 0
        from ray_tpu._private.pull_plane import PullClient
        self._pull_client = PullClient()
        self._head_pulling: set = set()       # oids being pulled to head

        self._task_errors: dict[str, str] = {}
        # Observability: task lifecycle records (reference: TaskEventBuffer →
        # GcsTaskManager) + per-process metrics snapshots pushed by workers.
        from ray_tpu._private.events import TaskEventRecorder
        self.task_events = TaskEventRecorder()
        self.metrics_by_proc: dict[str, list] = {}
        # the head's lane in merged chrome-trace exports
        from ray_tpu.util import tracing as _tracing
        _tracing.set_process_label("driver")
        # recorder occupancy counters on /metrics (events_tasks_tracked,
        # events_stage_samples, events_got_pending)
        from ray_tpu.util import telemetry as _telemetry
        _telemetry.register_stats_source("task_events", self.task_events,
                                         kind="events")
        self._shutdown = False
        self._spawning = 0      # generic workers currently starting up
        self._spawn_failures = 0  # consecutive startup failures

        # Pidfile lets a later init() garbage-collect sessions whose driver
        # crashed without shutdown (the reference GCs stale session dirs in
        # _private/node.py similarly).
        with open(os.path.join(session_dir, "driver.pid"), "w") as f:
            f.write(str(os.getpid()))

        # Session authkey, in precedence order: operator-pinned env (a
        # k8s Secret — head pod restarts keep the credential), an
        # existing session file (standalone restart into the same dir),
        # else freshly minted. Persisted (0600) so external processes —
        # the CLI, job drivers — can attach to this session (reference:
        # Redis password / GCS address in the session dir).
        keypath = os.path.join(session_dir, "authkey")
        env_key = os.environ.get("RAY_TPU_AUTHKEY") if standalone else None
        on_disk = None
        if os.path.exists(keypath):
            with open(keypath, "rb") as f:
                on_disk = f.read()
        if env_key:
            self._authkey = bytes.fromhex(env_key)
        elif standalone and on_disk:
            self._authkey = on_disk
        else:
            self._authkey = os.urandom(16)
        if on_disk != self._authkey:
            # write only on change: restarting heads must not truncate
            # the file under clients that are mid-read retrying attach
            fd = os.open(keypath,
                         os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
            with os.fdopen(fd, "wb") as f:
                f.write(self._authkey)
        self._address = os.path.join(session_dir, "node.sock")
        if standalone and os.path.exists(self._address):
            # leftover socket from the previous head incarnation
            os.unlink(self._address)
        if standalone:
            self._restore_state()
        self._sched_event = threading.Event()
        threading.Thread(target=self._scheduler_loop,
                         name="ray_tpu-scheduler", daemon=True).start()
        # free-fanout outbox: _maybe_free_locked runs under self.lock,
        # and O(workers) blocking sends in there would let one full pipe
        # stall the whole head during a release storm — a dedicated
        # thread drains the sends outside the lock
        import collections as _collections
        self._free_outbox: _collections.deque = _collections.deque()
        self._free_event = threading.Event()
        threading.Thread(target=self._free_fanout_loop,
                         name="ray_tpu-free-fanout", daemon=True).start()
        self._listener = netaddr.listener(self._address, self._authkey)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="ray_tpu-accept", daemon=True)
        self._accept_thread.start()
        # TCP tier: daemons and client drivers on OTHER machines dial this
        # listener (reference: gRPC-over-TCP everywhere cross-host,
        # src/ray/rpc/grpc_server.h; UDS stays for same-host workers).
        self.tcp_address = None
        self._tcp_listener = None
        if config.get("TRANSPORT") == "tcp" or config.get("HEAD_PORT"):
            bind = (config.get("HEAD_BIND_HOST"), config.get("HEAD_PORT"))
            self._tcp_listener = netaddr.listener(bind, self._authkey)
            self.tcp_address = netaddr.bound_address(self._tcp_listener)
            # published for operators/other machines (reference: GCS
            # address in the session files, services.py:1353)
            with open(os.path.join(session_dir, "head_address"), "w") as f:
                f.write(self.tcp_address)
            threading.Thread(
                target=self._accept_loop, args=(self._tcp_listener, True),
                name="ray_tpu-tcp-accept", daemon=True).start()
        if self.store.arena_stats() is not None:
            threading.Thread(target=self._spill_loop,
                             name="ray_tpu-spill", daemon=True).start()
        from ray_tpu._private.memory_monitor import MemoryMonitor
        self._memory_monitor = MemoryMonitor(self)
        self._memory_monitor.start()
        # Log pipeline (reference: log_monitor.py:102 + dashboard log
        # module): tail this host's per-process log files; daemons ship
        # theirs over the node channel; ring + subscribers fan out.
        from ray_tpu._private.log_monitor import LogRing, LogTailer
        self._log_ring = LogRing()
        self._log_subs: list = []     # conns (have .send) or callables
        # stack-dump collection + pubsub channels
        self._stack_req = itertools.count(1)
        self._stack_waits: dict = {}
        self._stack_cv = threading.Condition()
        self._pubsub: dict = {}       # channel -> [last_seq, ring]
        self._pubsub_cv = threading.Condition()
        self._log_tailer = LogTailer(
            os.path.join(session_dir, "logs"),
            lambda src, lines: self._publish_logs(
                protocol.LogBatch(src, None, lines))).start()
        if standalone:
            threading.Thread(target=self._snapshot_loop,
                             name="ray_tpu-gcs-snapshot",
                             daemon=True).start()
        # usage stats: local session snapshot always; network report only
        # when explicitly opted in (usage_lib.py:92 analog, inverted)
        from ray_tpu._private.usage_stats import UsageReporter
        self._usage_reporter = UsageReporter(self).start()
        atexit.register(self.shutdown)

    # ------------------------------------------------------------------
    # on-demand stack dumps (reference: `ray stack` CLI scripts.py:1786 +
    # py-spy profile_manager.py — workers self-sample, no ptrace)
    # ------------------------------------------------------------------

    def collect_stacks(self, worker_id: str | None = None,
                       timeout: float = 5.0) -> dict:
        """Fan DumpStack to head-local workers and every node; gather
        replies for up to `timeout`s. -> {worker_id: {pid, stacks}}."""
        req = next(self._stack_req)
        box: dict = {}
        with self._stack_cv:
            self._stack_waits[req] = box
        expect = 0
        with self.lock:
            for w in self.workers.values():
                if w.alive and w.kind != "attach" and (
                        worker_id is None or w.worker_id == worker_id):
                    if w.send(protocol.DumpStack(req, worker_id)):
                        expect += 1
            nodes = [n for n in self.nodes.values() if n.alive]
        for n in nodes:
            n.send(protocol.DumpStack(req, worker_id))
        deadline = time.monotonic() + timeout
        grace = 0.5    # node worker counts are unknown up front: stop
        #                once replies go quiet for this long
        last_size, quiet_since = 0, time.monotonic()
        with self._stack_cv:
            while True:
                rem = deadline - time.monotonic()
                if rem <= 0 or (worker_id is not None and box):
                    break
                if not nodes and expect and len(box) >= expect:
                    break
                if len(box) != last_size:
                    last_size, quiet_since = len(box), time.monotonic()
                elif box and time.monotonic() - quiet_since >= grace:
                    break
                self._stack_cv.wait(min(rem, 0.25))
            self._stack_waits.pop(req, None)
        return dict(box)

    def _on_stack_reply(self, msg: protocol.StackDumpReply) -> None:
        with self._stack_cv:
            box = self._stack_waits.get(msg.req_id)
            if box is not None:
                box[msg.worker_id] = {"pid": msg.pid, "stacks": msg.text}
                self._stack_cv.notify_all()

    # ------------------------------------------------------------------
    # pubsub channels (reference: src/ray/pubsub/publisher.h:307 long-
    # poll publisher/subscriber framework; here a head-held ring per
    # channel + long-poll control verbs)
    # ------------------------------------------------------------------

    def pubsub_publish(self, channel: str, message) -> int:
        with self._pubsub_cv:
            seq, ring = self._pubsub.setdefault(channel, [0, []])
            seq += 1
            ring.append((seq, message))
            cap = config.get("PUBSUB_RING_MESSAGES")
            if len(ring) > cap:
                del ring[:len(ring) - cap]
            self._pubsub[channel] = [seq, ring]
            self._pubsub_cv.notify_all()
        return seq

    def pubsub_poll(self, channel: str, after: int,
                    timeout: float = 30.0):
        """Long-poll: block until the channel holds messages with seq >
        after (or timeout) -> (last_seq, [messages]). Runs on a
        _BLOCKING_CONTROL thread, never a reader loop."""
        deadline = time.monotonic() + max(0.0, timeout)
        with self._pubsub_cv:
            while True:
                seq, ring = self._pubsub.get(channel, (0, []))
                fresh = [m for s, m in ring if s > after]
                if fresh:
                    return seq, fresh
                rem = deadline - time.monotonic()
                if rem <= 0 or self._shutdown:
                    return seq, []
                self._pubsub_cv.wait(min(rem, 0.5))

    # ------------------------------------------------------------------
    # log pipeline fanout
    # ------------------------------------------------------------------

    def _publish_logs(self, batch: protocol.LogBatch) -> None:
        key = batch.source if batch.node_id is None \
            else f"{batch.node_id}/{batch.source}"
        self._log_ring.append(key, batch.lines)
        with self.lock:
            subs = list(self._log_subs)
        dead = []
        for s in subs:
            if callable(s):
                try:
                    s(batch)
                except Exception:
                    dead.append(s)
            elif not s.send(batch) or not s.alive:
                dead.append(s)
        if dead:
            with self.lock:
                self._log_subs = [s for s in self._log_subs
                                  if s not in dead]

    def _log_subscribe(self, w) -> bool:
        if w is None:
            # driver-mode client: print straight to this process's stderr
            # (reference: worker.py log_to_driver printing with a
            # (pid=..., ip=...) prefix)
            def _print(batch: protocol.LogBatch):
                nid = batch.node_id or "head"
                for ln in batch.lines:
                    print(f"({batch.source}, node={nid}) {ln}",
                          file=sys.stderr)
            sub = _print
        else:
            sub = w
        with self.lock:
            self._log_subs.append(sub)
        return True

    # ------------------------------------------------------------------
    # autoscaler monitor (reference: autoscaler/_private/monitor.py:126 —
    # the head-side Monitor reads cluster load every tick,
    # update_load_metrics :249, and drives StandardAutoscaler.update)
    # ------------------------------------------------------------------

    def attach_autoscaler(self, config: dict, provider=None) -> dict:
        """Close the loop: demand flows head -> LoadMetrics ->
        StandardAutoscaler -> NodeProvider -> real HostDaemons."""
        from ray_tpu.autoscaler.autoscaler import StandardAutoscaler
        from ray_tpu.autoscaler.load_metrics import LoadMetrics
        from ray_tpu.autoscaler.node_provider import make_node_provider
        prov_spec = config.pop("provider", None) \
            if isinstance(config, dict) else None
        if prov_spec and prov_spec.get("type") == "gcp-tpu":
            # booted slices need somewhere to register; the head is the
            # only party that knows its own dialable address + authkey.
            # A UNIX-socket-only head would bake an unjoinable path into
            # every slice's startup script — refuse before billing starts.
            if not prov_spec.get("head_address"):
                if self.tcp_address is None:
                    raise RuntimeError(
                        "gcp-tpu provider requires the head to listen on "
                        "TCP so slices can join; start it with --port "
                        "(or RAY_TPU_TRANSPORT=tcp)")
                prov_spec["head_address"] = self.tcp_address
            prov_spec.setdefault("authkey_hex", self._authkey.hex())
        with self.lock:
            if getattr(self, "_autoscaler", None) is not None:
                raise RuntimeError("autoscaler already attached")
            self._load_metrics = LoadMetrics()
            self._pending_gangs: list = []
            self._autoscaler = StandardAutoscaler(
                provider or make_node_provider(prov_spec, self), config,
                self._load_metrics)
            self._autoscaler_err: str | None = None
            self._autoscaler_ts: float = 0.0
        threading.Thread(target=self._monitor_loop,
                         name="ray_tpu-autoscaler", daemon=True).start()
        return {"ok": True}

    def _monitor_loop(self):
        period = config.get("AUTOSCALER_UPDATE_INTERVAL_S")
        while not self._shutdown:
            time.sleep(period)
            if self._autoscaler is None:     # torn down
                return
            try:
                self._update_load_metrics()
                self._autoscaler.update()
                self._autoscaler_err = None
            except Exception as e:
                logger.exception("autoscaler update failed")
                self._autoscaler_err = repr(e)
            self._autoscaler_ts = time.time()
            # capacity may have arrived for a waiting placement group
            with self.cv:
                self.cv.notify_all()

    def _update_load_metrics(self):
        lm = self._load_metrics
        with self.lock:
            actor_nodes = {a.node for a in self.actors.values()
                           if not a.dead and a.ready}
            head_busy = any(w.current is not None
                            for w in self.workers.values())
            lm.update_node("head", self.total_resources, self.available,
                           busy=head_busy or None in actor_nodes)
            for nid, n in list(self.nodes.items()):
                if not n.alive:
                    lm.remove_node(nid)
                    continue
                pg_here = any(
                    nid in pg.bundle_nodes
                    for pg in self.placement_groups.values())
                lm.update_node(nid, n.total, n.available,
                               busy=bool(n.inflight)
                               or nid in actor_nodes or pg_here)
            # unplaced actor creations sit in self.pending too, so one
            # pass covers both task and actor demand
            demands = [dict(t.spec.resources) for t in self.pending
                       if not t.deps and not t.cancelled]
            gangs = [[dict(b) for b in g] for g in self._pending_gangs]
            lm.set_demands(demands, gangs)

    def dashboard_snapshot(self) -> dict:
        """One cheap gauge sample for the dashboard's timeseries charts
        (reference: dashboard/modules/metrics/ feeds grafana; here the
        UI buffers these client-side and draws its own sparklines)."""
        with self.lock:
            snap = {
                "ts": time.time(),
                "nodes_alive": 1 + sum(
                    1 for n in self.nodes.values() if n.alive),
                "workers_alive": sum(
                    1 for w in self.workers.values()
                    if w.alive and w.kind != "attach"),
                "actors_alive": sum(
                    1 for a in self.actors.values() if not a.dead),
                "tasks_pending": len(self.pending),
                "objects_tracked": len(self.directory),
            }
        st = self.store.arena_stats() or {}
        snap["store_used_bytes"] = int(st.get("used", 0))
        snap["store_num_objects"] = int(st.get("num_objects", 0))
        return snap

    def autoscaler_teardown(self) -> dict:
        """Terminate every provider node (cloud slices!) before the head
        dies — `ray-tpu down` must never leak billed TPU capacity. The
        head process is the only place the provider instance lives, so
        teardown is a control verb, not a CLI-side loop."""
        a = getattr(self, "_autoscaler", None)
        if a is None:
            return {"terminated": 0}
        # stop the monitor loop first or min_workers would relaunch what
        # we are about to terminate
        with self.lock:
            self._autoscaler = None
        errs = []
        nids = a.provider.non_terminated_nodes({})
        for nid in nids:
            try:
                a.provider.terminate_node(nid)
            except Exception as e:
                errs.append(f"{nid}: {e!r}")
        return {"terminated": len(nids) - len(errs), "errors": errs}

    def autoscaler_status(self) -> dict:
        a = getattr(self, "_autoscaler", None)
        if a is None:
            return {"enabled": False}
        with self.lock:
            pending = len([t for t in self.pending if not t.deps])
            gangs = len(self._pending_gangs)
        return {
            "enabled": True,
            "workers_by_type": a._workers_by_type(),
            "max_workers": a.config["max_workers"],
            "pending_demands": pending,
            "pending_gangs": gangs,
            "infeasible_gangs": len(a.infeasible_gangs),
            "last_update_ts": self._autoscaler_ts,
            "last_error": self._autoscaler_err,
        }

    # ------------------------------------------------------------------
    # metadata persistence (standalone head only; reference: Redis-backed
    # GCS store, store_client/redis_store_client.h:33 — daemons and
    # detached actors survive a head restart, test_gcs_fault_tolerance.py)
    # ------------------------------------------------------------------

    def _snapshot_path(self) -> str:
        return os.path.join(self.session_dir, "head_state.pkl")

    def _snapshot_loop(self):
        import pickle
        period = config.get("HEAD_SNAPSHOT_INTERVAL_S")
        uri = config.get("HEAD_SNAPSHOT_URI")
        last_digest = None
        while not self._shutdown:
            time.sleep(period)
            try:
                state = self._snapshot_state()
                blob = pickle.dumps(state)
                import hashlib
                digest = hashlib.sha1(blob).digest()
                if digest == last_digest:
                    continue      # unchanged: skip disk AND remote writes
                tmp = self._snapshot_path() + ".tmp"
                with open(tmp, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._snapshot_path())
                if uri:
                    # remote mirror -> a replacement head on another
                    # machine can take over (Redis-GCS analog)
                    from ray_tpu.util import storage
                    storage.write_bytes(
                        storage.uri_join(uri, "head_state.pkl"), blob)
                last_digest = digest
            except Exception:
                logger.exception("head snapshot failed")

    def _snapshot_state(self) -> dict:
        """Cluster METADATA only (no object payloads): what a restarted
        head needs to re-attach daemons and detached actors."""
        with self.lock:
            actors = {}
            for aid, a in self.actors.items():
                if a.dead:
                    continue
                actors[aid] = {
                    "creation_spec": a.creation_spec,
                    "max_concurrency": a.max_concurrency,
                    "max_restarts": a.max_restarts,
                    "restarts_used": a.restarts_used,
                    "max_task_retries": a.max_task_retries,
                    "name": a.name,
                    "resources": dict(a.resources),
                    "tpu_chips": list(a.tpu_chips),
                    "method_meta": a.method_meta,
                    "node": a.node,
                }
            pgs = {pid: {"bundles": pg.bundles, "strategy": pg.strategy,
                         "available": pg.available,
                         "bundle_nodes": pg.bundle_nodes,
                         "bandwidth": pg.bandwidth}
                   for pid, pg in self.placement_groups.items()}
            return {
                "named_actors": dict(self.named_actors),
                "actors": actors,
                "kv": dict(self.kv),
                "placement_groups": pgs,
            }

    def _restore_state(self):
        import pickle
        path = self._snapshot_path()
        blob = None
        if os.path.exists(path):
            try:
                with open(path, "rb") as f:
                    blob = f.read()
            except OSError:
                logger.exception("local head snapshot unreadable")
        if blob is None:
            uri = config.get("HEAD_SNAPSHOT_URI")
            if uri:
                # failover: a fresh machine with no session dir restores
                # the cluster metadata from the remote mirror
                try:
                    from ray_tpu.util import storage
                    blob = storage.read_bytes(
                        storage.uri_join(uri, "head_state.pkl"))
                    logger.warning("restoring head state from %s", uri)
                except FileNotFoundError:
                    pass
                except Exception:
                    logger.exception("remote head snapshot unreadable")
        if blob is None:
            return
        try:
            state = pickle.loads(blob)
        except Exception:
            logger.exception("head snapshot unreadable; starting fresh")
            return
        for aid, d in state.get("actors", {}).items():
            a = _ActorState(
                actor_id=aid, creation_spec=d["creation_spec"],
                max_concurrency=d["max_concurrency"],
                max_restarts=d["max_restarts"],
                restarts_used=d["restarts_used"],
                max_task_retries=d["max_task_retries"],
                name=d["name"], resources=d["resources"],
                tpu_chips=d["tpu_chips"], method_meta=d["method_meta"],
                node=d["node"])
            if d["node"] is None:
                # head-local actor processes died with the head
                a.dead = True
                a.death_cause = "head restarted (actor lived on the head)"
            else:
                # awaiting its daemon's re-registration
                a.ready = False
            self.actors[aid] = a
        for a in self.actors.values():
            if not a.dead:
                continue
            # the normal death path credits a PG actor's resources back to
            # its bundle (_release_actor_resources); the snapshot carries
            # the debit, so mirror that credit here or the slot leaks
            pg_state = state.get("placement_groups", {}).get(
                a.creation_spec.placement_group_id or "")
            if pg_state is not None and pg_state["available"]:
                _add(pg_state["available"][0], a.resources)
        self.named_actors.update(state.get("named_actors", {}))
        self.kv.update(state.get("kv", {}))
        for pid, d in state.get("placement_groups", {}).items():
            self.placement_groups[pid] = _PlacementGroup(
                pg_id=pid, bundles=d["bundles"], strategy=d["strategy"],
                available=d["available"], bundle_nodes=d["bundle_nodes"],
                bandwidth=d.get("bandwidth", 0.0))
            # bundles reserved on the head itself are re-held now;
            # daemon-side bundles are re-held at re-registration
            for b, nid in zip(d["bundles"], d["bundle_nodes"]):
                if nid is None:
                    _sub(self.available, b)
        logger.warning(
            "restored head state: %d actors (%d named), %d kv keys, "
            "%d placement groups",
            len(self.actors), len(self.named_actors), len(self.kv),
            len(self.placement_groups))

    # ------------------------------------------------------------------
    # connection plumbing
    # ------------------------------------------------------------------

    def _accept_loop(self, listener=None, remote=False):
        listener = listener or self._listener
        while not self._shutdown:
            try:
                conn = listener.accept()
            except Exception:
                # One bad handshake (EOF mid-connect, wrong authkey ->
                # AuthenticationError) must not kill the accept loop; only
                # shutdown ends it.
                if self._shutdown:
                    return
                time.sleep(0.05)
                continue
            threading.Thread(target=self._serve_conn, args=(conn, remote),
                             daemon=True).start()

    def _serve_conn(self, conn, remote=False):
        try:
            reg = conn.recv()
        except (EOFError, OSError, TypeError):
            return
        if isinstance(reg, protocol.RegisterNode):
            self._serve_node_conn(conn, reg)
            return
        if not isinstance(reg, protocol.RegisterWorker):
            conn.close()
            return
        with self.lock:
            w = self.workers.get(reg.worker_id)
            if w is None:
                # Late registration of a worker we spawned, or an external
                # attach client (CLI / job driver): never dispatch to those.
                w = _WorkerConn(reg.worker_id, conn)
                if reg.worker_id.startswith("attach_"):
                    w.kind = "attach"
                    w.idle = False
                self.workers[reg.worker_id] = w
            else:
                w.conn = conn
            w.remote = remote
            w.alive = True
            w.reg_event.set()
            self.cv.notify_all()
        self._reader_loop(w)

    def _reader_loop(self, w: _WorkerConn):
        conn = w.conn
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError, TypeError):
                self._on_worker_death(w)
                try:
                    conn.close()    # ends the channel's flusher thread too
                except OSError:
                    pass
                return
            try:
                self._handle(w, msg)
            except Exception:
                logger.exception("error handling %r from %s", type(msg),
                                 w.worker_id)

    def _handle(self, w: _WorkerConn, msg):
        if isinstance(msg, protocol.TaskDone):
            self._on_task_done(w, msg)
        elif isinstance(msg, protocol.StackDumpReply):
            self._on_stack_reply(msg)
        elif isinstance(msg, protocol.PutRequest):
            # the putting worker certainly holds its new ObjectRef right
            # now, but its batched "hold" report may lag by up to the
            # flush period: record an implicit hold so a fast consumer
            # can't free the object in that window (idempotent with the
            # explicit hold; cleared by the worker's eventual release)
            self.ref_hold(msg.object_id, w.worker_id)
            desc = msg.desc
            if (desc.inline is not None
                    and len(desc.inline) > constants.INLINE_OBJECT_MAX_BYTES):
                # oversized inline put from a cross-machine client: land the
                # bytes in the head's store so they don't ride every
                # subsequent control message
                desc = self.store.put_serialized(msg.object_id, desc.inline)
                # the head's store owns the bytes now, so the free path
                # must delete them here, not at the putting client
                self.register_object(msg.object_id, desc, origin="driver")
                return
            self.register_object(msg.object_id, desc,
                                 origin=w.worker_id)
        elif isinstance(msg, protocol.GetRequest):
            threading.Thread(
                target=self._serve_get, args=(w, msg), daemon=True).start()
        elif isinstance(msg, protocol.WaitRequest):
            threading.Thread(
                target=self._serve_wait, args=(w, msg), daemon=True).start()
        elif isinstance(msg, protocol.SubmitRequest):
            if msg.seq is not None:
                self._on_pipelined_submit(w, msg)
            else:
                try:
                    self.submit(msg.spec, submitter=w)
                    w.send(protocol.SubmitReply(msg.req_id, ok=True))
                except Exception as e:
                    w.send(protocol.SubmitReply(msg.req_id, ok=False,
                                                error=repr(e)))
        elif isinstance(msg, protocol.ActorCallRequest):
            self._dispatch_control(w, msg)
        else:
            logger.warning("unknown message %r", type(msg))

    # Credit cadence for pipelined submissions: ack every quarter window
    # so the sender's ring stays shallow without an ack per task.
    _SUBMIT_CREDIT_EVERY = max(1, constants.SUBMIT_WINDOW // 4)

    def _on_pipelined_submit(self, w: _WorkerConn, msg) -> None:
        """Seq state machine for one worker's pipelined submit stream
        (runs on that worker's reader thread, the only writer of
        `sub_next`/`sub_nacked`). In-order: apply + periodic credit.
        Duplicate (replay overlap): drop and re-credit, so the sender
        prunes its ring and learns the watermark even when the original
        credit was lost. Gap: nack once with the expected seq; the
        sender replays from there in order."""
        seq = msg.seq
        if seq == w.sub_next:
            w.sub_next = seq + 1
            w.sub_nacked = False
            try:
                self.submit(msg.spec, submitter=w)
            except Exception as e:
                if not isinstance(e, RayTpuError):
                    e = RayTpuError(f"submit failed: {e!r}")
                self._store_error(msg.spec.return_ids, e, spec=msg.spec)
            if w.sub_next % self._SUBMIT_CREDIT_EVERY == 0:
                w.send(protocol.SubmitCredit(w.sub_next - 1))
        elif seq < w.sub_next:
            w.send(protocol.SubmitCredit(w.sub_next - 1))
        elif not w.sub_nacked:
            w.sub_nacked = True
            w.send(protocol.SubmitNack(w.sub_next))

    # Control verbs that may block for a long time (autoscaler-waiting
    # placement groups) must not run inline on a connection's reader
    # thread: that would stall every other message on the channel —
    # including, on a node channel, the TaskDone that frees the very
    # capacity being waited for.
    _BLOCKING_CONTROL = frozenset({"create_pg", "pubsub_poll", "stack"})

    def _dispatch_control(self, w, msg: protocol.ActorCallRequest):
        def run():
            try:
                result = self._control(msg.method, msg.payload, w)
                w.send(protocol.ActorCallReply(msg.req_id, result=result))
            except Exception as e:
                w.send(protocol.ActorCallReply(msg.req_id, error=repr(e)))
        if msg.method in self._BLOCKING_CONTROL:
            threading.Thread(target=run, daemon=True,
                             name=f"ctl-{msg.method}").start()
        else:
            run()

    # ------------------------------------------------------------------
    # node channels (head <-> HostDaemon; the GCS side of the split)
    # ------------------------------------------------------------------

    def _serve_node_conn(self, conn, reg: protocol.RegisterNode):
        node = _RemoteNode(
            node_id=reg.node_id, conn=conn, address=reg.address,
            pid=reg.pid, total=dict(reg.resources),
            available=dict(reg.resources),
            free_tpu_chips=list(range(reg.num_tpu_chips)),
            links=list(reg.link_groups or ()),
            worker_id="node:" + reg.node_id)
        with self.lock:
            old = self.nodes.get(reg.node_id)
            readopted_actors = set(reg.actors or {})
            if old is not None:
                node.proc = old.proc
                # seq dedupe spans registrations of the same daemon
                # process: the replayed ring must not re-apply messages
                # the old channel already delivered
                node.last_seq = old.last_seq
                # The superseded registration must never drive teardown:
                # if its reader later sees EOF (channel blip + reconnect),
                # _on_node_death would otherwise pass the alive-guard and
                # rip down the LIVE node's actors/objects by node_id.
                old.alive = False
                # Migrate still-running leases: the daemon process
                # survived the blip and will report their completion on
                # the NEW channel — _on_node_task_done must find them
                # here, and their resource holds must be re-debited from
                # this fresh (fully-available) registration so the
                # eventual release balances. PG-task CPU holds are
                # covered by the whole-bundle re-debit below; a creating
                # actor's hold is covered by the ready-actor re-attach
                # below iff the daemon re-reported it.
                # A lease ABSENT from reg.leases was swallowed by the
                # blip (or its outcome already delivered): the daemon
                # will never report it, so re-dispatch instead of
                # migrating a wait-forever entry.
                known = (None if reg.leases is None else set(reg.leases))
                requeue = []
                # SHARE the table (don't copy): an old-channel reader that
                # passed the alive/seq guard just before this supersede
                # applies its terminal against the same dict the new
                # channel serves — with a copy, that in-flight apply would
                # pop an orphaned table and the completion would be lost
                # on both channels (its seq is already marked seen).
                node.inflight = old.inflight
                for tid, t in list(node.inflight.items()):
                    spec = t.spec
                    if known is not None and tid not in known:
                        requeue.append(t)
                        del node.inflight[tid]
                        continue
                    if spec.actor_creation:
                        a = self.actors.get(spec.actor_id)
                        if (a is not None
                                and spec.actor_id not in readopted_actors
                                and not spec.placement_group_id):
                            _sub(node.available, a.resources)
                    elif spec.actor_id is None \
                            and not spec.placement_group_id:
                        _sub(node.available, spec.resources)
                    for chip in t.tpu_chips:
                        if chip in node.free_tpu_chips:
                            node.free_tpu_chips.remove(chip)
                for t in requeue:
                    # release credits the superseded object (discarded)
                    # for node-pool holds and the persistent PG bundles
                    # for PG holds — the new registration starts fully
                    # available, so the books balance either way
                    spec = t.spec
                    if spec.actor_creation:
                        a = self.actors.get(spec.actor_id)
                        if a is None or a.dead:
                            continue
                        self._release_actor_resources(a)
                        if t in a.inflight:
                            a.inflight.remove(t)
                        t.tpu_chips = []
                        t.node = None
                        self.task_events.requeued(spec)
                        self.pending.append(t)
                    elif spec.actor_id is not None:
                        a = self.actors.get(spec.actor_id)
                        if a is None or a.dead:
                            continue
                        if t in a.inflight:
                            a.inflight.remove(t)
                        t.node = None
                        a.queue.insert(0, t)
                    else:
                        self._release_task_resources(t)
                        t.node = None
                        self.task_events.requeued(spec)
                        self.pending.append(t)
            self.nodes[reg.node_id] = node
            # RE-registration after a head restart: re-attach the actors
            # still alive on that daemon and re-hold their resources +
            # any placement-group bundles reserved there (reference:
            # NotifyGCSRestart resource resync). Only actors the head
            # still maps to THIS node re-attach — if the head stayed up
            # and already restarted an actor elsewhere (the channel blip
            # case), the daemon's copy is stale and gets killed below,
            # never a split-brain rebind.
            stale_actors = []
            for aid in (reg.actors or {}):
                a = self.actors.get(aid)
                if a is not None and not a.dead and a.node == reg.node_id:
                    a.ready = True
                    a.pending_restart = False
                    if not a.creation_spec.placement_group_id:
                        # PG actors were debited from pg.available, which
                        # the snapshot preserved; the bundle re-debit
                        # below covers node.available for them
                        _sub(node.available, a.resources)
                    for chip in a.tpu_chips:
                        if chip in node.free_tpu_chips:
                            node.free_tpu_chips.remove(chip)
                else:
                    stale_actors.append(aid)
            for pg in self.placement_groups.values():
                for b, nid in zip(pg.bundles, pg.bundle_nodes):
                    if nid == reg.node_id:
                        _sub(node.available, b)
            self.cv.notify_all()
        for aid in stale_actors:
            node.send(protocol.KillActorOnNode(aid))
        # rebuild the object directory from the daemon's surviving store;
        # refcount state died with the old head, so these are pinned
        # (escaped) rather than risking a premature free
        for oid, desc in (reg.objects or {}).items():
            with self.lock:
                known = oid in self.directory
            if not known:
                self.ref_escape(oid)
                self.register_object(oid, desc,
                                     origin="node:" + reg.node_id)
        logger.info("node %s registered: %s", reg.node_id, reg.resources)
        self._schedule()
        while True:
            try:
                msg = conn.recv()
            except (EOFError, OSError, TypeError):
                # TypeError: conn closed out from under us locally
                # (mp.connection raises it instead of OSError); the death
                # path must still run, never a silent reader crash
                try:
                    self._on_node_death(node)
                except Exception:
                    logger.exception("node death handling failed for %s",
                                     node.node_id)
                return
            try:
                if isinstance(msg, protocol.NodeSeq):
                    # Reliability envelope: drop blip-replay duplicates.
                    # Under the lock, and only while THIS registration is
                    # current: once superseded (alive=False, set under
                    # the same lock that copies last_seq into the new
                    # registration), late messages buffered on the old
                    # channel are discarded here and owned by the new
                    # channel's ring replay — otherwise a message applied
                    # after the last_seq snapshot would be applied twice.
                    with self.lock:
                        if not node.alive or msg.seq <= node.last_seq:
                            continue
                        node.last_seq = msg.seq
                    msg = msg.inner
                self._handle_node(node, msg)
            except Exception:
                logger.exception("error handling %r from node %s",
                                 type(msg), reg.node_id)

    def _handle_node(self, node: _RemoteNode, msg):
        if isinstance(msg, protocol.NodeTaskDone):
            self._on_node_task_done(node, msg)
        elif isinstance(msg, protocol.NodeTaskFailed):
            self._on_node_task_failed(node, msg)
        elif isinstance(msg, protocol.NodeActorDied):
            self._on_node_actor_died(node, msg)
        elif isinstance(msg, protocol.NodeWorkerBlocked):
            self._on_node_worker_blocked(node, msg)
        elif isinstance(msg, protocol.NodeWorkerGone):
            self._drop_ref_holder(msg.worker_id)
        elif isinstance(msg, protocol.StackDumpReply):
            self._on_stack_reply(msg)
        elif isinstance(msg, protocol.LogBatch):
            self._publish_logs(replace(msg, node_id=node.node_id))
        elif isinstance(msg, protocol.ObjectCopyNote):
            with self.lock:
                if msg.object_id in self.directory:
                    self.copy_nodes.setdefault(
                        msg.object_id, {})[msg.node_id] = msg.desc
        elif isinstance(msg, protocol.PullRequest):
            threading.Thread(target=self._serve_pull, args=(node, msg),
                             daemon=True).start()
        elif isinstance(msg, protocol.PullChunk):
            if msg.data is None:
                # raw body frame follows NOW on this channel (we're in
                # the node reader, synchronously before the next recv)
                self._pull_client.on_chunk_raw(msg, node.conn)
            else:
                self._pull_client.on_chunk(msg)
        elif isinstance(msg, protocol.PutRequest):
            if msg.origin:
                self.ref_hold(msg.object_id, msg.origin)
            self.register_object(msg.object_id, msg.desc,
                                 origin="node:" + node.node_id)
        elif isinstance(msg, protocol.GetRequest):
            threading.Thread(target=self._serve_get, args=(node, msg),
                             daemon=True).start()
        elif isinstance(msg, protocol.WaitRequest):
            threading.Thread(target=self._serve_wait, args=(node, msg),
                             daemon=True).start()
        elif isinstance(msg, protocol.SubmitRequest):
            # req_id < 0 marks a pipelined submission the daemon already
            # deduped and forwarded on the reliable (NodeSeq) channel:
            # apply it, never reply — failures become error objects
            # under the spec's return ids.
            try:
                self.submit(msg.spec,
                            submitter=msg.submitter or node.worker_id)
                if msg.req_id >= 0:
                    node.send(protocol.SubmitReply(msg.req_id, ok=True))
            except Exception as e:
                if msg.req_id >= 0:
                    node.send(protocol.SubmitReply(msg.req_id, ok=False,
                                                   error=repr(e)))
                else:
                    if not isinstance(e, RayTpuError):
                        e = RayTpuError(f"submit failed: {e!r}")
                    self._store_error(msg.spec.return_ids, e,
                                      spec=msg.spec)
        elif isinstance(msg, protocol.ActorCallRequest):
            self._dispatch_control(node, msg)
        else:
            logger.warning("unknown node message %r", type(msg))

    def _drop_ref_holder(self, holder: str) -> None:
        with self.lock:
            affected = [oid for oid, holders in self.ref_holders.items()
                        if holder in holders]
            for oid in affected:
                self.ref_holders[oid].discard(holder)
                self._maybe_free_locked(oid)

    # ------------------------------------------------------------------
    # control-plane RPCs (named actors, KV, kill, ...)
    # ------------------------------------------------------------------

    def _control(self, method: str, payload, w):
        if method == "get_actor":
            return self.get_named_actor(payload)
        if method == "kill_actor":
            return self.kill_actor(payload["actor_id"],
                                   no_restart=payload.get("no_restart", True))
        if method == "kv_put":
            ns, key, val = payload
            with self.lock:
                self.kv[(ns, key)] = val
            return True
        if method == "kv_get":
            ns, key = payload
            with self.lock:
                return self.kv.get((ns, key))
        if method == "kv_del":
            ns, key = payload
            with self.lock:
                return self.kv.pop((ns, key), None) is not None
        if method == "kv_list":
            ns, prefix = payload
            with self.lock:
                return [k for (n, k) in self.kv if n == ns
                        and k.startswith(prefix)]
        if method == "cluster_resources":
            with self.lock:
                out = dict(self.total_resources)
                for n in self.nodes.values():
                    if n.alive:
                        _add(out, n.total)
                return out
        if method == "available_resources":
            with self.lock:
                out = dict(self.available)
                for n in self.nodes.values():
                    if n.alive:
                        _add(out, n.available)
                return out
        if method == "node_address":
            with self.lock:
                n = self.nodes.get(payload)
                return n.address if n is not None and n.alive else None
        if method == "add_node":
            p = payload or {}
            return self.add_node(p.get("resources"),
                                 int(p.get("num_tpus", 0)))
        if method == "kill_node":
            p = payload or {}
            return self.kill_node(p["node_id"], force=p.get("force", True))
        if method == "attach_autoscaler":
            return self.attach_autoscaler(payload or {})
        if method == "autoscaler_status":
            return self.autoscaler_status()
        if method == "autoscaler_teardown":
            return self.autoscaler_teardown()
        if method == "stack":
            p = payload or {}
            return self.collect_stacks(p.get("worker_id"),
                                       float(p.get("timeout", 5.0)))
        if method == "pubsub_publish":
            return self.pubsub_publish(payload["channel"],
                                       payload["message"])
        if method == "pubsub_poll":
            t = float(payload.get("timeout", 30.0))
            # attach clients enforce a transport deadline
            # (ATTACH_CONTROL_TIMEOUT_S) that a full-length server poll
            # would race into a spurious ConnectionError on an idle
            # channel; cap their blocking window safely below it
            if w is not None and w.worker_id.startswith("attach_"):
                # max() guards an env-shrunk ATTACH_CONTROL_TIMEOUT_S
                # from turning long-polls into a busy loop
                t = min(t, max(1.0,
                               constants.ATTACH_CONTROL_TIMEOUT_S - 5.0))
            return self.pubsub_poll(payload["channel"],
                                    int(payload.get("after", 0)), t)
        if method == "log_subscribe":
            return self._log_subscribe(w)
        if method == "list_logs":
            return self._log_ring.sources()
        if method == "get_log":
            p = payload or {}
            return self._log_ring.tail(p["source"],
                                       int(p.get("lines", 200)))
        if method == "create_pg":
            return self.create_placement_group(**payload)
        if method == "remove_pg":
            return self.remove_placement_group(payload)
        if method == "cancel":
            return self.cancel(payload["object_id"], payload.get("force", False))
        if method == "list_tasks":
            return self.task_events.snapshot(
                filters=(payload or {}).get("filters"),
                limit=(payload or {}).get("limit", 10_000))
        if method == "summarize_tasks":
            return self.task_events.summary()
        if method == "timeline":
            # ONE merged chrome://tracing view: task events (cat="task")
            # interleaved with the telemetry plane — per-request engine
            # flight-recorder spans (cat="request") and application
            # tracing spans (cat="span"), including every span workers
            # drained up to this ring. All use epoch-µs timestamps, so
            # they line up on the same axis. Optional payload
            # {"trace": <trace_id>} narrows to one distributed trace.
            from ray_tpu.util import telemetry as _telemetry
            events = (self.task_events.chrome_trace()
                      + _telemetry.chrome_trace_events())
            trace = (payload or {}).get("trace")
            if trace:
                events = [e for e in events
                          if (e.get("args") or {}).get("trace_id") == trace]
            return events
        if method == "list_actors":
            with self.lock:
                return [{
                    "actor_id": a.actor_id,
                    "class_name": a.creation_spec.function_desc,
                    "name": a.name,
                    "state": ("DEAD" if a.dead else
                              "ALIVE" if a.ready else "PENDING_CREATION"),
                    "death_cause": a.death_cause or None,
                    "pending_tasks": len(a.queue),
                    "resources": dict(a.resources),
                    "worker_id": a.worker.worker_id if a.worker else None,
                } for a in itertools.islice(
                    self.actors.values(),
                    (payload or {}).get("limit", 10_000))]
        if method == "list_objects":
            with self.lock:
                return [{
                    "object_id": oid, "size_bytes": desc.size,
                    "store": ("inline" if desc.inline is not None else
                              "arena" if desc.arena else "file"),
                } for oid, desc in itertools.islice(
                    self.directory.items(),
                    (payload or {}).get("limit", 10_000))]
        if method == "list_workers":
            with self.lock:
                return [{
                    "worker_id": w.worker_id, "kind": w.kind,
                    "alive": w.alive, "idle": w.idle,
                    "current_task": (w.current.spec.task_id
                                     if w.current else None),
                    "pid": getattr(w.proc, "pid", None),
                } for w in itertools.islice(
                    self.workers.values(),
                    (payload or {}).get("limit", 10_000))]
        if method == "list_placement_groups":
            with self.lock:
                return [{
                    "placement_group_id": pg.pg_id,
                    "strategy": pg.strategy,
                    "bandwidth": pg.bandwidth,
                    "bundles": [dict(b) for b in pg.bundles],
                    "available": [dict(b) for b in pg.available],
                } for pg in itertools.islice(
                    self.placement_groups.values(),
                    (payload or {}).get("limit", 10_000))]
        if method == "list_nodes":
            with self.lock:
                out = [{
                    "node_id": self.node_id, "alive": True, "head": True,
                    "resources_total": dict(self.total_resources),
                    "resources_available": dict(self.available),
                    "session_dir": self.session_dir,
                }]
                out += [{
                    "node_id": n.node_id, "alive": n.alive, "head": False,
                    "resources_total": dict(n.total),
                    "resources_available": dict(n.available),
                    "inflight_tasks": len(n.inflight),
                } for n in self.nodes.values()]
                return out
        if method.startswith("job_"):
            jm = self._job_manager()
            if method == "job_submit":
                return jm.submit(payload["entrypoint"],
                                 job_id=payload.get("job_id"),
                                 runtime_env=payload.get("runtime_env"),
                                 metadata=payload.get("metadata"))
            if method == "job_status":
                return jm.status(payload)
            if method == "job_list":
                return jm.list()
            if method == "job_logs":
                return jm.logs(payload)
            if method == "job_stop":
                return jm.stop(payload)
        if method == "ref_update":
            # Events are applied in their original order: a worker that
            # releases and then re-holds an oid inside one flush window
            # must not have the hold applied first (which would net to
            # holder-removed and free an object with a live ref).
            holder = payload["holder"]
            with self.lock:
                for kind, oid in payload.get("events", ()):
                    if kind == "escape":
                        self.escaped_refs.add(oid)
                    elif kind == "hold":
                        self.ref_holders.setdefault(oid, set()).add(holder)
                    else:  # release
                        holders = self.ref_holders.get(oid)
                        if holders is not None:
                            holders.discard(holder)
                        self._maybe_free_locked(oid)
            return True
        if method == "push_metrics":
            wid, snap = payload
            with self.lock:
                self.metrics_by_proc[wid] = snap
            return True
        if method == "push_spans":
            # worker→head span drain (piggybacked on the metrics flush)
            _wid, spans = payload
            from ray_tpu.util import tracing as _tracing
            return _tracing.ingest(spans)
        if method == "stage_breakdown":
            return self.task_events.stage_breakdown()
        if method == "enable_tracing":
            return self.enable_tracing_broadcast()
        if method == "dashboard_snapshot":
            return self.dashboard_snapshot()
        if method == "free_objects":
            return self.free_objects(payload or [])
        if method == "get_metrics":
            from ray_tpu.util import metrics as _metrics
            with self.lock:
                snaps = list(self.metrics_by_proc.values())
            # driver-process metrics participate directly
            snaps.append(_metrics.snapshot())
            return _metrics.merge_snapshots(snaps)
        if method == "actor_state":
            with self.lock:
                a = self.actors.get(payload)
                if a is None:
                    return None
                return {"ready": a.ready, "dead": a.dead,
                        "cause": a.death_cause}
        raise ValueError(f"unknown control method {method}")

    def enable_tracing_broadcast(self) -> bool:
        """Turn span recording on in every live process of the session:
        this one, the head's workers, and remote daemons (which fan the
        protocol.SetTracing on to their workers). Future spawns inherit
        the RAY_TPU_TRACING env var instead."""
        from ray_tpu.util import tracing as _tracing
        _tracing._enable_local()
        msg = protocol.SetTracing(enabled=True)
        with self.lock:
            workers = [w for w in self.workers.values() if w.alive]
            nodes = [n for n in self.nodes.values() if n.alive]
        for w in workers:
            w.send(msg)
        for n in nodes:
            n.send(msg)
        return True

    # ------------------------------------------------------------------
    # object directory
    # ------------------------------------------------------------------

    def _job_manager(self):
        if not hasattr(self, "_jobs"):
            from ray_tpu.job_submission import JobManager
            with self.lock:
                if not hasattr(self, "_jobs"):
                    self._jobs = JobManager(
                        os.path.join(self.session_dir, "jobs"))
        return self._jobs

    # ------------------------------------------------------------------
    # reference counting
    # ------------------------------------------------------------------

    def ref_hold(self, oid: str, holder: str) -> None:
        with self.lock:
            self.ref_holders.setdefault(oid, set()).add(holder)

    def ref_release(self, oid: str, holder: str) -> None:
        with self.lock:
            holders = self.ref_holders.get(oid)
            if holders is not None:
                holders.discard(holder)
            self._maybe_free_locked(oid)

    def ref_escape(self, oid: str) -> None:
        with self.lock:
            self.escaped_refs.add(oid)

    def free_objects(self, oids) -> int:
        """Explicit unconditional release (reference:
        `_private/internal_api.py free()`): drops the escape pin and all
        holder records so the normal free path runs. The caller asserts
        nothing will read these refs again — the API exists for
        bulk-intermediate lifecycles (shuffle shards) whose nested refs
        otherwise escape to session lifetime."""
        n = 0
        with self.lock:
            for oid in oids:
                self.escaped_refs.discard(oid)
                self.ref_holders.pop(oid, None)
                if oid in self.directory:
                    n += 1
                self._maybe_free_locked(oid)
        return n

    def _pin_task_args_locked(self, spec) -> None:
        for kind, v in list(spec.args) + list(spec.kwargs.values()):
            if kind == "ref":
                self.task_arg_refs[v] = self.task_arg_refs.get(v, 0) + 1

    def _release_task_args(self, spec) -> None:
        """Exactly-once per task: its ref args are no longer needed by
        this consumer. Called from every terminal path."""
        with self.lock:
            if spec.task_id in self._args_released:
                return
            self._args_released[spec.task_id] = True
            while len(self._args_released) > constants.ARGS_RELEASED_CAP:
                self._args_released.popitem(last=False)
            for kind, v in list(spec.args) + list(spec.kwargs.values()):
                if kind == "ref":
                    n = self.task_arg_refs.get(v, 0) - 1
                    if n <= 0:
                        self.task_arg_refs.pop(v, None)
                        self._maybe_free_locked(v)
                    else:
                        self.task_arg_refs[v] = n

    def _maybe_free_locked(self, oid: str) -> None:
        """Free the object if nothing can reach it anymore (caller holds
        the lock)."""
        if oid in self.escaped_refs:
            return
        if self.ref_holders.get(oid):
            return
        if self.task_arg_refs.get(oid, 0) > 0:
            return
        desc = self.directory.get(oid)
        if desc is None:
            # released before the producing task finished: free on arrival
            self.dead_pending.add(oid)
            return
        del self.directory[oid]
        self.ref_holders.pop(oid, None)
        self.dead_pending.discard(oid)
        self.freed_refs[oid] = True
        self._poke_get_waiters(oid)
        while len(self.freed_refs) > constants.FREED_REFS_CAP:
            self.freed_refs.popitem(last=False)
        origin = self.obj_origin.pop(oid, "driver")
        dropped = self.lineage.pop(oid, None)
        if dropped is not None:
            self._lineage_bytes -= _lineage_size(dropped)
        self.reconstructions.pop(oid, None)
        # head-local cached copy of a remote object
        lc = self.local_copies.pop(oid, None)
        if lc is not None:
            self.store.delete(lc)
        copies = self.copy_nodes.pop(oid, ())
        if desc.node is None:
            self.store.delete(desc)
            # every LOCAL worker that read the object holds a pinned
            # arena view (or a cached mmap for file-backed descs); the
            # block's offset can't recycle until they all drop it —
            # origin-only fanout leaked reader pins and grew the arena
            # cold forever. Sends ride the outbox thread: O(workers)
            # blocking writes under self.lock would stall the head.
            targets = [w for w in self.workers.values()
                       if w.alive and not w.remote and w.kind != "attach"]
            if targets:
                self._free_outbox.append(
                    (targets, protocol.FreeObject(oid, desc)))
                self._free_event.set()
        else:
            node = self.nodes.get(desc.node)
            if node is not None and node.alive:
                node.send(protocol.FreeObjectNode(oid))
        for nid in copies:
            if nid == desc.node:
                continue
            n2 = self.nodes.get(nid)
            if n2 is not None and n2.alive:
                n2.send(protocol.FreeObjectNode(oid))
        self.cv.notify_all()   # wake racing gets so they fail fast

    def _register_locked(self, object_id: str, desc: Descriptor,
                         origin: str):
        """Directory insert + origin + dead_pending + dependent-task wakeup
        (single implementation for put, task returns, and error stores).
        Caller holds the lock; returns True if tasks were unblocked."""
        self.directory[object_id] = desc
        self.obj_origin[object_id] = origin
        self.lost_objects.pop(object_id, None)
        self.reconstructing.discard(object_id)
        if object_id in self.dead_pending:
            self.dead_pending.discard(object_id)
            self._maybe_free_locked(object_id)
        waiting = self.obj_waiting_tasks.pop(object_id, ())
        for t in waiting:
            t.deps.discard(object_id)
            if not t.deps:
                # last dependency resolved: the task is now runnable
                self.task_events.queued(t.spec.task_id)
        for waiter in self._get_waiters.pop(object_id, ()):
            waiter["n"] -= 1
            if waiter["n"] <= 0:
                ev = waiter.get("ev")
                if ev is not None:
                    ev.set()
        self.cv.notify_all()
        return bool(waiting)

    def _free_fanout_loop(self):
        while not self._shutdown:
            self._free_event.wait(timeout=1.0)
            self._free_event.clear()
            while self._free_outbox:
                try:
                    targets, msg = self._free_outbox.popleft()
                except IndexError:
                    break
                for w in targets:
                    w.send(msg)     # safe_send: dead workers are a no-op

    def _poke_get_waiters(self, oid: str) -> None:
        """Flag blocked get()s that `oid` was freed/lost so they re-check
        and raise promptly instead of waiting for a 1s timeout tick (which
        registration wakeups can starve indefinitely). Caller holds lock."""
        for waiter in self._get_waiters.get(oid, ()):
            waiter["dirty"] = True
            ev = waiter.get("ev")
            if ev is not None:
                ev.set()
        self.cv.notify_all()

    def register_object(self, object_id: str, desc: Descriptor,
                        origin: str = "driver"):
        with self.lock:
            waiting = self._register_locked(object_id, desc, origin)
        if waiting:
            self._schedule()

    def put_value(self, value) -> str:
        oid = ids.new_object_id()
        desc = self.store.put(oid, value)
        # Owner fast path: a FRESH object id cannot have get/wait
        # waiters, dependent tasks, or lost/reconstructing/dead-pending
        # state (its ObjectRef does not exist until this returns), so a
        # bare directory insert replaces the full registration sweep —
        # no waiter walk, no notify_all herd, nothing to schedule.
        with self.lock:
            self.directory[oid] = desc
            self.obj_origin[oid] = "driver"
        return oid

    def get_locations(self, object_ids, timeout=None, localize=True) -> dict:
        """Block until every id has a descriptor. With `localize` (the
        default), remote descriptors are pulled into the head's store first
        so the returned locations are all readable here. Blocking rides a
        COUNTER waiter that registrations decrement — a get() over 100k
        refs costs O(ids), not O(ids) per wakeup."""
        deadline = None if timeout is None else time.monotonic() + timeout
        # Fast path: everything already registered (the common shape for
        # put-then-get and for draining completed results) — one dict
        # sweep under the lock, no waiter bookkeeping.
        with self.cv:
            directory = self.directory
            locs = {}
            for o in object_ids:
                d = directory.get(o)
                if d is None:
                    locs = None
                    break
                locs[o] = d
        if locs is not None:
            self.task_events.mark_got(object_ids)
            if localize:
                locs = self._localize(locs, deadline=deadline)
            return locs
        while True:
            with self.lock:
                missing = [o for o in object_ids
                           if o not in self.directory]
                freed = [o for o in missing if o in self.freed_refs]
                if freed:
                    raise ObjectFreedError(
                        f"object {freed[0]} was freed by reference "
                        "counting before this get()")
                lost = [o for o in missing if o in self.lost_objects]
                if lost:
                    raise ObjectLostError(
                        f"object {lost[0]} was lost: "
                        f"{self.lost_objects[lost[0]]}")
                if not missing:
                    locs = {o: self.directory[o] for o in object_ids}
                    break
                # Private wakeup channel: registrations decrement the
                # counter and set the event only when it reaches ZERO
                # (free/loss paths set `dirty` + the event), so the
                # per-completion notify herd never lands on a blocked
                # get — draining N results wakes this thread once, not
                # once per TaskDone. The 1s tick stays as the
                # belt-and-braces re-check path.
                waiter = {"n": len(missing), "ev": threading.Event()}
                for o in missing:
                    self._get_waiters.setdefault(o, []).append(waiter)
            ev = waiter["ev"]
            try:
                while True:
                    if deadline is not None:
                        rem = deadline - time.monotonic()
                        if rem <= 0:
                            raise GetTimeoutError(
                                f"get() timed out waiting for "
                                f"{missing[:3]}...")
                        notified = ev.wait(min(rem, 1.0))
                    else:
                        notified = ev.wait(1.0)
                    with self.lock:
                        if waiter["n"] <= 0 or waiter.get("dirty"):
                            break
                        if not notified and any(
                                o in self.freed_refs
                                or o in self.lost_objects
                                for o in missing
                                if o not in self.directory):
                            break
            finally:
                with self.lock:
                    for o in missing:
                        lst = self._get_waiters.get(o)
                        if lst is not None:
                            try:
                                lst.remove(waiter)
                            except ValueError:
                                pass
                            if not lst:
                                self._get_waiters.pop(o, None)
            # loop back: re-verify everything under the lock (an object
            # may have been freed between registration and this read —
            # the outer while handles it)
        self.task_events.mark_got(object_ids)   # close the `got` stage
        if localize:
            locs = self._localize(locs, deadline=deadline)
        return locs

    def wait_objects(self, object_ids, num_returns, timeout):
        deadline = None if timeout is None else time.monotonic() + timeout
        with self.cv:
            while True:
                ready = [o for o in object_ids if o in self.directory]
                freed = [o for o in object_ids
                         if o not in self.directory and o in self.freed_refs]
                if freed:
                    from ray_tpu.exceptions import ObjectFreedError
                    raise ObjectFreedError(
                        f"object {freed[0]} was freed by reference "
                        "counting before this wait()")
                if len(ready) >= num_returns:
                    break
                if deadline is not None:
                    rem = deadline - time.monotonic()
                    if rem <= 0:
                        break
                    self.cv.wait(min(rem, 1.0))
                else:
                    self.cv.wait(1.0)
            ready_set = set(ready[:max(num_returns, 0)] if len(ready) >
                            num_returns else ready)
            ready_list = [o for o in object_ids if o in ready_set]
            not_ready = [o for o in object_ids if o not in ready_set]
            return ready_list, not_ready

    def _serve_get(self, w, msg: protocol.GetRequest):
        # Release the blocked worker's resources so nested tasks can run
        # (the reference releases the worker's lease while it blocks in get).
        with self.lock:
            if w.current is not None and not w.released:
                held = dict(w.current.spec.resources)
                if held:
                    _add(self.available, held)
                    w.released = held
        try:
            # Daemons localize to their own store themselves; local workers
            # need descriptors readable in the head's store.
            locs = self.get_locations(msg.object_ids, msg.timeout,
                                      localize=(w.kind != "node"))
            if w.remote:
                # cross-machine client: no shared memory with this host, so
                # ship the serialized envelopes inside the reply itself
                locs = {oid: (d if d.inline is not None else replace(
                    d, inline=self.store.raw_bytes(d), arena=False,
                    path=None)) for oid, d in locs.items()}
            reply = protocol.GetReply(msg.req_id, locs)
        except GetTimeoutError:
            reply = protocol.GetReply(msg.req_id, {}, timed_out=True)
        except (ObjectFreedError, ObjectLostError, OSError) as e:
            # OSError: a path-backed object freed/moved between the
            # directory read and raw_bytes for a remote client — must
            # still answer or the client's get() hangs forever
            name = type(e).__name__ if not isinstance(e, OSError) \
                else "ObjectLostError"
            reply = protocol.GetReply(msg.req_id, {},
                                      error=f"{name}: {e}")
        with self.lock:
            if w.released:
                _sub(self.available, w.released)  # may dip below zero briefly
                w.released = {}
        w.send(reply)
        self._schedule()

    def _serve_wait(self, w, msg: protocol.WaitRequest):
        ready, not_ready = self.wait_objects(
            msg.object_ids, msg.num_returns, msg.timeout)
        w.send(protocol.WaitReply(msg.req_id, ready, not_ready))

    # ------------------------------------------------------------------
    # cross-node object data plane (object_manager.h:117 equivalent)
    # ------------------------------------------------------------------

    def _localize(self, locs: dict, deadline: float | None = None) -> dict:
        """Return locations readable in the head's store, pulling remote
        primaries into a head-local cached copy as needed. `deadline`
        (monotonic) bounds the whole pass: a caller's get(timeout=) covers
        the transfer, not just the directory wait."""
        out = dict(locs)
        for oid, desc in locs.items():
            if desc.inline is not None or desc.node is None:
                continue
            if deadline is not None and time.monotonic() >= deadline:
                raise GetTimeoutError(
                    f"get() timed out pulling {oid} to the head")
            out[oid] = self._pull_to_head(oid, desc, deadline)
        return out

    def _pull_to_head(self, oid: str, desc: Descriptor,
                      deadline: float | None = None) -> Descriptor:
        def budget(default: float) -> float:
            if deadline is None:
                return default
            return max(min(default, deadline - time.monotonic()), 0.01)

        with self.cv:
            while True:
                lc = self.local_copies.get(oid)
                if lc is not None:
                    return lc
                if oid not in self._head_pulling:
                    self._head_pulling.add(oid)
                    break
                if deadline is not None and time.monotonic() >= deadline:
                    raise GetTimeoutError(
                        f"get() timed out awaiting pull of {oid}")
                self.cv.wait(0.2)
        try:
            for _attempt in range(constants.PULL_RETRY_ATTEMPTS):
                if deadline is not None and time.monotonic() >= deadline:
                    raise GetTimeoutError(
                        f"get() timed out pulling {oid}")
                try:
                    with self.lock:
                        node = self.nodes.get(desc.node)
                    if node is None or not node.alive:
                        raise ObjectLostError(
                            f"object {oid} lives on dead node {desc.node}")
                    seal_box = {}

                    def alloc(total: int, _oid=oid):
                        buf, seal = self.store.create_serialized(
                            _oid, total)
                        if buf is not None:
                            seal_box["seal"] = seal
                        return buf

                    # failure-path release belongs to the PullClient (a
                    # late frame may still be landing in the buffer)
                    payload, in_arena = self._pull_bytes(
                        node, oid, alloc=alloc,
                        cleanup=lambda _oid=oid:
                            self.store.abort_create(_oid),
                        timeout=budget(constants.PULL_TIMEOUT_S))
                    if in_arena:
                        local = seal_box["seal"]()
                    else:
                        local = self.store.put_serialized(oid, payload)
                    with self.lock:
                        # freed while we pulled? drop the stray copy now
                        if oid in self.freed_refs:
                            self.store.delete(local)
                            raise ObjectFreedError(
                                f"object {oid} was freed during pull")
                        self.local_copies[oid] = local
                    return local
                except ObjectLostError:
                    if (deadline is not None
                            and time.monotonic() >= deadline):
                        with self.lock:
                            n = self.nodes.get(desc.node)
                            source_alive = n is not None and n.alive
                        if source_alive:
                            # the caller's budget expired mid-transfer of
                            # a healthy object: that's a timeout, not loss
                            raise GetTimeoutError(
                                f"get() timed out pulling {oid}")
                    # the source died mid-pull: wait for a promoted copy
                    # or a reconstructed re-registration, then retry
                    desc = self._await_fresh_desc(
                        oid, desc,
                        timeout=budget(constants.OBJECT_REPLACEMENT_WAIT_S))
                    if desc.node is None or desc.inline is not None:
                        return desc     # now head-local (or error value)
            raise ObjectLostError(f"pull of {oid} kept failing")
        finally:
            with self.cv:
                self._head_pulling.discard(oid)
                self.cv.notify_all()

    def _await_fresh_desc(self, oid: str, stale: Descriptor,
                          timeout: float = 60.0) -> Descriptor:
        """Block until the directory carries a different descriptor for
        `oid` (promotion to a surviving copy, or lineage reconstruction);
        raise ObjectLostError if it is terminally lost."""
        deadline = time.monotonic() + timeout
        with self.cv:
            while True:
                if oid in self.lost_objects:
                    raise ObjectLostError(
                        f"object {oid} lost: {self.lost_objects[oid]}")
                d = self.directory.get(oid)
                if d is not None and d != stale:
                    return d
                rem = deadline - time.monotonic()
                if rem <= 0:
                    raise ObjectLostError(
                        f"object {oid} unavailable: source died and no "
                        "replacement appeared")
                self.cv.wait(min(rem, 0.5))

    def _pull_bytes(self, node: _RemoteNode, oid: str,
                    timeout: float | None = None, alloc=None,
                    cleanup=None):
        return self._pull_client.pull_into(
            node.send, oid, timeout=timeout, alloc=alloc, cleanup=cleanup,
            abort_check=lambda: None if node.alive
            else f"hit dead node {node.node_id}")

    def _serve_pull(self, node: _RemoteNode, msg: protocol.PullRequest):
        """A daemon asked for an object's bytes held by the head."""
        from ray_tpu._private.pull_plane import serve_pull
        with self.lock:
            desc = self.directory.get(msg.object_id)
            if desc is not None and desc.node is not None:
                desc = self.local_copies.get(msg.object_id)
        if desc is None:
            serve_pull((node.conn, node.send_lock), msg, None)
            return
        try:
            payload = self.store.raw_view(desc)
        except (ObjectLostError, OSError) as e:
            payload = e
        serve_pull((node.conn, node.send_lock), msg, payload)

    # ------------------------------------------------------------------
    # leased-task lifecycle + node failure (raylet-side events)
    # ------------------------------------------------------------------

    def _on_node_task_done(self, node: _RemoteNode, msg: protocol.NodeTaskDone):
        if msg.spans:
            # merge the remote host's drained spans (relayed by its daemon)
            from ray_tpu.util import tracing as _tracing
            _tracing.ingest(msg.spans)
        with self.lock:
            t = node.inflight.pop(msg.task_id, None)
            if t is None:
                logger.warning("NodeTaskDone for unknown task %s",
                               msg.task_id)
                return
            spec = t.spec
            a = self.actors.get(spec.actor_id) if spec.actor_id else None
            if (msg.error and t.retry_exceptions and t.retries_left > 0
                    and not spec.actor_creation):
                t.retries_left -= 1
                self.task_events.requeued(spec)
                if a is None:
                    self._release_task_resources(t)
                    t.node = None
                    self.pending.append(t)
                else:
                    if t in a.inflight:
                        a.inflight.remove(t)
                    a.queue.insert(0, t)
            else:
                self.task_events.finished(
                    msg.task_id,
                    error="application_error" if msg.error else None,
                    exec_start_ts=msg.exec_start_ts,
                    exec_end_ts=msg.exec_end_ts,
                    return_ids=spec.return_ids)
                self._release_task_args(spec)
                for oid, desc in zip(spec.return_ids, msg.return_descs):
                    self._register_locked(oid, desc,
                                          origin="node:" + node.node_id)
                self.cv.notify_all()
                if a is not None:
                    if t in a.inflight:
                        a.inflight.remove(t)
                    if spec.actor_creation:
                        if msg.error:
                            a.dead = True
                            a.death_cause = "constructor raised"
                            self._release_actor_resources(a)
                            failed, a.queue = a.queue, []
                            for qt in failed:
                                self._store_error(
                                    qt.spec.return_ids,
                                    ActorDiedError(
                                        f"actor {a.actor_id} constructor "
                                        "raised"),
                                    spec=qt.spec)
                        else:
                            a.ready = True
                else:
                    self._release_task_resources(t)
                    t.node = None
        self._schedule()

    def _on_node_task_failed(self, node: _RemoteNode,
                             msg: protocol.NodeTaskFailed):
        """A leased task's worker died on the node (actor-worker deaths
        arrive as NodeActorDied instead)."""
        with self.lock:
            t = node.inflight.pop(msg.task_id, None)
            if t is None:
                return
            spec = t.spec
            if spec.actor_creation or spec.actor_id is not None:
                # actor path (resources incl.) is driven by NodeActorDied
                retry = False
                t = None
            elif (msg.error.startswith("dependency pull failed")
                  and t.dep_failures < 10):
                # not the task's fault: requeue WITHOUT consuming a retry,
                # re-blocking on args whose directory entry is gone (they
                # may be reconstructing; if terminally lost, the stored
                # ObjectLostError value fails the task through normal dep
                # poisoning on the next dispatch). dep_failures caps a
                # persistent pull failure with an intact directory entry —
                # otherwise this would hot-loop forever.
                t.dep_failures += 1
                self._release_task_resources(t)
                t.node = None
                for kind, v in (list(spec.args)
                                + list(spec.kwargs.values())):
                    if kind == "ref" and v not in self.directory:
                        t.deps.add(v)
                        self.obj_waiting_tasks.setdefault(v, []).append(t)
                self.pending.append(t)
                self.task_events.requeued(spec)
                retry = True
            else:
                self._release_task_resources(t)
                t.node = None
                if t.retries_left > 0:
                    t.retries_left -= 1
                    self.pending.append(t)
                    self.task_events.requeued(spec)
                    retry = True
                else:
                    retry = False
        if t is not None and not retry:
            self._store_error(
                t.spec.return_ids,
                WorkerCrashedError(
                    f"worker died on {node.node_id} while running "
                    f"{t.spec.function_desc}: {msg.error}"),
                spec=t.spec)
        self._schedule()

    def _on_node_actor_died(self, node: _RemoteNode,
                            msg: protocol.NodeActorDied):
        with self.lock:
            a = self.actors.get(msg.actor_id)
            if a is None:
                return
            if msg.cause and not a.death_cause:
                a.death_cause = msg.cause
            for tid in [tid for tid, t in node.inflight.items()
                        if t.spec.actor_id == msg.actor_id]:
                node.inflight.pop(tid)
        self._on_actor_death(a)
        with self.lock:
            rid = a.creation_spec.return_ids[0]
            # an actor that died terminally WITHOUT ever becoming ready
            # must still resolve its creation ref (wait_for_actor_ready
            # would otherwise hang; the local path's _fail_actor does this)
            stranded = (a.dead and rid not in self.directory
                        and rid not in self.freed_refs)
        if stranded:
            self._store_error(
                [rid], ActorDiedError(
                    f"actor {a.actor_id} died: "
                    f"{a.death_cause or msg.cause or 'unknown'}"))

    def _on_node_worker_blocked(self, node: _RemoteNode,
                                msg: protocol.NodeWorkerBlocked):
        with self.lock:
            t = node.inflight.get(msg.task_id)
            if t is None:
                return
            if t.spec.placement_group_id:
                # PG tasks debited a bundle, not node.available; releasing
                # into the node pool would leak the bundle slot on death
                return
            held = dict(t.spec.resources)
            if msg.blocked and not t.node_released:
                t.node_released = True
                if held:
                    _add(node.available, held)
            elif not msg.blocked and t.node_released:
                t.node_released = False
                if held:
                    _sub(node.available, held)
        self._schedule()

    def _on_node_death(self, node: _RemoteNode):
        to_fail = []
        dead_actors = []
        lost_oids = []
        rebuild_oids = []
        with self.lock:
            if not node.alive:
                return
            if self.nodes.get(node.node_id) is not node:
                # a newer registration has replaced this object; only the
                # current one may tear down node state
                node.alive = False
                return
            node.alive = False
            logger.warning("node %s died", node.node_id)
            inflight, node.inflight = dict(node.inflight), {}
            dead_actors = [a for a in self.actors.values()
                           if a.node == node.node_id and not a.dead]
            dead_actor_ids = {a.actor_id for a in dead_actors}
            for t in inflight.values():
                if t.spec.actor_creation or t.spec.actor_id is not None:
                    continue    # handled via the actor restart path
                self._release_task_resources(t)
                t.node = None
                if t.retries_left > 0:
                    t.retries_left -= 1
                    self.pending.append(t)
                    self.task_events.requeued(t.spec)
                else:
                    to_fail.append(t)
            # drop ref-holders owned by the dead node's workers wholesale:
            # their ids are unknown here, but every holder whose holds came
            # through this node died with it — conservative: leave them;
            # the daemon reported NodeWorkerGone for orderly deaths, and
            # leaked holds from a killed node only delay frees.
            # Objects whose primary copy lived on the dead node: promote a
            # surviving copy (head cache first, then another node), else
            # mark lost (object_recovery_manager.h:41 recovery-from-copy).
            for oid, desc in list(self.directory.items()):
                if desc.node != node.node_id:
                    continue
                lc = self.local_copies.get(oid)
                if lc is not None:
                    self.directory[oid] = lc
                    self.obj_origin[oid] = "driver"
                    continue
                survivors = [
                    (nid, d) for nid, d in self.copy_nodes.get(
                        oid, {}).items()
                    if nid != node.node_id and d is not None
                    and (n2 := self.nodes.get(nid)) is not None and n2.alive]
                if survivors:
                    # promote the survivor's own descriptor — its backing
                    # (arena vs file) can differ from the dead primary's
                    nid, d = survivors[0]
                    self.directory[oid] = d
                    self.obj_origin[oid] = "node:" + nid
                    continue
                del self.directory[oid]
                self.obj_origin.pop(oid, None)
                if (oid in self.lineage
                        and self.reconstructions.get(oid, 0)
                        < constants.MAX_OBJECT_RECONSTRUCTIONS):
                    # rebuildable: leave a directory hole (readers keep
                    # waiting) and resubmit the producing task below
                    rebuild_oids.append(oid)
                else:
                    self.lost_objects[oid] = f"node {node.node_id} died"
                    self._poke_get_waiters(oid)
                    lost_oids.append(oid)
            for oid, copies in list(self.copy_nodes.items()):
                copies.pop(node.node_id, None)
            if rebuild_oids:
                # tasks whose deps were already satisfied would otherwise
                # dispatch into the directory hole and fail; re-block them
                # until the reconstructed object re-registers
                rb = set(rebuild_oids)

                def _reblock(t):
                    for kind, v in (list(t.spec.args)
                                    + list(t.spec.kwargs.values())):
                        if kind == "ref" and v in rb and v not in t.deps:
                            t.deps.add(v)
                            self.obj_waiting_tasks.setdefault(
                                v, []).append(t)
                for t in self.pending:
                    _reblock(t)
                for a2 in self.actors.values():
                    for t in a2.queue:
                        _reblock(t)
            # placement-group bundles reserved on the node can no longer
            # host anything (the reference reschedules bundles; v1 marks
            # them unavailable so dispatch skips them)
            for pg in self.placement_groups.values():
                for i, nid in enumerate(pg.bundle_nodes):
                    if nid == node.node_id:
                        pg.available[i] = {}
            self.cv.notify_all()    # wake gets blocked on now-lost objects
        self._pull_client.abort_all()    # wake pulls targeting the node
        # Every surviving reference to a lost object now resolves to an
        # ObjectLostError *value*: gets raise it, and tasks that consume
        # the object fail with it through the normal dep-poisoning path —
        # no pending task can reach a directory hole and wedge dispatch.
        # (Lineage reconstruction will replace this with resubmission.)
        for oid in lost_oids:
            self._store_error(
                [oid],
                ObjectLostError(
                    f"object {oid} lost: node {node.node_id} died and no "
                    "other copy exists"))
        for oid in rebuild_oids:
            self._reconstruct(oid)
        for a in dead_actors:
            self._on_actor_death(a)
        for t in to_fail:
            self._store_error(
                t.spec.return_ids,
                WorkerCrashedError(
                    f"node {node.node_id} died while running "
                    f"{t.spec.function_desc}"),
                spec=t.spec)
        self._schedule()

    # ------------------------------------------------------------------
    # object spilling (LocalObjectManager equivalent,
    # local_object_manager.h:110): above the arena high-water mark, sealed
    # head-primary objects move to disk; their directory descriptor flips
    # to file-backed, and the arena block is released (origin worker drops
    # its owner pin via FreeObject).
    # ------------------------------------------------------------------

    def _spill_loop(self):
        while not self._shutdown:
            time.sleep(constants.SPILL_PASS_INTERVAL_S)
            try:
                self._maybe_spill()
            except Exception:
                logger.exception("spill pass failed")

    def _maybe_spill(self):
        from ray_tpu._private.spill import run_spill_pass

        def candidates():
            with self.lock:
                return [(oid, desc) for oid, desc in self.directory.items()
                        if desc.node is None and desc.arena]

        def try_swap(oid, old, new):
            with self.lock:
                if self.directory.get(oid) != old:
                    return False
                self.directory[oid] = new
                origin = self.obj_origin.get(oid, "driver")
                self.obj_origin[oid] = "driver"
                if origin == "driver" or origin.startswith("node:"):
                    return None
                return self.workers.get(origin)

        run_spill_pass(self.store, candidates, try_swap)

    def _reconstruct(self, oid: str) -> bool:
        """Rebuild a lost task-produced object by re-executing its
        producing task (lineage resubmission, object_recovery_manager.h:41
        + TaskResubmissionInterface, task_manager.h:173). Walks the lost
        lineage chain iteratively (a long x = f.remote(x) chain must not
        overflow the Python stack). Returns False if the object cannot be
        rebuilt (an ObjectLostError value is stored instead)."""
        plan: list = []         # clones, discovery order (parents first)
        failed: list = []       # (oid, cause)
        stack = [oid]
        seen: set = set()
        while stack:
            cur = stack.pop()
            if cur in seen:
                continue
            seen.add(cur)
            with self.lock:
                if cur in self.directory or cur in self.reconstructing:
                    continue    # present, or a resubmission is in flight
                spec = self.lineage.get(cur)
                n = self.reconstructions.get(cur, 0)
                if (spec is None
                        or n >= constants.MAX_OBJECT_RECONSTRUCTIONS):
                    failed.append((cur, "no lineage" if spec is None
                                   else f"exceeded {n} reconstructions"))
                    self.lost_objects[cur] = failed[-1][1]
                    self._poke_get_waiters(cur)
                    continue
                # one resubmit rebuilds ALL the task's returns
                for rid in spec.return_ids:
                    self.reconstructions[rid] = max(
                        self.reconstructions.get(rid, 0), n + 1)
                    self.reconstructing.add(rid)
                # fresh task_id so event records and the exactly-once
                # arg-release guard treat this as a new execution
                clone = protocol.TaskSpec(
                    **{**spec.__dict__, "task_id": ids.new_task_id()})
                missing = [
                    v for kind, v in (list(clone.args)
                                      + list(clone.kwargs.values()))
                    if kind == "ref" and v not in self.directory]
            plan.append(clone)
            stack.extend(missing)
        for lost_oid, cause in failed:
            self._store_error(
                [lost_oid],
                ObjectLostError(f"object {lost_oid} lost: {cause}"))
        for clone in reversed(plan):    # inputs resubmit first
            logger.warning("reconstructing %s by re-running %s",
                           clone.return_ids[0], clone.function_desc)
            self.submit(clone)
        return bool(plan) and not any(f[0] == oid for f in failed)

    # ------------------------------------------------------------------
    # node management (add/kill; the Cluster fixture + autoscaler seam)
    # ------------------------------------------------------------------

    def add_node(self, resources: dict | None = None,
                 num_tpus: int = 0) -> str:
        """Spawn a HostDaemon subprocess for a new (possibly fake-resource)
        node and wait for it to register — the one-host multi-daemon
        fixture of the reference (python/ray/cluster_utils.py:99)."""
        import json as _json
        from ray_tpu._private import spawn as _spawn
        node_id = ids.new_node_id()
        res = {str(k): float(v) for k, v in (resources or {}).items()}
        res.setdefault("CPU", 1.0)
        if num_tpus:
            res["TPU"] = float(num_tpus)
        env = _spawn.propagate_pythonpath(dict(os.environ))
        env["RAY_TPU_AUTHKEY"] = self._authkey.hex()
        head_addr = self.tcp_address or self._address
        if self.tcp_address is not None:
            # same-host TCP tier: keep the node dir under the session dir
            # so shutdown/GC sweeps it like the UDS tier
            env["RAY_TPU_NODE_DIR"] = os.path.join(
                self.session_dir, "nodes", node_id)
        cmd = [sys.executable, "-m", "ray_tpu._private.daemon",
               head_addr, node_id, _json.dumps(res), str(int(num_tpus))]
        logf = _spawn.worker_log_file(
            os.path.join(self.session_dir, "logs"), "daemon-" + node_id[5:])
        try:
            proc = subprocess.Popen(
                cmd, env=env, stdin=subprocess.DEVNULL,
                stdout=logf or None,
                stderr=subprocess.STDOUT if logf else None)
        finally:
            if logf is not None:
                logf.close()
        deadline = time.monotonic() + constants.WORKER_REGISTER_TIMEOUT_S
        with self.cv:
            while node_id not in self.nodes:
                if self._shutdown or time.monotonic() > deadline \
                        or proc.poll() is not None:
                    try:
                        proc.terminate()
                    except OSError:
                        pass
                    raise RuntimeError(
                        f"node daemon {node_id} failed to register")
                self.cv.wait(0.2)
            self.nodes[node_id].proc = proc
        return node_id

    def kill_node(self, node_id: str, force: bool = True) -> bool:
        with self.lock:
            node = self.nodes.get(node_id)
            if node is None:
                return False
            proc = node.proc
        if force:
            if proc is not None:
                try:
                    proc.kill()     # SIGKILL: chaos-test path; EOF on the
                except OSError:     # channel triggers _on_node_death
                    pass
            else:
                self._on_node_death(node)
        else:
            node.send(protocol.KillNode())
        return True

    # ------------------------------------------------------------------
    # task submission + scheduling
    # ------------------------------------------------------------------

    def submit(self, spec: protocol.TaskSpec, submitter=None):
        t = _TaskState(spec=spec, submitter=submitter,
                       retries_left=spec.max_retries,
                       retry_exceptions=spec.retry_exceptions)
        with self.lock:
            ref_args = [v for kind, v in spec.args if kind == "ref"]
            ref_args += [v for kind, v in spec.kwargs.values()
                         if kind == "ref"]
            for v in ref_args:
                if v not in self.directory and v in self.freed_refs:
                    from ray_tpu.exceptions import ObjectFreedError
                    self._store_error(
                        spec.return_ids,
                        ObjectFreedError(
                            f"task argument {v} was already freed by "
                            "reference counting"),
                        spec=spec)
                    return
            for v in ref_args:
                if v not in self.directory:
                    t.deps.add(v)
                    self.obj_waiting_tasks.setdefault(v, []).append(t)
            self.task_events.submitted(spec, bool(t.deps))
            self._pin_task_args_locked(spec)
            if not spec.actor_creation and spec.actor_id is None:
                # lineage: remember how to rebuild these returns (actor
                # method outputs are not reconstructable, as in the
                # reference)
                size = _lineage_size(spec)
                for oid in spec.return_ids:
                    old = self.lineage.pop(oid, None)
                    if old is not None:
                        # reconstruction resubmits overwrite their entry;
                        # without the subtract, phantom bytes accumulate
                        # until eviction disables lineage entirely
                        self._lineage_bytes -= _lineage_size(old)
                    self.lineage[oid] = spec
                    self._lineage_bytes += size
                while self.lineage and (
                        len(self.lineage) > constants.MAX_LINEAGE_ENTRIES
                        or self._lineage_bytes
                        > constants.MAX_LINEAGE_BYTES):
                    _old_oid, old_spec = self.lineage.popitem(last=False)
                    self._lineage_bytes -= _lineage_size(old_spec)
            submitter_id = (submitter if isinstance(submitter, str)
                            else getattr(submitter, "worker_id", None))
            if submitter_id is not None:
                # worker-submitted task: the submitter holds the return
                # refs it just minted, but its batched hold report may
                # lag — record implicit holds (see PutRequest handler)
                for oid in spec.return_ids:
                    self.ref_holders.setdefault(oid, set()).add(
                        submitter_id)
            if spec.actor_creation:
                opts = spec.actor_options or {}
                _name = opts.get("name")
                if _name and _name in self.named_actors:
                    raise ValueError(f"actor name {_name!r} already taken")
                a = _ActorState(
                    actor_id=spec.actor_id, creation_spec=spec,
                    max_concurrency=opts.get("max_concurrency", 1),
                    max_restarts=opts.get("max_restarts", 0),
                    max_task_retries=opts.get("max_task_retries", 0),
                    name=_name,
                    resources=dict(spec.resources),
                    method_meta=opts.get("method_meta", {}),
                )
                self.actors[spec.actor_id] = a
                if a.name:
                    self.named_actors[a.name] = spec.actor_id
                if t.deps:
                    self.pending.append(t)
            elif spec.actor_id is not None:
                a = self.actors.get(spec.actor_id)
                if a is None or a.dead:
                    cause = a.death_cause if a else "unknown actor"
                    self._store_error(
                        spec.return_ids,
                        ActorDiedError(f"actor {spec.actor_id} is dead: "
                                       f"{cause}"),
                        spec=spec)
                    return
                a.queue.append(t)
            else:
                if t.deps:
                    self.pending.append(t)
            had_deps = bool(t.deps)
        if not had_deps:
            self._submit_fastpath(t, spec)

    def _submit_fastpath(self, t: _TaskState, spec) -> None:
        """Dispatch attempt scoped to the JUST-submitted work instead of
        rescanning the whole backlog (which turns a deep queue of
        unschedulable tasks into O(n^2) submission — the reference's
        submit path also only queue-and-schedules the new task,
        cluster_task_manager.cc:44 QueueAndScheduleTask). Only called
        for tasks with no deps at submit time (the task is NOT in
        self.pending here, so no racing pass can double-dispatch it);
        full scheduler passes drain the backlog on capacity events."""
        if spec.actor_id is not None and not spec.actor_creation:
            # actor method: pump just that actor's queue
            to_send = []
            with self.lock:
                a = self.actors.get(spec.actor_id)
                if a is not None:
                    self._pump_actor(a, to_send)
            for w, msg in to_send:
                w.send(msg)
            return
        with self.lock:
            if self._shutdown or t.cancelled:
                return
            if not spec.actor_creation and \
                    len(self.pending) > constants.SUBMIT_INLINE_BACKLOG:
                # Deep backlog: the inline dispatch attempt is almost
                # always futile (older tasks are already waiting on the
                # same capacity), and every completion pulls from the
                # backlog directly (_dispatch_freed_fastpath). Skipping
                # the scan makes saturated submission a pure enqueue —
                # the reference's submit path is queue-and-schedule for
                # the same reason (cluster_task_manager.cc:44).
                self.pending.append(t)
                # pending may be deep with dep-BLOCKED tasks while
                # capacity sits idle: the scheduler thread must still
                # look at this task now, not at its 1 s safety tick.
                # But ONLY when the task could actually go somewhere —
                # during a submit storm with the local pool saturated
                # (the common saturated-bench shape) an unconditional
                # wake keeps the scheduler thread scanning the backlog
                # full-time, stealing the core from the submitters and
                # executors. If the shape doesn't fit the local free
                # pool and there are no remote nodes, no pass can
                # dispatch or spawn for it now; the capacity-freeing
                # event that changes that fires its own _schedule().
                if self.nodes or _fits(self.available, spec.resources):
                    self._sched_event.set()
                return
            to_send = []
            if spec.actor_creation:
                disp = self._try_dispatch_actor_creation(t, to_send)
            else:
                disp = self._try_dispatch_generic(t, to_send)
            if disp is not True:
                # False/"localizing": nothing to rescan — the backlog is
                # unchanged. None: resources fit but no idle worker —
                # the scheduler pass owns the spawn logic, wake it.
                self.pending.append(t)
        for w, msg in to_send:
            w.send(msg)
        if disp is None:
            self._schedule()

    def _schedule(self):
        """Signal the scheduler thread: dispatch work soon. Call sites
        fire this after any capacity- or queue-changing event; the
        dedicated thread coalesces bursts of signals into bounded
        passes (reference: the raylet's ScheduleAndDispatchTasks loop
        runs on its own io_service the same way,
        cluster_task_manager.cc:130)."""
        self._sched_event.set()

    def _scheduler_loop(self):
        """Run window-bounded passes until the backlog stops yielding
        dispatches. The rotation in _schedule_pass walks a different
        backlog segment each time, so continuation passes guarantee
        every queued task is (re)examined without any single pass
        paying O(backlog)."""
        window = constants.SCHEDULER_DISPATCH_WINDOW
        while not self._shutdown:
            self._sched_event.wait(timeout=1.0)   # 1s tick = safety net
            if self._shutdown:
                return
            self._sched_event.clear()
            futile = 0
            while not self._shutdown:
                try:
                    dispatched, tripped = self._schedule_pass()
                except Exception:
                    logger.exception("scheduler pass failed")
                    break
                if self._sched_event.is_set():
                    self._sched_event.clear()
                    futile = 0
                    continue        # new capacity arrived mid-pass
                futile = 0 if dispatched else futile + 1
                if not tripped:
                    break           # whole backlog examined this pass
                with self.lock:
                    n = len(self.pending)
                if futile * window >= n:
                    break           # one full rotation, no progress
            # wait for the next signal

    def _schedule_pass(self):
        """One bounded dispatch pass. -> (n_dispatched, window_tripped)."""
        to_send = []   # (worker, message) executed outside the lock
        retired = []   # over-cap idle workers killed outside the lock
        n_dispatched = 0
        tripped = False
        with self.lock:
            if self._shutdown:
                return 0, False
            # --- generic + actor-creation tasks ---
            still = []
            want_spawn = 0
            # `sim` tracks how much concurrency the resource pool could
            # actually absorb, so we never spawn more workers than could
            # run at once (reference: prestart-on-backlog is similarly
            # resource-capped, node_manager.cc:1885).
            sim = dict(self.available)
            # Dispatch WINDOW: stop examining the queue after this many
            # consecutive tasks fail to dispatch (cluster saturated).
            # Without it every submit's schedule pass rescans the whole
            # backlog and a 100k-task queue turns submission O(n^2) —
            # the reference bounds its dispatch loop the same way
            # (cluster_task_manager dispatch caps per iteration).
            window = constants.SCHEDULER_DISPATCH_WINDOW
            misses = 0
            # Per-pass memo: once a PLAIN task (no affinity/PG) with
            # resource shape R failed to dispatch, every later plain-R
            # task in the same pass fails identically — skip the
            # placement scan (the backlog is usually many copies of one
            # shape, so this turns the rescan O(shapes), not O(tasks)).
            # The deque scan is IN PLACE: examined-and-kept tasks go
            # back to the front, the untouched tail never moves, so a
            # pass costs O(window), not O(backlog).
            unfit: dict = {}
            examined = 0
            n0 = len(self.pending)
            while self.pending and examined < n0 and misses < window:
                t = self.pending.popleft()
                examined += 1
                if t.cancelled:
                    continue
                if t.deps:
                    still.append(t)
                    continue
                if t.spec.actor_creation:
                    disp = self._try_dispatch_actor_creation(t, to_send)
                else:
                    plain = (not t.spec.placement_group_id
                             and not t.spec.scheduling_strategy)
                    sig = (frozenset(t.spec.resources.items())
                           if plain else None)
                    if sig is not None and sig in unfit:
                        disp = unfit[sig]
                    else:
                        disp = self._try_dispatch_generic(t, to_send)
                        # memoize only SHAPE-level outcomes; "localizing"
                        # is task-specific and must not poison the shape
                        if sig is not None and (disp is False
                                                or disp is None):
                            unfit[sig] = disp
                    if disp is True:
                        _sub(sim, t.spec.resources)
                    elif disp is None:   # resources fit but no idle worker
                        if _fits(sim, t.spec.resources):
                            _sub(sim, t.spec.resources)
                            want_spawn += 1
                        still.append(t)
                        misses += 1
                        continue
                if disp is True:
                    n_dispatched += 1
                else:
                    still.append(t)
                    misses += 1
            tripped = misses >= window and bool(self.pending)
            if tripped:
                # window tripped with tasks left unexamined: ROTATE the
                # examined-but-kept prefix to the back so successive
                # passes walk different segments of the backlog (no
                # starvation for shapes stuck behind other shapes)
                self.pending.extend(still)
            else:
                self.pending.extendleft(reversed(still))
            # --- actor method calls ---
            for a in self.actors.values():
                self._pump_actor(a, to_send)
            # --- worker pool scale-up ---
            # `_spawning` counts workers from Popen until registration (or
            # failure); without it every schedule pass would re-spawn for the
            # same pending tasks while the first worker is still importing.
            # Workers blocked in get() (w.released) gave their lease back,
            # so they don't count against the cap either: a nested/reduce
            # task blocked on an upstream result must never pin the last
            # pool slot, or the producer can never run (the reference
            # spawns replacement workers past the soft cap for exactly
            # this reason, worker_pool.cc's blocked-worker accounting).
            n_generic = sum(1 for w in self.workers.values()
                            if w.kind == "generic" and w.alive
                            and not w.released)
            can = constants.MAX_WORKERS_CAP - n_generic - self._spawning
            for _ in range(max(0, min(want_spawn - self._spawning, can))):
                self._spawning += 1
                threading.Thread(target=self._spawn_generic_worker,
                                 daemon=True).start()
            # --- worker pool scale-down ---
            # Inverse of the blocked-worker carve-out above: once the
            # blocked workers resume, the pool can sit over the cap.
            # Retire idle surplus (never a busy or blocked worker, and
            # only with an empty backlog) so one storm of nested gets
            # doesn't leave extra worker processes around for the rest
            # of the session.
            if not self.pending:
                alive_generic = [w for w in self.workers.values()
                                 if w.kind == "generic" and w.alive]
                excess = len(alive_generic) - constants.MAX_WORKERS_CAP
                for w in alive_generic:
                    if excess <= 0:
                        break
                    if w.idle and not w.released and w.current is None:
                        w.idle = False
                        w.alive = False
                        self.workers.pop(w.worker_id, None)
                        retired.append(w)
                        excess -= 1
        for w in retired:
            w.send(protocol.KillWorker())
        for w, msg in to_send:
            if not w.send(msg):
                if isinstance(w, _RemoteNode):
                    self._on_node_death(w)
                else:
                    self._on_worker_death(w)
        return n_dispatched, tripped

    def _pick_node(self, spec) -> str | None:
        """Cluster scheduling policy (counterpart of
        ClusterResourceScheduler::GetBestSchedulableNode + the hybrid
        pack-then-spread policy, hybrid_scheduling_policy.h:50): hard/soft
        node affinity first, then SPREAD round-robin when requested, then
        locality (most argument bytes), then pack head-first. Returns
        "head", a node id, or None (nothing fits now). Caller holds lock."""
        req = spec.resources
        n_tpu = int(req.get("TPU", 0))

        def head_fits():
            return (_fits(self.available, req)
                    and len(self.free_tpu_chips) >= n_tpu)

        def node_fits(node):
            return (node.alive and _fits(node.available, req)
                    and len(node.free_tpu_chips) >= n_tpu)

        strategy = spec.scheduling_strategy
        if isinstance(strategy, dict) and strategy.get("node_id"):
            nid = strategy["node_id"]
            if nid in ("head", self.node_id):
                if head_fits():
                    return "head"
            else:
                node = self.nodes.get(nid)
                if node is not None and node_fits(node):
                    return nid
                if not strategy.get("soft", False) and (
                        node is None or not node.alive):
                    # hard affinity to a node that can never come back:
                    # fail fast instead of pending forever
                    return "__infeasible__"
            if not strategy.get("soft", False):
                return None     # hard affinity: wait for the target
        candidates = []
        if head_fits():
            candidates.append("head")
        candidates += [nid for nid, node in self.nodes.items()
                       if node_fits(node)]
        if not candidates:
            if self._tpu_request_unmeetable(n_tpu):
                return "__infeasible__"
            return None
        if strategy == "SPREAD":
            self._spread_rr += 1
            return candidates[self._spread_rr % len(candidates)]
        arg_bytes: dict[str, int] = {}
        for kind, v in list(spec.args) + list(spec.kwargs.values()):
            if kind != "ref":
                continue
            d = self.directory.get(v)
            if d is None or d.inline is not None:
                continue
            where = d.node or "head"
            arg_bytes[where] = arg_bytes.get(where, 0) + d.size
        if arg_bytes:
            best = max(candidates, key=lambda c: arg_bytes.get(c, 0))
            if arg_bytes.get(best, 0) > 0:
                return best
        return candidates[0]

    def _tpu_request_unmeetable(self, n_tpu: int) -> bool:
        """True when a request for `n_tpu` chips would wait forever: a
        driver-mode session is one host, and with no daemon registered
        and no autoscaler attached nothing can ever add chips to it."""
        return (n_tpu > self.total_resources.get("TPU", 0)
                and not self.standalone and not self.nodes
                and getattr(self, "_autoscaler", None) is None)

    def _infeasible_reason(self, spec) -> str:
        n_tpu = int(spec.resources.get("TPU", 0))
        if self._tpu_request_unmeetable(n_tpu):
            return (f"asks for {n_tpu} TPU chip(s) but this host has "
                    f"{int(self.total_resources.get('TPU', 0))} (chips are "
                    "counted from /dev/accel* or /dev/vfio/* at init(); "
                    "pass init(num_tpus=...) or RAY_TPU_NUM_TPUS if that "
                    "count is wrong)")
        return "has hard node affinity to a dead or unknown node"

    def _needs_localize_locked(self, t: _TaskState) -> bool:
        """Head-local dispatch needs every ref arg readable in the head's
        store; kick off a background pull for remote ones. Caller holds
        the lock. True = not ready yet (stay pending)."""
        remote = {}
        for kind, v in list(t.spec.args) + list(t.spec.kwargs.values()):
            if kind != "ref":
                continue
            d = self.directory.get(v)
            if (d is None or d.inline is not None or d.node is None
                    or v in self.local_copies):
                continue
            remote[v] = d
        if not remote:
            return False
        if not t.localizing:
            t.localizing = True

            def _pull_all():
                try:
                    self._localize(remote)
                except Exception as e:
                    logger.warning("arg localization failed: %s", e)
                finally:
                    t.localizing = False
                    self._schedule()
            threading.Thread(target=_pull_all, daemon=True).start()
        return True

    def _lease_to_node(self, node: _RemoteNode, t: _TaskState, to_send):
        """Hand a scheduled task to a HostDaemon (caller holds the lock and
        has already debited resources/chips)."""
        spec = t.spec
        locs = {}
        for kind, v in list(spec.args) + list(spec.kwargs.values()):
            if kind == "ref":
                d = self.directory.get(v)
                if d is None:
                    # can't happen while task_arg_refs pins the entry, but a
                    # hole must fail the lease (daemon pull error -> retry/
                    # error), never KeyError the scheduler mid-pass
                    logger.error("arg %s missing from directory at lease "
                                 "time for %s", v, spec.task_id)
                    continue
                locs[v] = d
        peer_addrs = {nid: n.address for nid, n in self.nodes.items()
                      if n.alive and n.address}
        t.node = node.node_id
        node.inflight[spec.task_id] = t
        self.task_events.running(spec, "node:" + node.node_id)
        to_send.append((node, protocol.LeaseTask(
            spec=spec, arg_locations=locs, peer_addrs=peer_addrs,
            tpu_chips=list(t.tpu_chips))))

    def _pick_bundle_target(self, req: dict, n_tpu: int, pg):
        """Pick the first placement-group bundle that fits `req` and whose
        node can also supply the TPU chips; the chosen bundle pins the
        node (bundles were placed at PG creation; the 2PC of
        placement_group_resource_manager.h:46 collapses to this
        reservation). Returns (target, bundle_idx) or (None, None).
        Caller holds the lock."""
        for i, b in enumerate(pg.available):
            if not _fits(b, req):
                continue
            cand = pg.bundle_nodes[i] or "head"
            if cand == "head":
                if len(self.free_tpu_chips) >= n_tpu:
                    return "head", i
            else:
                node = self.nodes.get(cand)
                if (node is not None and node.alive
                        and len(node.free_tpu_chips) >= n_tpu):
                    return cand, i
        return None, None

    def _choose_target(self, t: _TaskState, req: dict, n_tpu: int, pg):
        """Resolve where a task/actor should run: ("head"|node_id|
        "__infeasible__"|None, bundle_idx|None). Caller holds the lock."""
        if pg is not None:
            return self._pick_bundle_target(req, n_tpu, pg)
        return self._pick_node(t.spec), None

    def _debit_target(self, target: str, idx, req: dict, n_tpu: int,
                      pg) -> list:
        """Debit `req` from the chosen pool (PG bundle, node, or head) and
        carve TPU chips from the target host; returns the chip list.
        Caller holds the lock and has verified fit (incl. chip count)."""
        if pg is not None:
            _sub(pg.available[idx], req)
        elif target == "head":
            _sub(self.available, req)
        else:
            _sub(self.nodes[target].available, req)
        pool = (self.free_tpu_chips if target == "head"
                else self.nodes[target].free_tpu_chips)
        chips = pool[:n_tpu]
        del pool[:n_tpu]
        return chips

    def _try_dispatch_generic(self, t: _TaskState, to_send):
        """True=dispatched, False=doesn't fit anywhere right now,
        None=head has the resources but no idle worker (caller spawns)."""
        req = t.spec.resources
        n_tpu = int(req.get("TPU", 0))
        pg = self.placement_groups.get(t.spec.placement_group_id or "")
        target, idx = self._choose_target(t, req, n_tpu, pg)
        if target is None:
            return False
        if target == "__infeasible__":
            self._store_error(
                t.spec.return_ids,
                SchedulingError(
                    f"task {t.spec.function_desc} "
                    + self._infeasible_reason(t.spec)),
                spec=t.spec)
            return True     # consumed: removed from pending as failed
        if target != "head":
            t.tpu_chips = self._debit_target(target, idx, req, n_tpu, pg)
            self._lease_to_node(self.nodes[target], t, to_send)
            return True
        if self._needs_localize_locked(t):
            return "localizing"   # task-specific wait: NEVER memoized
        from ray_tpu._private.runtime_env import is_trivial
        if n_tpu > 0 or not is_trivial(t.spec.runtime_env):
            # TPU tasks need TPU_VISIBLE_CHIPS in the environment BEFORE the
            # process initializes JAX (the reference's CUDA_VISIBLE_DEVICES
            # is equally process-birth-scoped for safety); runtime-env tasks
            # need their env materialized pre-exec. Both run on a dedicated
            # fresh worker that retires afterwards, not the pool.
            t.tpu_chips = self._debit_target("head", idx, req, n_tpu, pg)
            threading.Thread(target=self._spawn_dedicated_worker,
                             args=(t,), daemon=True).start()
            return True
        worker = next((w for w in self.workers.values()
                       if w.kind == "generic" and w.idle and w.alive), None)
        if worker is None:
            return None
        t.tpu_chips = self._debit_target("head", idx, req, 0, pg)
        worker.idle = False
        worker.current = t
        to_send.append((worker, self._push_msg(worker, t)))
        return True

    def _spawn_dedicated_worker(self, t: _TaskState):
        """Fresh single-task worker: used for TPU tasks (chip visibility is
        process-birth-scoped) and for tasks with a non-trivial runtime
        environment (the pool's workers have none)."""
        from ray_tpu._private import spawn as spawn_mod
        from ray_tpu.exceptions import RuntimeEnvSetupError
        worker_id = ids.new_worker_id()
        w = _WorkerConn(worker_id, None, proc=None, kind="dedicated",
                        idle=False, alive=False)
        with self.lock:
            self.workers[worker_id] = w
        try:
            env = self._worker_env(chips=t.tpu_chips,
                                   runtime_env=t.spec.runtime_env)
            env, python_exe, cwd, cmd_prefix = \
                spawn_mod.setup_runtime_env(t.spec.runtime_env, env)
            w.proc = spawn_mod.spawn_worker_proc(
                self._address, self._authkey, worker_id, env,
                python_exe, cwd,
                log_dir=os.path.join(self.session_dir, "logs"),
                cmd_prefix=cmd_prefix)
        except RuntimeEnvSetupError as e:
            with self.lock:
                self._release_task_resources(t)
                self.workers.pop(worker_id, None)
            self._store_error(t.spec.return_ids, e, spec=t.spec)
            return
        if not self._await_registration(w):
            with self.lock:
                self._release_task_resources(t)
                self.workers.pop(worker_id, None)
            self._store_error(
                t.spec.return_ids,
                WorkerCrashedError("dedicated worker failed to start"),
                spec=t.spec)
            return
        with self.lock:
            w.current = t
            msg = self._push_msg(w, t)
        w.send(msg)

    def _push_msg(self, worker: _WorkerConn, t: _TaskState):
        spec = t.spec
        if spec.function_id in worker.known_functions:
            spec = protocol.TaskSpec(**{**spec.__dict__, "function_blob": None})
        else:
            worker.known_functions.add(spec.function_id)
        locs = {}
        for kind, v in list(spec.args) + list(spec.kwargs.values()):
            if kind == "ref":
                d = self.directory.get(v)
                if d is not None and d.node is not None:
                    # remote primary: the dispatch gate (_needs_localize_
                    # locked) guaranteed a head-local copy exists
                    d = self.local_copies.get(v, d)
                if d is None:
                    # directory hole (should be unreachable): let the
                    # worker fail the task; never KeyError the scheduler
                    logger.error("arg %s missing from directory at push "
                                 "time for %s", v, spec.task_id)
                    continue
                locs[v] = d
        self.task_events.running(t.spec, worker.worker_id)
        return protocol.PushTask(spec=spec, arg_locations=locs)

    def _try_dispatch_actor_creation(self, t: _TaskState, to_send):
        a = self.actors[t.spec.actor_id]
        req = a.resources
        n_tpu = int(req.get("TPU", 0))
        pg = self.placement_groups.get(t.spec.placement_group_id or "")
        target, idx = self._choose_target(t, req, n_tpu, pg)
        if target is None:
            return False
        if target == "__infeasible__":
            self._fail_actor(
                a, "actor " + self._infeasible_reason(t.spec))
            return True         # consumed: removed from pending as failed
        if target != "head":
            a.tpu_chips = self._debit_target(target, idx, req, n_tpu, pg)
            a.node = target
            t.tpu_chips = list(a.tpu_chips)
            a.inflight.append(t)
            self._lease_to_node(self.nodes[target], t, to_send)
            return True
        if self._needs_localize_locked(t):
            return False
        a.tpu_chips = self._debit_target("head", idx, req, n_tpu, pg)
        if not a.tpu_chips and not t.spec.runtime_env:
            # Serve the creation from an idle pooled worker when one
            # exists (reference: the raylet's PopWorker hands actor
            # creations pooled workers the same way) — skips the whole
            # fork+init+register round (~15ms/actor on a 1-core box).
            # TPU/runtime-env actors still get dedicated spawns.
            w = next((w for w in self.workers.values()
                      if w.alive and w.idle and not w.remote
                      and w.kind == "generic"), None)
            if w is not None:
                w.kind = "actor"
                w.pooled_actor = True
                w.idle = False
                w.current = t
                a.worker = w
                a.inflight.append(t)
                to_send.append((w, self._push_msg(w, t)))
                return True
        threading.Thread(target=self._spawn_actor_worker, args=(a, t),
                        daemon=True).start()
        return True

    def _pump_actor(self, a: _ActorState, to_send):
        if a.dead or not a.ready:
            return
        if a.node is not None:
            node = self.nodes.get(a.node)
            if node is None or not node.alive:
                return
            while a.queue and len(a.inflight) < a.max_concurrency:
                t = a.queue[0]
                if t.deps:
                    break   # preserve submission order per actor
                if t.cancelled:
                    a.queue.pop(0)
                    continue
                a.queue.pop(0)
                a.inflight.append(t)
                self._lease_to_node(node, t, to_send)
            return
        if a.worker is None or not a.worker.alive:
            return
        while a.queue and len(a.inflight) < a.max_concurrency:
            t = a.queue[0]
            if t.deps:
                break   # preserve submission order per actor
            if t.cancelled:
                a.queue.pop(0)
                continue
            if self._needs_localize_locked(t):
                break
            a.queue.pop(0)
            a.inflight.append(t)
            to_send.append((a.worker, self._push_msg(a.worker, t)))

    # ------------------------------------------------------------------
    # worker processes
    # ------------------------------------------------------------------

    def _worker_env(self, chips=None, runtime_env=None):
        from ray_tpu._private import spawn
        return spawn.worker_env(chips=chips, runtime_env=runtime_env)

    def _spawn_proc(self, worker_id, env):
        from ray_tpu._private import spawn
        return spawn.spawn_worker_proc(
            self._address, self._authkey, worker_id, env,
            log_dir=os.path.join(self.session_dir, "logs"))

    def _spawn_generic_worker(self):
        worker_id = ids.new_worker_id()
        # Record the worker BEFORE Popen so a fast-registering child finds
        # its slot in _serve_conn instead of racing us into a duplicate.
        w = _WorkerConn(worker_id, None, proc=None, kind="generic",
                        idle=False, alive=False)
        with self.lock:
            self.workers[worker_id] = w
        w.proc = self._spawn_proc(worker_id, self._worker_env())
        ok = self._await_registration(w)
        with self.lock:
            self._spawning -= 1
            if ok:
                w.idle = True
                self._spawn_failures = 0
            else:
                self.workers.pop(worker_id, None)
                self._spawn_failures += 1
                if self._spawn_failures >= 3:
                    # Startup is systematically broken (bad env, missing
                    # package): fail queued work instead of a respawn storm.
                    failed, self.pending = self.pending, deque()
                    for t in failed:
                        if not t.spec.actor_creation:
                            self._store_error(
                                t.spec.return_ids,
                                WorkerCrashedError(
                                    "worker processes repeatedly failed to "
                                    "start; check worker logs"),
                                spec=t.spec)
        self._schedule()

    def _spawn_actor_worker(self, a: _ActorState, creation_task: _TaskState):
        from ray_tpu._private import spawn as spawn_mod
        from ray_tpu.exceptions import RuntimeEnvSetupError
        worker_id = ids.new_worker_id()
        w = _WorkerConn(worker_id, None, proc=None, kind="actor",
                        idle=False, alive=False)
        with self.lock:
            self.workers[worker_id] = w
        try:
            env = self._worker_env(
                chips=a.tpu_chips,
                runtime_env=a.creation_spec.runtime_env)
            env, python_exe, cwd, cmd_prefix = \
                spawn_mod.setup_runtime_env(
                    a.creation_spec.runtime_env, env)
            w.proc = spawn_mod.spawn_worker_proc(
                self._address, self._authkey, worker_id, env,
                python_exe, cwd,
                log_dir=os.path.join(self.session_dir, "logs"),
                cmd_prefix=cmd_prefix)
        except RuntimeEnvSetupError as e:
            with self.lock:
                self.workers.pop(worker_id, None)
            self._fail_actor(a, f"runtime env setup failed: {e}")
            return
        if not self._await_registration(w):
            self._fail_actor(a, "actor worker failed to start")
            return
        to_send = []
        with self.lock:
            a.worker = w
            w.current = creation_task
            a.inflight.append(creation_task)
            to_send.append((w, self._push_msg(w, creation_task)))
        for w2, msg in to_send:
            w2.send(msg)

    def _await_registration(self, w: _WorkerConn) -> bool:
        deadline = time.monotonic() + constants.WORKER_REGISTER_TIMEOUT_S
        while not w.alive:
            rem = deadline - time.monotonic()
            if rem <= 0 or self._shutdown:
                return False
            if w.proc is not None and w.proc.poll() is not None:
                return False
            # per-worker event: registration wakes exactly this waiter
            # (the global cv would thundering-herd under creation bursts)
            w.reg_event.wait(min(rem, 0.2))
        return True

    # ------------------------------------------------------------------
    # completion + failure
    # ------------------------------------------------------------------

    def _on_task_done(self, w: _WorkerConn, msg: protocol.TaskDone):
        if msg.spans:
            # merge the worker's drained spans before taking the node lock
            from ray_tpu.util import tracing as _tracing
            _tracing.ingest(msg.spans)
        retire = None
        with self.lock:
            t = w.current if (w.current and w.current.spec.task_id ==
                              msg.task_id) else None
            a = None
            if t is None:
                # actor task completing (possibly out of submission order
                # when max_concurrency > 1)
                for cand in self.actors.values():
                    for inf in cand.inflight:
                        if inf.spec.task_id == msg.task_id:
                            a, t = cand, inf
                            break
                    if a:
                        break
            if t is None:
                logger.warning("TaskDone for unknown task %s", msg.task_id)
                return
            spec = t.spec
            if a is None and spec.actor_id is not None:
                a = self.actors.get(spec.actor_id)
            # Retry on application error if requested.
            if (msg.error and t.retry_exceptions and t.retries_left > 0
                    and not spec.actor_creation):
                t.retries_left -= 1
                self.task_events.requeued(spec)
                self._requeue_after_failure(w, t, a)
                return
            self.task_events.finished(
                msg.task_id, error="application_error" if msg.error else None,
                exec_start_ts=msg.exec_start_ts, exec_end_ts=msg.exec_end_ts,
                return_ids=spec.return_ids)
            self._release_task_args(spec)
            for oid, desc in zip(spec.return_ids, msg.return_descs):
                # _register_locked already notifies waiters per oid; a
                # second notify_all here was pure herd overhead
                self._register_locked(oid, desc, origin=w.worker_id)
            if a is not None:
                if t in a.inflight:
                    a.inflight.remove(t)
                if spec.actor_creation:
                    if msg.error:
                        a.dead = True
                        a.death_cause = "constructor raised"
                        self._release_actor_resources(a)
                        failed, a.queue = a.queue, []
                        for qt in failed:
                            self._store_error(
                                qt.spec.return_ids,
                                ActorDiedError(
                                    f"actor {a.actor_id} constructor raised"),
                                spec=qt.spec)
                        if w.pooled_actor:
                            # the worker came from the pool and is still
                            # healthy (only the user constructor raised):
                            # hand it back instead of stranding it
                            w.pooled_actor = False
                            w.kind = "generic"
                            w.idle = True
                            a.worker = None
                            # a.worker was just nulled, so the `a.worker
                            # is w` check below can't clear w.current —
                            # do it here, or the recycled worker keeps
                            # pointing at the dead actor's creation task
                            # and a later worker death re-credits its
                            # resources / re-queues it.
                            w.current = None
                            self._sched_event.set()
                    else:
                        a.ready = True
                if a.worker is w:
                    w.current = None
            else:
                w.current = None
                if not w.released:
                    self._release_task_resources(t)
                w.released = {}
                if w.kind == "dedicated":
                    # Dedicated workers retire with their task: the TPU
                    # runtime (and a task-specific env) can't be re-scoped
                    # in a live process.
                    w.idle = False
                    w.alive = False
                    retire = w
                else:
                    w.idle = True
        if retire is not None:
            retire.send(protocol.KillWorker())
            with self.lock:
                self.workers.pop(retire.worker_id, None)
        # Completion fastpath (the submit path has the same shortcut,
        # _submit_fastpath; reference: cluster_task_manager.cc:44
        # QueueAndScheduleTask scoping): a completion frees exactly one
        # slot, so fill exactly that slot instead of waking the full
        # scheduler pass — on a deep homogeneous backlog the pass
        # examines a whole dispatch window per completion, which caps
        # drain throughput.
        if a is not None:
            # actor slot freed: pump exactly that actor's queue
            to_send = []
            with self.lock:
                self._pump_actor(a, to_send)
            for w2, m2 in to_send:
                w2.send(m2)
        elif self._dispatch_freed_fastpath():
            return
        self._schedule()

    def _dispatch_freed_fastpath(self) -> bool:
        """Hand freed slots the head-of-line pending tasks. Batched:
        dequeue -> match -> dispatch for up to SCHEDULER_FREED_BATCH
        plain tasks under ONE lock acquisition — concurrent completions
        free several slots at once, and the first reader through the
        lock fills them all instead of paying an acquire/release per
        task. Anything trickier (deps, actors, placement groups,
        scheduling strategies) falls back to the scheduler pass.
        Returns True iff the freed capacity was cleanly consumed (or
        nothing is runnable) so the scheduler event can be skipped —
        the next completion continues the chain."""
        to_send = []
        ok = False
        need_pass = False
        filled = 0
        with self.lock:
            if self._shutdown:
                return True
            for _ in range(64):        # bound: pops + dispatch attempts
                if filled >= constants.SCHEDULER_FREED_BATCH:
                    break
                if not self.pending:
                    ok = True          # nothing queued: slot stays free
                    break
                t = self.pending[0]
                if t.cancelled:
                    self.pending.popleft()
                    continue
                if (t.deps or t.spec.actor_creation
                        or t.spec.actor_id is not None
                        or t.spec.placement_group_id
                        or t.spec.scheduling_strategy):
                    need_pass = True   # needs the real pass
                    break
                if (filled and not self.nodes
                        and not _fits(self.available, t.spec.resources)):
                    # freed slot(s) already refilled and the local pool
                    # can't absorb another of this shape: stop before
                    # paying a full placement scan that must fail
                    break
                self.pending.popleft()
                n_before = len(to_send)
                if self._try_dispatch_generic(t, to_send) is True:
                    # "consumed" is not "slot filled": infeasible tasks
                    # return True with nothing sent, and a remote
                    # dispatch leaves the LOCAL slot idle — keep going,
                    # a later queued task may fill it
                    if any(isinstance(w, _WorkerConn)
                           for w, _ in to_send[n_before:]):
                        filled += 1
                        ok = True
                else:
                    # No capacity left (or needs localization). If we
                    # already filled the freed slot(s), the backlog is
                    # simply deeper than the capacity — the next
                    # completion continues the chain and a full pass
                    # would be pure overhead. Only an UNFILLED freed
                    # slot needs the real pass.
                    self.pending.appendleft(t)
                    if filled == 0:
                        need_pass = True
                    break
        for w, msg in to_send:
            if not w.send(msg):
                if isinstance(w, _RemoteNode):
                    self._on_node_death(w)
                else:
                    self._on_worker_death(w)
                ok = False
        return ok and not need_pass

    def _requeue_after_failure(self, w, t, a):
        """Re-run a failed task (called under lock)."""
        if a is not None:
            if t in a.inflight:
                a.inflight.remove(t)
            a.queue.insert(0, t)
            if a.worker is w:
                w.current = None
        else:
            w.idle = True
            w.current = None
            if not w.released:
                self._release_task_resources(t)
            w.released = {}
            self.pending.append(t)

    def _release_task_resources(self, t: _TaskState):
        if not t.node_released:
            pg = self.placement_groups.get(t.spec.placement_group_id or "")
            if t.spec.placement_group_id and pg is None:
                # The group was already removed (remove_pg credits the
                # FULL bundles back wholesale); crediting the node again
                # here would double-count — kill() is async, so actor/
                # task death often lands after the PG teardown.
                pass
            elif pg is not None:
                # return to the first bundle with headroom vs its spec
                for b, orig in zip(pg.available, pg.bundles):
                    if all(b.get(k, 0) + v <= orig.get(k, 0) + _EPS
                           for k, v in t.spec.resources.items()):
                        _add(b, t.spec.resources)
                        break
                else:
                    if pg.available:
                        _add(pg.available[0], t.spec.resources)
            elif t.node is not None:
                node = self.nodes.get(t.node)
                if node is not None:
                    _add(node.available, t.spec.resources)
            else:
                _add(self.available, t.spec.resources)
        t.node_released = False
        chips, t.tpu_chips = t.tpu_chips, []
        if chips:
            if t.node is not None:
                node = self.nodes.get(t.node)
                if node is not None:
                    node.free_tpu_chips.extend(chips)
            else:
                self.free_tpu_chips.extend(chips)

    def _release_actor_resources(self, a: _ActorState):
        pg = self.placement_groups.get(
            a.creation_spec.placement_group_id or "")
        if pg is not None and pg.available:
            _add(pg.available[0], a.resources)
        elif a.creation_spec.placement_group_id:
            # PG already removed; its bundles were credited wholesale
            # (see _release_task_resources) — don't double-credit.
            pass
        elif pg is None:
            if a.node is not None:
                node = self.nodes.get(a.node)
                if node is not None:
                    _add(node.available, a.resources)
            else:
                _add(self.available, a.resources)
        if a.tpu_chips:
            if a.node is not None:
                node = self.nodes.get(a.node)
                if node is not None:
                    node.free_tpu_chips.extend(a.tpu_chips)
            else:
                self.free_tpu_chips.extend(a.tpu_chips)
            a.tpu_chips = []
        a.node = None

    def _store_error(self, return_ids, exc, spec=None):
        """Store `exc` as the value of every return id (under or out of lock).
        `spec` records the terminal FAILED transition in the state API and
        releases the task's pinned args — this is the chokepoint every
        failure path goes through."""
        if spec is not None:
            self.task_events.finished(spec.task_id,
                                      error=type(exc).__name__)
            self._release_task_args(spec)
        for oid in return_ids:
            desc = self.store.put(oid, exc)
            with self.lock:
                self._register_locked(oid, desc, origin="driver")

    def _on_worker_death(self, w: _WorkerConn):
        with self.lock:
            if self._shutdown:
                return      # nothing to recover, and the store is closing
            if w.kind == "attach":
                # external CLI/monitoring connection: reap the entry, no
                # task/actor state to recover
                self.workers.pop(w.worker_id, None)
                return
            if not w.alive and w.current is None:
                return
            w.alive = False
            w.idle = False
            t = w.current
            w.current = None
            actor = next((a for a in self.actors.values()
                          if a.worker is w), None)
            # drop the dead process's ref holds (its ObjectRefs died with
            # it); objects it alone held become freeable
            affected = [oid for oid, holders in self.ref_holders.items()
                        if w.worker_id in holders]
            for oid in affected:
                self.ref_holders[oid].discard(w.worker_id)
                self._maybe_free_locked(oid)
            # Reclaim the dead process's shared-arena pins (plasma releases
            # a disconnected client's references the same way): first adopt
            # the owner pin of every live object it put — so force-release
            # can't leave them evictable — then drop everything the pid
            # still holds (reader pins, condemned pins, unsealed creations).
            pid = getattr(w.proc, "pid", None)
            if pid is not None:
                for oid, origin in list(self.obj_origin.items()):
                    if origin != w.worker_id:
                        continue
                    desc = self.directory.get(oid)
                    if desc is not None and desc.arena:
                        self.store.adopt(oid)
                    self.obj_origin[oid] = "driver"
                self.store.release_all_pins(pid)
        if actor is not None:
            self._on_actor_worker_death(actor)
        elif t is not None:
            with self.lock:
                if not w.released:
                    self._release_task_resources(t)
                w.released = {}
                if t.retries_left > 0:
                    t.retries_left -= 1
                    self.pending.append(t)
                    self.task_events.requeued(t.spec)
                    retry = True
                else:
                    retry = False
            if not retry:
                self._store_error(
                    t.spec.return_ids,
                    WorkerCrashedError(
                        f"worker died while running {t.spec.function_desc}"),
                    spec=t.spec)
        self._schedule()

    def _on_actor_worker_death(self, a: _ActorState):
        with self.lock:
            a.ready = False
            a.worker = None
            inflight, a.inflight = a.inflight, []
            can_restart = (not a.dead and
                           (a.max_restarts == -1 or
                            a.restarts_used < a.max_restarts))
            if can_restart:
                a.restarts_used += 1
                # Return the dead incarnation's resources/chips; the
                # re-queued creation task re-subtracts them on dispatch.
                self._release_actor_resources(a)
                # retry in-flight tasks if allowed, else fail them
                retry_tasks, fail_tasks = [], []
                for t in inflight:
                    if t.spec.actor_creation:
                        continue
                    if a.max_task_retries != 0:
                        retry_tasks.append(t)
                    else:
                        fail_tasks.append(t)
                a.queue[:0] = retry_tasks
                creation = _TaskState(spec=a.creation_spec)
                self.pending.append(creation)
            else:
                a.dead = True
                a.death_cause = a.death_cause or "worker process died"
                fail_tasks = [t for t in inflight
                              if not t.spec.actor_creation]
                fail_tasks.extend(a.queue)
                a.queue = []
                self._release_actor_resources(a)
        for t in fail_tasks:
            self._store_error(
                t.spec.return_ids,
                ActorDiedError(f"actor {a.actor_id} died"
                               f" ({a.death_cause or 'restarting'})"),
                spec=t.spec)
        self._schedule()

    # the same restart/fail state machine serves remote actors, whose
    # worker lives under a HostDaemon (we only hear NodeActorDied)
    _on_actor_death = _on_actor_worker_death

    def _fail_actor(self, a: _ActorState, cause: str):
        with self.lock:
            a.dead = True
            a.death_cause = cause
            tasks = list(a.inflight) + list(a.queue)
            a.inflight, a.queue = [], []
            self._release_actor_resources(a)
        for t in tasks:
            self._store_error(t.spec.return_ids, ActorDiedError(cause),
                              spec=t.spec)
        # creation return id too
        self._store_error(a.creation_spec.return_ids, ActorDiedError(cause),
                          spec=a.creation_spec)

    # ------------------------------------------------------------------
    # actor control
    # ------------------------------------------------------------------

    def get_named_actor(self, name: str):
        with self.lock:
            actor_id = self.named_actors.get(name)
            if actor_id is None:
                return None
            a = self.actors.get(actor_id)
            if a is None or a.dead:
                return None
            return {"actor_id": actor_id, "method_meta": a.method_meta,
                    "creation_return": a.creation_spec.return_ids[0]}

    def kill_actor(self, actor_id: str, no_restart=True):
        with self.lock:
            a = self.actors.get(actor_id)
            if a is None:
                return False
            if no_restart:
                a.dead = True
                a.death_cause = "killed via kill()"
                if a.name:
                    self.named_actors.pop(a.name, None)
            w = a.worker
            node = self.nodes.get(a.node) if a.node is not None else None
        if node is not None:
            node.send(protocol.KillActorOnNode(actor_id))
        elif w is not None and w.proc is not None:
            try:
                w.proc.terminate()
            except OSError:
                pass
        return True

    def cancel(self, object_id: str, force: bool = False):
        with self.lock:
            for t in self.pending:
                if object_id in t.spec.return_ids:
                    t.cancelled = True
                    self.pending.remove(t)
                    self._store_error(t.spec.return_ids,
                                      TaskCancelledError("task cancelled"),
                                      spec=t.spec)
                    return True
            for a in self.actors.values():
                for t in a.queue:
                    if object_id in t.spec.return_ids:
                        t.cancelled = True
                        a.queue.remove(t)
                        self._store_error(t.spec.return_ids,
                                          TaskCancelledError("task cancelled"),
                                          spec=t.spec)
                        return True
        return False

    # ------------------------------------------------------------------
    # placement groups: bundles are placed onto nodes at creation time by
    # strategy (PACK/SPREAD/STRICT_*), reserving resources on each node —
    # the reference's bundle scheduling policies
    # (policy/bundle_scheduling_policy.h:82-106) with the 2PC
    # (placement_group_resource_manager.h:46) collapsed into the head's
    # single resource ledger.
    # ------------------------------------------------------------------

    def _pool_links_locked(self) -> dict:
        """pool id -> link-group ids, for the contention model. The head's
        own links come from its env; daemons advertised theirs in
        RegisterNode."""
        links = {"head": tuple(
            s for s in config.get("LINK_GROUPS").split(",") if s)}
        for nid, n in self.nodes.items():
            if n.alive:
                links[nid] = tuple(n.links)
        return links

    def _link_load_locked(self, pool_links: dict) -> dict:
        """link id -> count of live bandwidth-tagged gangs touching it.
        Recomputed from the placement-group table at gang-creation time
        (rare), so the remove/failure paths carry no extra bookkeeping."""
        load: dict = {}
        for pg in self.placement_groups.values():
            if not pg.bandwidth:
                continue
            touched = set()
            for nid in pg.bundle_nodes:
                touched.update(pool_links.get(
                    "head" if nid is None else nid, ()))
            for link in touched:
                load[link] = load.get(link, 0) + 1
        return load

    def _assign_bundles(self, bundles, strategy, bandwidth=0.0):
        """Pick a node for every bundle. Returns list of node ids (None =
        head) or None if infeasible. Caller holds the lock. The head pool
        is keyed "head" internally so it can't collide with the "no
        fitting pool" sentinel; planning itself is the pure module-level
        plan_gang_placement."""
        pools = [("head", self.available)]
        pools += [(nid, n.available) for nid, n in self.nodes.items()
                  if n.alive]
        pool_links = self._pool_links_locked()
        assignment = plan_gang_placement(
            pools, bundles, strategy, links=pool_links,
            link_load=self._link_load_locked(pool_links),
            bandwidth=bandwidth)
        if assignment is None:
            return None
        return [None if pid == "head" else pid for pid in assignment]

    def _try_reserve_pg_locked(self, bundles, strategy, bandwidth=0.0):
        """Assign + debit atomically (caller holds the lock); returns the
        new pg_id or None if currently infeasible."""
        assignment = self._assign_bundles(bundles, strategy, bandwidth)
        if assignment is None:
            return None
        for b, nid in zip(bundles, assignment):
            if nid is None:
                _sub(self.available, b)
            else:
                _sub(self.nodes[nid].available, b)
        pg_id = ids.new_placement_group_id()
        self.placement_groups[pg_id] = _PlacementGroup(
            pg_id, bundles, strategy, bundle_nodes=list(assignment),
            bandwidth=float(bandwidth or 0.0))
        return pg_id

    def create_placement_group(self, bundles, strategy="PACK", name="",
                               bandwidth=0.0):
        bundles = [dict(b) for b in bundles]
        with self.lock:
            pg_id = self._try_reserve_pg_locked(bundles, strategy,
                                                bandwidth)
        if pg_id is not None:
            return pg_id
        if getattr(self, "_autoscaler", None) is not None:
            # With an autoscaler attached an infeasible group is DEMAND,
            # not an error: park it on the gang queue (visible to
            # LoadMetrics) and retry as capacity arrives (reference:
            # PENDING placement groups feed the autoscaler). Reservation
            # happens under the lock inside the loop, so a concurrent
            # task debiting fresh capacity just sends us back to waiting
            # instead of failing the group early.
            deadline = time.monotonic() + config.get("PG_AUTOSCALE_WAIT_S")
            with self.cv:
                self._pending_gangs.append(bundles)
            try:
                while True:
                    with self.cv:
                        pg_id = self._try_reserve_pg_locked(
                            bundles, strategy, bandwidth)
                        if pg_id is not None:
                            return pg_id
                        rem = deadline - time.monotonic()
                        if rem <= 0 or self._shutdown:
                            break
                        self.cv.wait(min(rem, 0.5))
            finally:
                with self.cv:
                    self._pending_gangs.remove(bundles)
        raise PlacementGroupError(
            f"infeasible placement group ({strategy}): bundles {bundles}")

    def remove_placement_group(self, pg_id: str):
        with self.lock:
            pg = self.placement_groups.pop(pg_id, None)
            if pg is None:
                return False
            for b, nid in zip(pg.bundles, pg.bundle_nodes):
                if nid is None:
                    _add(self.available, b)
                else:
                    node = self.nodes.get(nid)
                    if node is not None and node.alive:
                        _add(node.available, b)
        self._schedule()
        return True

    # ------------------------------------------------------------------
    # shutdown
    # ------------------------------------------------------------------

    def shutdown(self):
        """End the session in a time bounded by constants, whatever it
        still holds. No result has a reader any more, so a worker does not
        get to finish: its channel is hung up, its reader sees EOF and the
        process leaves at once (`exit_on_disconnect`), also from inside an
        actor method; `spawn.stop_procs` signals what stays. No step waits
        on a peer or on a lock without a limit."""
        from ray_tpu._private import spawn
        # a thread stuck under the lock must not hold the teardown
        locked = self.lock.acquire(timeout=2.0)
        already, self._shutdown = self._shutdown, True
        workers = list(self.workers.values())
        nodes = list(self.nodes.values())
        if locked:
            self.lock.release()
        if already:
            return
        try:
            self._usage_reporter.stop()
        except AttributeError:
            pass
        self._sched_event.set()   # release the scheduler thread
        for node in nodes:
            node.alive = False
        # a daemon takes EOF for a head that restarts, so it is told: from
        # a thread, since it may no longer read and its pipe may be full
        told = threading.Thread(
            target=lambda: [n.send(protocol.KillNode()) for n in nodes],
            daemon=True)
        told.start()
        told.join(1.0)
        for peer in workers + nodes:
            netaddr.hang_up(peer.conn)
        for lst in (self._listener, self._tcp_listener):
            if lst is None:
                continue
            try:
                lst.close()
            except OSError:
                pass
        spawn.stop_procs([p.proc for p in workers + nodes])
        self.store.purge_spill()
        for node in nodes:
            # SIGKILLed daemons can't purge their own spill dirs
            shutil.rmtree(os.path.join(constants.OBJECT_SPILL_ROOT,
                                       node.node_id), ignore_errors=True)
        self.store.close()
        shutil.rmtree(self.session_dir, ignore_errors=True)
        atexit.unregister(self.shutdown)
