"""Per-host daemon: local worker pool, object store, and pull server.

Counterpart of the reference's raylet (`src/ray/raylet/node_manager.h:117`
NodeManager + worker_pool.h:80 WorkerPool) plus the node-to-node object
manager (`src/ray/object_manager/object_manager.h:117`), with scheduling
deliberately left at the head: the head's cluster scheduler assigns a task
to a node and sends a `LeaseTask`; this daemon only localizes dependencies
(pulling from peer nodes or the head), runs the task on a local worker, and
reports the sealed results back. That matches the reference's
GCS-scheduling mode (gcs_actor_scheduler.h:349 ScheduleByGcs) rather than
its raylet-autonomy mode — the right trade for TPU pods, where gang
placement decisions need the global view anyway.

Data plane: objects live in this node's own shm arena (store.cc); remote
reads are chunked pulls over UNIX sockets (object_manager.h:130,139
HandlePush/HandlePull). Workers on this host connect to this daemon's
listener and share its arena zero-copy, exactly like workers on the head.
"""

from __future__ import annotations

import collections
import itertools
import json
import logging
import os
import sys
import threading
import time
from dataclasses import dataclass, field, replace
from multiprocessing import connection

from ray_tpu._private import constants, ids, netaddr, protocol, spawn
from ray_tpu._private.object_store import Descriptor, ObjectStore
from ray_tpu._private.pull_plane import PullClient, serve_pull
from ray_tpu.exceptions import ObjectLostError, RuntimeEnvSetupError


def _env_trivial(spec) -> bool:
    from ray_tpu._private.runtime_env import is_trivial
    return is_trivial(spec.runtime_env)


def _local_link_groups() -> list:
    """Interconnect link-group ids this host hangs off (ICI ring / DCN
    pod), advertised in RegisterNode for contention-aware gang
    placement. Read per registration: set by the provisioner's env."""
    from ray_tpu._private import config
    return [s for s in config.get("LINK_GROUPS").split(",") if s]

logger = logging.getLogger("ray_tpu.daemon")


@dataclass
class _DWorker:
    worker_id: str
    conn: connection.Connection | None = None
    proc: object = None
    kind: str = "generic"            # generic | tpu | actor
    idle: bool = False
    alive: bool = False
    actor_id: str | None = None
    known_functions: set = field(default_factory=set)
    inflight: dict = field(default_factory=dict)   # task_id -> TaskSpec
    send_lock: threading.Lock = field(default_factory=threading.Lock)
    # Pipelined-submission receive state (touched only by this worker's
    # reader thread): next expected seq + outstanding-nack flag. The
    # daemon dedupes the worker's stream here, then relays each
    # submission ONCE on the reliable NodeSeq channel to the head.
    sub_next: int = 0
    sub_nacked: bool = False

    def send(self, msg) -> bool:
        return protocol.safe_send(self.conn, self.send_lock, msg)


class HostDaemon:
    def __init__(self, head_address: str, node_id: str, resources: dict,
                 num_tpu_chips: int):
        self.node_id = node_id
        self.head_address = head_address
        self.resources = dict(resources)
        self.num_tpu_chips = num_tpu_chips
        self.authkey = bytes.fromhex(os.environ["RAY_TPU_AUTHKEY"])
        tcp = netaddr.is_tcp(head_address)
        if not tcp:
            # same-machine session: node dir lives under the head's
            # session dir so shutdown/GC can sweep it
            session_dir = os.path.dirname(head_address)
            self.node_dir = os.path.join(session_dir, "nodes", node_id)
        else:
            # cross-machine join: no shared filesystem with the head —
            # this host owns its node dir (spawner may pin it via env for
            # same-host TCP test tiers)
            self.node_dir = os.environ.get("RAY_TPU_NODE_DIR") or \
                os.path.join(constants.SHM_ROOT, "ray_tpu_node_" + node_id)
        os.makedirs(self.node_dir, exist_ok=True)
        self.store = ObjectStore(self.node_dir)
        # workers always connect over UDS to their local daemon (reference
        # keeps worker<->raylet on UDS too); only peer/head edges go TCP
        self.address = os.path.join(self.node_dir, "node.sock")

        self.lock = threading.RLock()
        self.cv = threading.Condition(self.lock)
        self.workers: dict[str, _DWorker] = {}
        self.actors: dict[str, _DWorker] = {}
        self._objs: dict[str, Descriptor] = {}     # sealed in OUR store
        self._origin: dict[str, str] = {}          # oid -> worker_id
        self._copies: dict[str, Descriptor] = {}   # pulled remote objects
        self._pulling: set = set()                 # oids with pull in flight
        self.peer_addrs: dict[str, str] = {}
        self._peers: dict[str, tuple] = {}         # node -> (conn, lock)
        self._req = itertools.count(1)
        self._pull_client = PullClient()
        # head_req_id -> (kind, worker, worker_req_id, task_id)
        self._proxy: dict[int, tuple] = {}
        self._ctl: dict[int, dict] = {}     # daemon's own head RPCs
        self._ctl_cv = threading.Condition()
        self._shutdown = False

        if os.path.exists(self.address):
            # leftover socket of a dead daemon that reused this node dir
            os.unlink(self.address)
        self._listener = netaddr.listener(self.address, self.authkey)
        self._head = netaddr.client(head_address, self.authkey)
        self._head_lock = threading.Lock()
        # Reliable-delivery state for head-bound messages: a blip can
        # swallow sends WITHOUT an exception (the first write into a
        # half-closed TCP socket succeeds silently), so reliable messages
        # are seq-wrapped (protocol.NodeSeq), retained in a bounded ring,
        # and the whole ring is replayed after reconnect — the head
        # dedupes on seq, so completions that land inside the blip window
        # arrive exactly once.
        self._send_seq = itertools.count(1)
        self._sent_ring: collections.deque = collections.deque(
            maxlen=constants.HEAD_BACKLOG_CAP)
        # lease task id -> None while running, else the seq of its
        # terminal message (NodeTaskDone/Failed/NodeActorDied). Reported
        # in re-registration so the head can requeue leases the blip
        # swallowed; entries whose terminal seq fell off the replay ring
        # were delivered long ago and are pruned at reconnect.
        self._live_leases: dict[str, int | None] = {}
        if tcp:
            # peer pulls dial us over TCP; bind an ephemeral port on the
            # interface that routes to the head and advertise host:port
            host = netaddr.local_endpoint_host(self._head) or \
                netaddr.advertise_host()
            self._peer_listener = netaddr.listener((host, 0), self.authkey)
            self.advertised_address = netaddr.bound_address(
                self._peer_listener)
        else:
            self._peer_listener = None
            self.advertised_address = self.address
        # raw (un-seq'd) send: RegisterNode must be the literal first
        # message on the channel for the head to classify it. A send
        # failure here must NOT kill the daemon — head_loop's first recv
        # fails the same way and drives reconnect-and-reregister.
        try:
            self._head.send(protocol.RegisterNode(
                node_id=node_id, pid=os.getpid(), resources=resources,
                num_tpu_chips=num_tpu_chips,
                address=self.advertised_address,
                link_groups=_local_link_groups()))
        except (OSError, ValueError, BrokenPipeError):
            logger.warning("initial register send failed; deferring to "
                           "the reconnect path")

        threading.Thread(target=self._accept_loop, daemon=True,
                         name="daemon-accept").start()
        # ship this host's per-process log lines to the head (reference:
        # the per-node log monitor publishing via GCS pubsub)
        from ray_tpu._private.log_monitor import LogTailer
        self._log_tailer = LogTailer(
            os.path.join(self.node_dir, "logs"),
            lambda src, lines: self._head_send(
                protocol.LogBatch(src, self.node_id, lines),
                reliable=False)).start()
        if self._peer_listener is not None:
            threading.Thread(
                target=self._accept_loop, args=(self._peer_listener,),
                daemon=True, name="daemon-peer-accept").start()
        if self.store.arena_stats() is not None:
            threading.Thread(target=self._spill_loop, daemon=True,
                             name="daemon-spill").start()

    # ------------------------------------------------------------------
    # channels
    # ------------------------------------------------------------------

    def _head_send(self, msg, reliable: bool = True) -> int | None:
        """Send to the head; returns the seq for reliable messages.
        `reliable` messages (completions, object registrations, lifecycle
        events) are seq-wrapped and retained for replay across channel
        blips; lossy streams (LogBatch, PullChunk) pass `reliable=False`
        and ride unwrapped. Outbound pull REQUESTS stay reliable on
        purpose: a blip-swallowed request would hang the puller, while
        the chunk REPLIES it triggers are the lossy part."""
        with self._head_lock:
            if reliable:
                msg = protocol.NodeSeq(next(self._send_seq), msg)
                self._sent_ring.append(msg)
            try:
                self._head.send(msg)
            except (OSError, ValueError, BrokenPipeError):
                # reliable: already in the ring, replayed on reconnect;
                # lossy: dropped by design
                pass
            return msg.seq if reliable else None

    def _lease_terminal(self, task_id: str, seq: int | None) -> None:
        """Record that `task_id`'s terminal message was sent with `seq`
        (its outcome now rides the replay ring, not this table)."""
        with self.lock:
            if seq is None:
                self._live_leases.pop(task_id, None)
            elif task_id in self._live_leases:
                self._live_leases[task_id] = seq
            if len(self._live_leases) > 2 * constants.HEAD_BACKLOG_CAP:
                # amortized bound: entries whose terminal fell off the
                # replay ring were delivered long ago (self.lock ->
                # _head_lock nesting is the one order used everywhere)
                with self._head_lock:
                    oldest = (self._sent_ring[0].seq
                              if self._sent_ring else None)
                for tid, s in list(self._live_leases.items()):
                    if s is not None and (oldest is None or s < oldest):
                        del self._live_leases[tid]

    def _send_terminal(self, task_id: str, msg) -> None:
        """Send a lease's terminal outcome and move its delivery guarantee
        from the live-lease table to the replay ring."""
        self._lease_terminal(task_id, self._head_send(msg))

    def head_loop(self):
        """Main thread: serve the head channel until it closes. A closed
        channel means the head died or restarted: ride it out by
        reconnect-and-reregister within the grace window (reference:
        raylets survive GCS restarts, node_manager.proto:358
        NotifyGCSRestart), else die."""
        while not self._shutdown:
            try:
                msg = self._head.recv()
            except (EOFError, OSError, TypeError):
                if self._reconnect_head():
                    continue
                break
            try:
                self._handle_head(msg)
            except Exception:
                logger.exception("error handling %r from head", type(msg))
        self._die()

    def _reconnect_head(self) -> bool:
        from ray_tpu._private import config
        grace = config.get("DAEMON_RECONNECT_GRACE_S")
        if grace <= 0:
            return False
        logger.warning("head channel closed; trying to reconnect for %ss",
                       grace)
        deadline = time.monotonic() + grace
        while time.monotonic() < deadline and not self._shutdown:
            time.sleep(1.0)
            try:
                conn = netaddr.client(self.head_address, self.authkey)
            except Exception:
                continue
            # fail every request proxied before the crash: the restarted
            # head has no record of those req ids, so waiting is forever
            with self._head_lock:
                oldest_seq = (self._sent_ring[0].seq
                              if self._sent_ring else None)
            with self.lock:
                proxied, self._proxy = self._proxy, {}
                live_actors = {aid: {} for aid, w in self.actors.items()
                               if w.alive}
                objects = {oid: self._tag(d)
                           for oid, d in self._objs.items()}
                # prune leases whose terminal message fell off the replay
                # ring — the head saw those long ago; what remains is
                # every lease still running or whose outcome replays below
                for tid, s in list(self._live_leases.items()):
                    if s is not None and (oldest_seq is None
                                          or s < oldest_seq):
                        del self._live_leases[tid]
                leases = list(self._live_leases)
            with self._ctl_cv:
                for box in self._ctl.values():
                    box["error"] = "head restarted"
                    box["done"] = True
                self._ctl.clear()
                self._ctl_cv.notify_all()
            for kind, w, wreq, task_id in proxied.values():
                if kind == "get":
                    w.send(protocol.GetReply(
                        wreq, {}, error="ObjectLostError: head restarted "
                        "while this get() was in flight"))
                else:
                    w.send(protocol.ErrorReply(wreq, "head restarted"))
            register = protocol.RegisterNode(
                node_id=self.node_id, pid=os.getpid(),
                resources=self.resources, num_tpu_chips=self.num_tpu_chips,
                address=self.advertised_address, actors=live_actors,
                objects=objects, leases=leases,
                link_groups=_local_link_groups())
            # RegisterNode must be the FIRST message on the new channel
            # (the head classifies connections by it); then the retained
            # seq ring replays in order — the head drops already-seen
            # seqs, so messages swallowed by the blip (TCP reports no
            # error on the first write into a half-closed socket) arrive
            # exactly once. All under _head_lock so no concurrent
            # _head_send can jump the replay.
            with self._head_lock:
                try:
                    conn.send(register)
                    for wrapped in self._sent_ring:
                        conn.send(wrapped)
                except (OSError, ValueError, BrokenPipeError):
                    try:
                        conn.close()   # don't leak the fd while the
                    except OSError:    # head keeps flapping
                        pass
                    continue     # new conn died mid-handshake: retry
                self._head = conn
            logger.warning("re-registered with head "
                           "(%d actors, %d objects, %d replayed)",
                           len(live_actors), len(objects),
                           len(self._sent_ring))
            return True
        return False

    def _handle_head(self, msg):
        if isinstance(msg, protocol.LeaseTask):
            with self.lock:
                self._live_leases[msg.spec.task_id] = None
            threading.Thread(target=self._run_lease, args=(msg,),
                             daemon=True).start()
        elif isinstance(msg, protocol.PullRequest):
            # chunks are a lossy raw-framed stream on the head channel:
            # the puller re-requests on stall, and retaining MB-sized
            # chunks in the replay ring would balloon it
            with self._head_lock:
                raw = (self._head, self._head_lock)
            threading.Thread(
                target=self._serve_pull, args=(raw, msg),
                daemon=True).start()
        elif isinstance(msg, protocol.PullChunk):
            if msg.data is None:
                # raw body frame follows NOW on this channel; land it
                # before the next recv
                self._pull_client.on_chunk_raw(msg, self._head)
            else:
                self._pull_client.on_chunk(msg)
        elif isinstance(msg, (protocol.GetReply, protocol.WaitReply,
                              protocol.SubmitReply, protocol.ActorCallReply,
                              protocol.ErrorReply)):
            self._route_reply(msg)
        elif isinstance(msg, protocol.FreeObjectNode):
            self._free_local(msg.object_id)
        elif isinstance(msg, protocol.DumpStack):
            # fan out to this host's workers; replies ride back up
            with self.lock:
                targets = [w for w in self.workers.values()
                           if w.alive and (msg.worker_id is None
                                           or w.worker_id == msg.worker_id)]
            for w in targets:
                w.send(msg)
        elif isinstance(msg, protocol.SetTracing):
            if msg.enabled:
                from ray_tpu.util import tracing as _tracing
                _tracing._enable_local()   # future spawns inherit the env
            with self.lock:
                targets = [w for w in self.workers.values() if w.alive]
            for w in targets:
                w.send(msg)
        elif isinstance(msg, protocol.KillActorOnNode):
            with self.lock:
                w = self.actors.get(msg.actor_id)
            if w is not None and w.proc is not None:
                try:
                    w.proc.terminate()
                except OSError:
                    pass
        elif isinstance(msg, (protocol.KillNode, protocol.KillWorker)):
            self._die()
        else:
            logger.warning("unknown head message %r", type(msg))

    def _accept_loop(self, listener=None):
        listener = listener or self._listener
        while not self._shutdown:
            try:
                conn = listener.accept()
            except Exception:
                if self._shutdown:
                    return
                time.sleep(0.05)
                continue
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def _serve_conn(self, conn):
        try:
            reg = conn.recv()
        except (EOFError, OSError, TypeError):
            return
        if isinstance(reg, protocol.RegisterWorker):
            with self.lock:
                w = self.workers.get(reg.worker_id)
                if w is None:
                    w = _DWorker(reg.worker_id, conn)
                    self.workers[reg.worker_id] = w
                else:
                    w.conn = conn
                w.alive = True
                w.pid = reg.pid
                self.cv.notify_all()
            self._worker_loop(w)
        elif isinstance(reg, protocol.RegisterPeer):
            psend = protocol.SafeConn(conn)
            raw = (conn, psend._lock)
            while True:
                try:
                    msg = conn.recv()
                except (EOFError, OSError, TypeError):
                    return
                if isinstance(msg, protocol.PullRequest):
                    threading.Thread(target=self._serve_pull,
                                     args=(raw, msg), daemon=True).start()
        else:
            conn.close()

    # ------------------------------------------------------------------
    # worker-facing protocol (same surface the head offers its workers)
    # ------------------------------------------------------------------

    def _worker_loop(self, w: _DWorker):
        while True:
            try:
                msg = w.conn.recv()
            except (EOFError, OSError, TypeError):
                self._on_worker_death(w)
                return
            try:
                self._handle_worker(w, msg)
            except Exception:
                logger.exception("error handling %r from %s", type(msg),
                                 w.worker_id)

    def _handle_worker(self, w: _DWorker, msg):
        if isinstance(msg, protocol.TaskDone):
            self._on_task_done(w, msg)
        elif isinstance(msg, protocol.StackDumpReply):
            self._head_send(msg)     # forward up to the collector
        elif isinstance(msg, protocol.PutRequest):
            with self.lock:
                if msg.desc.inline is None:
                    self._objs[msg.object_id] = msg.desc
                    self._origin[msg.object_id] = w.worker_id
            self._head_send(protocol.PutRequest(
                msg.object_id, self._tag(msg.desc), origin=w.worker_id))
        elif isinstance(msg, protocol.GetRequest):
            hreq = next(self._req)
            with self.lock:
                # resource release is only attributable when exactly one
                # task is in flight on this worker (a concurrent actor's
                # GetRequest doesn't say which method blocked)
                task_id = (next(iter(w.inflight))
                           if len(w.inflight) == 1 else None)
                self._proxy[hreq] = ("get", w, msg.req_id, task_id)
            if task_id is not None:
                self._head_send(protocol.NodeWorkerBlocked(task_id, True))
            self._head_send(protocol.GetRequest(
                hreq, msg.object_ids, msg.timeout))
        elif (isinstance(msg, protocol.SubmitRequest)
                and msg.seq is not None):
            self._on_pipelined_submit(w, msg)
        elif isinstance(msg, (protocol.WaitRequest, protocol.SubmitRequest,
                              protocol.ActorCallRequest)):
            hreq = next(self._req)
            with self.lock:
                self._proxy[hreq] = ("fwd", w, msg.req_id, None)
            if isinstance(msg, protocol.SubmitRequest):
                # identify the real submitter so the head keys the implicit
                # holds on its fresh return refs by the right worker id
                fwd = replace(msg, req_id=hreq, submitter=w.worker_id)
            else:
                fwd = replace(msg, req_id=hreq)
            self._head_send(fwd)
        else:
            logger.warning("unknown worker message %r", type(msg))

    _SUBMIT_CREDIT_EVERY = max(1, constants.SUBMIT_WINDOW // 4)

    def _on_pipelined_submit(self, w: _DWorker, msg) -> None:
        """Worker->daemon leg of the pipelined submit stream: the same
        seq state machine the head runs for local workers (in-order:
        apply; duplicate: drop + re-credit; gap: nack once). "Apply"
        here means relay ONCE on the reliable seq-wrapped head channel
        — NodeSeq replay gives daemon->head exactly-once, so the
        worker-side ring never needs to survive a daemon hop."""
        seq = msg.seq
        if seq == w.sub_next:
            w.sub_next = seq + 1
            w.sub_nacked = False
            self._head_send(replace(msg, req_id=-1, seq=None,
                                    submitter=w.worker_id))
            if w.sub_next % self._SUBMIT_CREDIT_EVERY == 0:
                w.send(protocol.SubmitCredit(w.sub_next - 1))
        elif seq < w.sub_next:
            w.send(protocol.SubmitCredit(w.sub_next - 1))
        elif not w.sub_nacked:
            w.sub_nacked = True
            w.send(protocol.SubmitNack(w.sub_next))

    def _head_control(self, method, payload=None,
                      timeout: float | None = None):
        """The daemon's OWN control RPC to the head (distinct from the
        worker-request proxying): e.g. resolving a peer address it was
        never told about."""
        if timeout is None:
            timeout = constants.HEAD_CONTROL_TIMEOUT_S
        hreq = next(self._req)
        box = {"done": False, "result": None, "error": None}
        with self._ctl_cv:
            self._ctl[hreq] = box
        self._head_send(protocol.ActorCallRequest(hreq, method, payload))
        deadline = time.monotonic() + timeout
        with self._ctl_cv:
            while not box["done"]:
                rem = deadline - time.monotonic()
                if rem <= 0 or self._shutdown:
                    self._ctl.pop(hreq, None)
                    raise ObjectLostError(
                        f"head control {method} timed out")
                self._ctl_cv.wait(min(rem, 0.5))
        if box["error"] is not None:
            raise ObjectLostError(
                f"head control {method} failed: {box['error']}")
        return box["result"]

    def _route_reply(self, msg):
        if isinstance(msg, protocol.ActorCallReply):
            with self._ctl_cv:
                box = self._ctl.pop(msg.req_id, None)
                if box is not None:
                    box["result"] = msg.result
                    box["error"] = msg.error
                    box["done"] = True
                    self._ctl_cv.notify_all()
                    return
        with self.lock:
            entry = self._proxy.pop(msg.req_id, None)
        if entry is None:
            return
        kind, w, wreq, task_id = entry
        if isinstance(msg, protocol.ErrorReply):
            if kind == "get":
                w.send(protocol.GetReply(wreq, {}, error=msg.error))
            else:
                w.send(protocol.ErrorReply(wreq, msg.error))
            return
        if kind == "get":
            def _finish():
                if msg.timed_out or msg.error is not None:
                    reply = protocol.GetReply(wreq, {}, msg.timed_out,
                                              msg.error)
                else:
                    try:
                        locs = {oid: self._ensure_local(d)
                                for oid, d in msg.locations.items()}
                        reply = protocol.GetReply(wreq, locs)
                    except (ObjectLostError, OSError) as e:
                        # OSError: a peer daemon died mid-pull (connect or
                        # stream failure) — must still answer the worker
                        reply = protocol.GetReply(
                            wreq, {}, error=f"ObjectLostError: {e}")
                if task_id is not None:
                    self._head_send(
                        protocol.NodeWorkerBlocked(task_id, False))
                w.send(reply)
            threading.Thread(target=_finish, daemon=True).start()
        else:
            w.send(replace(msg, req_id=wreq))

    # ------------------------------------------------------------------
    # task execution
    # ------------------------------------------------------------------

    def _tag(self, desc: Descriptor) -> Descriptor:
        if desc.inline is not None:
            return desc
        return replace(desc, node=self.node_id)

    def _run_lease(self, lease: protocol.LeaseTask):
        spec = lease.spec
        with self.lock:
            self.peer_addrs.update(lease.peer_addrs)
        try:
            arg_locs = {oid: self._ensure_local(d)
                        for oid, d in lease.arg_locations.items()}
        except (ObjectLostError, OSError) as e:
            self._send_terminal(spec.task_id, protocol.NodeTaskFailed(
                spec.task_id, f"dependency pull failed: {e}"))
            return
        if spec.actor_id is not None and not spec.actor_creation:
            with self.cv:
                deadline = time.monotonic() + constants.ACTOR_LEASE_WAIT_S
                w = self.actors.get(spec.actor_id)
                while w is None or not w.alive:
                    rem = deadline - time.monotonic()
                    if rem <= 0 or self._shutdown:
                        self._send_terminal(
                            spec.task_id, protocol.NodeTaskFailed(
                                spec.task_id,
                                "actor worker not on this node"))
                        return
                    self.cv.wait(min(rem, 0.2))
                    w = self.actors.get(spec.actor_id)
        elif spec.actor_creation:
            try:
                w = self._spawn_worker("actor", lease.tpu_chips,
                                       spec.runtime_env)
            except RuntimeEnvSetupError as e:
                # actor lifecycle runs through NodeActorDied (a plain
                # NodeTaskFailed for a creation task would strand the
                # actor in PENDING forever on the head)
                self._send_terminal(spec.task_id, protocol.NodeActorDied(
                    spec.actor_id, f"runtime env setup failed: {e}"))
                return
            if w is None:
                self._send_terminal(spec.task_id, protocol.NodeActorDied(
                    spec.actor_id, "actor worker failed to start"))
                return
            w.actor_id = spec.actor_id
            with self.cv:
                self.actors[spec.actor_id] = w
                self.cv.notify_all()
        elif spec.resources.get("TPU", 0) > 0 or not _env_trivial(spec):
            try:
                w = self._spawn_worker("dedicated", lease.tpu_chips,
                                       spec.runtime_env)
            except RuntimeEnvSetupError as e:
                self._send_terminal(spec.task_id, protocol.NodeTaskFailed(
                    spec.task_id, f"runtime env setup failed: {e}"))
                return
            if w is None:
                self._send_terminal(spec.task_id, protocol.NodeTaskFailed(
                    spec.task_id, "dedicated worker failed to start"))
                return
        else:
            with self.lock:
                w = next((x for x in self.workers.values()
                          if x.kind == "generic" and x.idle and x.alive),
                         None)
                if w is not None:
                    w.idle = False
            if w is None:
                try:
                    w = self._spawn_worker("generic", None, None)
                except RuntimeEnvSetupError:
                    w = None
                if w is None:
                    self._send_terminal(spec.task_id, protocol.NodeTaskFailed(
                        spec.task_id, "worker failed to start"))
                    return
        with self.lock:
            w.inflight[spec.task_id] = spec
            if spec.function_id in w.known_functions:
                spec = protocol.TaskSpec(
                    **{**spec.__dict__, "function_blob": None})
            else:
                w.known_functions.add(spec.function_id)
        w.send(protocol.PushTask(spec=spec, arg_locations=arg_locs))

    def _spawn_worker(self, kind, chips, runtime_env):
        """Raises RuntimeEnvSetupError if the env can't materialize;
        returns None on registration timeout/startup crash."""
        wid = ids.new_worker_id()
        w = _DWorker(wid, kind=kind)
        with self.lock:
            self.workers[wid] = w
        env = spawn.worker_env(chips=chips or None, runtime_env=runtime_env)
        env["RAY_TPU_NODE_ID"] = self.node_id
        try:
            env, python_exe, cwd, cmd_prefix = \
                spawn.setup_runtime_env(runtime_env, env)
        except RuntimeEnvSetupError:
            with self.lock:
                self.workers.pop(wid, None)
            raise
        w.proc = spawn.spawn_worker_proc(
            self.address, self.authkey, wid, env, python_exe, cwd,
            log_dir=os.path.join(self.node_dir, "logs"),
            cmd_prefix=cmd_prefix)
        deadline = time.monotonic() + constants.WORKER_REGISTER_TIMEOUT_S
        with self.cv:
            while not w.alive:
                rem = deadline - time.monotonic()
                if rem <= 0 or self._shutdown:
                    self.workers.pop(wid, None)
                    return None
                if w.proc.poll() is not None:
                    self.workers.pop(wid, None)
                    return None
                self.cv.wait(min(rem, 0.2))
        return w

    def _on_task_done(self, w: _DWorker, msg: protocol.TaskDone):
        retire = None
        with self.lock:
            spec = w.inflight.pop(msg.task_id, None)
            if spec is None:
                logger.warning("TaskDone for unknown task %s", msg.task_id)
                return
            tagged = []
            for oid, desc in zip(spec.return_ids, msg.return_descs):
                if desc.inline is None:
                    self._objs[oid] = desc
                    self._origin[oid] = w.worker_id
                tagged.append(self._tag(desc))
            if w.kind == "dedicated":
                retire = w
            elif w.kind == "generic":
                w.idle = True
        self._send_terminal(msg.task_id, protocol.NodeTaskDone(
            task_id=msg.task_id, return_descs=tagged, error=msg.error,
            actor_ready=msg.actor_ready,
            exec_start_ts=msg.exec_start_ts, exec_end_ts=msg.exec_end_ts,
            spans=msg.spans))
        if retire is not None:
            retire.send(protocol.KillWorker())
            with self.lock:
                self.workers.pop(retire.worker_id, None)

    def _on_worker_death(self, w: _DWorker):
        with self.lock:
            if not w.alive and not w.inflight:
                self.workers.pop(w.worker_id, None)
                return
            w.alive = False
            w.idle = False
            self.workers.pop(w.worker_id, None)
            inflight, w.inflight = w.inflight, {}
            actor_id = w.actor_id
            if actor_id is not None:
                self.actors.pop(actor_id, None)
            # Reclaim the dead process's arena pins; adopt the owner pin of
            # every live object it put first (same order as the head,
            # node.py _on_worker_death).
            pid = getattr(w.proc, "pid", None)
            if pid is not None:
                for oid, origin in list(self._origin.items()):
                    if origin != w.worker_id:
                        continue
                    desc = self._objs.get(oid)
                    if desc is not None and desc.arena:
                        self.store.adopt(oid)
                    self._origin[oid] = "daemon"
                self.store.release_all_pins(pid)
        self._head_send(protocol.NodeWorkerGone(w.worker_id))
        if actor_id is not None:
            seq = self._head_send(protocol.NodeActorDied(
                actor_id, "worker process died"))
            # the actor-death notice is terminal for every lease that was
            # running on the actor worker (the head requeues them through
            # its actor restart path)
            for tid in inflight:
                self._lease_terminal(tid, seq)
        else:
            for tid in inflight:
                self._send_terminal(tid, protocol.NodeTaskFailed(
                    tid, "worker died while running task"))

    # ------------------------------------------------------------------
    # object data plane
    # ------------------------------------------------------------------

    def _ensure_local(self, desc: Descriptor) -> Descriptor:
        if desc.inline is not None or desc.node == self.node_id:
            return desc
        oid = desc.object_id
        with self.cv:
            while True:
                c = self._copies.get(oid)
                if c is not None:
                    return c
                if oid not in self._pulling:
                    self._pulling.add(oid)
                    break
                self.cv.wait(0.2)
        seal_box = {}

        def alloc(total: int):
            buf, seal = self.store.create_serialized(oid, total)
            if buf is not None:
                seal_box["seal"] = seal
            return buf

        try:
            # on pull failure the PullClient owns releasing the arena
            # allocation (a late in-flight frame may still be landing in
            # it — freeing here would corrupt whatever recycles the
            # block); we only seal on success
            payload, in_arena = self._pull(
                desc.node, oid, alloc,
                cleanup=lambda: self.store.abort_create(oid))
            if in_arena:
                # bytes landed straight in the arena: seal, done — the
                # pull WAS the put (zero staging copies)
                local = seal_box["seal"]()
            else:
                local = self.store.put_serialized(oid, payload)
            # publish BEFORE dropping the _pulling claim, or a waiter can
            # wake to no-copy/no-claim and start a duplicate pull
            with self.lock:
                self._copies[oid] = local
        finally:
            with self.cv:
                self._pulling.discard(oid)
                self.cv.notify_all()
        self._head_send(protocol.ObjectCopyNote(
            oid, self.node_id, self._tag(local)))
        return local

    def _peer_send(self, node_id: str):
        with self.lock:
            entry = self._peers.get(node_id)
            addr = self.peer_addrs.get(node_id)
        if entry is not None:
            return entry[0]
        if addr is None:
            # never told about this node (it joined after our last lease):
            # ask the head's membership table
            addr = self._head_control("node_address", node_id)
            if addr is None:
                raise ObjectLostError(f"no address for node {node_id}")
            with self.lock:
                self.peer_addrs[node_id] = addr
        conn = netaddr.client(addr, self.authkey)
        send = protocol.SafeConn(conn)
        send(protocol.RegisterPeer(self.node_id))

        def reader(_c=conn):
            while True:
                try:
                    msg = _c.recv()
                except (EOFError, OSError, TypeError):
                    return
                if isinstance(msg, protocol.PullChunk):
                    if msg.data is None:
                        self._pull_client.on_chunk_raw(msg, _c)
                    else:
                        self._pull_client.on_chunk(msg)
        threading.Thread(target=reader, daemon=True,
                         name=f"peer-{node_id}").start()
        with self.lock:
            self._peers[node_id] = (send, conn)
        return send

    def _pull(self, source_node: str | None, oid: str, alloc=None,
              cleanup=None):
        """-> (payload, landed_in_alloc). Outbound pull REQUESTS stay
        reliable on purpose (a blip-swallowed request hangs the puller);
        the chunk replies are the lossy part."""
        if source_node is None:
            send = self._head_send
        else:
            send = self._peer_send(source_node)
        return self._pull_client.pull_into(send, oid, alloc=alloc,
                                           cleanup=cleanup)

    def _serve_pull(self, raw, msg: protocol.PullRequest):
        with self.lock:
            desc = self._objs.get(msg.object_id) or \
                self._copies.get(msg.object_id)
        if desc is None:
            serve_pull(raw, msg, None)
            return
        try:
            payload = self.store.raw_view(desc)
        except (ObjectLostError, OSError) as e:
            payload = e
        serve_pull(raw, msg, payload)

    def _spill_loop(self):
        """Above the arena high-water mark, move sealed local objects to
        the disk spill dir and re-register their descriptors with the head
        (LocalObjectManager equivalent on the daemon's own store)."""
        while not self._shutdown:
            time.sleep(constants.SPILL_PASS_INTERVAL_S)
            try:
                self._maybe_spill()
            except Exception:
                logger.exception("daemon spill pass failed")
            try:
                # reclaim condemned pull buffers even if this node never
                # pulls again (the sweep otherwise only runs on the next
                # pull / abort_all)
                self._pull_client.sweep()
            except Exception:
                logger.exception("tombstone sweep failed")

    def _maybe_spill(self):
        from ray_tpu._private.spill import run_spill_pass

        def candidates():
            with self.lock:
                return [(oid, d) for oid, d in self._objs.items()
                        if d.arena]

        def try_swap(oid, old, new):
            with self.lock:
                if self._objs.get(oid) != old:
                    return False
                self._objs[oid] = new
                origin = self._origin.get(oid)
                self._origin[oid] = "daemon"
                w = self.workers.get(origin) if origin else None
            # refresh the head's directory so future arg_locations carry
            # the file-backed descriptor
            self._head_send(protocol.PutRequest(oid, self._tag(new)))
            return w

        run_spill_pass(self.store, candidates, try_swap)

    def _free_local(self, oid: str):
        with self.lock:
            desc = self._objs.pop(oid, None)
            copy = self._copies.pop(oid, None)
            self._origin.pop(oid, None)
            workers = [w for w in self.workers.values() if w.alive]
        gone = desc or copy
        for d in (desc, copy):
            if d is not None:
                try:
                    self.store.delete(d)
                except Exception:
                    pass
        if gone is not None:
            # EVERY worker that read the object holds a pinned view of
            # the arena block (zero-copy reads) or a cached mmap; until
            # they all drop it the block is condemned, its offset can't
            # be reused, and the arena grows cold pages forever. Fan the
            # free out to the whole local pool (no-op for workers that
            # never read it) — the origin-only version leaked reader
            # pins.
            for w in workers:
                w.send(protocol.FreeObject(oid, gone))

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def _die(self):
        with self.lock:
            if self._shutdown:
                return
            self._shutdown = True
            workers = list(self.workers.values())
        for w in workers:
            w.send(protocol.KillWorker())
        for lst in (self._listener, self._peer_listener):
            if lst is None:
                continue
            try:
                lst.close()
            except OSError:
                pass
        spawn.stop_procs([w.proc for w in workers])
        self.store.purge_spill()
        self.store.close()
        if os.environ.get("RAY_TPU_NODE_DIR") is None and \
                os.path.basename(os.path.dirname(self.node_dir)) != "nodes":
            # we created this node dir ourselves (cross-machine TCP join):
            # nobody else will sweep it
            import shutil
            shutil.rmtree(self.node_dir, ignore_errors=True)
        os._exit(0)


def main():
    head_address = sys.argv[1]
    node_id = sys.argv[2]
    resources = json.loads(sys.argv[3])
    num_tpus = int(sys.argv[4]) if len(sys.argv) > 4 else 0
    logging.basicConfig(level=logging.INFO)
    daemon = HostDaemon(head_address, node_id, resources, num_tpus)
    daemon.head_loop()


if __name__ == "__main__":
    main()
