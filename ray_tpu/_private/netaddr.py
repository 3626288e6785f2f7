"""Address plumbing + batched framing for the control/data channels.

Channels ride `multiprocessing.connection` with HMAC authkey handshakes;
this module lets every channel be EITHER a UNIX socket (same-host: workers
to their daemon, single-host sessions) or TCP ("host:port" — daemons and
client drivers joining a head across machines, peer-to-peer object pulls
between hosts). The reference splits the same way: UDS to the local
raylet, gRPC over TCP for everything cross-host.

Every channel built here additionally carries the coalescing frame layer
(`BatchedConnection`): logical `send()`s land in an outbound queue that a
per-channel flusher drains into ONE wire pickle per flush, and `recv()`
unpacks frames back into individual messages. Bursts (completion storms,
lease fan-outs, metrics piggybacks) collapse from N syscalls + N pickles
into one of each, while per-channel FIFO order and per-logical-message
fault injection (`faults.maybe_wrap_connection` wraps OUTSIDE the frame
layer) are preserved. `RAY_TPU_CHANNEL_BATCHING=0` turns coalescing off;
the receive side always understands both framings, so mixed settings
across processes stay wire-compatible.
"""

from __future__ import annotations

import collections
import os
import socket
import threading
import time
from multiprocessing import connection

from ray_tpu._private import config
from ray_tpu._private.constants import CHANNEL_QUEUE_CAP
from ray_tpu.util import faults


def is_tcp(address) -> bool:
    if isinstance(address, tuple):
        return True
    return (isinstance(address, str) and ":" in address
            and not address.startswith("/"))


def parse(address):
    """'host:port' -> (host, port); path/tuple passes through."""
    if isinstance(address, tuple) or not is_tcp(address):
        return address
    host, _, port = address.rpartition(":")
    return (host or "127.0.0.1", int(port))


def fmt(address) -> str:
    if isinstance(address, tuple):
        return f"{address[0]}:{address[1]}"
    return address


class _Batch:
    """Wire frame carrying several logical messages in one send. Plain
    pickle-friendly holder; both ends of every channel run this module,
    so the class is always importable at unpickle time."""

    __slots__ = ("msgs",)

    def __init__(self, msgs):
        self.msgs = msgs


class BatchedConnection:
    """Coalescing wrapper over one mp.Connection.

    Send side: `send()` appends to an outbound deque and wakes the
    flusher thread, which drains the WHOLE deque into a single wire
    frame (`_Batch`) per pass — so messages queued while a previous
    frame is on the wire ride the next frame together. `send_bytes`
    (the PullChunk zero-copy raw frame) first flushes pending logical
    messages under the wire lock, then writes the raw frame under the
    same hold: a chunk header queued immediately before is guaranteed
    to be the wire frame right before its payload.

    Recv side: single-reader (every channel here has exactly one reader
    thread). Frames are unpacked into an inbound deque that `recv()`
    drains FIFO; `recv_bytes`/`recv_bytes_into` bypass the deque and
    read the wire directly, which is exactly the raw-frame adjacency
    the pull plane relies on.

    Wire errors on the flusher are latched and re-raised from the next
    `send()` so `protocol.safe_send` sees the usual OSError surface.
    """

    def __init__(self, conn, coalesce: bool | None = None):
        self._raw = conn
        if coalesce is None:
            coalesce = config.get("CHANNEL_BATCHING")
        self._coalesce = bool(coalesce)
        self._in: collections.deque = collections.deque()
        self._out: collections.deque = collections.deque()
        self._qcv = threading.Condition()
        self._wire_lock = threading.Lock()
        self._err: BaseException | None = None
        self._closed = False
        self._flushing = False   # a popped batch is still on the wire
        self._reader = None      # ident of the one thread that calls recv()
        if self._coalesce:
            threading.Thread(target=self._flush_loop, daemon=True,
                             name="netaddr-flush").start()

    # ---- send side --------------------------------------------------------

    def send(self, msg) -> None:
        """Thread-safe; callers hold no lock of their own across it."""
        if not self._coalesce:
            with self._wire_lock:
                self._raw.send(msg)
            return
        direct = False
        with self._qcv:
            if self._err is not None:
                raise self._err
            if self._closed:
                raise OSError("connection is closed")
            # Opportunistic direct write: when nothing is queued and no
            # popped batch is in flight (`_flushing` covers the window
            # where the flusher holds messages that are no longer in
            # `_out`), the wire is keeping up — write inline and skip
            # the flusher handoff entirely. Sparse senders (a worker's
            # one TaskDone per task, the head's per-dispatch PushTask)
            # pay zero thread wakes; only senders that outrun the wire
            # fall into the queue, which is exactly when coalescing
            # pays. The try-acquire is deadlock-free against the
            # flusher's wire->queue order, and FIFO holds: the wire
            # lock is taken while the queue is provably empty, so no
            # earlier logical message can be written after this one.
            # Not for this channel's reader thread: a write can wait for
            # the peer to read, and a peer in the mirror state waits for
            # THIS thread to read; queued, the flusher waits in its place.
            if (not self._out and not self._flushing
                    and threading.get_ident() != self._reader
                    and self._wire_lock.acquire(blocking=False)):
                direct = True
            else:
                while len(self._out) >= CHANNEL_QUEUE_CAP:
                    # a raw full pipe would block the sender here too
                    self._qcv.wait(0.05)
                    if self._err is not None:
                        raise self._err
                    if self._closed:
                        raise OSError("connection is closed")
                self._out.append(msg)
                self._qcv.notify_all()
        if direct:
            try:
                self._raw.send(msg)
            except Exception as e:
                err = e if isinstance(e, OSError) else OSError(str(e))
                with self._qcv:
                    self._err = err
                    self._qcv.notify_all()
                raise err
            finally:
                self._wire_lock.release()

    def _pop_pending(self) -> list:
        with self._qcv:
            if not self._out:
                return []
            batch = list(self._out)
            self._out.clear()
            self._flushing = True
            self._qcv.notify_all()   # backpressure waiters
            return batch

    def _done_flushing(self) -> None:
        with self._qcv:
            self._flushing = False
            self._qcv.notify_all()

    def _send_frame_locked(self, batch: list) -> None:
        if len(batch) == 1:
            self._raw.send(batch[0])
        else:
            self._raw.send(_Batch(batch))

    def _flush_loop(self) -> None:
        while True:
            with self._qcv:
                while not self._out and not self._closed:
                    self._qcv.wait()
                if self._closed and not self._out:
                    return
            while True:
                batch = self._pop_pending()
                if not batch:
                    break
                try:
                    with self._wire_lock:
                        self._send_frame_locked(batch)
                except Exception as e:
                    with self._qcv:
                        self._err = (e if isinstance(e, OSError)
                                     else OSError(str(e)))
                        self._flushing = False
                        self._qcv.notify_all()
                    return
                finally:
                    self._done_flushing()

    def send_bytes(self, buf, offset: int = 0, size=None) -> None:
        with self._wire_lock:
            batch = self._pop_pending()
            try:
                if batch:
                    self._send_frame_locked(batch)
                if size is None:
                    self._raw.send_bytes(buf, offset)
                else:
                    self._raw.send_bytes(buf, offset, size)
            finally:
                if batch:
                    self._done_flushing()

    def flush(self, timeout: float = 1.0) -> None:
        """Best-effort: wait until queued messages reached the wire."""
        deadline = time.monotonic() + timeout
        with self._qcv:
            while (self._out or self._flushing) and self._err is None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return
                self._qcv.wait(remaining)

    # ---- recv side (single reader) ----------------------------------------

    def recv(self):
        if self._in:
            return self._in.popleft()
        self._reader = threading.get_ident()
        msg = self._raw.recv()
        if type(msg) is _Batch:
            self._in.extend(msg.msgs)
            return self._in.popleft()
        return msg

    def poll(self, timeout: float = 0.0) -> bool:
        if self._in:
            return True
        return self._raw.poll(timeout)

    def recv_bytes(self, maxlength=None):
        if maxlength is None:
            return self._raw.recv_bytes()
        return self._raw.recv_bytes(maxlength)

    def recv_bytes_into(self, buf, offset: int = 0) -> int:
        return self._raw.recv_bytes_into(buf, offset)

    # ---- lifecycle --------------------------------------------------------

    def close(self) -> None:
        self.flush(timeout=0.5)
        with self._qcv:
            self._closed = True
            self._qcv.notify_all()
        self._raw.close()

    def fileno(self) -> int:
        return self._raw.fileno()

    @property
    def closed(self):
        return getattr(self._raw, "closed", self._closed)

    def __getattr__(self, name):
        return getattr(self._raw, name)


def hang_up(conn) -> None:
    """Shut a channel's socket down in both directions without waiting
    for the peer: its reader sees EOF, and a sender blocked on a pipe
    the peer no longer drains (here or there) gets EPIPE. The reader
    thread of `conn` still owns the close; None (a worker that has not
    registered yet) is nothing to hang up."""
    try:
        s = socket.socket(fileno=os.dup(conn.fileno()))
    except (OSError, ValueError, AttributeError):
        return                     # never opened, or closed already
    try:
        s.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    finally:
        s.close()


class _BatchingListener:
    """netaddr.listener wrapper: accepted connections get the frame
    layer, so the server side of every channel can unpack `_Batch`
    frames regardless of the client's coalescing setting."""

    def __init__(self, inner):
        self._inner = inner

    def accept(self):
        return BatchedConnection(self._inner.accept())

    @property
    def address(self):
        return self._inner.address

    def close(self):
        return self._inner.close()

    def __getattr__(self, name):
        return getattr(self._inner, name)


def client(address, authkey: bytes):
    addr = parse(address)
    family = "AF_INET" if isinstance(addr, tuple) else "AF_UNIX"
    conn = connection.Client(addr, family=family, authkey=authkey)
    # Frame layer first, fault proxy OUTSIDE it: while a FaultPlan with
    # netaddr.* sites is installed, drop/delay decisions and visit
    # numbering stay per LOGICAL message (the batch framing underneath
    # is invisible to the plan). The authkey handshake above always
    # runs on the raw socket.
    return faults.maybe_wrap_connection(BatchedConnection(conn), "netaddr")


def listener(address, authkey: bytes):
    addr = parse(address)
    family = "AF_INET" if isinstance(addr, tuple) else "AF_UNIX"
    return _BatchingListener(
        connection.Listener(addr, family=family, authkey=authkey))


def bound_address(listener) -> str:
    """'host:port' (or path) a peer should dial for this listener; resolves
    ephemeral ports and 0.0.0.0 binds to the advertised host."""
    addr = listener.address
    if isinstance(addr, tuple):
        host, port = addr
        if host in ("0.0.0.0", ""):
            host = advertise_host()
        return f"{host}:{port}"
    return addr


def local_endpoint_host(conn) -> str | None:
    """The local IP of an established TCP connection — exactly the
    interface that routes to the remote side, so it's the right host for
    this machine to advertise back to it."""
    try:
        fd = os.dup(conn.fileno())
        s = socket.socket(fileno=fd)
        try:
            name = s.getsockname()
        finally:
            s.close()
        if isinstance(name, tuple):
            return name[0]
    except OSError:
        pass
    return None


# advertise_host is on the connect path of every channel; the UDP-socket
# interface probe is memoized (it cannot change without the host's
# routing table changing) and the NODE_IP override is re-read per call —
# an env read, not a socket. config.reset_caches() flushes the probe.
_advertise_lock = threading.Lock()
_advertised: str | None = None


@config.on_reset
def _reset_advertise_cache() -> None:
    global _advertised
    with _advertise_lock:
        _advertised = None


def advertise_host() -> str:
    """The address other machines should dial for listeners bound on
    0.0.0.0 (reference: node_ip_address detection in services.py)."""
    override = config.get("NODE_IP")
    if override:
        return override
    global _advertised
    host = _advertised
    if host is not None:
        return host
    with _advertise_lock:
        if _advertised is None:
            try:
                # a UDP "connection" to a public address picks the
                # outbound iface without sending anything
                s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
                s.connect(("8.8.8.8", 80))
                _advertised = s.getsockname()[0]
                s.close()
            except OSError:
                _advertised = "127.0.0.1"
        return _advertised
