"""ray_tpu — a TPU-native distributed compute & ML framework.

Public core API, counterpart of the reference's `ray` package surface
(`python/ray/_private/worker.py`: init :1106, get :2408, put :2517,
wait :2580, remote :3022, get_actor :2711, kill :2746, cancel :2777).

Import stays light: JAX and the ML libraries (`ray_tpu.train`, `.tune`,
`.data`, `.parallel`, `.models`) load lazily so spawning a worker process
costs milliseconds, not a JAX import.
"""

from __future__ import annotations

import glob
import os

from ray_tpu._private import constants, ids
from ray_tpu._private import worker as _worker
from ray_tpu._private.worker import ObjectRef, get, put, wait
from ray_tpu.actor import ActorClass, ActorHandle, get_actor, kill, method
from ray_tpu.remote_function import RemoteFunction
from ray_tpu.runtime_context import get_runtime_context
from ray_tpu import exceptions

__version__ = "0.1.0"

__all__ = [
    "init", "shutdown", "is_initialized", "remote", "get", "put", "wait",
    "get_actor", "kill", "cancel", "free", "method", "ObjectRef",
    "ActorHandle",
    "available_resources", "cluster_resources", "get_runtime_context",
    "exceptions", "__version__",
]


def _detect_tpu_chips() -> int:
    """Count local TPU chips without importing JAX (the reference detects
    GPUs via NVML-free heuristics similarly, _private/resource_spec.py)."""
    env = os.environ.get("RAY_TPU_NUM_TPUS")
    if env is not None:
        return int(env)
    chips = glob.glob("/dev/accel*") or glob.glob("/dev/vfio/*")
    chips = [c for c in chips if not c.endswith("vfio")]
    return len(chips)


def init(num_cpus: int | None = None,
         num_tpus: int | None = None,
         resources: dict | None = None,
         *,
         address: str | None = None,
         ignore_reinit_error: bool = False,
         namespace: str | None = None,
         logging_level: str = "INFO",
         dashboard_port: int | None = None,
         log_to_driver: bool | None = None,
         **kwargs):
    """Start a session (driver mode), or — with `address` — connect this
    process as a SECOND driver to an existing session (the reference's Ray
    Client, `util/client/worker.py:81`: `ray.init("ray://...")`).

    `address` accepts "auto" (newest live session on this host), a session
    directory, or its node.sock path. Client drivers get the full
    get/put/remote/actor API over the worker protocol; shutdown() just
    disconnects them — the session stays up.
    """
    if address is not None:
        dropped = [name for name, v in (
            ("num_cpus", num_cpus), ("num_tpus", num_tpus),
            ("resources", resources), ("namespace", namespace),
            ("dashboard_port", dashboard_port)) if v is not None]
        if dropped or kwargs:
            raise ValueError(
                f"init(address=...) joins an EXISTING session; "
                f"{dropped + sorted(kwargs)} cannot be configured from a "
                "client driver")
        return _connect_client(address, ignore_reinit_error, log_to_driver)
    if _worker.is_initialized():
        if ignore_reinit_error:
            return _worker.get_client()
        raise RuntimeError("ray_tpu.init() called twice "
                           "(pass ignore_reinit_error=True to allow)")
    if num_cpus is None:
        num_cpus = os.cpu_count() or 1
    if num_tpus is None:
        num_tpus = _detect_tpu_chips()
    total = {"CPU": float(num_cpus)}
    if num_tpus:
        total["TPU"] = float(num_tpus)
    for k, v in (resources or {}).items():
        total[k] = float(v)

    from ray_tpu._private.node import NodeServer
    if constants.GC_STALE_SESSIONS:
        _gc_stale_sessions()
    session_dir = os.path.join(
        constants.SHM_ROOT,
        constants.SESSION_PREFIX + ids.new_node_id())
    os.makedirs(session_dir, exist_ok=True)
    # Claim the directory at once: NodeServer writes the same pidfile
    # only after it has opened the object store, and a session starting
    # beside this one meanwhile must not collect the directory as stale.
    with open(os.path.join(session_dir, "driver.pid"), "w") as f:
        f.write(str(os.getpid()))
    node = NodeServer(total, session_dir, num_tpu_chips=int(num_tpus or 0))
    client = _worker.connect_driver_mode(node)
    if log_to_driver is None:
        # jobs stream their cluster's logs by default (the job log file
        # then carries worker output); interactive drivers opt in
        log_to_driver = os.environ.get("RAY_TPU_LOG_TO_DRIVER") == "1"
    if log_to_driver:
        client.control("log_subscribe")
    if dashboard_port is not None:
        from ray_tpu.dashboard import start_dashboard
        try:
            start_dashboard(dashboard_port)
        except BaseException:
            # don't leak a live, un-reinitializable session behind a
            # failed init (e.g. dashboard port already in use)
            shutdown()
            raise
    return client


def _connect_client(address: str, ignore_reinit_error: bool = False,
                    log_to_driver: bool | None = None):
    """Join an existing session as a remote driver: register on the head's
    socket with an attach-class worker id (never dispatched to) and run
    the full worker protocol — get/put/submit/actors all work."""
    import threading
    import uuid

    if _worker.is_initialized():
        if ignore_reinit_error:
            return _worker.get_client()
        raise RuntimeError("ray_tpu.init() called twice "
                           "(pass ignore_reinit_error=True to allow)")
    from ray_tpu._private import netaddr
    if netaddr.is_tcp(address):
        # cross-machine driver: dial the head's TCP listener; the secret
        # comes from RAY_TPU_AUTHKEY (hex), like the reference's
        # redis-password handoff for remote `ray.init(address=...)`
        key = os.environ.get("RAY_TPU_AUTHKEY")
        if not key:
            raise ConnectionError(
                "joining a remote head over TCP requires RAY_TPU_AUTHKEY "
                "(hex of the session authkey file)")
        sock, authkey = address, bytes.fromhex(key)
    else:
        if address == "auto":
            from ray_tpu._private.attach import find_sessions
            sessions = find_sessions(constants.SHM_ROOT)
            if not sessions:
                raise ConnectionError(
                    f"no live ray_tpu session found under "
                    f"{constants.SHM_ROOT}")
            session_dir = sessions[0]
        elif address.endswith("node.sock"):
            session_dir = os.path.dirname(address)
        else:
            session_dir = address
        sock = os.path.join(session_dir, "node.sock")
        if not os.path.exists(sock):
            raise ConnectionError(f"no session socket at {sock}")
        with open(os.path.join(session_dir, "authkey"), "rb") as f:
            authkey = f.read()
    from ray_tpu._private import protocol
    from ray_tpu._private.worker_main import WorkerRuntime
    wid = f"attach_client_{os.getpid()}_{uuid.uuid4().hex[:6]}"
    rt = WorkerRuntime(sock, wid, authkey, exit_on_disconnect=False)
    rt.send(protocol.RegisterWorker(wid, os.getpid()))
    threading.Thread(target=rt.reader_loop, daemon=True,
                     name="ray_tpu-client-reader").start()
    client = _worker.connect_worker_mode(rt)
    if log_to_driver or (log_to_driver is None and
                         os.environ.get("RAY_TPU_LOG_TO_DRIVER") == "1"):
        client.control("log_subscribe")
    return client


def _younger_than(path: str, seconds: float) -> bool:
    import time
    try:
        return time.time() - os.stat(path).st_mtime < seconds
    except OSError:
        return False


def _gc_stale_sessions():
    """Remove session dirs whose driver process is gone (crash leftovers)."""
    import shutil
    for d in glob.glob(os.path.join(constants.SHM_ROOT,
                                    constants.SESSION_PREFIX + "*")):
        pidfile = os.path.join(d, "driver.pid")
        try:
            with open(pidfile) as f:
                pid = int(f.read().strip())
            os.kill(pid, 0)       # raises if the driver is dead
        except (FileNotFoundError, ValueError, ProcessLookupError) as e:
            if not isinstance(e, ProcessLookupError) and _younger_than(
                    d, 60.0):
                continue          # being created right now, not stale
            for sub in glob.glob(os.path.join(d, "nodes", "*")):
                shutil.rmtree(
                    os.path.join(constants.OBJECT_SPILL_ROOT,
                                 os.path.basename(sub)),
                    ignore_errors=True)
            shutil.rmtree(
                os.path.join(constants.OBJECT_SPILL_ROOT,
                             os.path.basename(d)), ignore_errors=True)
            shutil.rmtree(d, ignore_errors=True)
        except PermissionError:
            pass                  # someone else's live session


def shutdown():
    if not _worker.is_initialized():
        return
    from ray_tpu.dashboard import stop_dashboard
    stop_dashboard()
    client = _worker.get_client()
    if client.mode == "driver":
        client.node.shutdown()
    elif getattr(client, "rt", None) is not None and \
            client.rt.worker_id.startswith("attach_client_"):
        # remote driver: just drop the connection; the session stays up
        client.rt.shutdown = True      # stops the ref-flush loop too
        try:
            client.rt.conn.close()
        except OSError:
            pass
    _worker.disconnect()


def is_initialized() -> bool:
    return _worker.is_initialized()


def remote(*args, **kwargs):
    """`@remote` decorator for functions and classes (reference:
    worker.py:3022). Usable bare or with options:

        @ray_tpu.remote
        def f(x): ...

        @ray_tpu.remote(num_cpus=2, num_tpus=1)
        class Learner: ...
    """
    import inspect

    def make(target, options):
        if inspect.isclass(target):
            return ActorClass(target, options)
        if not callable(target):
            raise TypeError("@remote target must be a function or class")
        return RemoteFunction(target, options)

    if len(args) == 1 and not kwargs and callable(args[0]):
        return make(args[0], {})
    if args:
        raise TypeError("@remote() takes only keyword options")
    return lambda target: make(target, kwargs)


def cancel(ref: ObjectRef, *, force: bool = False, recursive: bool = True):
    """Best-effort cancel of a pending task (reference: worker.py:2777).
    Running tasks are not interrupted in v1."""
    return _worker.get_client().control(
        "cancel", {"object_id": ref._id, "force": force})


def free(refs) -> int:
    """Unconditionally release objects (reference:
    `_private/internal_api.py free()`): the caller asserts nothing will
    read these refs again. Exists for bulk-intermediate lifecycles
    (e.g. shuffle shards) whose refs rode inside other objects and
    therefore escaped normal refcounting; returns how many objects were
    still live."""
    from ray_tpu._private.worker import ObjectRef as _Ref
    oids = [r._id if isinstance(r, _Ref) else str(r) for r in refs]
    return _worker.get_client().control("free_objects", oids)


def cluster_resources() -> dict:
    return _worker.get_client().control("cluster_resources")


def available_resources() -> dict:
    return _worker.get_client().control("available_resources")


def nodes() -> list:
    res = cluster_resources()
    return [{"NodeID": "local", "Alive": True, "Resources": res}]


def timeline(filename: str | None = None):
    """Chrome-trace task timeline (`ray.timeline` counterpart)."""
    from ray_tpu.util import state as _state
    return _state.timeline(filename)
