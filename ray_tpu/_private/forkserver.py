"""Warm-fork worker factory: pay interpreter + module import once.

Counterpart of the reference's prestarted worker pool
(`src/ray/raylet/worker_pool.h:80` + prestart-on-backlog
`node_manager.cc:1885`): cold worker exec on this image costs ~140ms of
imports, which caps actor creation at a few per second. This process
imports the worker module tree ONCE, then forks per request
— a child is live in milliseconds and initializes its own jax backend
lazily if user code ever imports it (fork happens strictly before any
backend exists, the one ordering that makes fork+jax safe).

Only the common case forks: CPU workers with no runtime-env interpreter/
cwd/path overrides. TPU-chip workers (chip scoping is read from the
environment when the runtime starts) and venv workers (different
interpreter) still exec.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
from multiprocessing import connection


def _proc_start(pid: int):
    """Kernel start ticks of `pid` (/proc stat f22, paren-safe), or
    None if it is already gone."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            data = f.read()
        return int(data.rsplit(b")", 1)[1].split()[19])
    except (OSError, ValueError, IndexError):
        return None


def _spawn_child(req: dict) -> int:
    import warnings
    with warnings.catch_warnings():
        # CPython warns on fork-from-multithreaded generically; the
        # factory's extra threads (parent watcher, per-spawner serve
        # loops) only sleep/recv and hold no locks the child touches —
        # the child immediately re-execs worker_main.run on fresh state
        warnings.simplefilter("ignore", DeprecationWarning)
        pid = os.fork()
    if pid != 0:
        return pid
    # ---- child ----
    try:
        signal.signal(signal.SIGCHLD, signal.SIG_DFL)
        os.setsid()                      # own group: group kills don't
        # reach the factory or siblings
        log_path = req.get("log_path")
        devnull = os.open(os.devnull, os.O_RDONLY)
        os.dup2(devnull, 0)
        if log_path:
            fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                         0o644)
            os.dup2(fd, 1)
            os.dup2(fd, 2)
        os.environ.clear()
        os.environ.update(req["env"])
        from ray_tpu._private import ids as _ids
        _ids.reseed()       # forked children must not replay the
        # factory's id stream (duplicate object ids across siblings)
        from ray_tpu._private import worker_main
        worker_main.run(req["address"], req["worker_id"])
        os._exit(0)
    except BaseException:
        import traceback
        traceback.print_exc()
        os._exit(1)


def _watch_parent(ppid: int, sock_path: str):
    """The factory must not outlive its spawner (head/daemon): orphaned
    factories would leak across sessions."""
    import time
    while os.getppid() == ppid:
        time.sleep(1.0)
    try:
        os.unlink(sock_path)
    except OSError:
        pass
    os._exit(0)


def main():
    sock_path = sys.argv[1]
    authkey = bytes.fromhex(os.environ["RAY_TPU_AUTHKEY"])
    # Preload the full worker import tree (the fork dividend); our env
    # carries JAX_PLATFORMS=cpu, so no child reaches for the chip.
    # asyncio matters measurably: this image ships no stdlib .pyc cache,
    # so a cold `import asyncio` (async actor runtime, main_loop) costs
    # ~85ms of bytecode compilation per child without the preload.
    import asyncio  # noqa: F401
    from ray_tpu._private import worker_main  # noqa: F401
    # The kernel reaps an ignored SIGCHLD's children itself. A handler
    # cannot: SIGCHLD goes to the thread that forked (a `_serve` thread),
    # Python runs handlers in the main thread, and that one sleeps in
    # accept(), so a dead worker stayed a zombie, which `ForkedProc.poll`
    # reads as alive, until the next spawner connected.
    signal.signal(signal.SIGCHLD, signal.SIG_IGN)
    # the spawner's pid as it gave it: one that died while this process
    # was still importing has already handed it to another parent
    threading.Thread(target=_watch_parent,
                     args=(int(sys.argv[2]), sock_path), daemon=True).start()
    if os.path.exists(sock_path):
        os.unlink(sock_path)
    with connection.Listener(family="AF_UNIX", address=sock_path,
                             authkey=authkey) as listener:
        # children must not inherit the listener
        os.set_inheritable(listener._listener._socket.fileno(), False)
        # no "ready" print: the factory inherits the spawner's stdio so
        # children without a log file keep a REAL stdout (a pipe nobody
        # drains would deadlock a chatty worker); readiness is simply
        # the socket accepting connections
        while True:
            try:
                conn = listener.accept()
            except (OSError, EOFError):
                return
            threading.Thread(target=_serve, args=(conn,),
                             daemon=True).start()


def _serve(conn):
    """One spawner (head or daemon) per connection; requests are
    serialized per-connection by the caller."""
    while True:
        try:
            req = conn.recv()
        except (EOFError, OSError, TypeError):
            return
        if req is None:       # orderly shutdown
            os._exit(0)
        try:
            pid = _spawn_child(req)
            # start ticks = pid-reuse-proof identity (the factory ignores
            # SIGCHLD, so a bare pid is recyclable the moment the child
            # dies)
            conn.send({"pid": pid, "start": _proc_start(pid)})
        except BaseException as e:
            try:
                conn.send({"error": repr(e)})
            except (OSError, ValueError):
                return


if __name__ == "__main__":
    main()
