"""Worker-process spawning shared by the head NodeServer and HostDaemons.

Counterpart of the reference's worker-command assembly in
`python/ray/_private/services.py` (start_raylet builds the worker command
string the raylet's WorkerPool execs, worker_pool.h:80): environment
scoping (TPU chip visibility, JAX platform forcing) and sys.path
propagation so cloudpickled functions resolve in the child.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time as _time

from ray_tpu._private import constants


# What scopes a worker to its chips; a container runtime env forwards these.
CHIP_SCOPE_VARS = (constants.TPU_VISIBLE_CHIPS_ENV,
                   "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS")


# TPU_CHIPS_PER_PROCESS_BOUNDS by chip count, as tried on a v5e 2x2 host
# with libtpu 0.0.34 (PR 21): "1,2,1" was tried for chips 0,1 only, and
# "2,1,1" is refused there.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def chip_scope_env(chips) -> dict:
    """Variables under which libtpu gives a process exactly `chips` of
    this host (the reference scopes GPUs with CUDA_VISIBLE_DEVICES the
    same way). Two chips of four also need the process bounds: with the
    visible chips alone libtpu still expects the whole host's topology
    ("expected 4, actual: 2"). Several one-chip processes run side by
    side this way, each seeing its chip as device 0."""
    env = {constants.TPU_VISIBLE_CHIPS_ENV: ",".join(map(str, chips))}
    bounds = _CHIP_BOUNDS.get(len(chips))
    if bounds:
        env["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
        env["TPU_PROCESS_BOUNDS"] = "1,1,1"
    return env


def worker_env(chips=None, runtime_env=None) -> dict:
    env = dict(os.environ)
    env["RAY_TPU_WORKER"] = "1"
    # Per-task/actor env overrides first (reference: runtime_env env_vars,
    # _private/runtime_env/) so an explicit JAX_PLATFORMS override wins
    # over the CPU default below.
    overrides = {
        str(k): str(v)
        for k, v in ((runtime_env or {}).get("env_vars") or {}).items()
    }
    env.update(overrides)
    if chips:
        env.update(chip_scope_env(chips))
    elif "JAX_PLATFORMS" not in overrides:
        # Workers must not grab the host's TPU runtime by default: only
        # tasks that requested TPU resources see chips (the reference
        # hides GPUs the same way via CUDA_VISIBLE_DEVICES=""). A chip
        # belongs to one process, so a CPU worker that initialised the
        # TPU backend would take it from the worker it was given to.
        env["JAX_PLATFORMS"] = "cpu"
    return env


def propagate_pythonpath(env: dict) -> dict:
    """Make the child resolve the same modules as this process: cloudpickle
    serializes module-level functions by reference, so the full sys.path
    (including the uninstalled checkout and the user's script dir) is
    propagated (reference: workers inherit the driver's load path /
    working_dir runtime env, services.py).

    Runtime-env paths (RAY_TPU_RUNTIME_ENV_PATHS: working_dir, py_modules,
    pip-venv site-packages) go FIRST — a runtime env must be able to
    shadow the parent's installed packages, or pip:["pkg==2.0"] silently
    resolves to the base image's pkg 1.0."""
    pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    rt_paths = [p for p in env.get(
        "RAY_TPU_RUNTIME_ENV_PATHS", "").split(os.pathsep) if p]
    entries = rt_paths + [pkg_root]
    entries += [p for p in sys.path if p]
    pypath = env.get("PYTHONPATH", "")
    entries += [p for p in pypath.split(os.pathsep) if p]
    seen, uniq = set(), []
    for p in entries:
        if p not in seen:
            seen.add(p)
            uniq.append(p)
    env["PYTHONPATH"] = os.pathsep.join(uniq)
    return env


def worker_log_file(log_dir: str | None, name: str):
    """Open `<log_dir>/<name>.log` for append if per-process log capture
    is on (reference: worker-*.out files under the session dir); None =
    inherit the parent's stdio."""
    from ray_tpu._private import config
    if log_dir is None or not config.get("WORKER_LOG_REDIRECT"):
        return None
    os.makedirs(log_dir, exist_ok=True)
    return open(os.path.join(log_dir, name + ".log"), "ab")


class ForkedProc:
    """Popen-compatible handle for a worker forked by the forkserver.
    The factory ignores SIGCHLD and the kernel reaps the child, so the
    bare pid is recyclable the moment the child dies — every probe and
    signal is therefore guarded by the start-ticks identity recorded at fork
    (signal-0 alone would report a recycled pid as alive forever and
    kill() could SIGKILL an unrelated process)."""

    def __init__(self, pid: int, start_ticks=None):
        self.pid = pid
        self._start = start_ticks
        self._dead = start_ticks is None

    def _same_proc(self) -> bool:
        from ray_tpu._private.forkserver import _proc_start
        return _proc_start(self.pid) == self._start

    def poll(self):
        if self._dead:
            return 0
        if not self._same_proc():
            self._dead = True
            return 0
        return None

    def wait(self, timeout=None):
        import time as _time
        deadline = None if timeout is None else _time.monotonic() + timeout
        while self.poll() is None:
            if deadline is not None and _time.monotonic() > deadline:
                raise subprocess.TimeoutExpired("forked-worker", timeout)
            _time.sleep(0.02)
        return 0

    def _signal(self, sig):
        if self._dead or not self._same_proc():
            self._dead = True
            return
        try:
            os.kill(self.pid, sig)
        except (ProcessLookupError, PermissionError):
            self._dead = True

    def terminate(self):
        import signal as _signal
        self._signal(_signal.SIGTERM)

    def kill(self):
        import signal as _signal
        self._signal(_signal.SIGKILL)


def stop_procs(procs, grace: float = 2.0) -> None:
    """End every process in `procs` (Popen or ForkedProc) in at most
    three waits of `grace` seconds, whatever their number: one wait for
    all that were already asked to leave, SIGTERM to every survivor at
    once and one wait, SIGKILL to what is left and one wait to reap.
    libtpu's SIGTERM handler takes a second to re-raise, so a wait a
    process would cost a session a second a worker."""
    def wait_all(left):
        deadline = _time.monotonic() + grace
        while True:
            left = [p for p in left if p.poll() is None]
            if not left or _time.monotonic() >= deadline:
                return left
            _time.sleep(0.02)

    left = wait_all([p for p in procs if p is not None])
    for signal_all in ("terminate", "kill"):
        for p in left:
            try:
                getattr(p, signal_all)()
            except OSError:
                pass
        left = wait_all(left)


class _ForkServerClient:
    """Lazy per-process handle on a forkserver child (forkserver.py).
    Thread-safe: requests are serialized over one connection."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self._proc = None
        self._conn = None

    def _ensure(self, authkey: bytes):
        from multiprocessing import connection as mpc
        if self._conn is not None and self._proc.poll() is None:
            return True
        if self._proc is not None:
            # a previous factory whose connection dropped is still ours to
            # reap — left alone it would keep the old socket path open and
            # linger as an orphan beside the replacement
            try:
                self._proc.kill()
                self._proc.wait(timeout=5)
            except (OSError, subprocess.TimeoutExpired):
                pass
            self._proc = None
        sock = os.path.join(constants.SHM_ROOT,
                            f"ray_tpu_fs_{os.getpid()}.sock")
        env = propagate_pythonpath(dict(os.environ))
        env["RAY_TPU_AUTHKEY"] = authkey.hex()
        # the factory itself is a CPU process
        env["JAX_PLATFORMS"] = "cpu"
        try:
            # stdio INHERITED (not piped): forked children without a log
            # file keep the spawner's real stdout/stderr — a pipe nobody
            # drains would block a chatty worker at ~64KB
            self._proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu._private.forkserver",
                 sock, str(os.getpid())],
                env=env, stdin=subprocess.DEVNULL)
            deadline = _time.monotonic() + 30.0
            while True:
                try:
                    self._conn = mpc.Client(sock, family="AF_UNIX",
                                            authkey=authkey)
                    break
                except (FileNotFoundError, ConnectionRefusedError,
                        OSError):
                    if (_time.monotonic() > deadline
                            or self._proc.poll() is not None):
                        raise OSError("forkserver failed to start")
                    _time.sleep(0.05)
            return True
        except Exception:
            if self._proc is not None:
                try:
                    self._proc.kill()
                except OSError:
                    pass
            self._proc = None
            self._conn = None
            return False

    def spawn(self, address, authkey, worker_id, env, log_path):
        with self._lock:
            if not self._ensure(authkey):
                return None
            try:
                self._conn.send({"address": address,
                                 "worker_id": worker_id,
                                 "env": env, "log_path": log_path})
                reply = self._conn.recv()
            except (OSError, EOFError, ValueError, TypeError):
                self._conn = None
                return None
            pid = reply.get("pid")
            if not pid:
                return None
            return ForkedProc(pid, reply.get("start"))


_forkserver = _ForkServerClient()


def _fork_eligible(env: dict, python_exe, cwd,
                   cmd_prefix=None) -> bool:
    """Fork only the common case: CPU worker, default interpreter, no
    runtime-env path/cwd overrides, no container wrapper. TPU workers
    get their chip scoping from the environment at exec time, and
    venv/conda/container workers need their own interpreter/command
    line."""
    return (python_exe is None and cwd is None and cmd_prefix is None
            and not env.get("RAY_TPU_RUNTIME_ENV_PATHS")
            and constants.TPU_VISIBLE_CHIPS_ENV not in env
            and env.get("JAX_PLATFORMS") == "cpu"
            and env.get("RAY_TPU_DISABLE_FORKSERVER") != "1")


def spawn_worker_proc(address: str, authkey: bytes, worker_id: str,
                      env: dict, python_exe: str | None = None,
                      cwd: str | None = None,
                      log_dir: str | None = None,
                      cmd_prefix: list | None = None):
    """Start a worker process that will register at `address`. The
    common (CPU, default-env) case forks from a warm factory —
    milliseconds instead of a cold interpreter exec; everything else
    execs a fresh python so the child env is exact and no TPU runtime
    handles/locks are inherited. `python_exe`/`cwd` come from a
    materialized runtime environment (pip venv / working_dir)."""
    env = propagate_pythonpath(dict(env))
    env["RAY_TPU_AUTHKEY"] = authkey.hex()
    from ray_tpu._private import config
    if _fork_eligible(env, python_exe, cwd, cmd_prefix):
        log_path = None
        if log_dir is not None and config.get("WORKER_LOG_REDIRECT"):
            os.makedirs(log_dir, exist_ok=True)
            log_path = os.path.join(log_dir, worker_id + ".log")
        proc = _forkserver.spawn(address, authkey, worker_id, env,
                                 log_path)
        if proc is not None:
            return proc
        # factory unavailable: fall through to exec
    # inside a container the HOST interpreter path means nothing; the
    # image's python3 + the mounted checkout (PYTHONPATH forwarded by
    # the runtime's --env passthrough) resolve the worker
    exe = python_exe or ("python3" if cmd_prefix else sys.executable)
    cmd = list(cmd_prefix or []) + [
        exe, "-m", "ray_tpu._private.worker_main", address, worker_id]
    logf = worker_log_file(log_dir, worker_id)   # ids carry their prefix
    try:
        return subprocess.Popen(
            cmd, env=env, stdin=subprocess.DEVNULL, cwd=cwd,
            stdout=logf or None, stderr=subprocess.STDOUT if logf else None)
    finally:
        if logf is not None:
            logf.close()     # the child holds its own fd now


def setup_runtime_env(runtime_env: dict | None, env: dict):
    """Materialize a runtime environment (runtime_env.py) and merge its
    env overrides into `env`. Returns (env, python_exe, cwd,
    cmd_prefix); raises RuntimeEnvSetupError on failure."""
    from ray_tpu._private.runtime_env import get_manager, is_trivial
    from ray_tpu.exceptions import RuntimeEnvSetupError
    if is_trivial(runtime_env):
        return env, None, None, None
    try:
        overrides, cwd, python_exe, cmd_prefix = \
            get_manager().setup(runtime_env)
    except RuntimeEnvSetupError:
        raise
    except Exception as e:
        # cache races / fs errors must surface as setup failures, not
        # escape the spawn thread and strand the task
        raise RuntimeEnvSetupError(
            f"runtime env setup failed: {e!r}") from e
    env.update(overrides)
    return env, python_exe, cwd, cmd_prefix
