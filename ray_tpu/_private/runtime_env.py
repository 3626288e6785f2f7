"""Per-task/actor runtime environments: working_dir, pip venvs, env_vars.

Counterpart of the reference's `python/ray/_private/runtime_env/`
(`working_dir.py`, `pip.py`, `uri_cache.py`) + the runtime-env agent
(`dashboard/modules/runtime_env/runtime_env_agent.py:161`): the node that
spawns a worker materializes the environment FIRST — a content-addressed
cache entry per distinct environment — then launches the worker inside it
(venv python, working_dir cwd, merged env vars).

Supported runtime_env keys (same schema shape as the reference):

- ``env_vars``:   {name: value} merged into the worker's environment
- ``working_dir``: a local directory (copied into the cache; the worker
                   starts with cwd there and the dir on sys.path)
- ``pip``:        list of requirement strings / local wheel paths, or
                   {"packages": [...]}. Installed into a cached venv
                   created with --system-site-packages so the image's
                   jax/numpy remain importable. No-network installs work
                   when requirements are local wheels; anything needing
                   egress fails with RuntimeEnvSetupError.
- ``py_modules``:  list of local module dirs/files appended to sys.path.
- ``conda``:       an environment spec dict (environment.yml content) or
                   a path to one — materialized once into a cached env
                   via the `conda` binary (reference:
                   `_private/runtime_env/conda.py`); the worker execs
                   that env's python. Requires conda on PATH (override:
                   RAY_TPU_CONDA_BINARY).
- ``container``:   {"image": ..., "run_options": [...]} — the worker
                   command is wrapped in `<runtime> run` (docker or
                   podman, RAY_TPU_CONTAINER_RUNTIME) with /dev/shm and
                   the checkout mounted so the containerized worker
                   reaches the node socket and shm arena (reference:
                   `_private/runtime_env/container.py` worker command
                   wrapping).

The cache is doubly bounded: entry count AND total bytes
(RUNTIME_ENV_CACHE_BYTES), LRU-evicted (reference: uri_cache.py).
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import threading
import time

from ray_tpu._private import constants
from ray_tpu._private.spawn import CHIP_SCOPE_VARS
from ray_tpu.exceptions import RuntimeEnvSetupError

from ray_tpu._private.constants import (
    RUNTIME_ENV_CACHE as _CACHE_ROOT,
    RUNTIME_ENV_CACHE_ENTRIES as _MAX_CACHE_ENTRIES,
)

_SETUP_KEYS = ("working_dir", "pip", "py_modules", "env_vars", "conda",
               "container")


def is_trivial(runtime_env: dict | None) -> bool:
    """True when the task can reuse a pool worker: no materialization AND
    no env_vars (pool workers were spawned without them; the reference
    likewise keys worker reuse on the runtime-env hash)."""
    if not runtime_env:
        return True
    return not any(runtime_env.get(k) for k in _SETUP_KEYS)


def _normalize_pip(spec) -> list[str]:
    if isinstance(spec, dict):
        spec = spec.get("packages", [])
    return [str(p) for p in spec]


_SIZE_SIDECAR = ".rtpu_size"


def _entry_bytes(path: str) -> int:
    """Cached entry size: the sidecar written at commit time, or one
    walk (then memoized to the sidecar) for pre-sidecar entries."""
    sidecar = os.path.join(path, _SIZE_SIDECAR)
    try:
        with open(sidecar) as f:
            return int(f.read())
    except (OSError, ValueError):
        pass
    n = _tree_bytes(path)
    try:
        with open(sidecar, "w") as f:
            f.write(str(n))
    except OSError:
        pass
    return n


def _tree_bytes(path: str) -> int:
    if os.path.isfile(path):
        try:
            return os.path.getsize(path)
        except OSError:
            return 0
    total = 0
    for root, _dirs, files in os.walk(path):
        for name in files:
            try:
                total += os.path.getsize(os.path.join(root, name))
            except OSError:
                pass
    return total


def _dir_fingerprint(path: str) -> str:
    """Content hash of a directory tree (URI of the packaged working_dir;
    reference: packaging.py hashes the zip the same way)."""
    h = hashlib.sha1()
    for root, dirs, files in sorted(os.walk(path)):
        dirs.sort()
        for name in sorted(files):
            fp = os.path.join(root, name)
            rel = os.path.relpath(fp, path)
            h.update(rel.encode())
            try:
                st = os.stat(fp)
                h.update(f"{st.st_size}:{int(st.st_mtime)}".encode())
            except OSError:
                continue
    return h.hexdigest()[:16]


class RuntimeEnvManager:
    """Materializes runtime environments into a content-addressed cache.

    One instance per worker-spawning process (head NodeServer and each
    HostDaemon). Entries are shared across sessions (the point of the
    cache: venv creation is seconds); an LRU cap bounds disk usage
    (reference: uri_cache.py)."""

    def __init__(self, cache_root: str = _CACHE_ROOT):
        self.cache_root = cache_root
        self._lock = threading.Lock()
        self._entry_locks: dict[str, threading.Lock] = {}

    # -- public -----------------------------------------------------------

    def setup(self, runtime_env: dict | None):
        """Materialize `runtime_env`. Returns (env_overrides, cwd,
        python_exe, cmd_prefix) — python_exe is None unless a pip venv /
        conda env applies; cmd_prefix is a command-line wrapper (the
        container runtime invocation) or None.
        Raises RuntimeEnvSetupError on any failure."""
        env: dict[str, str] = {}
        cwd = None
        python_exe = None
        cmd_prefix = None
        if not runtime_env:
            return env, cwd, python_exe, cmd_prefix
        # validate the SHAPE before materializing anything — a rejected
        # combination must not first burn minutes building a venv
        if runtime_env.get("conda") and runtime_env.get("pip"):
            raise RuntimeEnvSetupError(
                "runtime_env cannot combine 'pip' and 'conda' "
                "(pin pip packages inside the conda spec instead)")
        if runtime_env.get("container"):
            clash = [k for k in ("pip", "conda", "working_dir",
                                 "py_modules") if runtime_env.get(k)]
            if clash:
                # host-side cache paths (venvs, conda envs, staged
                # working dirs) don't exist inside the image; forwarding
                # them would fail at import time with no hint why
                raise RuntimeEnvSetupError(
                    f"runtime_env cannot combine 'container' with "
                    f"{clash} — bake packages and code into the image "
                    "(env_vars still apply)")
        for k, v in (runtime_env.get("env_vars") or {}).items():
            env[str(k)] = str(v)
        pypath: list[str] = []
        wd = runtime_env.get("working_dir")
        if wd:
            cwd = self._setup_working_dir(wd)
            pypath.append(cwd)
        for mod in runtime_env.get("py_modules") or []:
            pypath.append(self._setup_py_module(mod))
        pip = _normalize_pip(runtime_env.get("pip") or [])
        if pip:
            python_exe, site_dir = self._setup_pip(pip)
            if site_dir:
                # the venv's site-packages must SHADOW the parent's
                # propagated sys.path or version pins are silently ignored
                pypath.append(site_dir)
        conda = runtime_env.get("conda")
        if conda:
            python_exe = self._setup_conda(conda)
        container = runtime_env.get("container")
        if container:
            cmd_prefix = self._container_prefix(
                container, runtime_env.get("env_vars") or {})
        if pypath:
            # spawn.propagate_pythonpath places these first so the env
            # wins over inherited paths
            env["RAY_TPU_RUNTIME_ENV_PATHS"] = os.pathsep.join(pypath)
        return env, cwd, python_exe, cmd_prefix

    # -- working_dir ------------------------------------------------------

    def _setup_working_dir(self, src: str) -> str:
        src = os.path.abspath(os.path.expanduser(src))
        if not os.path.isdir(src):
            raise RuntimeEnvSetupError(
                f"runtime_env working_dir {src!r} is not a directory")
        key = "wd_" + _dir_fingerprint(src)
        dest = os.path.join(self.cache_root, key)
        with self._entry_lock(key):
            if not os.path.isdir(dest):
                tmp = dest + ".tmp.%d" % os.getpid()
                shutil.copytree(src, tmp)
                self._commit(tmp, dest)
            self._touch(dest)
        self._prune()
        return dest

    def _setup_py_module(self, mod: str) -> str:
        mod = os.path.abspath(os.path.expanduser(mod))
        if os.path.isdir(mod):
            # containing dir goes on sys.path so `import <basename>` works
            staged = self._setup_working_dir(mod)
            parent = os.path.join(
                os.path.dirname(staged), "pkg_" + os.path.basename(staged))
            os.makedirs(parent, exist_ok=True)
            link = os.path.join(parent, os.path.basename(mod))
            if not os.path.exists(link):
                try:
                    os.symlink(staged, link)
                except OSError:
                    shutil.copytree(staged, link, dirs_exist_ok=True)
            return parent
        raise RuntimeEnvSetupError(
            f"runtime_env py_modules entry {mod!r} is not a directory")

    # -- pip --------------------------------------------------------------

    def _setup_pip(self, packages: list[str]):
        """Returns (python_exe, site_packages_dir)."""
        # local wheels/sdists contribute content identity (size+mtime) to
        # the key: a rebuilt wheel at the same path must NOT reuse the
        # stale venv
        key_parts = []
        for p in sorted(packages):
            if os.path.exists(p):
                st = os.stat(p)
                # nanosecond mtime: a rebuild within the same second with
                # identical size must still invalidate the cached venv
                key_parts.append(f"{p}:{st.st_size}:{st.st_mtime_ns}")
            else:
                key_parts.append(p)
        key = "pip_" + hashlib.sha1(
            json.dumps(key_parts).encode()).hexdigest()[:16]
        venv_dir = os.path.join(self.cache_root, key)
        python_exe = os.path.join(venv_dir, "bin", "python")
        with self._entry_lock(key):
            if not os.path.exists(python_exe):
                tmp = venv_dir + ".tmp.%d" % os.getpid()
                shutil.rmtree(tmp, ignore_errors=True)
                try:
                    # --system-site-packages: the baked-in jax/numpy stack
                    # stays importable; the venv only ADDs packages
                    subprocess.run(
                        [sys.executable, "-m", "venv",
                         "--system-site-packages", tmp],
                        check=True, capture_output=True,
                        timeout=constants.RUNTIME_ENV_VENV_CREATE_TIMEOUT_S)
                    subprocess.run(
                        [os.path.join(tmp, "bin", "python"), "-m", "pip",
                         "install", "--quiet", "--no-input", *packages],
                        check=True, capture_output=True,
                        timeout=constants.RUNTIME_ENV_PIP_INSTALL_TIMEOUT_S)
                except subprocess.CalledProcessError as e:
                    shutil.rmtree(tmp, ignore_errors=True)
                    raise RuntimeEnvSetupError(
                        "pip runtime_env setup failed: "
                        f"{(e.stderr or b'').decode()[-2000:]}") from None
                except subprocess.TimeoutExpired:
                    shutil.rmtree(tmp, ignore_errors=True)
                    raise RuntimeEnvSetupError(
                        "pip runtime_env setup timed out") from None
                self._commit(tmp, venv_dir)
            self._touch(venv_dir)
        self._prune()
        import glob as _glob
        sites = _glob.glob(os.path.join(
            venv_dir, "lib", "python*", "site-packages"))
        return python_exe, (sites[0] if sites else None)

    # -- conda ------------------------------------------------------------

    def _setup_conda(self, spec) -> str:
        """Materialize a conda env into the cache; returns its python.
        `spec` is an environment.yml dict or a path to one (reference:
        `_private/runtime_env/conda.py` get_or_create_conda_env)."""
        from ray_tpu._private import config as _config
        conda_bin = shutil.which(_config.get("CONDA_BINARY"))
        if conda_bin is None:
            raise RuntimeEnvSetupError(
                "runtime_env 'conda' requires the conda binary on PATH "
                "(or RAY_TPU_CONDA_BINARY); it is not installed here")
        if isinstance(spec, str):
            spec = os.path.abspath(os.path.expanduser(spec))
            if not os.path.isfile(spec):
                raise RuntimeEnvSetupError(
                    f"conda spec file {spec!r} does not exist")
            with open(spec) as f:
                content = f.read()
        else:
            content = json.dumps(spec, sort_keys=True)
        key = "conda_" + hashlib.sha1(content.encode()).hexdigest()[:16]
        dest = os.path.join(self.cache_root, key)
        python_exe = os.path.join(dest, "bin", "python")
        with self._entry_lock(key):
            if not os.path.exists(python_exe):
                os.makedirs(self.cache_root, exist_ok=True)
                import tempfile
                tmp = dest + ".tmp.%d" % os.getpid()
                shutil.rmtree(tmp, ignore_errors=True)
                # spec lives OUTSIDE the cache (a sidecar in cache_root
                # would count as its own LRU entry and skew eviction)
                with tempfile.NamedTemporaryFile(
                        "w", suffix=".yml", delete=False) as f:
                    f.write(content)
                    spec_path = f.name
                try:
                    subprocess.run(
                        [conda_bin, "env", "create", "--yes",
                         "-p", tmp, "-f", spec_path],
                        check=True, capture_output=True,
                        timeout=constants.RUNTIME_ENV_CONDA_TIMEOUT_S)
                except subprocess.CalledProcessError as e:
                    shutil.rmtree(tmp, ignore_errors=True)
                    raise RuntimeEnvSetupError(
                        "conda runtime_env setup failed: "
                        f"{(e.stderr or b'').decode()[-2000:]}") from None
                except subprocess.TimeoutExpired:
                    shutil.rmtree(tmp, ignore_errors=True)
                    raise RuntimeEnvSetupError(
                        "conda runtime_env setup timed out") from None
                finally:
                    try:
                        os.unlink(spec_path)
                    except OSError:
                        pass
                self._commit(tmp, dest)
                if not os.path.exists(python_exe):
                    raise RuntimeEnvSetupError(
                        f"conda env at {dest} has no bin/python")
            self._touch(dest)
        self._prune()
        return python_exe

    # -- container --------------------------------------------------------

    @staticmethod
    def _container_prefix(spec, env_vars: dict | None = None) -> list[str]:
        """Command prefix wrapping the worker in a container (reference:
        `_private/runtime_env/container.py` worker command wrapping).
        /dev/shm (session dirs, arena, node sockets) and the checkout
        ride host mounts so the containerized worker still reaches its
        daemon and shares the zero-copy store. Bare `--env NAME` entries
        forward values from the spawner's Popen env, which carries the
        worker-env decisions (CPU gating, chip visibility, node id) and
        the runtime_env env_vars."""
        from ray_tpu._private import config as _config
        if isinstance(spec, str):
            spec = {"image": spec}
        image = spec.get("image")
        if not image:
            raise RuntimeEnvSetupError(
                "runtime_env 'container' needs an 'image'")
        runtime = _config.get("CONTAINER_RUNTIME")
        if not runtime:
            runtime = ("docker" if shutil.which("docker")
                       else "podman" if shutil.which("podman") else None)
        if runtime is None or shutil.which(runtime) is None:
            raise RuntimeEnvSetupError(
                "runtime_env 'container' requires docker or podman on "
                "PATH (or RAY_TPU_CONTAINER_RUNTIME)")
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        prefix = [runtime, "run", "--rm", "--network=host",
                  "-v", "/dev/shm:/dev/shm",
                  "-v", f"{pkg_root}:{pkg_root}:ro"]
        forward = ["RAY_TPU_AUTHKEY", "PYTHONPATH", "RAY_TPU_WORKER",
                   "JAX_PLATFORMS", "RAY_TPU_NODE_ID",
                   "RAY_TPU_RUNTIME_ENV_PATHS", *CHIP_SCOPE_VARS]
        forward += [str(k) for k in (env_vars or {})]
        for name in forward:
            prefix += ["--env", name]
        prefix += [str(o) for o in spec.get("run_options") or []]
        prefix.append(image)
        return prefix

    # -- cache plumbing ---------------------------------------------------

    @staticmethod
    def _commit(tmp: str, dest: str) -> None:
        """Publish a finished cache entry. The entry locks are
        per-process; another daemon on this host may have won the same
        key — losing the rename race just means the entry already exists
        (content-addressed, so identical). The entry's tree size is
        recorded once here so _prune never re-walks big trees (a conda
        env is easily 100k files)."""
        try:
            with open(os.path.join(tmp, _SIZE_SIDECAR), "w") as f:
                f.write(str(_tree_bytes(tmp)))
        except OSError:
            pass
        try:
            os.rename(tmp, dest)
        except OSError:
            if os.path.isdir(dest):
                shutil.rmtree(tmp, ignore_errors=True)
            else:
                raise

    def _entry_lock(self, key: str) -> threading.Lock:
        with self._lock:
            return self._entry_locks.setdefault(key, threading.Lock())

    @staticmethod
    def _touch(path: str) -> None:
        try:
            os.utime(path, None)
        except OSError:
            pass

    def _prune(self) -> None:
        """Drop least-recently-used cache entries above the caps: entry
        COUNT and total BYTES (reference: uri_cache.py evicts on a byte
        budget)."""
        from ray_tpu._private import config as _config
        try:
            entries = [
                os.path.join(self.cache_root, e)
                for e in os.listdir(self.cache_root)
                if ".tmp." not in e]       # in-flight builds carry pids
        except FileNotFoundError:
            return
        max_bytes = _config.get("RUNTIME_ENV_CACHE_BYTES")
        sizes = {p: _entry_bytes(p) for p in entries}
        total = sum(sizes.values())
        if len(entries) <= _MAX_CACHE_ENTRIES and total <= max_bytes:
            return
        entries.sort(key=lambda p: os.path.getmtime(p))
        # never evict the newest entry for the BYTE budget: a single
        # entry larger than the budget was just handed to a spawner —
        # deleting it would strand the worker on a vanished interpreter
        # (and rebuild/evict forever)
        while entries and (len(entries) > _MAX_CACHE_ENTRIES
                           or (total > max_bytes and len(entries) > 1)):
            path = entries.pop(0)
            total -= sizes.get(path, 0)
            shutil.rmtree(path, ignore_errors=True)
            if os.path.isfile(path):           # spec sidecars (.yml)
                try:
                    os.unlink(path)
                except OSError:
                    pass


_manager: RuntimeEnvManager | None = None
_manager_lock = threading.Lock()


def get_manager() -> RuntimeEnvManager:
    global _manager
    with _manager_lock:
        if _manager is None:
            _manager = RuntimeEnvManager()
        return _manager
