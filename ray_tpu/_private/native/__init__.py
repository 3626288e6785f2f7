"""Native (C++) runtime components, built on demand with the host toolchain.

The reference ships its native core prebuilt via bazel
(`src/ray/BUILD.bazel`); here the native pieces are small enough to compile
at first import with `g++ -O2 -shared -fPIC` and cache next to the source.
Set RAY_TPU_DISABLE_NATIVE=1 to force the pure-Python fallbacks.
"""

from __future__ import annotations

import logging
import os
import subprocess
import threading

logger = logging.getLogger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_LOCK = threading.Lock()


def native_disabled() -> bool:
    return os.environ.get("RAY_TPU_DISABLE_NATIVE", "") == "1"


def build_extension(name: str) -> str | None:
    """Compile native/<name>.cc -> native/lib<name>.so if stale; return the
    .so path, or None if native is disabled or the toolchain fails (the
    failure is logged: callers then run their pure-Python fallbacks).

    RAY_TPU_SANITIZE=thread|address builds a separate sanitizer-
    instrumented library (lib<name>.tsan.so / .asan.so) — the stress
    harness runs against it the way the reference's plasma tests run
    under bazel's TSAN/ASAN configs (ci/)."""
    if native_disabled():
        return None
    sanitize = os.environ.get("RAY_TPU_SANITIZE", "")
    src = os.path.join(_DIR, name + ".cc")
    suffix = {"thread": ".tsan", "address": ".asan"}.get(sanitize, "")
    out = os.path.join(_DIR, "lib" + name + suffix + ".so")
    flags = ["-O2"]
    if sanitize in ("thread", "address"):
        flags = ["-O1", "-g", f"-fsanitize={sanitize}",
                 "-fno-omit-frame-pointer"]
    with _BUILD_LOCK:
        try:
            if (os.path.exists(out)
                    and os.path.getmtime(out) >= os.path.getmtime(src)):
                return out
            tmp = out + ".tmp.%d" % os.getpid()
            subprocess.run(
                ["g++", *flags, "-std=c++17", "-shared", "-fPIC",
                 "-o", tmp, src, "-lpthread"],
                check=True, capture_output=True, timeout=120)
            os.replace(tmp, out)  # atomic: concurrent builders race safely
            return out
        except (OSError, subprocess.SubprocessError) as e:
            stderr = getattr(e, "stderr", None) or b""
            logger.warning(
                "native extension %s did not build, running without it: "
                "%s %s", name, e, stderr[-400:].decode(errors="replace"))
            return None
