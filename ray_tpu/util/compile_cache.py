"""Where a process keeps JAX's persistent compilation cache.

The 12-layer train and serve programs take tens of seconds to compile,
and every worker process that owns a chip would otherwise compile them
from cold. The cache's path is part of its key, so it must not move
between runs: it is either wherever the deployment says
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads by itself) or one fixed
directory inside the checkout.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Call before the first jit of a process that compiles for a chip
    (chip workers, `chip_smoke.py`'s children). Returns the
    directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
