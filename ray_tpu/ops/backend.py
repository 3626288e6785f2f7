"""What the ops ask of the JAX backend: whether a Pallas kernel is
compiled or interpreted, and a trace-time record when a TPU backend is
handed a shape the kernels have no plan for."""

from __future__ import annotations

import logging

import jax

logger = logging.getLogger("ray_tpu.ops")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Pallas interpret mode exists so the CPU backend can run the
    kernels' math in tests; every other backend compiles them or fails."""
    return jax.default_backend() == "cpu"


def note_fallback(op: str, why: str) -> None:
    """`impl="auto"` found no kernel plan and takes the pure-JAX path.
    On the CPU backend that is the normal path. On a TPU it silently
    costs the kernel, so it is logged once per trace on the
    ``ray_tpu.ops`` logger — `chip_smoke.py` collects these records and
    fails on any for the shapes it runs."""
    if on_tpu():
        logger.warning("%s: no Pallas plan on a TPU backend (%s); "
                       "running the pure-JAX path", op, why)
