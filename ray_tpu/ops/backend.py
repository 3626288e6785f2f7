"""What the ops ask of the JAX backend: whether a Pallas kernel is
compiled or interpreted, how much VMEM its core has, and a trace-time
record when a TPU backend is handed a shape the kernels have no plan
for."""

from __future__ import annotations

import logging

import jax
from jax.experimental.pallas import tpu as pltpu

logger = logging.getLogger("ray_tpu.ops")

# Mosaic's scoped VMEM for a kernel whose call asks for no limit
SCOPED_VMEM_DEFAULT = 16 << 20


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """Pallas interpret mode exists so the CPU backend can run the
    kernels' math in tests; every other backend compiles them or fails."""
    return jax.default_backend() == "cpu"


def vmem_capacity() -> int:
    """The VMEM of the core the kernels compile for. Off a TPU (the
    interpreter; a compile for a described chip) the v5e's 128 MiB, the
    chip this repo's cells and AOT tests describe."""
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return 128 << 20


def note_fallback(op: str, why: str) -> None:
    """`impl="auto"` found no kernel plan and takes the pure-JAX path.
    On the CPU backend that is the normal path. On a TPU it silently
    costs the kernel, so it is logged once per trace on the
    ``ray_tpu.ops`` logger — `chip_smoke.py` collects these records and
    fails on any for the shapes it runs."""
    if on_tpu():
        logger.warning("%s: no Pallas plan on a TPU backend (%s); "
                       "running the pure-JAX path", op, why)
