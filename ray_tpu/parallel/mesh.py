"""Device mesh construction — the TPU-native parallelism substrate.

The reference has no model-partitioning layer at all (SURVEY.md §2.4: TP/PP/
SP/EP absent); its parallelism is orchestration (N workers x DDP over NCCL,
`train/torch/config.py:113`). On TPU, partitioning belongs to the compiler:
one `jax.sharding.Mesh` with named axes replaces every bolt-on. This module
standardizes the axis vocabulary and mesh construction for the whole
framework (train/tune/serve/rl all build meshes through here).

Axis names (any subset, in logical-outer to logical-inner order):

- ``data``    pure data parallelism (gradient psum over ICI/DCN)
- ``fsdp``    data parallelism with parameter/optimizer sharding (ZeRO-3
              equivalent, but expressed as a PartitionSpec, not a wrapper)
- ``tensor``  tensor parallelism (megatron-style sharded matmuls)
- ``seq``     sequence/context parallelism (ring attention over ICI)
- ``expert``  expert parallelism for MoE layers
- ``pipe``    pipeline stages
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

AXIS_ORDER = ("pipe", "data", "fsdp", "seq", "expert", "tensor")


@dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape. Sizes of -1 are inferred from the device
    count (at most one -1). Axes of size 1 are kept (harmless to XLA and
    they make PartitionSpecs stable across scale changes)."""

    data: int = -1
    fsdp: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1
    pipe: int = 1

    def sizes(self) -> dict:
        return {"pipe": self.pipe, "data": self.data, "fsdp": self.fsdp,
                "seq": self.seq, "expert": self.expert,
                "tensor": self.tensor}

    def resolve(self, n_devices: int) -> dict:
        sizes = self.sizes()
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one axis may be -1, got {unknown}")
        known = math.prod(v for v in sizes.values() if v != -1)
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"cannot infer {unknown[0]}: {n_devices} devices not "
                    f"divisible by {known}")
            sizes[unknown[0]] = n_devices // known
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {math.prod(sizes.values())} devices, "
                f"have {n_devices}")
        return sizes

    def build(self, devices=None) -> Mesh:
        """Construct the Mesh. Axis order puts `tensor` innermost so tensor-
        parallel collectives ride the fastest ICI links, and `pipe`/`data`
        outermost (DCN-friendly) — the scaling-book layout recipe."""
        if devices is None:
            devices = jax.devices()
        sizes = self.resolve(len(devices))
        shape = tuple(sizes[a] for a in AXIS_ORDER)
        try:
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=np.asarray(devices))
        except (ValueError, AssertionError):
            # CPU devices have no topology to lay a mesh out on: any
            # order is as good, so row-major. On a real chip a layout
            # that cannot be built is an error, not a slower mesh.
            if devices[0].platform != "cpu":
                raise
            dev_array = np.asarray(devices).reshape(shape)
        return Mesh(dev_array, AXIS_ORDER)

    def build_multislice(self, num_slices: int, devices=None) -> Mesh:
        """Multi-slice (DCN) mesh: the OUTER factor of the `data` (or,
        when data==1, `pipe`) axis spans slices, so gradient psums do a
        hierarchical reduce (in-slice over ICI, then one cross-slice hop
        over DCN) while every model axis (fsdp/seq/expert/tensor) stays
        inside a slice — the scaling-book multi-pod recipe. On real
        multi-slice TPU runtimes this delegates to
        `mesh_utils.create_hybrid_device_mesh` (slice-aware placement);
        elsewhere (CPU simulation, single-slice) devices are grouped
        into `num_slices` contiguous blocks, which preserves the
        collective structure the compiler sees."""
        if devices is None:
            devices = jax.devices()
        n = len(devices)
        if n % num_slices:
            raise ValueError(
                f"{n} devices cannot split into {num_slices} slices")
        sizes = self.resolve(n)
        dcn_axis = "data" if sizes["data"] % num_slices == 0 \
            else "pipe"
        if sizes[dcn_axis] % num_slices:
            raise ValueError(
                f"neither data={sizes['data']} nor pipe={sizes['pipe']} "
                f"divides into {num_slices} slices (the DCN axis must)")
        ici_sizes = dict(sizes)
        ici_sizes[dcn_axis] //= num_slices
        ici_shape = tuple(ici_sizes[a] for a in AXIS_ORDER)
        dcn_shape = tuple(num_slices if a == dcn_axis else 1
                          for a in AXIS_ORDER)
        try:
            dev_array = mesh_utils.create_hybrid_device_mesh(
                ici_shape, dcn_shape, devices=np.asarray(devices))
        except (ValueError, AssertionError, KeyError, AttributeError):
            # No slice metadata (CPU sim / single-slice): contiguous
            # blocks of n/num_slices devices play the slices, stacked
            # along the DCN axis.
            per = n // num_slices
            blocks = [
                np.asarray(devices[i * per:(i + 1) * per]).reshape(
                    ici_shape)
                for i in range(num_slices)
            ]
            axis = AXIS_ORDER.index(dcn_axis)
            dev_array = np.concatenate(blocks, axis=axis)
        return Mesh(dev_array, AXIS_ORDER)


def single_device_mesh() -> Mesh:
    """A 1-device mesh so the same pjit code paths run everywhere."""
    return MeshSpec(data=1).build(jax.devices()[:1])


def dp_mesh(n: int | None = None) -> Mesh:
    devs = jax.devices() if n is None else jax.devices()[:n]
    return MeshSpec(data=-1).build(devs)


# Mesh axes that shard the batch dimension: anything data-like.
BATCH_AXES = ("data", "fsdp")
