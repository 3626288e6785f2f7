"""Logical-axis sharding rules: PartitionSpecs from readable names.

Parameters and activations are annotated with *logical* axis names
("embed", "mlp", "heads", "batch", "length"); a rule table maps logical
axes to mesh axes. This is the t5x/flax-partitioning idiom, exposed here as
the framework's single sharding vocabulary — the TPU-native replacement for
everything the reference delegates to DDP/FSDP wrappers
(`train/torch/train_loop_utils.py:75-101`).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Default rule table for transformer-family models. Each logical axis maps
# to a mesh axis (or None = replicated). Tuples shard one logical axis over
# several mesh axes.
DEFAULT_RULES: dict[str, object] = {
    "batch": ("data", "fsdp"),   # batch sharded over all data-like axes
    "length": "seq",             # sequence/context parallelism
    "embed": "fsdp",             # ZeRO-3-style parameter sharding
    "mlp": "tensor",             # megatron column/row parallel
    "heads": "tensor",
    "kv": None,
    "vocab": "tensor",
    "expert": "expert",
    "stage": "pipe",
}


# Every shard_map in the tree is imported from here.
shard_map = jax.shard_map


def _mesh_axes(mesh: Mesh) -> set:
    return set(mesh.axis_names)


def logical_to_spec(logical_axes: Sequence[str | None],
                    rules: dict | None = None,
                    mesh: Mesh | None = None) -> PartitionSpec:
    """Map logical axis names to a PartitionSpec via the rule table.

    Mesh axes that don't exist on `mesh` (or have size 1) still produce valid
    specs — XLA treats sharding over a size-1 axis as replication, which is
    what makes one model definition portable from 1 chip to a pod.
    """
    rules = DEFAULT_RULES if rules is None else rules
    present = _mesh_axes(mesh) if mesh is not None else None
    used = set()
    out = []
    for ax in logical_axes:
        if ax is None:
            out.append(None)
            continue
        target = rules.get(ax)
        if target is None:
            out.append(None)
            continue
        axes = (target,) if isinstance(target, str) else tuple(target)
        axes = tuple(a for a in axes
                     if (present is None or a in present) and a not in used)
        used.update(axes)
        if not axes:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    return PartitionSpec(*out)


def named_sharding(mesh: Mesh, *logical_axes, rules: dict | None = None
                   ) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules, mesh))


def tree_shardings(mesh: Mesh, logical_tree, rules: dict | None = None):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(
            mesh, logical_to_spec(axes, rules, mesh)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) or x is None)


def constrain(x, mesh: Mesh, *logical_axes, rules: dict | None = None):
    """In-jit sharding constraint by logical names (replaces the reference's
    nothing — XLA propagates the rest)."""
    return jax.lax.with_sharding_constraint(
        x, named_sharding(mesh, *logical_axes, rules=rules))


def shard_batch(batch, mesh: Mesh):
    """Host->device: place a host batch sharded over the data-like axes."""
    spec = logical_to_spec(("batch",), mesh=mesh)

    def place(arr):
        ndim_spec = PartitionSpec(*(list(spec) + [None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(mesh, ndim_spec))
    return jax.tree.map(place, batch)


def fused_xent_specs(mesh: Mesh, rules: dict | None = None
                     ) -> tuple[PartitionSpec, PartitionSpec,
                                PartitionSpec]:
    """(x, embed, targets) PartitionSpecs for ops.fused_xent's
    vocab-parallel shard_map.

    Activations and targets follow the batch/length rules; the embedding
    keeps its vocab sharding but replicates d_model (each shard reduces
    its local vocab rows to a partial log-sum-exp and partial target
    logit, then one psum over the vocab mesh axis combines them — the
    only cross-shard traffic the fused loss needs is two [B, T] f32
    arrays, vs. the dense path's [B, T, V] logits collective)."""
    x_spec = logical_to_spec(("batch", "length", None), rules, mesh)
    t_spec = logical_to_spec(("batch", "length"), rules, mesh)
    e_spec = logical_to_spec(("vocab", None), rules, mesh)
    return x_spec, e_spec, t_spec


def kv_pool_specs(mesh: Mesh, rules: dict | None = None, *,
                  quantized: bool = False):
    """PartitionSpec pytree for a paged KV block pool {"k", "v"} of
    [L, n_blocks, block_size, H, D]: heads ride the tensor axis
    (matching the wq/wk/wv column split — the blocks a tensor shard
    writes hold the heads it attends over, no cross-shard traffic in
    decode). The block axis is replicated: the allocator hands any
    physical block to any sequence, so blocks cannot be pinned to data
    shards. With ``quantized`` (an int8 pool) the pytree grows
    {"k_scale", "v_scale"} of [L, n_blocks, block_size, H]: the head
    axis shards with its payload rows — each tensor shard dequantizes
    from scales it already owns — and blocks stay replicated."""
    from ray_tpu.models.gpt import kv_pool_logical_axes
    return {name: logical_to_spec(axes, rules, mesh)
            for name, axes in kv_pool_logical_axes(quantized).items()}


def kv_pool_shardings(mesh: Mesh, rules: dict | None = None, *,
                      quantized: bool = False
                      ) -> dict[str, NamedSharding]:
    """NamedShardings for `kv_pool_specs` — what
    `models.gpt.init_kv_pool(mesh=...)` places the pool with."""
    return {name: NamedSharding(mesh, spec)
            for name, spec in kv_pool_specs(
                mesh, rules, quantized=quantized).items()}


def replicated(mesh: Mesh):
    return NamedSharding(mesh, PartitionSpec())


def engine_io_shardings(mesh: Mesh) -> dict[str, NamedSharding]:
    """Sharding for the inference engine's per-program host input: the
    one packed int32 array a decode, verify, propose or prefill program
    takes (`serve.engine.pack_rows` / `pack_chunk`: tokens or the
    speculation window, positions, temperatures' bits, block tables, the
    step counter). Replicated: it is a tiny vector the scheduler
    rebuilds for every program, and every shard of the paged pool needs
    the full batch's tables — but routing it through an explicit
    device_put keeps each step's transfer off XLA's implicit-transfer
    path and makes the engine's placement auditable."""
    return {"inputs": NamedSharding(mesh, PartitionSpec())}


# -- PartitionSpec (de)serialization for checkpoint manifests ---------------
#
# Mesh axis NAMES are stable across scale changes (MeshSpec keeps size-1
# axes for exactly this reason), so a spec recorded at save time can be
# re-applied to a mesh with a different device count at restore time —
# the elastic-resume path in train/ft.py. Sizes are not recorded: only
# names travel, and `valid_spec_for` re-validates them against the mesh
# that exists at restore.

def spec_to_json(spec) -> list:
    """PartitionSpec -> JSON-serializable list (None | str | [str, ...]
    per dim)."""
    out = []
    for entry in tuple(spec):
        if entry is None or isinstance(entry, str):
            out.append(entry)
        else:
            out.append(list(entry))
    return out


def spec_from_json(entries) -> PartitionSpec:
    """Inverse of `spec_to_json`."""
    out = []
    for entry in entries:
        if entry is None or isinstance(entry, str):
            out.append(entry)
        else:
            out.append(tuple(entry))
    return PartitionSpec(*out)


def valid_spec_for(mesh: Mesh, spec, shape) -> PartitionSpec:
    """Re-validate a recorded PartitionSpec against a (possibly different)
    mesh: axes that don't exist on `mesh`, are already used by an earlier
    dim, or don't divide the dim evenly are dropped (replicated) — the
    same degrade-to-replication contract as `logical_to_spec`, applied at
    restore time."""
    present = _mesh_axes(mesh)
    used: set = set()
    out = []
    entries = list(tuple(spec))[:len(shape)]
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        axes = tuple(a for a in axes if a in present and a not in used)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if not axes or (total and dim % total):
            out.append(None)
            continue
        used.update(axes)
        out.append(axes[0] if len(axes) == 1 else axes)
    return PartitionSpec(*out)


def global_from_local(mesh: Mesh, local_batch, rules: dict | None = None):
    """Build a global batch-sharded array from each process's local shard —
    the multi-host ingest path (each host feeds its own data; the global
    array spans all processes). Works single-process too, so train loops
    don't branch on world size."""
    spec = logical_to_spec(("batch",), rules, mesh)

    def place(arr):
        full_spec = PartitionSpec(*(list(spec) + [None] * (arr.ndim - 1)))
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, full_spec), arr)
    return jax.tree.map(place, local_batch)


def replicate_tree(mesh: Mesh, tree):
    """Replicate host values onto every device of a (possibly multi-host)
    mesh."""
    import numpy as np

    def place(arr):
        return jax.make_array_from_process_local_data(
            replicated(mesh), np.asarray(arr))
    return jax.tree.map(place, tree)
