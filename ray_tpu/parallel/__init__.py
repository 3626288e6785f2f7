"""TPU-native parallelism layer: meshes, sharding rules, SPMD collectives.

This is where the framework *exceeds* the reference (SURVEY.md §2.4): DP,
FSDP, TP, SP (ring attention), EP and PP are all PartitionSpecs over one
`jax.sharding.Mesh` instead of N separate wrapper integrations.
"""

from ray_tpu.parallel.mesh import (
    AXIS_ORDER,
    BATCH_AXES,
    MeshSpec,
    dp_mesh,
    single_device_mesh,
)
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    constrain,
    global_from_local,
    logical_to_spec,
    named_sharding,
    replicate_tree,
    replicated,
    shard_batch,
    tree_shardings,
)
from ray_tpu.parallel.ring_attention import reference_attention, ring_attention
from ray_tpu.parallel.pipeline import pipeline_apply, stack_stage_params

__all__ = [
    "AXIS_ORDER", "BATCH_AXES", "MeshSpec", "dp_mesh", "single_device_mesh",
    "DEFAULT_RULES", "constrain", "global_from_local",
    "logical_to_spec",
    "named_sharding", "replicate_tree", "replicated", "shard_batch",
    "tree_shardings",
    "reference_attention", "ring_attention",
    "pipeline_apply", "stack_stage_params",
]
