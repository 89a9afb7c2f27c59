"""Parallelism: device mesh, GSPMD sharding rules, multi-host bootstrap.

Replaces the reference's hand-rolled per-module FSDP interceptor and
single-axis "dp" shard_map program (dinov3_jax/fsdp/utils.py:19-110,
dinov3_jax/train/train.py:322-354) with the TPU-native design from
SURVEY.md §7.1: one global mesh with named axes
``(dcn_data, data, pipe, fsdp, seq, tensor)``, parameters born sharded via
``NamedSharding``, and XLA's SPMD partitioner inserting all collectives.
"""

from dinov3_tpu.parallel.context import (
    get_current_mesh,
    seq_axis_size,
    set_current_mesh,
)
from dinov3_tpu.parallel.distributed import (
    initialize_distributed,
    is_main_process,
    process_count,
    process_index,
)
from dinov3_tpu.parallel.mesh import MeshSpec, build_mesh
from dinov3_tpu.parallel.pipeline import PipelinedBlocks, pipe_axis_size
from dinov3_tpu.parallel.reshard import (
    RESHARD_SCOPES,
    TopologyDesc,
    describe_topology,
    moments_convert_needed,
    reshard_state,
    topology_of,
)
from dinov3_tpu.parallel.ring_attention import (
    ring_attention,
    ring_attention_local,
)
from dinov3_tpu.parallel.sharding import (
    DEFAULT_LOGICAL_RULES,
    UPDATE_SHARD_AXES,
    ZERO3_AXES,
    batch_sharding,
    batch_specs,
    constrain_update_shard,
    make_sharded_init,
    replicated,
    state_shardings_from_abstract,
    update_shard_size,
    zero3_materialize_tree,
    zero3_shard_size,
    zero3_shardings_from_abstract,
)

__all__ = [
    "MeshSpec",
    "build_mesh",
    "get_current_mesh",
    "set_current_mesh",
    "seq_axis_size",
    "PipelinedBlocks",
    "pipe_axis_size",
    "ring_attention",
    "ring_attention_local",
    "RESHARD_SCOPES",
    "TopologyDesc",
    "describe_topology",
    "moments_convert_needed",
    "reshard_state",
    "topology_of",
    "initialize_distributed",
    "is_main_process",
    "process_count",
    "process_index",
    "DEFAULT_LOGICAL_RULES",
    "UPDATE_SHARD_AXES",
    "batch_sharding",
    "batch_specs",
    "constrain_update_shard",
    "make_sharded_init",
    "replicated",
    "state_shardings_from_abstract",
    "update_shard_size",
    "ZERO3_AXES",
    "zero3_materialize_tree",
    "zero3_shard_size",
    "zero3_shardings_from_abstract",
]
