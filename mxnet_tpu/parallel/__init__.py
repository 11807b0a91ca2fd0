"""Parallelism primitives — the TPU-native replacement for the reference's
device-placement + KVStore machinery (SURVEY §2.2, §5.8).

The reference scales by slicing batches across explicit device contexts
(``python/mxnet/module/executor_group.py:143``) and reducing gradients through
a KVStore backed by ps-lite / NCCL (``src/kvstore/``).  Here scaling is
declarative: pick a :class:`jax.sharding.Mesh`, annotate array shardings, and
XLA inserts the collectives over ICI/DCN.

Public surface:
- :func:`make_mesh` / :func:`current_mesh` — named device meshes (dp/tp/pp/sp/ep axes)
- :func:`shard` / :func:`replicate` — NamedSharding helpers
- :func:`allreduce` / :func:`allgather` — pytree collectives usable inside shard_map
- :mod:`mxnet_tpu.parallel.dist` — multi-host bootstrap (jax.distributed), the
  replacement for ``tools/launch.py`` + dmlc tracker roles
- :mod:`mxnet_tpu.parallel.ring` — ring attention (sequence/context parallelism)
"""
from .mesh import (
    make_mesh,
    current_mesh,
    default_mesh,
    set_default_mesh,
    shard,
    replicate,
    named_sharding,
    shard_params,
    local_mesh_devices,
    place_committed,
    zero_shard_spec,
    zero1_shardings,
    zero1_place,
    zero1_state_bytes,
    mesh_process_count,
    mesh_spans_processes,
    mesh_axis_local_size,
    mesh_axis_spans_processes,
    mesh_batch_factor,
    global_batch_array,
    host_local_rows,
)
from .collectives import (allreduce, allgather, reduce_scatter, pmean,
                          psum_scatter, note_derived)
from . import dist
from . import checkpoint
from .ring import ring_attention, ring_self_attention
from .pipeline import gpipe, stack_stage_params
from .moe import (held_experts_ffn, moe_ffn, moe_layer, route,
                  stack_expert_params)

__all__ = [
    "make_mesh",
    "current_mesh",
    "default_mesh",
    "set_default_mesh",
    "shard",
    "replicate",
    "named_sharding",
    "shard_params",
    "local_mesh_devices",
    "place_committed",
    "zero_shard_spec",
    "zero1_shardings",
    "zero1_place",
    "zero1_state_bytes",
    "mesh_process_count",
    "mesh_spans_processes",
    "mesh_axis_local_size",
    "mesh_axis_spans_processes",
    "mesh_batch_factor",
    "global_batch_array",
    "host_local_rows",
    "allreduce",
    "allgather",
    "reduce_scatter",
    "pmean",
    "psum_scatter",
    "note_derived",
    "dist",
    "checkpoint",
    "ring_attention",
    "ring_self_attention",
    "gpipe",
    "stack_stage_params",
    "moe_ffn",
    "moe_layer",
    "held_experts_ffn",
    "route",
    "stack_expert_params",
]
