"""Distributed backend — the KVStore replacement.

Reference: MXNet KVStore (`local`/`device`/`dist_sync` — C++ ps-lite) plus
DataParallelExecutorGroup batch slicing (SURVEY.md §2 L0, §3 'KVStore / comm
backend'). Here: one `jax.sharding.Mesh`, batch sharded on the `data` axis,
parameters replicated, gradient allreduce inserted by XLA over ICI/DCN.
"""

from mx_rcnn_tpu.parallel.mesh import (
    batch_sharding,
    create_mesh,
    parse_mesh_shape,
    place_replicated,
    replicated,
    shard_batch,
)
from mx_rcnn_tpu.parallel.partition import (
    TP_RULES,
    shard_params,
    shard_train_state,
    tp_param_specs,
)
from mx_rcnn_tpu.parallel.pipeline import pipeline_apply

__all__ = [
    "create_mesh",
    "parse_mesh_shape",
    "batch_sharding",
    "replicated",
    "place_replicated",
    "shard_batch",
    "TP_RULES",
    "tp_param_specs",
    "shard_params",
    "shard_train_state",
    "pipeline_apply",
]
