"""Device mesh construction + sharding helpers.

Replaces the reference's device plumbing: ``ctx = [mx.gpu(i) for i in
--gpus]`` + ``mx.kvstore.create(args.kvstore)`` (train_end2end.py) and the
batch slicing of ``DataParallelExecutorGroup``. The ``--tpu-mesh`` CLI flag
("8", "4x2", "4x4") maps to a Mesh with axes ``(data, model)``:

- ``data``: the DP axis — per-device batch shards, gradients reduced by XLA
  ``psum`` over ICI (the KVStore 'device' mode analog).
- ``model``: reserved for tensor/spatial sharding of the later large configs
  (the reference has no model parallelism — SURVEY.md §3.2 — so default 1).

Multi-host: `jax.distributed.initialize` + the same mesh over all processes
covers the reference's `dist_sync` ps-lite mode; the DCN axis is the leading
mesh dim so gradient collectives ride ICI within a slice.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def parse_mesh_shape(spec: str) -> Tuple[int, int]:
    """'8' → (8, 1); '4x2' → (4, 2) as (data, model)."""
    parts = [int(p) for p in str(spec).lower().split("x") if p]
    if len(parts) == 1:
        return parts[0], 1
    if len(parts) == 2:
        return parts[0], parts[1]
    raise ValueError(f"bad mesh spec {spec!r}; expected 'N' or 'NxM'")


def create_mesh(spec: str = "", devices=None) -> Mesh:
    """Create the (data, model) mesh. Empty spec → all available devices DP."""
    devices = devices if devices is not None else jax.devices()
    if not spec:
        d, m = len(devices), 1
    else:
        d, m = parse_mesh_shape(spec)
    if d * m > len(devices):
        raise ValueError(
            f"mesh {d}x{m} needs {d*m} devices, have {len(devices)}")
    arr = np.asarray(devices[: d * m]).reshape(d, m)
    return Mesh(arr, ("data", "model"))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis (batch) sharding over the data axis."""
    return NamedSharding(mesh, P("data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def place_replicated(tree, mesh: Mesh):
    """Put a pytree (the train state) on the mesh, replicated, BEFORE the
    first step. jax types an array by the mesh it lives on, so a step
    first traced for host or one-device state is traced and compiled a
    SECOND time when its own mesh-placed output comes back as the next
    input — a whole extra compile of the train step (minutes on the
    chip). Placed up front, every call sees the same types. Multi-host,
    where the replicated sharding is not addressable from one process,
    the jit's in_shardings keep doing the placement."""
    sharding = replicated(mesh)
    if not sharding.is_fully_addressable:
        return tree
    return jax.device_put(tree, sharding)


def shard_batch(batch: dict, mesh: Mesh) -> dict:
    """Place a host batch dict onto the mesh, sharded along the batch axis
    (axis 0).

    The analog of DataParallelExecutorGroup slicing a batch across contexts
    (reference: mxnet executor_group via work_load_list) — here one
    device_put with a NamedSharding; the batch's leading dim must divide by
    the data-axis size. Under a multi-process runtime each process passes
    its LOCAL slice and the global array is assembled across hosts
    (parallel/distributed.py).
    """
    from mx_rcnn_tpu.parallel.distributed import make_global_batch

    return make_global_batch(batch, mesh, batch_sharding(mesh))
