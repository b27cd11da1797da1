"""Pipeline parallelism — GPipe-style microbatched stage pipeline.

The reference has no model parallelism at all (SURVEY.md §3.2); like
parallel/partition.py (TP) this is TPU-native surface, built the idiomatic
JAX way: the mesh axis IS the pipeline, stages talk over ICI with
``lax.ppermute`` ring hops inside one ``shard_map``-ped program, and the
whole schedule is a ``lax.scan`` — fully traceable, differentiable (the
ppermute/where transpose is its own reverse schedule), and jit-compiled
once.

Schedule (classic GPipe, S stages, M microbatches, T = M + S - 1 ticks)::

    tick t: stage 0 injects microbatch t (t < M); every stage applies its
            block to the activation it holds; activations hop one stage
            down the ring; stage S-1 emits microbatch t-(S-1) (t >= S-1).

Stages run on *every* tick (devices compute on zero/stale buffers during
fill/drain) — the standard bubble; efficiency is M / (M + S - 1).

``stage_params`` carries a leading stage axis (leaf shape (S, ...)), the
layout produced by ``flax.linen.scan`` over a homogeneous stage module
(models/vit.py builds exactly that), so the same params run EITHER
sequentially (nn.scan) or pipelined (here) with identical numerics.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def _mark_varying(x, axes):
    """shard_map manual-axes type tracking (see ops/ring_attention.py)."""
    return lax.pcast(x, axes, to="varying")


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jnp.ndarray,
    mesh: Mesh,
    axis: str = "model",
    microbatches: Optional[int] = None,
    batch_axis: Optional[str] = "data",
):
    """Run ``y = stage_{S-1}(...stage_1(stage_0(x)))`` as a pipeline.

    Args:
      stage_fn: (params_one_stage, activation) -> activation, identical
        structure for every stage (the activation shape must be preserved
        — stages are ring-connected).
      stage_params: pytree with a leading stage axis of size S = mesh
        axis size on every leaf.
      x: (B, ...) batch; split into ``microbatches`` chunks along axis 0
        (B must divide). Default: one microbatch per stage (the smallest
        sensible choice; more microbatches shrink the bubble).
      mesh/axis: the mesh axis acting as the pipeline.
      batch_axis: mesh axis the batch dim is data-sharded over (composes
        DP x PP: each data shard keeps its slice while activations ring
        over `axis`). Ignored if absent from the mesh.

    Returns:
      (B, ...) output, replicated over the pipeline axis (still sharded
      over `batch_axis`).
    """
    s = mesh.shape[axis]
    bad = [tuple(leaf.shape) for leaf in jax.tree.leaves(stage_params)
           if leaf.shape[:1] != (s,)]
    if bad:
        # A larger multiple would pass shard_map's divisibility check and
        # silently compose only every (S/s)-th stage — hard error instead.
        raise ValueError(
            f"stage_params leading axis must equal the {s}-way '{axis}' "
            f"mesh axis; got leaf shapes {bad[:3]}")
    m = microbatches or s
    if x.shape[0] % m:
        raise ValueError(
            f"batch {x.shape[0]} must divide into {m} microbatches")
    xs = x.reshape(m, x.shape[0] // m, *x.shape[1:])
    t_total = m + s - 1
    shift_down = [(i, (i + 1) % s) for i in range(s)]
    b_ax = batch_axis if (batch_axis and batch_axis != axis
                          and batch_axis in mesh.axis_names) else None
    if b_ax and xs.shape[1] % mesh.shape[b_ax]:
        raise ValueError(
            f"microbatch size {xs.shape[1]} (batch {x.shape[0]} / "
            f"{m} microbatches) must divide over the {mesh.shape[b_ax]}-way "
            f"'{b_ax}' data axis")

    def pipelined(params, xs):
        # Inside shard_map: params leaves arrive as (1, ...) slices of the
        # stage axis — drop it to get MY stage's params.
        params = jax.tree.map(lambda p: p[0], params)
        idx = lax.axis_index(axis)
        zero = _mark_varying(jnp.zeros_like(xs[0]), (axis,))

        def tick(buf, t):
            inject = _mark_varying(xs[jnp.clip(t, 0, m - 1)], (axis,))
            buf = jnp.where(idx == 0, inject, buf)
            y = stage_fn(params, buf)
            recv = lax.ppermute(y, axis, shift_down)
            return recv, y

        _, ys = lax.scan(tick, zero, jnp.arange(t_total))
        # ys[t] on the LAST stage is microbatch t-(s-1) for t >= s-1.
        outs = lax.dynamic_slice_in_dim(ys, s - 1, m, axis=0)
        # Replicate the last stage's outputs to every device in the ring.
        return lax.psum(jnp.where(idx == s - 1, outs, 0.0), axis)

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)
    xs_spec = P(None, b_ax) if b_ax else P()
    out = shard_map(
        pipelined, mesh=mesh,
        in_specs=(param_specs, xs_spec), out_specs=xs_spec,
    )(stage_params, xs)
    return out.reshape(x.shape[0], *out.shape[2:])
