"""Static-shape greedy NMS, jit-traceable.

Replaces the reference's three NMS paths (rcnn/processing/nms.py wrappers over
rcnn/cython/cpu_nms.pyx, rcnn/cython/gpu_nms.pyx + nms_kernel.cu, and the pure
python reference) with a single TPU formulation:

- input is a fixed-size padded set of boxes + scores + validity mask;
- output is the top `max_output` surviving indices, padded, plus a validity
  mask — shapes are static, so the op lives inside jit (the reference's GPU
  NMS requires a device->host sync for the host-side bitmask scan).

Algorithm: exact greedy NMS. Iteratively select the highest-scoring live box,
emit it, suppress all boxes with IoU > thresh against it. `max_output`
iterations of an O(N) step inside `lax.fori_loop`. This matches the
sequential-suppression semantics of the Cython/CUDA kernels exactly
(including the strict `>` threshold comparison).

The blocked Pallas kernel (ops/nms_pallas.py, the nms_kernel.cu formulation
on the vector unit: per 128-box block a diagonal IoU tile, a resolve on whole
vectors, and a fused sweep of the later columns only; no matrix product) is
the TPU path; the jnp versions here are the reference implementation and its
correctness oracle. ``nms_dispatch``
at the bottom is the one place that decides which runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from mx_rcnn_tpu.ops.boxes import bbox_overlaps

_NEG = -1e10


def nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    valid: jnp.ndarray,
    iou_threshold: float,
    max_output: int,
):
    """Greedy NMS over a padded box set.

    Args:
      boxes: (N, 4) float, (x1,y1,x2,y2) inclusive coords.
      scores: (N,) float.
      valid: (N,) bool — padded rows must be False.
      iou_threshold: suppress IoU strictly greater than this (reference
        cpu_nms.pyx uses `ovr >= thresh` suppression? No — classic uses
        `ovr > thresh` kept check via np.where(ovr <= thresh); we keep
        boxes with IoU <= thresh, i.e. suppress strictly-greater).
      max_output: static number of survivors to emit.

    Returns:
      keep_idx: (max_output,) int32 indices into boxes (0-padded),
      keep_valid: (max_output,) bool.
    """
    n = boxes.shape[0]
    live_scores = jnp.where(valid, scores.astype(jnp.float32), _NEG)

    def body(i, carry):
        live, keep_idx, keep_valid = carry
        best = jnp.argmax(live)
        best_ok = live[best] > _NEG / 2
        keep_idx = keep_idx.at[i].set(jnp.where(best_ok, best, 0).astype(jnp.int32))
        keep_valid = keep_valid.at[i].set(best_ok)
        best_box = boxes[best]
        iou = _iou_one_to_many(best_box, boxes)
        suppress = (iou > iou_threshold) & best_ok
        live = jnp.where(suppress, _NEG, live)
        live = live.at[best].set(_NEG)
        return live, keep_idx, keep_valid

    keep_idx = jnp.zeros((max_output,), jnp.int32)
    keep_valid = jnp.zeros((max_output,), bool)
    _, keep_idx, keep_valid = lax.fori_loop(
        0, max_output, body, (live_scores, keep_idx, keep_valid)
    )
    return keep_idx, keep_valid


def _iou_one_to_many(box: jnp.ndarray, boxes: jnp.ndarray) -> jnp.ndarray:
    iw = jnp.minimum(box[2], boxes[:, 2]) - jnp.maximum(box[0], boxes[:, 0]) + 1.0
    ih = jnp.minimum(box[3], boxes[:, 3]) - jnp.maximum(box[1], boxes[:, 1]) + 1.0
    inter = jnp.maximum(iw, 0.0) * jnp.maximum(ih, 0.0)
    area = (box[2] - box[0] + 1.0) * (box[3] - box[1] + 1.0)
    areas = (boxes[:, 2] - boxes[:, 0] + 1.0) * (boxes[:, 3] - boxes[:, 1] + 1.0)
    return inter / jnp.maximum(area + areas - inter, 1e-14)


def nms_bitmask(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    valid: jnp.ndarray,
    iou_threshold: float,
    max_output: int,
):
    """Bitmask-formulation greedy NMS (the nms_kernel.cu algorithm, XLA-side).

    Phase 1 (parallel, MXU-friendly): sort boxes by score, compute the full
    N×N suppression matrix in one shot. Phase 2 (sequential scan over N):
    box i survives iff it is not suppressed by any earlier survivor. The scan
    is O(N) steps of O(N) vector work — much fewer sequential steps than
    `nms` when max_output << N is false (e.g. 12000→2000 training proposals).

    Memory: N×N bool matrix. Fine for N ≤ ~8k on one v5e core; the training
    12k case is handled by pre-trimming to pre_nms_top_n first (as the
    reference also does) or by the future Pallas blocked kernel.

    Returns indices into the ORIGINAL (unsorted) box array, padded, + mask.
    """
    n = boxes.shape[0]
    neg_scores = jnp.where(valid, scores.astype(jnp.float32), _NEG)
    order = jnp.argsort(-neg_scores)  # descending
    sboxes = boxes[order]
    svalid = valid[order]
    iou = bbox_overlaps(sboxes, sboxes)
    sup = (iou > iou_threshold) & svalid[None, :] & svalid[:, None]
    # Keep lower triangle: sup[j, i] = True iff earlier box i (higher score)
    # would suppress later box j, for i < j.
    sup = jnp.tril(sup, k=-1)

    def body(carry, j):
        kept = carry
        suppressed = jnp.any(sup[j] & kept)
        keep_j = svalid[j] & ~suppressed
        kept = kept.at[j].set(keep_j)
        return kept, keep_j

    kept0 = jnp.zeros((n,), bool)
    _, keep_flags = lax.scan(body, kept0, jnp.arange(n))
    # Select the first max_output kept boxes in score order.
    rank = jnp.cumsum(keep_flags) - 1
    take = keep_flags & (rank < max_output)
    # Scatter sorted positions into output slots.
    out_idx = jnp.zeros((max_output,), jnp.int32)
    out_valid = jnp.zeros((max_output,), bool)
    slot = jnp.where(take, rank, max_output)  # invalid rows -> OOB slot
    out_idx = out_idx.at[slot].set(order.astype(jnp.int32), mode="drop")
    out_valid = out_valid.at[slot].set(True, mode="drop")
    return out_idx, out_valid


# Past this size the (N, N) bitmask suppression matrix costs more than the
# O(N·max_output) iterative formulation (measured crossover region on CPU;
# the Pallas kernel owns the TPU path regardless).
BITMASK_NMS_MAX_BOXES = 6144


def nms_dispatch(boxes, scores, valid, iou_threshold: float,
                 max_output: int, impl: str = "auto"):
    """THE batched NMS policy, shared by every proposal path
    (ops/proposal.py, models/fpn.py): Pallas on TPU, jnp elsewhere with
    the bitmask-vs-iterative size guard.

    boxes (B, N, 4), scores (B, N), valid (B, N) → (keep_idx (B, max_output),
    keep_valid (B, max_output)).
    impl: "auto" | "pallas" | "pallas_interpret" | "xla". "auto" never
    resolves to the interpreter: a chip run gets the compiled kernel, and
    only a caller that names "pallas_interpret" (the CPU tests) gets the
    Pallas interpreter.

    Under a mesh (train/step.py traces the step inside
    ``use_abstract_mesh``) the Pallas path runs in a ``shard_map`` over
    ``data``: Mosaic kernels cannot be partitioned by GSPMD, and per-image
    NMS is independent along the batch. Every other mesh axis sees the
    operands replicated, so each of its devices runs the same kernel.
    """
    from functools import partial

    from mx_rcnn_tpu.ops.nms_pallas import batched_nms

    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl in ("pallas", "pallas_interpret"):
        run = partial(batched_nms, iou_threshold=iou_threshold,
                      max_output=max_output,
                      interpret=impl == "pallas_interpret")
        mesh = jax.sharding.get_abstract_mesh()
        if not mesh.empty and mesh.size > 1:
            spec = P("data")
            run = jax.shard_map(run, in_specs=spec, out_specs=spec,
                                check_vma=False)
        return run(boxes, scores, valid)
    if impl == "xla":
        nms_fn = (nms_bitmask if boxes.shape[1] <= BITMASK_NMS_MAX_BOXES
                  else nms)
        return jax.vmap(
            partial(nms_fn, iou_threshold=iou_threshold,
                    max_output=max_output))(boxes, scores, valid)
    raise ValueError(f"unknown nms impl {impl!r}")
