"""RCNN ROI sampling — `sample_rois`, traceable.

Reference: rcnn/io/rcnn.py::sample_rois called from the ProposalTarget custom
op (rcnn/symbol/proposal_target.py) — the reference's single worst TPU
anti-pattern: a numpy sampler in the middle of the graph, forcing a device →
host → device round trip every step. Here it is a pure function under jit.

Reference semantics reproduced:
- gt boxes are appended to the proposal set before sampling (so early
  training always has positives);
- fg rois: IoU ≥ fg_thresh, up to fg_fraction·batch_rois, sampled without
  replacement; bg rois: IoU in [bg_thresh_lo, bg_thresh_hi), filling the
  remainder, sampled *with replacement* when short (modular refill here);
- class label = matched gt class for fg, 0 for bg;
- bbox targets = bbox_transform(roi, matched gt), normalized by
  (means, stds) when bbox_normalization_precomputed, expanded to per-class
  4-blocks with weight (1,1,1,1) on the label block
  (rcnn/processing/bbox_regression.py::expand_bbox_regression_targets).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.ops.boxes import bbox_overlaps, bbox_transform


class RoiSamples(NamedTuple):
    rois: jnp.ndarray          # (R, 4) sampled boxes
    labels: jnp.ndarray        # (R,) int32 class ids (0 = bg)
    bbox_targets: jnp.ndarray  # (R, 4*num_classes)
    bbox_weights: jnp.ndarray  # (R, 4*num_classes)
    valid: jnp.ndarray         # (R,) bool — False only in degenerate cases
    fg_mask: jnp.ndarray       # (R,) bool
    matched_gt: jnp.ndarray    # (R,) int32 index into the gt arrays
    # (meaningful on fg slots only — the mask head resamples
    # gt_masks[matched_gt]; the reference has no analog because its
    # ProposalTarget recomputes matches on the host.)


def _ranked_candidates(mask: jnp.ndarray, key) -> tuple:
    """Random permutation of True indices first, then the rest; plus count."""
    n = mask.shape[0]
    keys = jnp.where(mask, jax.random.uniform(key, (n,)), 2.0)
    order = jnp.argsort(keys).astype(jnp.int32)
    count = jnp.sum(mask.astype(jnp.int32))
    return order, count


def fg_rois_per_image(batch_rois: int, fg_fraction: float) -> int:
    """How many of an image's sampled slots may hold foreground rois: the
    leading block of ``sample_rois``' slots, and the block the mask branch
    runs over (models/fpn.py::mask_branch)."""
    return round(fg_fraction * batch_rois)


def sample_rois(
    rois: jnp.ndarray,
    roi_valid: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_classes: jnp.ndarray,
    gt_valid: jnp.ndarray,
    key: jax.Array,
    *,
    num_classes: int,
    batch_rois: int = 128,
    fg_fraction: float = 0.25,
    fg_thresh: float = 0.5,
    bg_thresh_hi: float = 0.5,
    bg_thresh_lo: float = 0.0,
    bbox_means=(0.0, 0.0, 0.0, 0.0),
    bbox_stds=(0.1, 0.1, 0.2, 0.2),
) -> RoiSamples:
    """Single-image ROI sampling. vmap over batch at the call site.

    Args:
      rois: (P, 4) proposals (image coords).
      roi_valid: (P,) bool.
      gt_boxes: (G, 4) padded gt boxes; gt_classes: (G,) int; gt_valid: (G,).
    """
    k_fg, k_bg = jax.random.split(key)
    # Append gt boxes to the candidate set (reference: proposal_target.py
    # `all_rois = np.vstack((rois, gt_boxes))`).
    cand = jnp.concatenate([rois, gt_boxes], axis=0)
    cand_valid = jnp.concatenate([roi_valid, gt_valid], axis=0)

    iou = bbox_overlaps(cand, gt_boxes)
    iou = jnp.where(gt_valid[None, :], iou, -1.0)
    max_iou = jnp.where(cand_valid, jnp.max(iou, axis=1), -1.0)
    argmax_gt = jnp.argmax(iou, axis=1)

    fg_cand = cand_valid & (max_iou >= fg_thresh)
    bg_cand = cand_valid & (max_iou < bg_thresh_hi) & (max_iou >= bg_thresh_lo)

    fg_per_image = fg_rois_per_image(batch_rois, fg_fraction)
    fg_order, fg_count = _ranked_candidates(fg_cand, k_fg)
    bg_order, bg_count = _ranked_candidates(bg_cand, k_bg)
    n_fg = jnp.minimum(fg_count, fg_per_image)

    slots = jnp.arange(batch_rois, dtype=jnp.int32)
    is_fg_slot = slots < n_fg
    # fg slots index the fg candidate list directly (without replacement —
    # n_fg <= fg_count by construction). bg slots refill modularly when short
    # (reference: npr.choice(..., replace=True)).
    fg_idx = fg_order[jnp.minimum(slots, fg_count - 1)]
    bg_slot = slots - n_fg
    bg_idx = bg_order[jnp.where(bg_count > 0, bg_slot % jnp.maximum(bg_count, 1), 0)]
    # Degenerate case: no bg candidates at all -> refill from fg (keeps
    # shapes; weight masking below keeps the loss sane).
    any_bg = bg_count > 0
    take = jnp.where(is_fg_slot, fg_idx, jnp.where(any_bg, bg_idx, fg_idx))
    slot_valid = is_fg_slot | (any_bg & ~is_fg_slot)
    # If there are neither fg nor bg candidates (all-padding image), mark all
    # slots invalid but keep index 0.
    slot_valid = slot_valid & (fg_count + bg_count > 0)
    take = jnp.where(slot_valid, take, 0)

    out_rois = cand[take]
    matched = argmax_gt[take]
    labels = jnp.where(
        is_fg_slot & slot_valid, gt_classes[matched].astype(jnp.int32), 0
    )
    fg_mask = is_fg_slot & slot_valid

    # Regression targets, normalized (reference: sample_rois under
    # BBOX_NORMALIZATION_PRECOMPUTED).
    t = bbox_transform(out_rois, gt_boxes[matched])
    t = (t - jnp.asarray(bbox_means)) / jnp.asarray(bbox_stds)
    # Expand to per-class blocks (expand_bbox_regression_targets).
    class_onehot = jax.nn.one_hot(labels, num_classes, dtype=t.dtype)  # (R, C)
    expanded = class_onehot[:, :, None] * t[:, None, :]  # (R, C, 4)
    weights = class_onehot[:, :, None] * fg_mask[:, None, None].astype(t.dtype)
    r = out_rois.shape[0]
    return RoiSamples(
        rois=out_rois.astype(jnp.float32),
        labels=labels,
        bbox_targets=expanded.reshape(r, num_classes * 4).astype(jnp.float32),
        bbox_weights=jnp.broadcast_to(weights, expanded.shape)
        .reshape(r, num_classes * 4)
        .astype(jnp.float32),
        valid=slot_valid,
        fg_mask=fg_mask,
        matched_gt=matched.astype(jnp.int32),
    )
