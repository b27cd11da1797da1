"""RPN anchor target assignment — `assign_anchors`, traceable.

Reference: rcnn/io/rpn.py::assign_anchor, which runs on the host inside
AnchorLoader with Cython IoU. Here it is a pure static-shape JAX function that
runs inside the jitted train step, written over the whole batch: every pass
over the anchor axis is a dense elementwise-and-reduce pass over
(images, anchors), and their number follows the data.

Reference semantics reproduced:
- only anchors fully inside the (true, unpadded) image ± allowed_border
  participate; the rest stay at label −1 (ignore);
- label 0 where max IoU < negative_overlap;
- label 1 for the best anchor(s) per gt box (ties included) and wherever
  max IoU ≥ positive_overlap (in that order — positives clobber negatives
  unless rpn_clobber_positives);
- subsample to `rpn_batch_size` anchors with at most
  `rpn_fg_fraction·batch` positives, disabling the excess *at random*
  (label → −1);
- bbox targets = bbox_transform(anchor, matched gt), weight 1 on positives.

Static-shape deltas vs the reference: nothing is dropped — all H·W·A anchors
flow through with labels; gt boxes arrive padded to a fixed count with a
validity mask. What the work follows is the data, not the padding:
- overlaps are taken one ground-truth slot at a time, in ONE loop a step
  whose trip count is the largest number of valid boxes among the step's
  images (the valid slots compacted to a prefix first; no (anchors, slots)
  matrix exists, and a padded slot costs nothing);
- the random subsampling takes the `cap` smallest of the uniform keys
  (`lax.top_k`) and keeps every anchor at or before the last chosen one in
  the stable sort's order — the same anchors a rank of every anchor keeps,
  without the inverse permutation (a scatter the chip does an element at a
  time);
- regression targets are computed for the kept positives only (at most
  `rpn_fg_fraction·batch` an image) and added into a zero (N, 4).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.ops.boxes import bbox_overlaps, bbox_transform


class RpnTargets(NamedTuple):
    labels: jnp.ndarray        # (B, N) int32 in {-1, 0, 1}
    bbox_targets: jnp.ndarray  # (B, N, 4) float32
    bbox_weights: jnp.ndarray  # (B, N, 1) float32 (1 on positives)
    # (4,) int32, how far the work engaged: gt slots walked (the loop's trip
    # count), gt slots padded (G), kept positives and kept negatives of the
    # whole batch
    counts: jnp.ndarray


def _random_subsample(mask: jnp.ndarray, limit, cap: int, keys):
    """Keep at most `limit` True entries of each row of mask, chosen uniformly.

    Matches the reference's `npr.choice(fg_inds, size=excess, replace=False)`
    disabling, and keeps the anchors a stable argsort of the same keys ranks
    under `limit` (`lax.top_k` returns the lower index first among equal
    keys, as that sort does). mask (B, N); `limit` (B,) may be traced and is
    at most the static `cap`; keys: one PRNG key a row.

    Returns (kept (B, N) bool, idx (B, cap) the cap smallest keys' anchors,
    chosen (B, cap) which of those are kept).
    """
    n = mask.shape[1]
    cap = min(cap, n)
    draw = jax.vmap(lambda k: jax.random.uniform(k, (n,)))(keys)
    u = jnp.where(mask, draw, 2.0)
    neg, idx = lax.top_k(-u, cap)
    smallest = -neg  # ascending
    chosen = (jnp.arange(cap) < limit[:, None]) & (smallest < 2.0)
    # The last chosen (key, anchor) pair bounds the kept set in the sort's
    # order: smaller keys, and equal keys at no higher an anchor. No row
    # chosen: the bound is -inf and nothing is kept.
    u_last = jnp.max(jnp.where(chosen, smallest, -jnp.inf), axis=1,
                     keepdims=True)
    i_last = jnp.max(jnp.where(chosen & (smallest == u_last), idx, -1),
                     axis=1, keepdims=True)
    at = jnp.arange(n, dtype=idx.dtype)
    kept = mask & ((u < u_last) | ((u == u_last) & (at <= i_last)))
    return kept, idx, chosen


def _targets_of_kept(anchors, gt_boxes, argmax_gt, idx, chosen):
    """One image's (N, 4) regression targets: zero but on the chosen of the
    `idx` anchors, each against the gt box it matched."""
    # the anchors are a constant of the program: picked a coordinate at a
    # time from its columns (which the overlaps read too), not as rows of 4
    # (a row-major (N, 4) constant is padded to 128 lanes a row on the chip:
    # 11.8 MB at C4's 23,040 anchors)
    kept = jnp.stack([anchors[:, c][idx] for c in range(4)], axis=-1)
    t = bbox_transform(kept, gt_boxes[argmax_gt[idx]])
    t = jnp.where(chosen[:, None], t, 0.0)
    return jnp.zeros(anchors.shape, jnp.float32).at[idx].add(
        t, unique_indices=True)


def assign_anchors(
    anchors: jnp.ndarray,
    gt_boxes: jnp.ndarray,
    gt_valid: jnp.ndarray,
    im_info: jnp.ndarray,
    keys: jax.Array,
    *,
    rpn_batch_size: int = 256,
    rpn_fg_fraction: float = 0.5,
    positive_overlap: float = 0.7,
    negative_overlap: float = 0.3,
    allowed_border: float = 0.0,
    clobber_positives: bool = False,
) -> RpnTargets:
    """Anchor assignment for a batch of images.

    Args:
      anchors: (N, 4) static anchor grid (ops.anchors.anchor_grid).
      gt_boxes: (B, G, 4) padded gt boxes (x1,y1,x2,y2).
      gt_valid: (B, G) bool; the valid slots need not be a prefix.
      im_info: (B, 3) = (height, width, scale) of the true image extent —
        or PACKED (B, 5) rows [h, w, scale, y0, x0] (graftcanvas), where
        the extent is the image's placement RECT inside the canvas and
        the anchors/gt boxes arrive in canvas coordinates. The inside
        test then bounds against the rect, so only the image's own
        anchors participate; cross-image IoU is structurally zero
        (placements are disjoint).
      keys: (B,) PRNG keys for the subsampling, one an image.
    """
    b, g = gt_valid.shape
    n = anchors.shape[0]
    k_fg, k_bg = jnp.moveaxis(jax.vmap(jax.random.split)(keys), 1, 0)

    packed = im_info.shape[1] >= 5
    y0 = im_info[:, 3:4] if packed else 0.0
    x0 = im_info[:, 4:5] if packed else 0.0
    inside = (
        (anchors[:, 0] >= x0 - allowed_border)
        & (anchors[:, 1] >= y0 - allowed_border)
        & (anchors[:, 2] < x0 + im_info[:, 1:2] + allowed_border)
        & (anchors[:, 3] < y0 + im_info[:, 0:1] + allowed_border)
    )  # (B, N)

    # Valid slots first, in their own order (a stable sort: the first
    # maximum over the compacted slots is the first over the valid ones).
    order = jnp.argsort(~gt_valid, axis=1, stable=True)
    boxes = jax.vmap(lambda bx, o: bx[o])(gt_boxes, order)
    n_gt = jnp.sum(gt_valid.astype(jnp.int32), axis=1, keepdims=True)
    slots_walked = jnp.max(n_gt)

    def walk(j, carry):
        """Slot j of every image: one IoU column over (B, N), −1 where the
        image has no j-th box, folded into the running maximum (strict `>`:
        the first maximum, as `jnp.argmax`) and into the best-anchor mask —
        the reference recomputes equality against the per-gt maximum over
        the inside anchors, ties included."""
        max_iou, argmax_gt, is_gt_best = carry
        there = j < n_gt
        iou = bbox_overlaps(anchors, lax.dynamic_index_in_dim(
            boxes, j, axis=1, keepdims=False)).T
        iou = jnp.where(there, iou, -1.0)
        gt_best = jnp.max(jnp.where(inside, iou, -1.0), axis=1, keepdims=True)
        is_gt_best |= (jnp.abs(iou - gt_best) < 1e-9) & there & (gt_best > 0)
        better = iou > max_iou
        return (jnp.where(better, iou, max_iou),
                jnp.where(better, j, argmax_gt), is_gt_best)

    max_iou, argmax_gt, is_gt_best = lax.fori_loop(
        0, slots_walked, walk,
        (jnp.full((b, n), -1.0, jnp.float32), jnp.zeros((b, n), jnp.int32),
         jnp.zeros((b, n), bool)))

    labels = jnp.full((b, n), -1, jnp.int32)
    neg = max_iou < negative_overlap
    pos = (max_iou >= positive_overlap) | is_gt_best
    if clobber_positives:
        labels = jnp.where(inside & pos, 1, labels)
        labels = jnp.where(inside & neg, 0, labels)
    else:
        labels = jnp.where(inside & neg, 0, labels)
        labels = jnp.where(inside & pos, 1, labels)
    # No gt boxes at all: everything inside is background (reference branch
    # for empty gt in assign_anchor).
    labels = jnp.where(n_gt > 0, labels, jnp.where(inside, 0, -1))

    # Subsample: cap positives, then fill the rest of the batch with negatives.
    num_fg_cap = int(rpn_batch_size * rpn_fg_fraction)  # graftlint: disable=host-sync-in-jit — static keywords of the configuration, not traced values
    fg_mask, fg_idx, fg_chosen = _random_subsample(
        labels == 1, jnp.full((b,), num_fg_cap), num_fg_cap, k_fg)
    labels = jnp.where((labels == 1) & ~fg_mask, -1, labels)
    n_fg = jnp.sum(fg_chosen.astype(jnp.int32), axis=1)
    bg_mask, _, bg_chosen = _random_subsample(
        labels == 0, rpn_batch_size - n_fg, rpn_batch_size, k_bg)
    labels = jnp.where((labels == 0) & ~bg_mask, -1, labels)

    bbox_targets = jax.vmap(_targets_of_kept, in_axes=(None, 0, 0, 0, 0))(
        anchors, boxes, argmax_gt, fg_idx, fg_chosen)
    bbox_weights = jnp.where((labels == 1)[..., None], 1.0, 0.0)
    counts = jnp.stack([slots_walked, jnp.asarray(g, jnp.int32), jnp.sum(n_fg),
                        jnp.sum(bg_chosen.astype(jnp.int32))])
    return RpnTargets(labels, bbox_targets, bbox_weights.astype(jnp.float32),
                      counts)


def assign_anchor(anchors, gt_boxes, gt_valid, im_info, key,
                  **kw) -> RpnTargets:
    """Single-image `assign_anchors`: (G, 4) boxes, (G,) validity, one
    im_info row, one key; the same path at a batch of one."""
    t = assign_anchors(anchors, gt_boxes[None], gt_valid[None],
                       im_info[None], key[None], **kw)
    return RpnTargets(t.labels[0], t.bbox_targets[0], t.bbox_weights[0],
                      t.counts)
