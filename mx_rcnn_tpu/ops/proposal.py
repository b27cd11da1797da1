"""Proposal generation — in-graph, static-shape.

Replaces the reference's Proposal custom op (rcnn/symbol/proposal.py
ProposalOperator, and the C++/CUDA ``mx.contrib.sym.Proposal`` selected by
config.CXX_PROPOSAL). In the reference this op is a host round-trip when the
Python version is used (GPU→CPU→GPU per step); here it is a pure traced
function inside the jitted train step.

Pipeline (reference semantics, static shapes):
  anchors (compile-time const) + rpn deltas → bbox_pred → clip to image
  → min-size filter (mask, not drop) → top pre_nms_top_n by score
  → greedy NMS(thresh) → top post_nms_top_n, padded + validity mask.

NMS dispatch (``nms_impl``):
  - "pallas": the blocked greedy-NMS Pallas TPU kernel
    (ops/nms_pallas.py::batched_nms — the nms_kernel.cu analog), one batched
    call over all images.
  - "xla": the jnp formulations (ops/nms.py) — bitmask for small candidate
    sets, iterative otherwise — vmapped per image.
  - "pallas_interpret": the same kernel in the Pallas interpreter — what
    the CPU tests name; "auto" never picks it.
  - "auto" (default): "pallas" on the TPU backend, "xla" elsewhere.

The reference pads a short post-NMS set by *re-sampling kept rois*
(proposal.py pads with random duplicates) so downstream shapes hold; we pad
with the first kept roi and carry an explicit validity mask — downstream
sampling (ProposalTarget analog) respects the mask, which the reference's
duplicate-padding only approximates.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.nms import BITMASK_NMS_MAX_BOXES, nms_dispatch

# Backwards-compat alias; the policy (and the guard rationale) lives in
# ops/nms.py::nms_dispatch now.
_BITMASK_NMS_MAX_BOXES = BITMASK_NMS_MAX_BOXES


def generate_proposals(
    rpn_cls_prob: jnp.ndarray,
    rpn_bbox_pred: jnp.ndarray,
    im_info: jnp.ndarray,
    anchors: jnp.ndarray,
    *,
    pre_nms_top_n: int,
    post_nms_top_n: int,
    nms_thresh: float,
    min_size: float,
    feat_stride: int = 16,
    nms_impl: str = "auto",
    topk_impl: str = "exact",
):
    """Batched proposal generation.

    Args:
      rpn_cls_prob: (B, H, W, 2A) — softmaxed scores, channel layout
        [bg*A, fg*A] along the last dim (matching the reference's
        (2A, H, W) NCHW layout transposed to NHWC).
      rpn_bbox_pred: (B, H, W, 4A) deltas.
      im_info: (B, 3) rows (im_height, im_width, im_scale) — the true
        (unpadded) image extent, as in the reference.
      anchors: (H*W*A, 4) from ops.anchors.anchor_grid (static const).
      min_size: min box side at the ORIGINAL scale; scaled by im_scale as in
        the reference (proposal.py: min_size * im_info[2]).
      nms_impl: "auto" | "pallas" | "pallas_interpret" | "xla" (see
        module docstring).
      topk_impl: "exact" (lax.top_k) | "approx" (lax.approx_max_k,
        recall_target 0.95 — the TPU PartialReduce op; ~1.2 ms faster at
        the 245k-score C4 size, identical on backends without the op).

    Returns:
      rois: (B, post_nms_top_n, 4) image-coordinate boxes,
      roi_valid: (B, post_nms_top_n) bool,
      roi_scores: (B, post_nms_top_n) float32 RPN fg scores (0 on padding) —
        the reference's Proposal op drops scores in-graph but the alternate
        -training proposal dump (tester.py::generate_proposals) saves them.
    """
    b, h, w, twice_a = rpn_cls_prob.shape
    a = twice_a // 2
    # fg scores: channels [A:2A). Reshape to (B, H*W*A) matching anchor order
    # (H, then W, then A fastest).
    fg = rpn_cls_prob[..., a:]  # (B, H, W, A)
    scores = fg.reshape(b, -1).astype(jnp.float32)
    deltas = rpn_bbox_pred.reshape(b, -1, 4).astype(jnp.float32)

    k = min(pre_nms_top_n, scores.shape[1])
    top_boxes, top_scores, top_valid = jax.vmap(
        partial(_decode_one_image, pre_nms_top_n=k, min_size=min_size,
                topk_impl=topk_impl),
        in_axes=(0, 0, 0, None),
    )(scores, deltas, im_info, anchors)

    return _nms_select(top_boxes, top_scores, top_valid, nms_thresh,
                       post_nms_top_n, nms_impl)


def generate_proposals_packed(
    fg_scores: jnp.ndarray,
    deltas: jnp.ndarray,
    im_info: jnp.ndarray,
    anchors: jnp.ndarray,
    *,
    pre_nms_top_n: int,
    post_nms_top_n: int,
    nms_thresh: float,
    min_size: float,
    nms_impl: str = "auto",
    topk_impl: str = "exact",
):
    """generate_proposals over a packed canvas (graftcanvas).

    Args:
      fg_scores: (B, N) per-IMAGE rows of the image's PLANE's fg scores
        over the full canvas anchor grid (ops/canvas.py::plane_take).
      deltas: (B, N, 4) likewise.
      im_info: (B, 5) packed rows [h, w, scale, y0, x0].
      anchors: (N, 4) canvas anchor grid (static const).

    Per image, only anchors whose center lies inside the placement rect
    participate, decoded boxes clip to the RECT (not the canvas), and
    min-size uses the image's own scale — so no proposal ever crosses a
    placement border (tests/test_canvas.py border-isolation gate) and
    each image reproduces the bucketed per-image decode exactly.
    Returns (rois, roi_valid, roi_scores) in CANVAS coordinates, same
    shapes/padding as generate_proposals.
    """
    k = min(pre_nms_top_n, fg_scores.shape[1])
    top_boxes, top_scores, top_valid = jax.vmap(
        partial(_decode_one_window, pre_nms_top_n=k, min_size=min_size,
                topk_impl=topk_impl),
        in_axes=(0, 0, 0, None),
    )(fg_scores.astype(jnp.float32), deltas.astype(jnp.float32), im_info,
      anchors)
    return _nms_select(top_boxes, top_scores, top_valid, nms_thresh,
                       post_nms_top_n, nms_impl)


def _nms_select(top_boxes, top_scores, top_valid, nms_thresh: float,
                post_nms_top_n: int, nms_impl: str):
    """Shared post-decode tail of the bucketed and packed proposal
    paths: NMS, gather, score zeroing, pad-with-first-kept-roi."""
    keep_idx, keep_valid = nms_dispatch(
        top_boxes, top_scores, top_valid, nms_thresh, post_nms_top_n,
        impl=nms_impl)
    rois = jnp.take_along_axis(top_boxes, keep_idx[..., None], axis=1)
    kept_scores = jnp.take_along_axis(top_scores, keep_idx, axis=1)
    roi_scores = jnp.where(keep_valid, kept_scores, 0.0)
    # Pad invalid slots with the first (highest-score) kept roi so
    # downstream pooling reads a real box; validity masks them out.
    rois = jnp.where(keep_valid[..., None], rois, rois[:, :1, :])
    return rois, keep_valid, roi_scores


def _decode_one_image(scores, deltas, im_info, anchors, *, pre_nms_top_n,
                      min_size, topk_impl: str = "exact"):
    """Per-image decode: deltas → boxes → clip → min-size mask → top-k."""
    boxes = bbox_pred(anchors, deltas)  # (N, 4)
    boxes = clip_boxes(boxes, (im_info[0], im_info[1]))
    # min-size filter (reference: _filter_boxes with min_size * im_scale).
    ws = boxes[:, 2] - boxes[:, 0] + 1.0
    hs = boxes[:, 3] - boxes[:, 1] + 1.0
    min_sz = min_size * im_info[2]
    size_ok = (ws >= min_sz) & (hs >= min_sz)
    scores = jnp.where(size_ok, scores, -1e10)
    # top-k pre-NMS trim. "approx" keeps score ORDER within the returned
    # set (approx_max_k returns sorted results; only membership at the
    # tail is approximate), so downstream NMS semantics are unchanged.
    if topk_impl == "approx":
        top_scores, top_idx = lax.approx_max_k(
            scores, pre_nms_top_n, recall_target=0.95)
    elif topk_impl == "exact":
        top_scores, top_idx = lax.top_k(scores, pre_nms_top_n)
    else:
        raise ValueError(
            f"topk_impl must be 'exact' or 'approx', got {topk_impl!r}")
    top_boxes = boxes[top_idx]
    top_valid = top_scores > -1e9
    return top_boxes, top_scores, top_valid


def _decode_one_window(scores, deltas, info, anchors, *, pre_nms_top_n,
                       min_size, topk_impl: str = "exact"):
    """_decode_one_image against a placement WINDOW of a packed canvas.

    info = [h, w, scale, y0, x0]. Same pipeline and ordering as the
    bucketed decode — decode, clip, min-size, top-k — with two deltas:
    anchors outside the window (center test) are masked out of the score
    race, and clipping happens in window-local coordinates (shift, clip
    to (h, w), shift back — identical arithmetic to the bucketed clip,
    so a canvas placement reproduces its bucketed image bit-for-bit).
    """
    from mx_rcnn_tpu.ops.canvas import anchors_in_window

    boxes = bbox_pred(anchors, deltas)  # (N, 4) canvas coords
    shift = jnp.stack([info[4], info[3], info[4], info[3]])
    boxes = clip_boxes(boxes - shift, (info[0], info[1])) + shift
    ws = boxes[:, 2] - boxes[:, 0] + 1.0
    hs = boxes[:, 3] - boxes[:, 1] + 1.0
    min_sz = min_size * info[2]
    keep = (ws >= min_sz) & (hs >= min_sz) & anchors_in_window(anchors, info)
    scores = jnp.where(keep, scores, -1e10)
    if topk_impl == "approx":
        top_scores, top_idx = lax.approx_max_k(
            scores, pre_nms_top_n, recall_target=0.95)
    elif topk_impl == "exact":
        top_scores, top_idx = lax.top_k(scores, pre_nms_top_n)
    else:
        raise ValueError(
            f"topk_impl must be 'exact' or 'approx', got {topk_impl!r}")
    top_boxes = boxes[top_idx]
    top_valid = top_scores > -1e9
    return top_boxes, top_scores, top_valid
