"""graftcanvas — in-graph placement machinery for packed batches.

Host side (planning, assembly, config contract) lives in data/canvas.py;
this module is the traced half: placement masks the backbone re-zeros its
gap cells with, and the packed-batch view helpers every forward shares.

Packed batch contract (data/loader.py::AnchorLoader under
image.canvas_pack):

  image       (P, Hc, Wc, 3)    one fixed canvas per plane
  im_info     (P, I, 5)         rows [h, w, scale, y0, x0] per image
  gt_boxes    (P, I, G, 4)      CANVAS coordinates (offset-shifted)
  gt_classes  (P, I, G)         int32
  gt_valid    (P, I, G)         bool
  gt_masks    (P, I, G, m, m)   box-frame (shift-invariant), when used

P = planes (one per data shard x accum chunk), I = images per plane.
Forwards flatten (P, I) -> B images; `plane_of` maps image -> plane for
per-image reads of per-plane tensors (RPN outputs). ROI pooling goes the
other way, `rois_by_plane`: plane p's images are rows p*I .. p*I+I-1, so
their rois regroup to the plane by shape alone.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax.numpy as jnp


def is_packed_batch(batch) -> bool:
    """Packed batches carry (P, I, 5) im_info; bucketed ones (B, 3)."""
    info = batch.get("im_info") if hasattr(batch, "get") else None
    return info is not None and getattr(info, "ndim", 0) == 3


def packed_views(batch):
    """(im_info (B,5), plane_of (B,), gt views flattened to (B, ...)).

    The packed forward's common preamble: flatten the (P, I) image grid
    to B = P*I rows while remembering each image's plane."""
    info = batch["im_info"]
    p, ipp = info.shape[0], info.shape[1]
    b = p * ipp
    plane_of = jnp.repeat(jnp.arange(p, dtype=jnp.int32), ipp)
    views = {"im_info": info.reshape(b, info.shape[-1]),
             "plane_of": plane_of}
    for key in ("gt_boxes", "gt_classes", "gt_valid", "gt_masks"):
        if key in batch:
            v = batch[key]
            views[key] = v.reshape(b, *v.shape[2:])
    return views


def plane_take(per_plane: jnp.ndarray, plane_of: jnp.ndarray) -> jnp.ndarray:
    """Per-plane tensor (P, ...) -> per-image rows (B, ...)."""
    return jnp.take(per_plane, plane_of, axis=0)


def rois_by_plane(planes: int, rois: jnp.ndarray, windows=None):
    """Per-image rois (B, R, 4) -> the groups ROIAlign pools, (P, I*R, 4).

    `windows` (B, 4), one placement rect per image, comes back per roi in
    the same grouping (None stays None). A bucketed batch is the case
    P = B, I = 1: its rois come back as they are."""
    grouped = rois.reshape(planes, -1, 4)
    if windows is None:
        return grouped, None
    per_roi = jnp.repeat(windows, rois.shape[1], axis=0)
    return grouped, per_roi.reshape(planes, -1, 4)


def placement_masks(im_info: jnp.ndarray, canvas_hw: Tuple[int, int],
                    strides: Sequence[int]) -> Dict[int, jnp.ndarray]:
    """{stride: (P, Hc/s, Wc/s, 1) float32} content masks of the canvas.

    A cell is 1 iff it overlaps ANY placement's content rect — the
    backbone multiplies activations by these after every residual block
    so gap cells stay exactly zero (the per-level analog of the
    rpn_forward_packed zero-gap argument: each conv then sees zeros
    beyond the content boundary, identical to the bucketed path's
    implicit SAME padding at its canvas edge). Offsets are aligned to
    the max stride (data/canvas.py), so start cells are exact; trailing
    partial cells count as content, matching the bucketed map's
    ceil-extent. Pure broadcasted comparisons — a few comparisons per
    canvas cell, folded by XLA."""
    ch, cw = canvas_hw
    h = im_info[..., 0]   # (P, I)
    w = im_info[..., 1]
    y0 = im_info[..., 3]
    x0 = im_info[..., 4]
    out = {}
    for s in strides:
        ys = (jnp.arange(ch // s, dtype=jnp.float32) * s)[None, None, :]
        xs = (jnp.arange(cw // s, dtype=jnp.float32) * s)[None, None, :]
        row_in = (ys + s > y0[..., None]) & (ys < (y0 + h)[..., None])
        col_in = (xs + s > x0[..., None]) & (xs < (x0 + w)[..., None])
        # (P, I, hs, ws) any-image union -> (P, hs, ws, 1)
        cell = jnp.any(row_in[..., :, None] & col_in[..., None, :], axis=1)
        out[s] = cell.astype(jnp.float32)[..., None]
    return out


def anchors_in_window(anchors: jnp.ndarray, info: jnp.ndarray) -> jnp.ndarray:
    """(N,) bool: anchor CENTER inside the image's placement rect.

    The packed analog of "this anchor belongs to this image's grid":
    center-inside keeps the border-straddling anchors the bucketed grid
    also has (they get clipped), and excludes every anchor over a gap or
    a neighboring placement. info row = [h, w, scale, y0, x0]."""
    cy = (anchors[:, 1] + anchors[:, 3]) * 0.5
    cx = (anchors[:, 0] + anchors[:, 2]) * 0.5
    return ((cy >= info[3]) & (cy < info[3] + info[0])
            & (cx >= info[4]) & (cx < info[4] + info[1]))
