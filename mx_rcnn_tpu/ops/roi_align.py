"""ROIAlign / ROIPooling as traceable JAX ops, MXU-formulated.

Replaces MXNet's C++/CUDA builtins ``mx.symbol.ROIPooling`` and
``mx.contrib.sym.ROIAlign`` that the reference wires into its graphs
(rcnn/symbol/symbol_vgg.py 7x7 pool, rcnn/symbol/symbol_resnet.py 14x14 pool,
spatial_scale 1/16).

TPU formulation: bilinear interpolation is SEPARABLE, so ROIAlign is exactly
two small matmuls per ROI,

    pooled[i, j, c] = sum_h sum_w  Wy[i, h] * feat[h, w, c] * Wx[j, w]

where ``Wy (P, H)`` / ``Wx (P, W)`` hold the tent-function (hat) bilinear
weights of each bin's sample points, bin-averaging folded in. That maps the
op onto the MXU instead of the CUDA kernels' per-point gathers — gathers
lower to slow scalar loads on TPU, while these matmuls' transposes ARE the
backward pass.

The rois of an image only ever touch that image's feature map, and every
caller holds them grouped by image, ``(B, R, 4)``: the image axis is a BATCH
dimension of both contractions, ``(R·P, H) x (H, W·C)`` per image and then
``(P, W) x (W, C)`` per roi and row. Under a mesh that shards the batch over
``data`` GSPMD partitions both with no exchange. What the op costs on a v5e
is the HBM traffic of the ``(B, R, P, W, C)`` intermediate, not its FLOPs:
PERF.md sections 5 and 6 carry the measured share of the train step (PR 26;
the masked loop over every image of the batch that this replaced was 56 % of
the one-chip step and 84 % of the four-chip one: ledger, PR 25).

The op is two parts: ``roi_align_weights`` builds ``Wy`` / ``Wx`` of rois
against ONE map, and ``contract_weights`` contracts weights against a map.
``roi_align`` is the one after the other (C4's single map); the pyramid heads
(models/fpn.py::pyramid_roi_align) lay each roi's weights, built against its
own level, on a canvas of all the levels and contract once.

- ``roi_align``: bilinear sampling, ``sampling_ratio`` points per bin axis,
  average-pooled (He et al. Mask R-CNN semantics; ``aligned=True`` applies the
  -0.5 half-pixel correction of Detectron2, default False matches the classic
  MXNet contrib op). Border behavior matches the CUDA kernels: sample coords
  clamp to the feature extent.
- ``roi_pool``: quantized max pooling (classic Fast R-CNN semantics used by
  the reference's training graphs). Max over a rectangular bin is separable
  too (max over rows, then cols), giving an O(P·H·W·C)/ROI masked reduction
  instead of the O(P²·H·W·C) dense mask this module used to carry.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def _tent_weights(lo, bin_size, p: int, s: int, extent: int,
                  clip_lo=0.0, clip_hi=None):
    """Per-bin averaged bilinear sample weights along one axis.

    For bin i, the s sample points sit at ``lo + (i + (k+0.5)/s) * bin_size``;
    each contributes tent-function (hat) weights to its two integer
    neighbors. Points are clamped to [clip_lo, clip_hi] — by default the
    full feature extent [0, extent-1] (CUDA-kernel border semantics);
    packed canvases pass the ROI's placement window instead (graftcanvas),
    so a border sample clamps to the IMAGE's last cell exactly as the
    bucketed per-image map would, rather than drifting into the zero gap.
    Returns (P, extent) float32 with the 1/s bin average folded in, so
    ``W @ feat`` directly yields bin-averaged bilinear samples.
    """
    grid = (jnp.arange(p * s, dtype=jnp.float32) + 0.5) / s  # (p*s,)
    pts = lo + grid * bin_size
    pts = jnp.clip(pts, clip_lo, extent - 1.0 if clip_hi is None else clip_hi)
    idx = jnp.arange(extent, dtype=jnp.float32)
    tent = jnp.maximum(0.0, 1.0 - jnp.abs(pts[:, None] - idx[None, :]))
    return tent.reshape(p, s, extent).mean(axis=1)  # (p, extent)


def roi_align(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: int,
    spatial_scale: float,
    sampling_ratio: int = 2,
    aligned: bool = False,
    windows: jnp.ndarray = None,
) -> jnp.ndarray:
    """ROIAlign of each image's rois against that image's feature map.

    Args:
      features: (B, H, W, C) feature maps (NHWC — TPU-native layout; the
        reference's graphs are NCHW because cuDNN prefers it).
      rois: (B, R, 4) rows of (x1, y1, x2, y2) in image coords, grouped by
        image: ``rois[i]`` are pooled from ``features[i]`` and from nothing
        else (the reference's Proposal op carries a batch_idx column
        instead; here the map is in the shape).
      output_size: pooled grid side P.
      spatial_scale: e.g. 1/16 for C4.
      sampling_ratio: sample points per bin axis.
      aligned: half-pixel correction.
      windows: optional (B, R, 4) rows [y0, x0, h, w] in image coords —
        each ROI's placement rect on a packed canvas (graftcanvas). Sample
        points then clamp to the rect's feature cells instead of the
        whole map, reproducing the bucketed per-image border behavior.

    Returns: (B, R, P, P, C), features.dtype.
    """
    wy, wx = roi_align_weights(rois, features.shape[1:3], output_size,
                               spatial_scale, sampling_ratio, aligned,
                               windows)
    return contract_weights(wy, wx, features)


def roi_align_weights(
    rois: jnp.ndarray,
    extent: Tuple[int, int],
    output_size: int,
    spatial_scale: float,
    sampling_ratio: int = 2,
    aligned: bool = False,
    windows: jnp.ndarray = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The bilinear weights of ``roi_align``'s rois against ONE map of
    ``extent`` (H, W) cells at ``spatial_scale``: ``wy (B, R, P, H)`` and
    ``wx (B, R, P, W)``, float32. Arguments as ``roi_align``'s."""
    h, w = extent
    p = output_size
    s = sampling_ratio
    offset = 0.5 if aligned else 0.0

    def one_roi_weights(roi, win):
        x1 = roi[0] * spatial_scale - offset
        y1 = roi[1] * spatial_scale - offset
        x2 = roi[2] * spatial_scale - offset
        y2 = roi[3] * spatial_scale - offset
        rw = jnp.maximum(x2 - x1, 1.0) if not aligned else (x2 - x1)
        rh = jnp.maximum(y2 - y1, 1.0) if not aligned else (y2 - y1)
        cy = cx = (0.0, None)
        if win is not None:
            wy0 = win[0] * spatial_scale
            wx0 = win[1] * spatial_scale
            cy = (wy0, wy0 + jnp.ceil(win[2] * spatial_scale) - 1.0)
            cx = (wx0, wx0 + jnp.ceil(win[3] * spatial_scale) - 1.0)
        wy = _tent_weights(y1, rh / p, p, s, h, *cy)  # (P, H)
        wx = _tent_weights(x1, rw / p, p, s, w, *cx)  # (P, W)
        return wy, wx

    in_axes = (0, None if windows is None else 0)
    return jax.vmap(jax.vmap(one_roi_weights, in_axes=in_axes),
                    in_axes=in_axes)(rois, windows)


def contract_weights(wy: jnp.ndarray, wx: jnp.ndarray,
                     features: jnp.ndarray) -> jnp.ndarray:
    """``wy (B, R, P, H)``, ``wx (B, R, Q, W)`` against ``features
    (B, H, W, C)`` -> (B, R, P, Q, C), features.dtype: over H, then W."""
    dt = features.dtype
    # The image axis is a batch dimension of both operands: no roi meets
    # another image's map, and a batch sharded over a mesh axis stays put.
    tmp = jnp.einsum("brph,bhwc->brpwc", wy.astype(dt), features,
                     preferred_element_type=jnp.float32)
    out = jnp.einsum("brqw,brpwc->brpqc", wx.astype(dt), tmp.astype(dt),
                     preferred_element_type=jnp.float32)
    return out.astype(dt)


def roi_pool(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: int,
    spatial_scale: float,
) -> jnp.ndarray:
    """Classic quantized max ROIPooling (mx.symbol.ROIPooling semantics).

    Bin boundaries are computed by integer quantization (round of scaled
    coords, floor/ceil of bin edges); empty bins yield 0 (the CUDA kernel
    emits 0 for empty bins). Max over a rectangular bin separates into a
    row-max then a col-max, each a masked reduction over one spatial axis.
    """
    p = output_size
    h, w = features.shape[1], features.shape[2]
    fy = jnp.arange(h, dtype=jnp.float32)
    fx = jnp.arange(w, dtype=jnp.float32)

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        # Reference quantizes roi coords with round().
        x1 = jnp.round(roi[1] * spatial_scale)
        y1 = jnp.round(roi[2] * spatial_scale)
        x2 = jnp.round(roi[3] * spatial_scale)
        y2 = jnp.round(roi[4] * spatial_scale)
        rw = jnp.maximum(x2 - x1 + 1.0, 1.0)
        rh = jnp.maximum(y2 - y1 + 1.0, 1.0)
        bin_w = rw / p
        bin_h = rh / p
        i = jnp.arange(p, dtype=jnp.float32)
        ys_lo = jnp.floor(y1 + i * bin_h)  # (p,)
        ys_hi = jnp.ceil(y1 + (i + 1.0) * bin_h)
        xs_lo = jnp.floor(x1 + i * bin_w)
        xs_hi = jnp.ceil(x1 + (i + 1.0) * bin_w)
        row_in = (fy[None, :] >= ys_lo[:, None]) & (fy[None, :] < ys_hi[:, None])
        col_in = (fx[None, :] >= xs_lo[:, None]) & (fx[None, :] < xs_hi[:, None])
        feat = features[b]  # (H, W, C)
        neg = jnp.asarray(-jnp.inf, feat.dtype)
        # Row reduction: (p, H, 1, 1) mask over (H, W, C) -> (p, W, C).
        rowmax = jnp.where(row_in[:, :, None, None], feat[None], neg).max(axis=1)
        # Col reduction: (p, W, 1) mask over (p, W, C) -> (p, p, C).
        out = jnp.where(col_in[None, :, :, None], rowmax[:, None], neg).max(axis=2)
        return jnp.where(jnp.isfinite(out), out, 0.0).astype(feat.dtype)

    return jax.vmap(one_roi)(rois)


def roi_align_gather(
    features: jnp.ndarray,
    rois: jnp.ndarray,
    output_size: int,
    spatial_scale: float,
    sampling_ratio: int = 2,
    aligned: bool = False,
) -> jnp.ndarray:
    """Point-gather ROIAlign — the semantic oracle for ``roi_align``.

    Direct transcription of the CUDA kernel's per-sample-point bilinear
    gather. Kept for differential testing only; the matmul formulation above
    is the production path (gathers lower poorly on TPU).
    """
    p = output_size
    s = sampling_ratio
    offset = 0.5 if aligned else 0.0

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        x1 = roi[1] * spatial_scale - offset
        y1 = roi[2] * spatial_scale - offset
        x2 = roi[3] * spatial_scale - offset
        y2 = roi[4] * spatial_scale - offset
        rw = jnp.maximum(x2 - x1, 1.0) if not aligned else (x2 - x1)
        rh = jnp.maximum(y2 - y1, 1.0) if not aligned else (y2 - y1)
        grid = (jnp.arange(p * s, dtype=features.dtype) + 0.5) / s
        ys = y1 + grid * (rh / p)
        xs = x1 + grid * (rw / p)
        yy, xx = jnp.meshgrid(ys, xs, indexing="ij")
        vals = _bilinear_gather(features[b], yy, xx)  # (p*s, p*s, C)
        c = vals.shape[-1]
        return vals.reshape(p, s, p, s, c).mean(axis=(1, 3))

    return jax.vmap(one_roi)(rois)


def _bilinear_gather(feat: jnp.ndarray, y: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """Sample feat (H, W, C) at continuous points y, x (...,) -> (..., C).

    Out-of-bounds points clamp to the border (matching the CUDA kernels'
    behavior of clipping sample coords to the feature extent).
    """
    h, w = feat.shape[0], feat.shape[1]
    y = jnp.clip(y, 0.0, h - 1.0)
    x = jnp.clip(x, 0.0, w - 1.0)
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    y1 = jnp.minimum(y0 + 1.0, h - 1.0)
    x1 = jnp.minimum(x0 + 1.0, w - 1.0)
    ly = y - y0
    lx = x - x0
    hy = 1.0 - ly
    hx = 1.0 - lx
    y0i, x0i, y1i, x1i = (a.astype(jnp.int32) for a in (y0, x0, y1, x1))
    v00 = feat[y0i, x0i]
    v01 = feat[y0i, x1i]
    v10 = feat[y1i, x0i]
    v11 = feat[y1i, x1i]
    wdt = feat.dtype
    return (
        v00 * (hy * hx)[..., None].astype(wdt)
        + v01 * (hy * lx)[..., None].astype(wdt)
        + v10 * (ly * hx)[..., None].astype(wdt)
        + v11 * (ly * lx)[..., None].astype(wdt)
    )
