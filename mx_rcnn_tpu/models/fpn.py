"""FPN Faster R-CNN — neck, multi-level heads, functional forwards.

BASELINE.json config 3 ("ResNet-101 + FPN Faster R-CNN e2e, COCO"): the
reference repo itself never shipped FPN (its graphs are the C4 models of
rcnn/symbol/symbol_resnet.py), so this module follows Lin et al. (FPN,
CVPR'17) and the Detectron-lineage conventions the north star names, built
on the same TPU-first machinery as models/faster_rcnn.py: static shapes,
in-graph targets, batched Pallas NMS, matmul ROIAlign.

Level layout:
  backbone C2..C5 (strides 4..32) → lateral 1x1 (256ch) + top-down nearest
  ×2 + output 3x3 → P2..P5; P6 = stride-2 maxpool of P5 (RPN only).
  RPN head shared across levels; one anchor scale per level (cfg
  anchor_scales=(8,) → 32..512 px areas on P2..P6), 3 ratios.
  ROI features: level k = floor(k0 + log2(sqrt(area)/224)) clamped to
  [2, 5] (FPN Eq. 1), pooled 7x7 from the assigned level.

Static-shape strategy: proposals are decoded + top-k'd per level (a fixed
per-level budget), NMS'd within each level, and the top post_nms of the
score-ranked union is taken (Detectron-lineage semantics; the joint
union-NMS variant stays available via fpn_nms_per_level=False) — every
shape is compile-time fixed either way.
ROI-to-level assignment pools each roi ONCE from a canvas of all four levels,
with bilinear weights that are zero outside the roi's own level: static
shapes, one pair of contractions (pyramid_roi_align).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.backbones import ResNetStages
from mx_rcnn_tpu.models.faster_rcnn import _assign_anchors_batch
from mx_rcnn_tpu.models.losses import rcnn_losses, rpn_losses
from mx_rcnn_tpu.models.rpn import RPNHead
from mx_rcnn_tpu.obs.profile import stage
from mx_rcnn_tpu.ops.anchors import anchor_grid
from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.canvas import rois_by_plane
from mx_rcnn_tpu.ops.nms import nms_dispatch
from mx_rcnn_tpu.ops.proposal import _decode_one_image
from mx_rcnn_tpu.ops.roi_align import (contract_weights, roi_align,
                                       roi_align_weights)
from mx_rcnn_tpu.targets.rcnn_targets import fg_rois_per_image, sample_rois
from mx_rcnn_tpu.train.precision import island, model_dtype

Dtype = Any

# RPN levels P2..P6; ROI pooling levels P2..P5 (FPN paper).
RPN_LEVELS = (2, 3, 4, 5, 6)
ROI_LEVELS = (2, 3, 4, 5)


class FPNNeck(nn.Module):
    """Lateral + top-down feature pyramid (Lin et al. §3).

    Input (C2, C3, C4, C5) NHWC; output dict {2: P2, ..., 5: P5, 6: P6}.
    """

    channels: int = 256
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, feats: Sequence[jnp.ndarray],
                 masks=None) -> Dict[int, jnp.ndarray]:
        """masks (graftcanvas): {stride: (B, H/s, W/s, 1)} placement
        masks re-zeroing packed-canvas gap cells after every biased conv
        (laterals and 3x3 outputs both carry biases, so a gap cell would
        otherwise turn into a bias halo the next conv — or the RPN head —
        reads where the bucketed path reads implicit zero padding). The
        nearest-neighbor upsample maps masked-zero cells onto masked-zero
        cells (offsets are max-stride aligned), and P6's kernel-1 pool
        subsamples masked P5, so those need no masks of their own."""
        m = masks or {}
        c2, c3, c4, c5 = [f.astype(self.dtype) for f in feats]
        laterals = []
        for i, c in enumerate((c2, c3, c4, c5)):
            lat = nn.Conv(self.channels, (1, 1), dtype=self.dtype,
                          param_dtype=jnp.float32, name=f"lateral{i + 2}")(c)
            stride = 2 ** (i + 2)
            if stride in m:
                lat = lat * m[stride].astype(lat.dtype)
            laterals.append(lat)
        # Top-down: nearest-neighbor x2 upsample, accumulate.
        merged = [None] * 4
        merged[3] = laterals[3]
        for i in (2, 1, 0):
            up = _upsample2x(merged[i + 1])
            merged[i] = laterals[i] + up
        out = {}
        for i in range(4):
            o = nn.Conv(self.channels, (3, 3),
                        padding=[(1, 1), (1, 1)], dtype=self.dtype,
                        param_dtype=jnp.float32,
                        name=f"output{i + 2}")(merged[i])
            stride = 2 ** (i + 2)
            if stride in m:
                o = o * m[stride].astype(o.dtype)
            out[i + 2] = o
        # P6: stride-2 subsample of P5 (FPN paper: max-pool, kernel 1).
        out[6] = nn.max_pool(out[5], (1, 1), strides=(2, 2))
        return out


def _upsample2x(x: jnp.ndarray) -> jnp.ndarray:
    """Nearest-neighbor 2x spatial upsample, NHWC."""
    b, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (b, h, 2, w, 2, c))
    return x.reshape(b, h * 2, w * 2, c)


class TwoFCHead(nn.Module):
    """2-FC box head (FPN paper §4.2; replaces the C4 stage-5 head)."""

    width: int = 1024
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, pooled: jnp.ndarray) -> jnp.ndarray:
        r = pooled.shape[0]
        x = pooled.astype(self.dtype).reshape(r, -1)
        x = nn.relu(nn.Dense(self.width, dtype=self.dtype,
                             param_dtype=jnp.float32, name="fc6")(x))
        x = nn.relu(nn.Dense(self.width, dtype=self.dtype,
                             param_dtype=jnp.float32, name="fc7")(x))
        return x


class MaskHead(nn.Module):
    """Mask branch (He et al., Mask R-CNN): 4x conv 3x3 → deconv x2 → 1x1.

    Input (R, 14, 14, 256) → per-class logits (R, 28, 28, num_classes).
    """

    num_classes: int = 81
    channels: int = 256
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, pooled: jnp.ndarray) -> jnp.ndarray:
        x = pooled.astype(self.dtype)
        for i in range(4):
            x = nn.relu(nn.Conv(self.channels, (3, 3),
                                padding=[(1, 1), (1, 1)], dtype=self.dtype,
                                param_dtype=jnp.float32,
                                name=f"mask_conv{i}")(x))
        x = nn.relu(nn.ConvTranspose(self.channels, (2, 2), strides=(2, 2),
                                     dtype=self.dtype,
                                     param_dtype=jnp.float32,
                                     name="mask_deconv")(x))
        logits = nn.Conv(self.num_classes, (1, 1), dtype=self.dtype,
                         param_dtype=jnp.float32,
                         kernel_init=nn.initializers.normal(0.001),
                         name="mask_logits")(x)
        return island(logits)


class FPNFasterRCNN(nn.Module):
    """ResNet-FPN Faster/Mask R-CNN parameter tree.

    Mirrors models/faster_rcnn.py::FasterRCNN's method-based apply contract
    so the functional forwards wire the non-parametric middle differently for
    train/test while sharing parameters.
    """

    depth: int = 50
    num_classes: int = 81
    num_anchors: int = 3  # per level: 1 scale x 3 ratios
    fpn_channels: int = 256
    roi_pool_size: int = 7
    use_mask: bool = False
    mask_pool_size: int = 14
    norm: str = "frozen_bn"
    freeze_at: int = 2
    dtype: Dtype = jnp.bfloat16
    remat: bool = False

    def setup(self):
        self.features = ResNetStages(depth=self.depth,
                                     freeze_at=self.freeze_at,
                                     norm=self.norm, dtype=self.dtype,
                                     remat=self.remat)
        self.neck = FPNNeck(channels=self.fpn_channels, dtype=self.dtype)
        self.rpn = RPNHead(num_anchors=self.num_anchors,
                           channels=self.fpn_channels, dtype=self.dtype)
        self.head = TwoFCHead(dtype=self.dtype)
        self.cls_score = nn.Dense(
            self.num_classes, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.01), name="cls_score")
        self.bbox_pred = nn.Dense(
            self.num_classes * 4, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.001), name="bbox_pred")
        if self.use_mask:
            self.mask_head = MaskHead(num_classes=self.num_classes,
                                      dtype=self.dtype)

    def extract(self, images: jnp.ndarray,
                masks=None) -> Dict[int, jnp.ndarray]:
        """masks (graftcanvas): packed-canvas placement masks threaded
        through the backbone stages and the neck (see FPNNeck)."""
        with stage("backbone"):
            feats = self.features(images, masks)
        with stage("neck"):
            return self.neck(feats, masks)

    def rpn_forward(self, pyramid: Dict[int, jnp.ndarray]):
        """Shared RPN over P2..P6 → per-level (cls_logits, bbox_deltas)."""
        return {lv: self.rpn(pyramid[lv]) for lv in RPN_LEVELS}

    def rpn_forward_packed(self, pyramid: Dict[int, jnp.ndarray]):
        """Shared RPN over P2..P6 as ONE head application.

        Five separate per-level head convs run at tiny grids (P5: 20x32,
        P6: 10x16) where the MXU idles behind launch/tiling floors —
        measured util 0.050 at 6.8 ms fwd (PERF.md r4 FPN roofline). The
        levels are packed into one zero-gapped canvas (~1.13x the real
        pixel count), the head runs once at a big grid, and the per-level
        outputs are sliced back out. A 3x3 SAME conv on the canvas equals
        per-level 3x3 SAME convs exactly: every level border sees zeros
        either way (gap rows/cols or the conv's own zero padding).
        """
        return apply_rpn_head_packed(self.rpn, pyramid)

    def box_head(self, pooled: jnp.ndarray):
        x = self.head(pooled)
        cls = island(self.cls_score(x))
        box = island(self.bbox_pred(x))
        return cls, box

    def mask_forward(self, pooled: jnp.ndarray):
        return self.mask_head(pooled)

    def __call__(self, images: jnp.ndarray, rois: jnp.ndarray):
        """Init-only path touching every submodule."""
        pyramid = self.extract(images)
        rpn_out = self.rpn_forward(pyramid)
        pooled = roi_align(pyramid[2], rois, self.roi_pool_size, 1.0 / 4.0)[0]
        cls, box = self.box_head(pooled)
        outs = (pyramid, rpn_out, cls, box)
        if self.use_mask:
            mp = roi_align(pyramid[2], rois, self.mask_pool_size, 1.0 / 4.0)
            outs = outs + (self.mask_forward(mp[0]),)
        return outs


# ---------------------------------------------------------------------------
# Level packing (fused shared-head application)
# ---------------------------------------------------------------------------


def pack_placements(shapes: Sequence[Tuple[int, int]], gap: int = 1
                    ) -> Tuple[Tuple[int, int], List[Tuple[int, int, int, int]]]:
    """Shelf-pack (h, w) rectangles into one canvas with `gap` px between
    any two rectangles (not at canvas edges — conv zero padding covers
    those). Returns ((Hc, Wc), [(y, x, h, w) per input, input order]).

    Greedy shelves in the given order; pyramid levels arrive tallest
    first, so P2 fills shelf 1 and P3..P6 share shelf 2 (canvas ~1.13x
    the real pixel count at the flagship shapes). Pure-Python on static
    shapes — runs at trace time.
    """
    canvas_w = max(w for _, w in shapes)
    places: List[Tuple[int, int, int, int]] = []
    shelf_y = 0      # top row of the current shelf
    shelf_h = 0      # height of the tallest rect on the current shelf
    cur_x = 0        # next free column on the current shelf
    for h, w in shapes:
        if cur_x > 0 and cur_x + w > canvas_w:  # start a new shelf
            shelf_y += shelf_h + gap
            shelf_h, cur_x = 0, 0
        places.append((shelf_y, cur_x, h, w))
        shelf_h = max(shelf_h, h)
        cur_x += w + gap
    return (shelf_y + shelf_h, canvas_w), places


def apply_rpn_head_packed(rpn_head, pyramid: Dict[int, jnp.ndarray]):
    """Apply a shared RPN head to all RPN_LEVELS as one packed-canvas
    call; shared by FPNFasterRCNN and ViTDetector.

    The inter-level gap is the head's declared spatial receptive radius
    (``SPATIAL_RADIUS`` — 1 for RPNHead's single 3x3 conv): any deeper
    head must declare its radius, and a head class that doesn't declare
    one fails loudly here rather than silently leaking activations
    across adjacent levels on the canvas."""
    radius = getattr(type(rpn_head), "SPATIAL_RADIUS", None)
    if radius is None:
        raise ValueError(
            f"{type(rpn_head).__name__} declares no SPATIAL_RADIUS: the "
            "packed-canvas RPN application needs the head's spatial "
            "receptive radius to size the inter-level gap (declare "
            "`SPATIAL_RADIUS: ClassVar[int]` on the head, or disable "
            "network.fpn_packed_rpn_head)")
    tensors = [pyramid[lv] for lv in RPN_LEVELS]
    canvas, places = pack_levels(tensors, gap=int(radius))
    cls_c, box_c = rpn_head(canvas)
    out = {}
    for lv, (y, x, h, w) in zip(RPN_LEVELS, places):
        out[lv] = (cls_c[:, y:y + h, x:x + w, :],
                   box_c[:, y:y + h, x:x + w, :])
    return out


def pack_levels(tensors: Sequence[jnp.ndarray], gap: int = 1):
    """Pack same-channel NHWC tensors into one zero-gapped canvas.

    Returns (canvas (B, Hc, Wc, C), placements [(y, x, h, w), ...]).
    Offsets are static, so placement lowers to cheap in-place updates and
    unpacking to slices; the backward pass of a slice is a zero-pad.
    """
    shapes = [(t.shape[1], t.shape[2]) for t in tensors]
    (hc, wc), places = pack_placements(shapes, gap)
    b, c = tensors[0].shape[0], tensors[0].shape[3]
    canvas = jnp.zeros((b, hc, wc, c), tensors[0].dtype)
    for t, (y, x, h, w) in zip(tensors, places):
        canvas = jax.lax.dynamic_update_slice(canvas, t, (0, y, x, 0))
    return canvas, places


# ---------------------------------------------------------------------------
# Anchors / proposals over the pyramid
# ---------------------------------------------------------------------------


def pyramid_anchors(pyramid_shapes: Dict[int, Tuple[int, int]],
                    cfg: Config) -> Dict[int, np.ndarray]:
    """Per-level anchor grids. Level k uses stride 2^k and scales scaled so
    cfg.network.anchor_scales (default (8,)) are relative to the stride —
    the FPN convention (scale 8 x stride 4..64 → 32..512 px anchors)."""
    out = {}
    for lv in RPN_LEVELS:
        h, w = pyramid_shapes[lv]
        stride = 2 ** lv
        out[lv] = anchor_grid(
            h, w,
            stride=stride,
            base_size=stride,
            ratios=cfg.network.anchor_ratios,
            scales=cfg.network.anchor_scales,
        )
    return out


def fpn_proposals(
    rpn_out: Dict[int, Tuple[jnp.ndarray, jnp.ndarray]],
    anchors: Dict[int, jnp.ndarray],
    im_info: jnp.ndarray,
    cfg: Config,
    *,
    train: bool,
):
    """Multi-level proposal generation: per-level decode + top-k, concat,
    NMS per level or jointly over the union (tc.fpn_nms_per_level), top
    post_nms_top_n.

    Returns rois (B, post, 4), roi_valid (B, post), roi_scores (B, post).
    """
    tc = cfg.train if train else cfg.test

    def decode(scores, dl, k, anch):
        return jax.vmap(
            partial(_decode_one_image, pre_nms_top_n=k,
                    min_size=tc.rpn_min_size,
                    topk_impl=cfg.network.proposal_topk),
            in_axes=(0, 0, 0, None),
        )(scores, dl, im_info, anch)

    return _select_level_proposals(
        *_decode_levels(rpn_out, anchors, cfg.network.num_anchors,
                        tc.fpn_rpn_pre_nms_per_level, lambda x: x, decode),
        tc.fpn_nms_per_level, tc.rpn_nms_thresh, tc.rpn_post_nms_top_n)


def fpn_proposals_packed(
    rpn_out: Dict[int, Tuple[jnp.ndarray, jnp.ndarray]],
    anchors: Dict[int, jnp.ndarray],
    im_info: jnp.ndarray,
    plane_of: jnp.ndarray,
    cfg: Config,
    *,
    train: bool,
):
    """fpn_proposals over a packed canvas (graftcanvas).

    rpn_out holds per-PLANE level maps; im_info (B, 5) packed rows and
    plane_of (B,) expand them to per-image candidate sets: each image
    reads its plane's scores/deltas over the canvas grid, keeps only
    anchors centered in its placement rect, and clips decoded boxes to
    the rect (ops/proposal.py::_decode_one_window) — so proposals never
    cross a placement border. Selection semantics (per-level NMS + union
    top-k, or joint) are fpn_proposals' unchanged.
    """
    from mx_rcnn_tpu.ops.canvas import plane_take
    from mx_rcnn_tpu.ops.proposal import _decode_one_window

    tc = cfg.train if train else cfg.test

    def decode(scores, dl, k, anch):
        return jax.vmap(
            partial(_decode_one_window, pre_nms_top_n=k,
                    min_size=tc.rpn_min_size,
                    topk_impl=cfg.network.proposal_topk),
            in_axes=(0, 0, 0, None),
        )(scores, dl, im_info, anch)

    return _select_level_proposals(
        *_decode_levels(rpn_out, anchors, cfg.network.num_anchors,
                        tc.fpn_rpn_pre_nms_per_level,
                        lambda x: plane_take(x, plane_of), decode),
        tc.fpn_nms_per_level, tc.rpn_nms_thresh, tc.rpn_post_nms_top_n)


def _decode_levels(rpn_out, anchors, num_anchors: int, per_level: int,
                   row_fn, decode_fn):
    """Shared per-level head of the (packed and bucketed) FPN proposal
    paths: fg softmax, row prep (`row_fn`: identity for bucketed rows,
    plane→image expansion for packed), per-level budgeted decode.

    decode_fn(scores (B, N_l), deltas (B, N_l, 4), k, anchors (N_l, 4))
    → (boxes, scores, valid) per image; returns the three per-level
    candidate lists _select_level_proposals consumes."""
    boxes_all: List[jnp.ndarray] = []
    scores_all: List[jnp.ndarray] = []
    valid_all: List[jnp.ndarray] = []
    for lv in RPN_LEVELS:
        cls_logits, deltas = rpn_out[lv]
        n = cls_logits.shape[0]
        prob = _rpn_softmax_fg(cls_logits, num_anchors)
        scores = island(row_fn(prob.reshape(n, -1)))
        dl = island(row_fn(deltas.reshape(n, -1, 4)))
        k = min(per_level, scores.shape[1])
        tb, ts, tv = decode_fn(scores, dl, k, jnp.asarray(anchors[lv]))
        boxes_all.append(tb)
        scores_all.append(ts)
        valid_all.append(tv)
    return boxes_all, scores_all, valid_all


def _select_level_proposals(boxes_all, scores_all, valid_all,
                            per_level_nms: bool, thresh: float, post: int):
    """Shared tail of the (packed and bucketed) FPN proposal paths."""
    if per_level_nms:
        return per_level_nms_union(boxes_all, scores_all, valid_all,
                                   thresh, post)

    boxes = jnp.concatenate(boxes_all, axis=1)
    scores = jnp.concatenate(scores_all, axis=1)
    valid = jnp.concatenate(valid_all, axis=1)

    keep_idx, keep_valid = nms_dispatch(boxes, scores, valid, thresh, post)
    rois = jnp.take_along_axis(boxes, keep_idx[..., None], axis=1)
    kept_scores = jnp.take_along_axis(scores, keep_idx, axis=1)
    roi_scores = jnp.where(keep_valid, kept_scores, 0.0)
    rois = jnp.where(keep_valid[..., None], rois, rois[:, :1, :])
    return rois, keep_valid, roi_scores


def per_level_nms_union(boxes_all, scores_all, valid_all,
                        thresh: float, post: int):
    """Detectron-lineage RPN selection: NMS WITHIN each level, then the
    top `post` of the union by score — no cross-level suppression.

    Inputs are per-level lists of (B, k_l, 4) boxes / (B, k_l) scores &
    validity. Returns (rois (B, post, 4), keep_valid, roi_scores)."""
    kept_boxes, kept_scores = [], []
    for bl, sl, vl in zip(boxes_all, scores_all, valid_all):
        idx, kv = nms_dispatch(bl, sl, vl, thresh, bl.shape[1])
        kept_boxes.append(jnp.take_along_axis(bl, idx[..., None], axis=1))
        sk = jnp.take_along_axis(sl, idx, axis=1)
        # -1 marks suppressed/invalid slots out of the union top-k
        # (valid RPN scores are softmax probs, strictly > 0)
        kept_scores.append(jnp.where(kv, sk, -1.0))
    boxes = jnp.concatenate(kept_boxes, axis=1)
    scores = jnp.concatenate(kept_scores, axis=1)
    top_s, top_i = jax.lax.top_k(scores, post)
    keep_valid = top_s >= 0.0
    rois = jnp.take_along_axis(boxes, top_i[..., None], axis=1)
    roi_scores = jnp.where(keep_valid, top_s, 0.0)
    rois = jnp.where(keep_valid[..., None], rois, rois[:, :1, :])
    return rois, keep_valid, roi_scores




def _rpn_softmax_fg(cls_logits: jnp.ndarray, num_anchors: int) -> jnp.ndarray:
    """(B,H,W,2A) [bg×A, fg×A] logits → (B,H,W,A) fg probability."""
    a = num_anchors
    bg, fg = cls_logits[..., :a], cls_logits[..., a:]
    return jax.nn.sigmoid(fg - bg)  # 2-way softmax fg prob == sigmoid(fg-bg)


# ---------------------------------------------------------------------------
# ROI-to-level assignment + pyramid pooling
# ---------------------------------------------------------------------------


def roi_levels(rois: jnp.ndarray, k0: int = 4, canonical: float = 224.0
               ) -> jnp.ndarray:
    """FPN Eq. 1: k = floor(k0 + log2(sqrt(wh)/224)), clamped to ROI_LEVELS.

    rois: (..., 4) image-coordinate boxes → (...,) int32 level ids.
    """
    w = rois[..., 2] - rois[..., 0] + 1.0
    h = rois[..., 3] - rois[..., 1] + 1.0
    scale = jnp.sqrt(jnp.maximum(w * h, 1e-6))
    k = jnp.floor(k0 + jnp.log2(scale / canonical))
    return jnp.clip(k, ROI_LEVELS[0], ROI_LEVELS[-1]).astype(jnp.int32)


def roi_canvas(pyramid: Dict[int, jnp.ndarray]):
    """(canvas (B, Hc, Wc, C), placements): the pooled levels in the one
    map ``pyramid_roi_align`` contracts against. No gap: nothing convolves
    over it, and a roi's weights are zero outside its level's rectangle."""
    return pack_levels([pyramid[lv] for lv in ROI_LEVELS], gap=0)


def pyramid_roi_align(
    pyramid: Dict[int, jnp.ndarray],
    rois: jnp.ndarray,
    roi_valid: jnp.ndarray,
    pool_size: int,
    windows: jnp.ndarray = None,
) -> jnp.ndarray:
    """(B, R, 4) rois → (B·R, P, P, C) pooled from each roi's FPN level.

    Each roi is pooled ONCE, from ``roi_canvas``: the four levels stacked
    into one map (at 832x1344, P2's 208x336 over a shelf of P3, P4, P5:
    312x336; other pyramids open more shelves). A roi's bilinear weights
    are built against its Eq. 1 level alone, in that level's coordinates
    (so a border sample clamps as ``roi_align`` of that level clamps it),
    laid at the level's rows and columns of the canvas, and are zero
    everywhere else and for an invalid roi: the pooled values are
    ``roi_align``'s of the assigned level, the other terms products with
    an exact zero. Shapes are static and the cost is the same whatever
    the levels' split: one pair of contractions at the canvas's width
    (PERF.md sections 5 and 6, PR 36; pooling from every level and
    selecting was 4x the poolings and 59.6 of the pyramid cell's 211 ms).
    The rois stay grouped by image (ops/roi_align.py).

    graftcanvas: on a packed batch the pyramid holds PLANES, I images each
    in row order (ops/canvas.py::rois_by_plane), and `windows` (B, 4)
    [y0, x0, h, w] placement rects clamp border samples to the image's
    own cells (ops/roi_align.py).
    """
    b, r = rois.shape[0], rois.shape[1]
    with stage("roi_align"):
        canvas, places = roi_canvas(pyramid)
        hc, wc = canvas.shape[1:3]
        grouped, win = rois_by_plane(canvas.shape[0], rois, windows)
        levels = roi_levels(grouped)
        live = roi_valid.reshape(levels.shape)
        wy = wx = 0.0
        whole = ((0, 0),) * 3  # (B, R, P): only the map's axis is padded
        for lv, (y, x, h, w) in zip(ROI_LEVELS, places):
            ly, lx = roi_align_weights(grouped, (h, w), pool_size,
                                       1.0 / (2 ** lv), windows=win)
            on = ((levels == lv) & live)[..., None, None].astype(ly.dtype)
            wy = wy + jnp.pad(ly * on, whole + ((y, hc - y - h),))
            wx = wx + jnp.pad(lx * on, whole + ((x, wc - x - w),))
        out = contract_weights(wy, wx, canvas)
        return out.reshape(b * r, *out.shape[2:])


# ---------------------------------------------------------------------------
# Functional forwards
# ---------------------------------------------------------------------------


def _pyramid_rpn(model: FPNFasterRCNN, params, images, cfg: Config,
                 masks=None):
    pyramid = model.apply(params, images, masks, method="extract")
    rpn_method = ("rpn_forward_packed" if cfg.network.fpn_packed_rpn_head
                  else "rpn_forward")
    with stage("rpn_head"):
        rpn_out = model.apply(params, pyramid, method=rpn_method)
    shapes = {lv: (pyramid[lv].shape[1], pyramid[lv].shape[2])
              for lv in RPN_LEVELS}
    anchors = pyramid_anchors(shapes, cfg)
    return pyramid, rpn_out, anchors


def _concat_level_outputs(rpn_out, num_anchors: int):
    """Per-level (B,H,W,2A)/(B,H,W,4A) → (B, N, 2) logits + (B, N, 4) deltas
    concatenated in the same order as the concatenated anchor grid."""
    logits_all, deltas_all = [], []
    for lv in RPN_LEVELS:
        cls_logits, deltas = rpn_out[lv]
        b = cls_logits.shape[0]
        a = num_anchors
        bg = cls_logits[..., :a].reshape(b, -1)
        fg = cls_logits[..., a:].reshape(b, -1)
        logits_all.append(jnp.stack([bg, fg], axis=-1))
        deltas_all.append(deltas.reshape(b, -1, 4))
    return (jnp.concatenate(logits_all, axis=1),
            jnp.concatenate(deltas_all, axis=1))


def _level_counts(rois, valid):
    """(B, R, 4) rois, (B, R) which of them count -> how many Eq. 1 sends
    to each of ROI_LEVELS, float32."""
    return island(jnp.sum(
        (roi_levels(rois)[..., None] == jnp.asarray(ROI_LEVELS))
        & valid[..., None], axis=(0, 1)))


def mask_branch(model, params, pyramid, samples, gt_boxes, gt_masks,
                windows, cfg: Config):
    """The mask loss (He et al. 2017, section 3) over the FOREGROUND block
    of the sampled rois, and the block's counts.

    ``sample_rois`` lays an image's foreground out as a prefix of its slots
    (``is_fg_slot = slots < n_fg``, ``n_fg <= round(fg_fraction *
    batch_rois)``), so the rois the recipe gives the branch are a static
    slice: pooling, head, targets and loss run over that block (128 of 512
    slots at the published sizes) and no slot outside it could have
    entered the loss. Over all slots the 14x14 pooling alone does not fit
    a v5e at 8 images (PERF.md section 6, PR 34).

    Returns ``(loss, counts)``: the mean per-pixel sigmoid cross-entropy
    of each live roi's ground-truth class's map, averaged over the batch's
    live rois; ``counts`` (7,) = live rois an image (min, mean, max) and
    how many of them Eq. 1 sends to each of ``ROI_LEVELS``.
    """
    from mx_rcnn_tpu.targets.mask_targets import mask_targets_for_rois

    b = samples.rois.shape[0]
    n = fg_rois_per_image(cfg.train.batch_rois, cfg.train.fg_fraction)
    rois = samples.rois[:, :n]
    live = (samples.valid & samples.fg_mask)[:, :n]
    with stage("mask_align"):
        pooled = pyramid_roi_align(pyramid, rois, live, model.mask_pool_size,
                                   windows=windows)
    with stage("mask_head"):
        logits = model.apply(params, pooled, method="mask_forward")
    m_res = logits.shape[1]
    with stage("mask_targets"):
        # gt_masks are BOX-frame, so the canvas shift cancels: rois and
        # gt boxes are both canvas-coordinate on a packed batch.
        targets = jax.vmap(
            partial(mask_targets_for_rois, resolution=m_res)
        )(rois, samples.matched_gt[:, :n], gt_boxes, gt_masks)  # (B, n, m, m)
        targets = targets.reshape(b * n, m_res, m_res)
    with stage("mask_loss"):
        # The class's map by a dense select over the classes, not
        # `take_along_axis` (a gather of one element a cell, and a scatter
        # going back: an element at a time on the chip, as
        # models/losses.py::softmax_ce_with_ignore found). One term of the
        # sum is not zero, so the value is the gathered one to the bit.
        cls = samples.labels[:, :n].reshape(-1)  # a live roi's is > 0
        at_cls = cls[:, None] == jnp.arange(logits.shape[-1])
        per_roi = jnp.sum(
            jnp.where(at_cls[:, None, None, :], logits, 0.0), axis=-1)
        bce = optax_sigmoid_bce(per_roi, targets)
        fg = island(live.reshape(-1))
        loss = (jnp.sum(jnp.mean(bce, axis=(1, 2)) * fg)
                / jnp.maximum(jnp.sum(fg), 1.0))
    an_image = jnp.sum(island(live), axis=1)
    counts = jnp.concatenate([
        jnp.stack([jnp.min(an_image), jnp.mean(an_image),
                   jnp.max(an_image)]), _level_counts(rois, live)])
    return loss, counts


def forward_train(
    model: FPNFasterRCNN,
    params,
    batch: Dict[str, jnp.ndarray],
    rng: jax.Array,
    cfg: Config,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """FPN end-to-end train forward. Same batch contract as
    models/faster_rcnn.py::forward_train; adds gt_masks (B, G, M, M) when
    cfg.network.use_mask (box-frame rasterized instance masks).

    graftcanvas: a PACKED batch (ops/canvas.py contract — planes of
    shelf-packed images, im_info (P, I, 5) placement rows) runs the
    backbone/neck once over the canvas planes with gap cells re-masked,
    then threads placements through anchors/targets, proposals and ROI
    pooling so per-image semantics match the bucketed path (gated in
    tests/test_canvas.py)."""
    from mx_rcnn_tpu.ops.canvas import (is_packed_batch, packed_views,
                                        placement_masks, plane_take)

    images = batch["image"]
    a = model.num_anchors
    packed = is_packed_batch(batch)
    if packed:
        from mx_rcnn_tpu.data.canvas import packed_strides

        v = packed_views(batch)
        im_info, plane_of = v["im_info"], v["plane_of"]
        gt_boxes, gt_classes = v["gt_boxes"], v["gt_classes"]
        gt_valid, gt_masks = v["gt_valid"], v.get("gt_masks")
        b = im_info.shape[0]
        windows = jnp.stack([im_info[:, 3], im_info[:, 4],
                             im_info[:, 0], im_info[:, 1]], axis=1)
        masks = placement_masks(batch["im_info"], images.shape[1:3],
                                packed_strides(cfg))
    else:
        im_info, plane_of, windows, masks = batch["im_info"], None, None, None
        gt_boxes, gt_classes = batch["gt_boxes"], batch["gt_classes"]
        gt_valid, gt_masks = batch["gt_valid"], batch.get("gt_masks")
        b = images.shape[0]

    pyramid, rpn_out, anchors = _pyramid_rpn(model, params, images, cfg,
                                             masks)
    anchors_cat = jnp.asarray(
        np.concatenate([anchors[lv] for lv in RPN_LEVELS], axis=0))

    k_anchor, k_sample, k_dummy = jax.random.split(rng, 3)
    rpn_t = _assign_anchors_batch(anchors_cat, gt_boxes, gt_valid, im_info,
                                  k_anchor, cfg)

    with stage("rpn_loss"):
        rpn_logits, rpn_deltas = _concat_level_outputs(rpn_out, a)
        if packed:
            # Per-plane head outputs → per-image rows: each image reads ITS
            # plane's canvas grid; its labels ignore every out-of-rect
            # anchor.
            rpn_logits = plane_take(rpn_logits, plane_of)
            rpn_deltas = plane_take(rpn_deltas, plane_of)
        rpn_l = rpn_losses(rpn_logits, rpn_deltas, rpn_t.labels,
                           rpn_t.bbox_targets, rpn_t.bbox_weights,
                           cfg.train.rpn_batch_size)

    with stage("proposal"):
        rpn_sg = {lv: (jax.lax.stop_gradient(c), jax.lax.stop_gradient(d))
                  for lv, (c, d) in rpn_out.items()}
        if packed:
            rois, roi_valid, _ = fpn_proposals_packed(
                rpn_sg, anchors, im_info, plane_of, cfg, train=True)
        else:
            rois, roi_valid, _ = fpn_proposals(rpn_sg, anchors, im_info,
                                               cfg, train=True)

    with stage("roi_sample"):
        samples = jax.vmap(
            partial(
                sample_rois,
                num_classes=model.num_classes,
                batch_rois=cfg.train.batch_rois,
                fg_fraction=cfg.train.fg_fraction,
                fg_thresh=cfg.train.fg_thresh,
                bg_thresh_hi=cfg.train.bg_thresh_hi,
                bg_thresh_lo=cfg.train.bg_thresh_lo_value,
                bbox_means=cfg.train.bbox_means,
                bbox_stds=cfg.train.bbox_stds,
            ),
        )(rois, roi_valid, gt_boxes, gt_classes,
          gt_valid, jax.random.split(k_sample, b))

    r = cfg.train.batch_rois
    pooled = pyramid_roi_align(pyramid, samples.rois, samples.valid,
                               model.roi_pool_size, windows=windows)
    with stage("box_head"):
        cls_logits, bbox_deltas = model.apply(params, pooled,
                                              method="box_head")

    with stage("rcnn_loss"):
        labels = jnp.where(samples.valid.reshape(-1),
                           samples.labels.reshape(-1), -1)
        rcnn_l = rcnn_losses(
            cls_logits, bbox_deltas, labels,
            samples.bbox_targets.reshape(b * r, -1),
            samples.bbox_weights.reshape(b * r, -1),
            cfg.train.batch_rois, b)

    total = (rpn_l["rpn_cls_loss"] + rpn_l["rpn_bbox_loss"]
             + rcnn_l["rcnn_cls_loss"] + rcnn_l["rcnn_bbox_loss"])

    aux = {
        "rpn_cls_loss": rpn_l["rpn_cls_loss"],
        "rpn_bbox_loss": rpn_l["rpn_bbox_loss"],
        "rcnn_cls_loss": rcnn_l["rcnn_cls_loss"],
        "rcnn_bbox_loss": rcnn_l["rcnn_bbox_loss"],
        "rpn_logits": rpn_logits,
        "rpn_labels": rpn_t.labels,
        "rcnn_logits": cls_logits,
        "rcnn_labels": labels,
        "num_fg": jnp.sum(samples.fg_mask),
        # gt slots walked / padded, kept positives / negatives
        "rpn_target_counts": island(rpn_t.counts),
        "roi_level_counts": _level_counts(samples.rois, samples.valid),
        # the pooling's static form: the canvas's rows and columns, and
        # the pairs of contractions a pyramid_roi_align call makes
        "roi_pooling_form": island(jnp.asarray(
            jax.eval_shape(lambda p: roi_canvas(p)[0], pyramid).shape[1:3]
            + (1,))),
    }

    if model.use_mask:
        mask_loss, aux["mask_roi_counts"] = mask_branch(
            model, params, pyramid, samples, gt_boxes, gt_masks, windows,
            cfg)
        total = total + mask_loss
        aux["mask_loss"] = mask_loss

    aux["total_loss"] = total
    return total, aux


def optax_sigmoid_bce(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Elementwise sigmoid BCE (numerically stable)."""
    zeros = jnp.zeros_like(logits)
    return (jnp.maximum(logits, zeros) - logits * labels
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def forward_test(
    model: FPNFasterRCNN,
    params,
    images: jnp.ndarray,
    im_info: jnp.ndarray,
    cfg: Config,
):
    """FPN test forward → (rois, roi_valid, scores (B,R,C), boxes (B,R,4C)).

    Same output contract as models/faster_rcnn.py::forward_test so the
    Predictor/pred_eval stack is model-agnostic.
    """
    pyramid, rpn_out, anchors = _pyramid_rpn(model, params, images, cfg)
    rois, roi_valid, _ = fpn_proposals(rpn_out, anchors, im_info, cfg,
                                       train=False)
    b, r = rois.shape[0], rois.shape[1]
    pooled = pyramid_roi_align(pyramid, rois, roi_valid, model.roi_pool_size)
    cls_logits, bbox_deltas = model.apply(params, pooled,
                                          method="box_head")
    scores = jax.nn.softmax(cls_logits, axis=-1).reshape(b, r, -1)
    stds = jnp.tile(island(jnp.asarray(cfg.train.bbox_stds)),
                    model.num_classes)
    means = jnp.tile(island(jnp.asarray(cfg.train.bbox_means)),
                     model.num_classes)
    deltas = bbox_deltas.reshape(b, r, -1) * stds + means
    boxes = jax.vmap(bbox_pred)(rois, deltas)
    boxes = jax.vmap(lambda bx, ii: clip_boxes(bx, (ii[0], ii[1])))(
        boxes, im_info)
    scores = scores * roi_valid[..., None].astype(scores.dtype)
    return rois, roi_valid, scores, boxes


def forward_test_masks(
    model: FPNFasterRCNN,
    params,
    images: jnp.ndarray,
    det_boxes: jnp.ndarray,
    det_classes: jnp.ndarray,
    det_valid: jnp.ndarray,
):
    """Mask branch on final detections → (B, D, m, m) sigmoid probabilities.

    det_boxes: (B, D, 4); det_classes: (B, D) int32; det_valid: (B, D).
    Run AFTER detection post-processing (the Mask R-CNN inference recipe:
    masks are predicted on the post-NMS boxes, not the proposals).
    """
    pyramid = model.apply(params, images, method="extract")
    b, d = det_boxes.shape[0], det_boxes.shape[1]
    pooled = pyramid_roi_align(pyramid, det_boxes, det_valid,
                               model.mask_pool_size)
    logits = model.apply(params, pooled, method="mask_forward")
    m = logits.shape[1]
    cls_sel = jnp.maximum(det_classes.reshape(-1), 0)
    per_det = jnp.take_along_axis(
        logits, cls_sel[:, None, None, None], axis=-1)[..., 0]
    probs = jax.nn.sigmoid(per_det).reshape(b, d, m, m)
    return probs * det_valid[..., None, None].astype(probs.dtype)


def forward_rpn(
    model: FPNFasterRCNN,
    params,
    images: jnp.ndarray,
    im_info: jnp.ndarray,
    cfg: Config,
):
    """Proposal-only forward → (rois, roi_valid, roi_scores).

    The FPN analog of models/faster_rcnn.py::forward_rpn (proposal dumping);
    uses the test-time per-level budget with the PROPOSAL_* post count."""
    from dataclasses import replace as _replace

    pyramid, rpn_out, anchors = _pyramid_rpn(model, params, images, cfg)
    dump_cfg = cfg.with_updates(test=_replace(
        cfg.test,
        rpn_post_nms_top_n=cfg.test.proposal_post_nms_top_n,
        rpn_nms_thresh=cfg.test.proposal_nms_thresh))
    return fpn_proposals(rpn_out, anchors, im_info, dump_cfg, train=False)


def build_fpn_model(cfg: Config) -> FPNFasterRCNN:
    return FPNFasterRCNN(
        depth=cfg.network.depth,
        num_classes=cfg.dataset.num_classes,
        num_anchors=cfg.network.num_anchors,
        fpn_channels=cfg.network.fpn_channels,
        roi_pool_size=cfg.network.roi_pool_size,
        use_mask=cfg.network.use_mask,
        mask_pool_size=cfg.network.mask_pool_size,
        norm=cfg.network.norm,
        freeze_at=cfg.network.freeze_at,
        dtype=model_dtype(cfg),
        remat=cfg.network.remat,
    )


def init_fpn_params(model: FPNFasterRCNN, cfg: Config, rng: jax.Array,
                    image_shape=None):
    h, w = image_shape or (64, 64)
    images = jnp.zeros((1, h, w, 3), jnp.float32)
    rois = jnp.asarray([[[0.0, 0.0, 31.0, 31.0]]], jnp.float32)
    return model.init(rng, images, rois)
