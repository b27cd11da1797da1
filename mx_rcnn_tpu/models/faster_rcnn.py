"""Faster R-CNN — model module + pure train/test forwards.

Replaces the reference's train/test symbol builders
(rcnn/symbol/symbol_vgg.py::get_vgg_train/get_vgg_test,
rcnn/symbol/symbol_resnet.py::get_resnet_train/get_resnet_test) and the
graph-embedded Proposal/ProposalTarget custom ops
(rcnn/symbol/proposal.py, rcnn/symbol/proposal_target.py).

The single biggest design delta vs the reference (SURVEY.md §8): the whole
step — backbone, RPN, proposal generation, anchor/ROI target assignment, ROI
pooling, heads, losses — is ONE traced XLA program. The reference bounces to
the host for ProposalTarget (numpy sampling) every step; here everything is
static-shape and stays on device.

Data layout: NHWC images, (B, N, ·) flattened anchor grids where
N = H/16 · W/16 · A, matching ops/anchors.anchor_grid ordering.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.backbones import ResNetC4, ResNetHead, VGGConv, VGGHead
from mx_rcnn_tpu.models.losses import rcnn_losses, rpn_losses
from mx_rcnn_tpu.models.rpn import RPNHead
from mx_rcnn_tpu.obs.profile import stage
from mx_rcnn_tpu.ops.anchors import anchor_grid
from mx_rcnn_tpu.ops.boxes import bbox_pred, clip_boxes
from mx_rcnn_tpu.ops.canvas import rois_by_plane
from mx_rcnn_tpu.ops.proposal import generate_proposals
from mx_rcnn_tpu.ops.roi_align import roi_align, roi_pool
from mx_rcnn_tpu.targets.rcnn_targets import sample_rois
from mx_rcnn_tpu.targets.rpn_targets import assign_anchors
from mx_rcnn_tpu.train.precision import island, model_dtype


class FasterRCNN(nn.Module):
    """Backbone + RPN + box head as one parameter tree.

    Methods are exposed individually (via ``apply(..., method=...)``) so the
    train and test forwards can wire the non-parametric middle (proposals,
    target sampling, ROI pooling) differently while sharing parameters —
    the analog of the reference's get_*_train/get_*_test sharing arg_params.
    """

    backbone: str = "resnet50"  # "resnet50" | "resnet101" | "vgg"
    num_classes: int = 81
    num_anchors: int = 9
    roi_pool_size: int = 14
    roi_pool_type: str = "align"
    norm: str = "frozen_bn"
    freeze_at: int = 2
    dtype: Any = jnp.bfloat16
    remat: bool = False

    def setup(self):
        if self.backbone.startswith("resnet"):
            depth = int(self.backbone.replace("resnet", ""))
            self.features = ResNetC4(depth=depth, freeze_at=self.freeze_at,
                                     norm=self.norm, dtype=self.dtype,
                                     remat=self.remat)
            self.head = ResNetHead(depth=depth, norm=self.norm,
                                   dtype=self.dtype)
        elif self.backbone == "vgg":
            if self.remat:
                from mx_rcnn_tpu.logger import logger

                logger.warning("network.remat is not implemented for the "
                               "VGG backbone; running without remat")
            # freeze_at=0 (from-scratch profile) unfreezes conv1-2 too;
            # any other value keeps the reference's conv1-2 cut.
            self.features = VGGConv(
                freeze_blocks=0 if self.freeze_at == 0 else 2,
                dtype=self.dtype)
            self.head = VGGHead(dtype=self.dtype)
        else:
            raise ValueError(f"unknown backbone {self.backbone!r}")
        self.rpn = RPNHead(num_anchors=self.num_anchors, dtype=self.dtype)
        self.cls_score = nn.Dense(
            self.num_classes, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.01), name="cls_score")
        self.bbox_pred = nn.Dense(
            self.num_classes * 4, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.001), name="bbox_pred")

    def extract(self, images: jnp.ndarray, masks=None) -> jnp.ndarray:
        """masks (graftcanvas): {stride: (B, H/s, W/s, 1)} packed-canvas
        placement masks the backbone re-zeros its gap cells with
        (models/backbones.py). None = the classic bucketed path."""
        return self.features(images, masks)

    def rpn_forward(self, feat: jnp.ndarray):
        return self.rpn(feat)

    def box_head(self, pooled: jnp.ndarray, deterministic: bool = True):
        if self.backbone == "vgg":
            x = self.head(pooled, deterministic=deterministic)
        else:
            x = self.head(pooled)
        cls = island(self.cls_score(x))
        box = island(self.bbox_pred(x))
        return cls, box

    def __call__(self, images: jnp.ndarray, rois: jnp.ndarray):
        """Init-only path touching every submodule."""
        feat = self.extract(images)
        rpn_cls, rpn_box = self.rpn_forward(feat)
        pooled = roi_align(feat, rois, self.roi_pool_size, 1.0 / 16.0)[0]
        cls, box = self.box_head(pooled)
        return feat, rpn_cls, rpn_box, cls, box


# ---------------------------------------------------------------------------
# Functional forwards
# ---------------------------------------------------------------------------


def _rpn_softmax(cls_logits: jnp.ndarray, num_anchors: int) -> jnp.ndarray:
    """(B,H,W,2A) logits, [bg×A, fg×A] layout → softmaxed probs same layout.

    Reference: rpn_cls_score reshape to (2, A·H·W) + SoftmaxOutput over the
    2-way axis (symbol_*.py rpn_cls_prob).
    """
    a = num_anchors
    bg, fg = cls_logits[..., :a], cls_logits[..., a:]
    m = jnp.maximum(bg, fg)
    ebg = jnp.exp(bg - m)
    efg = jnp.exp(fg - m)
    denom = ebg + efg
    return jnp.concatenate([ebg / denom, efg / denom], axis=-1)


def _pair_logits(cls_logits: jnp.ndarray, num_anchors: int) -> jnp.ndarray:
    """(B,H,W,2A) → (B, H·W·A, 2) per-anchor [bg, fg] logits."""
    b, h, w, _ = cls_logits.shape
    a = num_anchors
    bg = cls_logits[..., :a].reshape(b, -1)
    fg = cls_logits[..., a:].reshape(b, -1)
    return jnp.stack([bg, fg], axis=-1)


def _pool_rois(feat, rois, roi_valid, pool_size, pool_type, windows=None):
    """Batched ROI pooling: (B,Hf,Wf,C) + (B,R,4) → (B·R,P,P,C).

    The rois stay grouped by image through the op (ops/roi_align.py); only
    the quantized `roi_pool` still takes the reference's ROIPooling layout,
    (batch_idx, x1..y2) rows.

    graftcanvas: on a packed batch `feat` holds PLANES, I images each in
    row order (ops/canvas.py::rois_by_plane), and `windows` (B, 4)
    [y0, x0, h, w] clamps border samples to the image's own cells.
    """
    b, r = rois.shape[0], rois.shape[1]
    planes = feat.shape[0]
    with stage("roi_align"):
        if pool_type == "align":
            grouped, win = rois_by_plane(planes, rois, windows)
            pooled = roi_align(feat, grouped, pool_size, 1.0 / 16.0,
                               windows=win)
            pooled = pooled.reshape(b * r, *pooled.shape[2:])
        else:
            plane_idx = jnp.repeat(
                jnp.arange(planes, dtype=jnp.float32), b * r // planes)
            flat = jnp.concatenate(
                [plane_idx[:, None], rois.reshape(b * r, 4)], axis=1)
            pooled = roi_pool(feat, flat, pool_size, 1.0 / 16.0)
        # Zero padded slots so dead rois contribute nothing downstream.
        return pooled * roi_valid.reshape(b * r, 1, 1, 1).astype(
            pooled.dtype)


def _backbone_rpn(model: FasterRCNN, params, images: jnp.ndarray, cfg: Config,
                  masks=None):
    """Shared preamble: backbone features + RPN outputs + the anchor grid
    (compile-time const). Used by every forward variant."""
    with stage("backbone"):
        feat = model.apply(params, images, masks, method=FasterRCNN.extract)
    with stage("rpn_head"):
        rpn_cls_logits, rpn_bbox_deltas = model.apply(
            params, feat, method=FasterRCNN.rpn_forward)
    anchors = jnp.asarray(anchor_grid(
        feat.shape[1], feat.shape[2],
        stride=cfg.network.rpn_feat_stride,
        base_size=cfg.network.anchor_base_size,
        ratios=cfg.network.anchor_ratios,
        scales=cfg.network.anchor_scales,
    ))
    return feat, rpn_cls_logits, rpn_bbox_deltas, anchors


def _assign_anchors_batch(anchors, gt_boxes, gt_valid, im_info, rng,
                          cfg: Config):
    """assign_anchors over per-image rows (train-mode RPN targets), one
    key an image. Rows may be bucketed (im_info (B, 3)) or graftcanvas
    packed ((B, 5) placement rows in canvas coordinates). Every family
    that labels anchors comes through here (models/fpn.py too)."""
    with stage("rpn_targets"):
        return assign_anchors(
            anchors, gt_boxes, gt_valid, im_info,
            jax.random.split(rng, gt_boxes.shape[0]),
            rpn_batch_size=cfg.train.rpn_batch_size,
            rpn_fg_fraction=cfg.train.rpn_fg_fraction,
            positive_overlap=cfg.train.rpn_positive_overlap,
            negative_overlap=cfg.train.rpn_negative_overlap,
            allowed_border=cfg.train.rpn_allowed_border,
            clobber_positives=cfg.train.rpn_clobber_positives)


def forward_train(
    model: FasterRCNN,
    params,
    batch: Dict[str, jnp.ndarray],
    rng: jax.Array,
    cfg: Config,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One fused train forward: images → total loss + metric auxiliaries.

    batch keys: image (B,H,W,3) float32 (mean-subtracted), im_info (B,3),
    gt_boxes (B,G,4), gt_classes (B,G) int32, gt_valid (B,G) bool.

    graftcanvas: a PACKED batch (ops/canvas.py contract) instead carries
    canvas planes + (P, I, 5) placement im_info; the backbone runs once
    over the planes (gap cells re-masked) and placements thread through
    anchors/targets, proposals and ROI pooling so per-image semantics
    match the bucketed path (tests/test_canvas.py).
    """
    from mx_rcnn_tpu.ops.canvas import (is_packed_batch, packed_views,
                                        placement_masks, plane_take)
    from mx_rcnn_tpu.ops.proposal import generate_proposals_packed

    images = batch["image"]
    a = model.num_anchors
    stride = cfg.network.rpn_feat_stride
    packed = is_packed_batch(batch)
    if packed:
        from mx_rcnn_tpu.data.canvas import packed_strides

        v = packed_views(batch)
        im_info, plane_of = v["im_info"], v["plane_of"]
        gt = {k: v[k] for k in ("gt_boxes", "gt_classes", "gt_valid")}
        b = im_info.shape[0]
        windows = jnp.stack([im_info[:, 3], im_info[:, 4],
                             im_info[:, 0], im_info[:, 1]], axis=1)
        masks = placement_masks(batch["im_info"], images.shape[1:3],
                                packed_strides(cfg))
    else:
        im_info, plane_of, windows, masks = batch["im_info"], None, None, None
        gt = {k: batch[k] for k in ("gt_boxes", "gt_classes", "gt_valid")}
        b = images.shape[0]

    feat, rpn_cls_logits, rpn_bbox_deltas, anchors = _backbone_rpn(
        model, params, images, cfg, masks)

    # --- RPN targets (reference: assign_anchor on host in AnchorLoader) ---
    k_anchor, k_sample, k_drop = jax.random.split(rng, 3)
    rpn_t = _assign_anchors_batch(anchors, gt["gt_boxes"], gt["gt_valid"],
                                  im_info, k_anchor, cfg)

    with stage("rpn_loss"):
        rpn_logits_pairs = _pair_logits(rpn_cls_logits, a)
        rpn_deltas_rows = rpn_bbox_deltas.reshape(
            rpn_bbox_deltas.shape[0], -1, 4)
        if packed:
            # Per-plane head outputs → per-image rows over the canvas grid.
            rpn_logits_pairs = plane_take(rpn_logits_pairs, plane_of)
            rpn_deltas_rows = plane_take(rpn_deltas_rows, plane_of)
        rpn_l = rpn_losses(
            rpn_logits_pairs,
            rpn_deltas_rows,
            rpn_t.labels,
            rpn_t.bbox_targets,
            rpn_t.bbox_weights,
            cfg.train.rpn_batch_size,
        )

    # --- Proposals (reference: Proposal op; gradients do not flow) ---
    with stage("proposal"):
        rpn_prob = _rpn_softmax(jax.lax.stop_gradient(rpn_cls_logits), a)
        if packed:
            p = rpn_prob.shape[0]
            fg = rpn_prob[..., a:].reshape(p, -1)
            rois, roi_valid, _ = generate_proposals_packed(
                plane_take(fg, plane_of),
                jax.lax.stop_gradient(rpn_deltas_rows),  # already per-image
                im_info,
                anchors,
                pre_nms_top_n=cfg.train.rpn_pre_nms_top_n,
                post_nms_top_n=cfg.train.rpn_post_nms_top_n,
                nms_thresh=cfg.train.rpn_nms_thresh,
                min_size=cfg.train.rpn_min_size,
                topk_impl=cfg.network.proposal_topk,
            )
        else:
            rois, roi_valid, _ = generate_proposals(
                rpn_prob,
                jax.lax.stop_gradient(rpn_bbox_deltas),
                im_info,
                anchors,
                pre_nms_top_n=cfg.train.rpn_pre_nms_top_n,
                post_nms_top_n=cfg.train.rpn_post_nms_top_n,
                nms_thresh=cfg.train.rpn_nms_thresh,
                min_size=cfg.train.rpn_min_size,
                feat_stride=stride,
                topk_impl=cfg.network.proposal_topk,
            )

    # --- ROI sampling (reference: ProposalTarget op — host numpy there) ---
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
        )(rois, roi_valid, gt["gt_boxes"], gt["gt_classes"], gt["gt_valid"],
          jax.random.split(k_sample, b))

    r = cfg.train.batch_rois
    pooled = _pool_rois(feat, samples.rois, samples.valid,
                        model.roi_pool_size, model.roi_pool_type,
                        windows=windows)
    with stage("box_head"):
        cls_logits, bbox_deltas = model.apply(
            params, pooled, False, method=FasterRCNN.box_head,
            rngs={"dropout": k_drop})

    with stage("rcnn_loss"):
        labels = jnp.where(samples.valid.reshape(-1),
                           samples.labels.reshape(-1), -1)
        rcnn_l = rcnn_losses(
            cls_logits,
            bbox_deltas,
            labels,
            samples.bbox_targets.reshape(b * r, -1),
            samples.bbox_weights.reshape(b * r, -1),
            cfg.train.batch_rois,
            b,
        )

    total = (rpn_l["rpn_cls_loss"] + rpn_l["rpn_bbox_loss"]
             + rcnn_l["rcnn_cls_loss"] + rcnn_l["rcnn_bbox_loss"])

    aux = {
        "rpn_cls_loss": rpn_l["rpn_cls_loss"],
        "rpn_bbox_loss": rpn_l["rpn_bbox_loss"],
        "rcnn_cls_loss": rcnn_l["rcnn_cls_loss"],
        "rcnn_bbox_loss": rcnn_l["rcnn_bbox_loss"],
        "total_loss": total,
        # Metric auxiliaries (train/metrics.py — the reference's 6 metrics).
        "rpn_logits": rpn_logits_pairs,  # per-image rows (packed: gathered)
        "rpn_labels": rpn_t.labels,
        "rcnn_logits": cls_logits,
        "rcnn_labels": labels,
        "num_fg": jnp.sum(samples.fg_mask),
        # gt slots walked / padded, kept positives / negatives
        "rpn_target_counts": island(rpn_t.counts),
    }
    return total, aux


def forward_test(
    model: FasterRCNN,
    params,
    images: jnp.ndarray,
    im_info: jnp.ndarray,
    cfg: Config,
):
    """Test forward: images → (rois, roi_scores (B,R,C), pred_boxes (B,R,4C)).

    Reference: get_*_test symbol + rcnn/core/tester.py::im_detect. Box
    decoding (bbox_pred → clip) happens here on device; per-class NMS lives
    in ops/detection.py (the reference does all of it on host).
    """
    a = model.num_anchors
    stride = cfg.network.rpn_feat_stride
    feat, rpn_cls_logits, rpn_bbox_deltas, anchors = _backbone_rpn(
        model, params, images, cfg)
    rpn_prob = _rpn_softmax(rpn_cls_logits, a)
    rois, roi_valid, _ = generate_proposals(
        rpn_prob, rpn_bbox_deltas, im_info, anchors,
        pre_nms_top_n=cfg.test.rpn_pre_nms_top_n,
        post_nms_top_n=cfg.test.rpn_post_nms_top_n,
        nms_thresh=cfg.test.rpn_nms_thresh,
        min_size=cfg.test.rpn_min_size,
        feat_stride=stride,
        topk_impl=cfg.network.proposal_topk,
    )
    b, r = rois.shape[0], rois.shape[1]
    pooled = _pool_rois(feat, rois, roi_valid,
                        model.roi_pool_size, model.roi_pool_type)
    cls_logits, bbox_deltas = model.apply(
        params, pooled, True, method=FasterRCNN.box_head)
    scores = jax.nn.softmax(cls_logits, axis=-1).reshape(b, r, -1)
    # Un-normalize deltas (reference folds means/stds into saved weights at
    # checkpoint time — rcnn/core/callback.py do_checkpoint; we keep weights
    # normalized and decode explicitly, see train/checkpoint.py contract).
    stds = jnp.tile(island(jnp.asarray(cfg.train.bbox_stds)),
                    model.num_classes)
    means = jnp.tile(island(jnp.asarray(cfg.train.bbox_means)),
                     model.num_classes)
    deltas = bbox_deltas.reshape(b, r, -1) * stds + means
    boxes = jax.vmap(bbox_pred)(rois, deltas)  # (B, R, 4C)
    boxes = jax.vmap(lambda bx, ii: clip_boxes(bx, (ii[0], ii[1])))(boxes, im_info)
    scores = scores * roi_valid[..., None].astype(scores.dtype)
    return rois, roi_valid, scores, boxes


def forward_train_rpn(
    model: FasterRCNN,
    params,
    batch: Dict[str, jnp.ndarray],
    rng: jax.Array,
    cfg: Config,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """RPN-only training forward (alternate-optimization stages 1 and 4).

    Reference: the rpn-only symbols get_*_rpn + rcnn/tools/train_rpn.py.
    Same batch contract as forward_train; only the RPN pair of losses.
    """
    if batch["im_info"].ndim == 3:
        raise ValueError("canvas packing (image.canvas_pack) supports the "
                         "end2end forward only; the alternate-training "
                         "stages run bucketed")
    images = batch["image"]
    b = images.shape[0]
    a = model.num_anchors
    feat, rpn_cls_logits, rpn_bbox_deltas, anchors = _backbone_rpn(
        model, params, images, cfg)
    rpn_t = _assign_anchors_batch(anchors, batch["gt_boxes"],
                                  batch["gt_valid"], batch["im_info"],
                                  rng, cfg)
    rpn_l = rpn_losses(
        _pair_logits(rpn_cls_logits, a),
        rpn_bbox_deltas.reshape(b, -1, 4),
        rpn_t.labels, rpn_t.bbox_targets, rpn_t.bbox_weights,
        cfg.train.rpn_batch_size,
    )
    total = rpn_l["rpn_cls_loss"] + rpn_l["rpn_bbox_loss"]
    aux = {
        "rpn_cls_loss": rpn_l["rpn_cls_loss"],
        "rpn_bbox_loss": rpn_l["rpn_bbox_loss"],
        "total_loss": total,
        "rpn_logits": _pair_logits(rpn_cls_logits, a),
        "rpn_labels": rpn_t.labels,
    }
    return total, aux


def forward_train_rcnn(
    model: FasterRCNN,
    params,
    batch: Dict[str, jnp.ndarray],
    rng: jax.Array,
    cfg: Config,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Fast-R-CNN training forward over PRECOMPUTED proposals.

    Reference: get_*_rcnn symbols + rcnn/tools/train_rcnn.py over ROIIter
    (selective-search or stage-RPN proposals). Batch additionally carries
    proposals (B, P, 4) + proposal_valid (B, P).
    """
    if batch["im_info"].ndim == 3:
        raise ValueError("canvas packing (image.canvas_pack) supports the "
                         "end2end forward only; the alternate-training "
                         "stages run bucketed")
    images = batch["image"]
    b = images.shape[0]
    feat = model.apply(params, images, method=FasterRCNN.extract)
    k_sample, k_drop = jax.random.split(rng)
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
    )(batch["proposals"], batch["proposal_valid"], batch["gt_boxes"],
      batch["gt_classes"], batch["gt_valid"], jax.random.split(k_sample, b))
    r = cfg.train.batch_rois
    pooled = _pool_rois(feat, samples.rois, samples.valid,
                        model.roi_pool_size, model.roi_pool_type)
    cls_logits, bbox_deltas = model.apply(
        params, pooled, False, method=FasterRCNN.box_head,
        rngs={"dropout": k_drop})
    labels = jnp.where(samples.valid.reshape(-1), samples.labels.reshape(-1), -1)
    rcnn_l = rcnn_losses(
        cls_logits, bbox_deltas, labels,
        samples.bbox_targets.reshape(b * r, -1),
        samples.bbox_weights.reshape(b * r, -1),
        cfg.train.batch_rois, b,
    )
    total = rcnn_l["rcnn_cls_loss"] + rcnn_l["rcnn_bbox_loss"]
    aux = {
        "rcnn_cls_loss": rcnn_l["rcnn_cls_loss"],
        "rcnn_bbox_loss": rcnn_l["rcnn_bbox_loss"],
        "total_loss": total,
        "rcnn_logits": cls_logits,
        "rcnn_labels": labels,
        "num_fg": jnp.sum(samples.fg_mask),
    }
    return total, aux


def forward_rpn(
    model: FasterRCNN,
    params,
    images: jnp.ndarray,
    im_info: jnp.ndarray,
    cfg: Config,
):
    """RPN-only forward → (rois, roi_valid, roi_scores).

    The proposal-generation path of the alternate-training pipeline
    (reference: tools/test_rpn.py → tester.py im_proposal), skipping the box
    head entirely — proposals cost only backbone + RPN.
    """
    a = model.num_anchors
    feat, rpn_cls_logits, rpn_bbox_deltas, anchors = _backbone_rpn(
        model, params, images, cfg)
    rpn_prob = _rpn_softmax(rpn_cls_logits, a)
    # PROPOSAL_* counts, not the detection-path RPN counts: the dump feeds
    # Fast-R-CNN training, which samples from ~2000 candidates per image
    # (reference TEST.PROPOSAL_PRE/POST_NMS_TOP_N).
    return generate_proposals(
        rpn_prob, rpn_bbox_deltas, im_info, anchors,
        pre_nms_top_n=cfg.test.proposal_pre_nms_top_n,
        post_nms_top_n=cfg.test.proposal_post_nms_top_n,
        nms_thresh=cfg.test.proposal_nms_thresh,
        min_size=cfg.test.rpn_min_size,
        feat_stride=cfg.network.rpn_feat_stride,
        topk_impl=cfg.network.proposal_topk,
    )


def build_model(cfg: Config) -> FasterRCNN:
    return FasterRCNN(
        backbone="vgg" if cfg.network.name == "vgg" else f"resnet{cfg.network.depth}",
        num_classes=cfg.dataset.num_classes,
        num_anchors=cfg.network.num_anchors,
        roi_pool_size=cfg.network.roi_pool_size,
        roi_pool_type=cfg.network.roi_pool_type,
        norm=cfg.network.norm,
        freeze_at=cfg.network.freeze_at,
        dtype=model_dtype(cfg),
        remat=cfg.network.remat,
    )


def init_params(model: FasterRCNN, cfg: Config, rng: jax.Array,
                image_shape=None):
    """Initialize the full parameter tree on tiny shapes (shape-polymorphic
    convs make the real padded shape unnecessary at init)."""
    h, w = image_shape or (64, 64)
    images = jnp.zeros((1, h, w, 3), jnp.float32)
    rois = jnp.asarray([[[0.0, 0.0, 31.0, 31.0]]], jnp.float32)
    return model.init(rng, images, rois)
