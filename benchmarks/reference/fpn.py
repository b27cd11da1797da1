"""Plain reference: ResNet-FPN Faster R-CNN, the end-to-end training step in
straightforward float32 ``jax.numpy``.

Written from the published description (Lin et al. 2017, "Feature Pyramid
Networks for Object Detection", sections 3 and 4; the recipe of Detectron's
``e2e_faster_rcnn_R-101-FPN_1x.yaml``), independent of ``mx_rcnn_tpu``: it
imports nothing of the program and is given nothing the program made. Weights
come from ``benchmarks/weights.py`` by path, inputs from the traffic
generator. One image at a time; every product at ``highest``.

What a pyramid detector shares with the C4 one is imported from
``benchmarks/reference/c4.py`` and not written twice: the bottleneck stages,
the shared RPN head, anchors, box coding, IoU, the greedy NMS loop, the
four-tap ROIAlign gather, anchor labelling, roi sampling, the losses, the
SGD-momentum trainer and the input plane. What is the pyramid's own is here:
the four-stage trunk, the neck, anchors and proposals level by level, the
roi -> level rule (Eq. 1), pooling from the assigned level, the two-FC head.

Departures from the paper that the recipe states and the program shares, so
the reference follows them:

- frozen BN is an affine map of stored statistics; the stem and stage 1 are
  fixed (a stop-gradient below C3's input);
- v1.5 bottleneck (stride on the 3x3), as in ``c4.py``;
- P6 is P5 subsampled by two (a max-pool of kernel 1), for the RPN only;
- one anchor size a level (8 x stride: 32-512 px), three ratios; anchors are
  labelled over the union of all levels, 256 sampled an image;
- proposals: per level the top ``fpn_rpn_pre_nms_per_level`` by score, NMS
  within the level, then the top ``rpn_post_nms_top_n`` of the union by
  score with no NMS across levels (Detectron's ``collect_and_distribute``);
  ``rpn_min_size`` is the program's 16 where Detectron's FPN recipe has 0;
- ROIAlign 7x7, two samples a bin axis, no half-pixel shift, widths of
  Eq. 1 counted inclusive (+1), as Detectron does;
- ground-truth boxes join the proposals before sampling; a fixed normaliser
  for the box losses; elementwise gradient clipping (the program's
  ``clip_gradient``; Detectron has none).

``precision`` is ``c4.py``'s: "f32", or the stand-ins "bf16", "fp8" and
"f32/rpn_bf16".
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference import c4
from benchmarks.reference.c4 import prepare_boxes, prepare_image  # noqa: F401

STAGE_WIDTHS = (64, 128, 256, 512)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def param_shapes(spec: dict) -> dict:
    """{path: shape} of every leaf, from the configuration's sizes."""
    blocks = c4.STAGE_BLOCKS[spec["depth"]]
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    c, f = spec["num_classes"], spec["fpn_channels"]
    r, w, p = spec["rpn_channels"], spec["head_width"], spec["roi_pool_size"]
    s = {"features/conv0/kernel": (7, 7, 3, 64)}
    c4._bn(s, "features/bn0", 64)
    cin = 64
    for i, (n, width) in enumerate(zip(blocks, STAGE_WIDTHS)):
        cin = c4._stage(s, f"features/stage{i + 1}", n, cin, width)
        lv = i + 2
        s[f"neck/lateral{lv}/kernel"] = (1, 1, cin, f)
        s[f"neck/lateral{lv}/bias"] = (f,)
        s[f"neck/output{lv}/kernel"] = (3, 3, f, f)
        s[f"neck/output{lv}/bias"] = (f,)
    for name, shape in (("rpn/rpn_conv", (3, 3, f, r)),
                        ("rpn/rpn_cls_score", (1, 1, r, 2 * a)),
                        ("rpn/rpn_bbox_pred", (1, 1, r, 4 * a)),
                        ("head/fc6", (p * p * f, w)), ("head/fc7", (w, w)),
                        ("cls_score", (w, c)), ("bbox_pred", (w, 4 * c))):
        s[f"{name}/kernel"] = shape
        s[f"{name}/bias"] = shape[-1:]
    return s


# --------------------------------------------------------------------------
# the pyramid
# --------------------------------------------------------------------------

def trunk(p, image, spec, precision):
    """(H, W, 3) mean-subtracted image -> [C2, C3, C4, C5], each
    (1, H/s, W/s, C) at strides 4, 8, 16, 32."""
    blocks = c4.STAGE_BLOCKS[spec["depth"]]
    x = c4._conv(image[None], p["features/conv0/kernel"], 2, 3, precision)
    x = jax.nn.relu(c4._frozen_bn(x, p, "features/bn0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    feats = []
    for i, n in enumerate(blocks):
        x = c4._run_stage(x, p, f"features/stage{i + 1}", n,
                          1 if i == 0 else 2, precision)
        if i == 0:
            x = lax.stop_gradient(x)  # conv0 and stage 1 are fixed
        feats.append(x)
    return feats


def neck(p, feats, precision):
    """[C2..C5] -> {2: P2, ..., 6: P6}, each (H_l, W_l, 256): lateral 1x1,
    top-down nearest x2 and add, output 3x3; P6 = every second cell of P5."""
    lat = {lv: c4._conv(c, p[f"neck/lateral{lv}/kernel"], 1, 0, precision)
           + p[f"neck/lateral{lv}/bias"]
           for lv, c in zip((2, 3, 4, 5), feats)}
    merged = {5: lat[5]}
    for lv in (4, 3, 2):
        up = jnp.repeat(jnp.repeat(merged[lv + 1], 2, axis=1), 2, axis=2)
        merged[lv] = lat[lv] + up
    out = {lv: (c4._conv(m, p[f"neck/output{lv}/kernel"], 1, 1, precision)
                + p[f"neck/output{lv}/bias"])[0]
           for lv, m in merged.items()}
    out[6] = out[5][::2, ::2]
    return dict(sorted(out.items()))


def pyramid(p, image, spec, precision="f32"):
    return neck(p, trunk(p, image, spec, precision), precision)


def level_shapes(h, w, levels):
    """{level: (rows, columns)} of a canvas: a stride-2 convolution or pool
    of kernel 3 and padding 1 (or 7 and 3) gives ceil(n / 2) cells."""
    out = {}
    for lv in range(1, max(levels) + 1):
        h, w = -(-h // 2), -(-w // 2)
        if lv in levels:
            out[lv] = (h, w)
    return out


def level_anchors(fh, fw, level, spec):
    """One size a level: ``anchor_scales`` x the level's stride."""
    stride = 2 ** level
    return c4.anchor_grid(fh, fw, dict(
        anchor_base_size=stride, feat_stride=stride,
        anchor_ratios=spec["anchor_ratios"],
        anchor_scales=spec["anchor_scales"]))


# --------------------------------------------------------------------------
# proposals, roi -> level, pooling, head
# --------------------------------------------------------------------------

def proposals(rpn_out, anchors, im_info, t, a):
    """{level: (cls, box)} of one image -> (post, 4) rois, their validity and
    scores: per level the top candidates and NMS among them, then the best
    of the union by score (levels in rising order; earlier wins a tie)."""
    boxes, scores = [], []
    for lv, (cls, box) in rpn_out.items():
        k = min(int(t["fpn_rpn_pre_nms_per_level"]), anchors[lv].shape[0])
        b, ok, s = c4.proposals(cls, box, anchors[lv], im_info, k, k,
                                t["rpn_nms_thresh"],
                                float(t["rpn_min_size"]), a)
        boxes.append(b)
        scores.append(jnp.where(ok, s, -1.0))  # a kept score is above 0
    boxes, scores = jnp.concatenate(boxes), jnp.concatenate(scores)
    top, idx = lax.top_k(scores, int(t["rpn_post_nms_top_n"]))
    ok = top >= 0.0
    rois = jnp.where(ok[:, None], boxes[idx], boxes[idx[0]][None])
    return rois, ok, jnp.where(ok, top, 0.0)


def roi_levels(rois, spec):
    """FPN Eq. 1: k = floor(k0 + log2(sqrt(w h) / 224)), held to the levels
    that are pooled from."""
    w = rois[:, 2] - rois[:, 0] + 1.0
    h = rois[:, 3] - rois[:, 1] + 1.0
    k = jnp.floor(spec["roi_k0"] + jnp.log2(
        jnp.sqrt(jnp.maximum(w * h, 1e-6)) / spec["roi_canonical_size"]))
    lo, hi = min(spec["roi_levels"]), max(spec["roi_levels"])
    return jnp.clip(k, lo, hi).astype(jnp.int32)


def pool(pyr, rois, spec):
    """Each roi from the level Eq. 1 assigns it: (R, P, P, C)."""
    levels = roi_levels(rois, spec)
    out = 0.0
    for lv in spec["roi_levels"]:
        got = c4.roi_align(pyr[lv], rois, spec["roi_pool_size"],
                           1.0 / 2 ** lv, spec["roi_sampling_ratio"])
        out = out + jnp.where((levels == lv)[:, None, None, None], got, 0.0)
    return out


def box_head(p, pooled, precision):
    """(R, P, P, C) -> class logits (R, classes), box deltas (R, 4 classes)
    through two fully connected layers."""
    x = pooled.reshape(pooled.shape[0], -1)
    for name in ("head/fc6", "head/fc7"):
        x = jax.nn.relu(c4._dense(x, p[f"{name}/kernel"], p[f"{name}/bias"],
                                  precision))
    return (c4._dense(x, p["cls_score/kernel"], p["cls_score/bias"],
                      precision),
            c4._dense(x, p["bbox_pred/kernel"], p["bbox_pred/bias"],
                      precision))


# --------------------------------------------------------------------------
# losses and the training step
# --------------------------------------------------------------------------

def image_parts(p, row, keys, spec, precision="f32"):
    """Everything one image's losses are made of, by name (the tests hold
    the program to these piece by piece)."""
    t = spec["train"]
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    pyr = pyramid(p, row["image"], spec, precision)
    rpn_out = {lv: c4.rpn_head(p, pyr[lv], precision)
               for lv in spec["rpn_levels"]}
    anchors = {lv: jnp.asarray(level_anchors(*pyr[lv].shape[:2], lv, spec))
               for lv in spec["rpn_levels"]}
    every = jnp.concatenate(list(anchors.values()))
    labels, tg, wt = c4.anchor_targets(every, row["gt_boxes"],
                                       row["gt_valid"], row["im_info"],
                                       keys[0], t)
    pair = jnp.concatenate([
        jnp.stack([cls[..., :a].reshape(-1), cls[..., a:].reshape(-1)], -1)
        for cls, _ in rpn_out.values()])
    deltas = jnp.concatenate([box.reshape(-1, 4) for _, box in
                              rpn_out.values()])
    rpn_ce, _ = c4._ce_sum(pair, labels)
    rpn_l1 = jnp.sum(c4.smooth_l1(deltas - tg, 3.0) * wt)
    rois, roi_ok, roi_scores = proposals(
        jax.tree.map(lax.stop_gradient, rpn_out), anchors, row["im_info"],
        t, a)
    s_rois, s_labels, s_tg, s_wt, s_ok = c4.sample_rois(
        rois, roi_ok, row["gt_boxes"], row["gt_classes"], row["gt_valid"],
        keys[1], t, spec["num_classes"])
    pooled = pool(pyr, s_rois, spec)
    pooled = pooled * s_ok[:, None, None, None].astype(pooled.dtype)
    logits, box = box_head(p, pooled, precision)
    rcnn_ce, n_ok = c4._ce_sum(logits, jnp.where(s_ok, s_labels, -1))
    rcnn_l1 = jnp.sum(c4.smooth_l1(box - s_tg, 1.0) * s_wt)
    return {"sums": jnp.stack([rpn_ce, rpn_l1, rcnn_ce, rcnn_l1]),
            "n_ok": n_ok, "pyramid": pyr, "rois": rois, "roi_ok": roi_ok,
            "roi_scores": roi_scores, "sampled": s_rois, "sampled_ok": s_ok,
            "levels": roi_levels(s_rois, spec), "pooled": pooled}


def rpn_valid_count(row, key, spec):
    """How many anchors of one image carry a label (needs no weights)."""
    shapes = level_shapes(*row["image"].shape[:2], spec["rpn_levels"])
    every = jnp.asarray(np.concatenate(
        [level_anchors(*shapes[lv], lv, spec) for lv in spec["rpn_levels"]]))
    labels, _, _ = c4.anchor_targets(every, row["gt_boxes"], row["gt_valid"],
                                     row["im_info"], key, spec["train"])
    return jnp.sum((labels >= 0).astype(jnp.float32))


class Trainer(c4.Trainer):
    """``c4.Trainer`` (per-image gradients summed under the step's
    normalisers, clipped SGD with momentum and decay, the ``precision``
    stand-ins) over the pyramid's losses."""

    def __init__(self, spec: dict, params: dict, precision: str = "f32"):
        super().__init__(spec, params, precision)
        self._count = jax.jit(partial(rpn_valid_count, spec=spec))

    def _weighted(self, train_p, fixed_p, row, keys, norm):
        got = image_parts({**fixed_p, **train_p}, row, keys, self.spec,
                          self.precision)
        return jnp.sum(got["sums"] * norm), (got["sums"] * norm, got["n_ok"])
