"""Plain reference: ResNet-C4 Faster R-CNN, end-to-end training step and test
forward, in straightforward float32 ``jax.numpy``.

Written from the published description (Ren et al. 2015; the mx-rcnn
end2end recipe as SURVEY.md reconstructs it), independent of
``mx_rcnn_tpu``: it imports nothing of the program and is given nothing the
program made. Weights come from ``benchmarks/weights.py`` by path, inputs
from the traffic generator. One image at a time (frozen BN couples no rows,
and every loss is a sum over images with a constant in front), so the
activations of one image are all that is live.

Departures from the paper that the recipe states and the program shares, so
the reference follows them: v1.5 bottleneck (stride on the 3x3), ROIAlign
14x14 with two samples per bin axis and no half-pixel shift
(``network.roi_pool_type=align``), ground-truth boxes appended to the
proposals before sampling, a fixed normaliser for the box losses, the
random subsampling drawn as uniform keys ranked by ``argsort``.

``precision``: "f32" computes every convolution and matrix product at
``highest``. The stand-ins that the control of ``correct`` runs round both
operands of each one first: "bf16" (values only), "fp8" (e4m3 forward, e5m2
backward, unscaled). "f32/rpn_bf16" rounds only the RPN head's outputs to
bfloat16, as a bfloat16 head emits them: the look at what re-ranked proposals
alone do to the numbers.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

STAGE_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
BN_EPS = 1e-5
XFORM_CLIP = float(np.log(1000.0 / 16.0))


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _bn(shapes, prefix, c):
    for leaf in ("gamma", "beta", "moving_mean", "moving_var"):
        shapes[f"{prefix}/{leaf}"] = (c,)


def _stage(shapes, prefix, blocks, cin, width):
    for b in range(blocks):
        p = f"{prefix}/block{b}"
        shapes[f"{p}/conv1/kernel"] = (1, 1, cin, width)
        _bn(shapes, f"{p}/bn1", width)
        shapes[f"{p}/conv2/kernel"] = (3, 3, width, width)
        _bn(shapes, f"{p}/bn2", width)
        shapes[f"{p}/conv3/kernel"] = (1, 1, width, 4 * width)
        _bn(shapes, f"{p}/bn3", 4 * width)
        if b == 0:
            shapes[f"{p}/downsample_conv/kernel"] = (1, 1, cin, 4 * width)
            _bn(shapes, f"{p}/downsample_bn", 4 * width)
        cin = 4 * width
    return cin


def param_shapes(spec: dict) -> dict:
    """{path: shape} of every leaf, from the configuration's sizes."""
    blocks = STAGE_BLOCKS[spec["depth"]]
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    c = spec["num_classes"]
    s = {}
    s["features/conv0/kernel"] = (7, 7, 3, 64)
    _bn(s, "features/bn0", 64)
    cin = _stage(s, "features/stage1", blocks[0], 64, 64)
    cin = _stage(s, "features/stage2", blocks[1], cin, 128)
    cin = _stage(s, "features/stage3", blocks[2], cin, 256)
    s["rpn/rpn_conv/kernel"] = (3, 3, cin, 512)
    s["rpn/rpn_conv/bias"] = (512,)
    s["rpn/rpn_cls_score/kernel"] = (1, 1, 512, 2 * a)
    s["rpn/rpn_cls_score/bias"] = (2 * a,)
    s["rpn/rpn_bbox_pred/kernel"] = (1, 1, 512, 4 * a)
    s["rpn/rpn_bbox_pred/bias"] = (4 * a,)
    cout = _stage(s, "head/stage4", blocks[3], cin, 512)
    s["cls_score/kernel"] = (cout, c)
    s["cls_score/bias"] = (c,)
    s["bbox_pred/kernel"] = (cout, 4 * c)
    s["bbox_pred/bias"] = (4 * c,)
    return s


def is_trainable(path: str) -> bool:
    """The recipe's fixed set: the stem, stage 1 and every BN leaf."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("gamma", "beta", "moving_mean", "moving_var"):
        return False
    return not any(f in path for f in ("conv0", "bn0", "stage1"))


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

@jax.custom_vjp
def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (g.astype(jnp.float8_e5m2).astype(jnp.float32),))


def _round_to(x, precision):
    """Operand rounding of the stand-ins. "bf16": values rounded, the
    backward pass untouched. "fp8": the unscaled fp8 recipe, e4m3 forward
    and e5m2 for what flows back."""
    if precision.startswith("f32"):
        return x
    if precision == "fp8":
        return _fp8(x)
    # reduce_precision, not a cast there and back: the TPU compiler may drop
    # a pair of converts (excess precision is allowed), and did
    return x + lax.stop_gradient(lax.reduce_precision(x, 8, 7) - x)


def _conv(x, w, stride, pad, precision):
    return lax.conv_general_dilated(
        _round_to(x, precision), _round_to(w, precision), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _dense(x, w, b, precision):
    return jnp.dot(_round_to(x, precision), _round_to(w, precision),
                   precision=lax.Precision.HIGHEST) + b


def _frozen_bn(x, p, prefix):
    scale = p[f"{prefix}/gamma"] * lax.rsqrt(p[f"{prefix}/moving_var"] + BN_EPS)
    return x * scale + (p[f"{prefix}/beta"] - p[f"{prefix}/moving_mean"] * scale)


def _bottleneck(x, p, prefix, stride, precision):
    y = _conv(x, p[f"{prefix}/conv1/kernel"], 1, 0, precision)
    y = jax.nn.relu(_frozen_bn(y, p, f"{prefix}/bn1"))
    y = _conv(y, p[f"{prefix}/conv2/kernel"], stride, 1, precision)
    y = jax.nn.relu(_frozen_bn(y, p, f"{prefix}/bn2"))
    y = _conv(y, p[f"{prefix}/conv3/kernel"], 1, 0, precision)
    y = _frozen_bn(y, p, f"{prefix}/bn3")
    if f"{prefix}/downsample_conv/kernel" in p:
        x = _conv(x, p[f"{prefix}/downsample_conv/kernel"], stride, 0,
                  precision)
        x = _frozen_bn(x, p, f"{prefix}/downsample_bn")
    return jax.nn.relu(y + x)


def _run_stage(x, p, prefix, blocks, stride, precision):
    for b in range(blocks):
        x = _bottleneck(x, p, f"{prefix}/block{b}", stride if b == 0 else 1,
                        precision)
    return x


def trunk(p, image, spec, precision):
    """(H, W, 3) mean-subtracted image -> (H/16, W/16, 1024) features."""
    blocks = STAGE_BLOCKS[spec["depth"]]
    x = _conv(image[None], p["features/conv0/kernel"], 2, 3, precision)
    x = jax.nn.relu(_frozen_bn(x, p, "features/bn0"))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    x = _run_stage(x, p, "features/stage1", blocks[0], 1, precision)
    x = lax.stop_gradient(x)  # conv0 and stage 1 are fixed
    x = _run_stage(x, p, "features/stage2", blocks[1], 2, precision)
    x = _run_stage(x, p, "features/stage3", blocks[2], 2, precision)
    return x[0]


def rpn_head(p, feat, precision):
    x = _conv(feat[None], p["rpn/rpn_conv/kernel"], 1, 1, precision)
    x = jax.nn.relu(x + p["rpn/rpn_conv/bias"])
    cls = _conv(x, p["rpn/rpn_cls_score/kernel"], 1, 0, precision)
    box = _conv(x, p["rpn/rpn_bbox_pred/kernel"], 1, 0, precision)
    cls = (cls + p["rpn/rpn_cls_score/bias"])[0]
    box = (box + p["rpn/rpn_bbox_pred/bias"])[0]
    if precision == "f32/rpn_bf16":  # the look: only the head's outputs rounded
        cls, box = _round_to(cls, "bf16"), _round_to(box, "bf16")
    return cls, box


def box_head(p, pooled, spec, precision):
    """(R, 14, 14, 1024) -> class logits (R, C), box deltas (R, 4C)."""
    blocks = STAGE_BLOCKS[spec["depth"]]
    x = _run_stage(pooled, p, "head/stage4", blocks[3], 2, precision)
    x = jnp.mean(x, axis=(1, 2))
    return (_dense(x, p["cls_score/kernel"], p["cls_score/bias"], precision),
            _dense(x, p["bbox_pred/kernel"], p["bbox_pred/bias"], precision))


# --------------------------------------------------------------------------
# boxes
# --------------------------------------------------------------------------

def base_anchors(base, ratios, scales):
    """The classic enumeration: ratios (with rounding), then scales."""
    def whc(a):
        w, h = a[2] - a[0] + 1.0, a[3] - a[1] + 1.0
        return w, h, a[0] + 0.5 * (w - 1), a[1] + 0.5 * (h - 1)

    def mk(ws, hs, cx, cy):
        return np.stack([cx - 0.5 * (ws - 1), cy - 0.5 * (hs - 1),
                         cx + 0.5 * (ws - 1), cy + 0.5 * (hs - 1)], axis=1)

    w, h, cx, cy = whc(np.array([0, 0, base - 1, base - 1], np.float64))
    ws = np.round(np.sqrt(w * h / np.asarray(ratios, np.float64)))
    hs = np.round(ws * np.asarray(ratios, np.float64))
    out = []
    for ra in mk(ws, hs, cx, cy):
        w, h, cx, cy = whc(ra)
        sc = np.asarray(scales, np.float64)
        out.append(mk(w * sc, h * sc, cx, cy))
    return np.vstack(out).astype(np.float32)


def anchor_grid(fh, fw, spec):
    """(fh * fw * A, 4): rows, then columns, then the A base anchors."""
    base = base_anchors(spec["anchor_base_size"], spec["anchor_ratios"],
                        spec["anchor_scales"])
    s = spec["feat_stride"]
    sx, sy = np.meshgrid(np.arange(fw, dtype=np.float32) * s,
                         np.arange(fh, dtype=np.float32) * s)
    shifts = np.stack([sx, sy, sx, sy], axis=-1)
    return (shifts[:, :, None, :] + base[None, None]).reshape(-1, 4)


def _whc(b):
    w = b[..., 2] - b[..., 0] + 1.0
    h = b[..., 3] - b[..., 1] + 1.0
    return w, h, b[..., 0] + 0.5 * (w - 1.0), b[..., 1] + 0.5 * (h - 1.0)


def encode(ex, gt):
    ew, eh, ecx, ecy = _whc(ex)
    gw, gh, gcx, gcy = _whc(gt)
    return jnp.stack([(gcx - ecx) / (ew + 1e-14), (gcy - ecy) / (eh + 1e-14),
                      jnp.log(gw / (ew + 1e-14) + 1e-14),
                      jnp.log(gh / (eh + 1e-14) + 1e-14)], axis=-1)


def decode(boxes, deltas):
    """boxes (N, 4), deltas (N, 4K) -> (N, 4K)."""
    w, h, cx, cy = _whc(boxes)
    d = deltas.reshape(deltas.shape[0], -1, 4)
    pcx = d[..., 0] * w[:, None] + cx[:, None]
    pcy = d[..., 1] * h[:, None] + cy[:, None]
    pw = jnp.exp(jnp.minimum(d[..., 2], XFORM_CLIP)) * w[:, None]
    ph = jnp.exp(jnp.minimum(d[..., 3], XFORM_CLIP)) * h[:, None]
    out = jnp.stack([pcx - 0.5 * (pw - 1.0), pcy - 0.5 * (ph - 1.0),
                     pcx + 0.5 * (pw - 1.0), pcy + 0.5 * (ph - 1.0)], axis=-1)
    return out.reshape(deltas.shape)


def clip(boxes, h, w):
    b = boxes.reshape(boxes.shape[0], -1, 4)
    out = jnp.stack([jnp.clip(b[..., 0], 0.0, w - 1.0),
                     jnp.clip(b[..., 1], 0.0, h - 1.0),
                     jnp.clip(b[..., 2], 0.0, w - 1.0),
                     jnp.clip(b[..., 3], 0.0, h - 1.0)], axis=-1)
    return out.reshape(boxes.shape)


def iou_matrix(a, b):
    """(N, 4) x (K, 4) -> (N, K), inclusive pixel coordinates."""
    a, b = a[:, None, :], b[None, :, :]
    iw = jnp.maximum(jnp.minimum(a[..., 2], b[..., 2])
                     - jnp.maximum(a[..., 0], b[..., 0]) + 1.0, 0.0)
    ih = jnp.maximum(jnp.minimum(a[..., 3], b[..., 3])
                     - jnp.maximum(a[..., 1], b[..., 1]) + 1.0, 0.0)
    inter = iw * ih
    area_a = (a[..., 2] - a[..., 0] + 1.0) * (a[..., 3] - a[..., 1] + 1.0)
    area_b = (b[..., 2] - b[..., 0] + 1.0) * (b[..., 3] - b[..., 1] + 1.0)
    return inter / jnp.maximum(area_a + area_b - inter, 1e-14)


def greedy_nms(boxes, valid, thresh, max_out):
    """Boxes sorted by falling score. Emits the first ``max_out`` survivors
    of sequential suppression (IoU strictly above ``thresh`` suppresses)."""
    n = boxes.shape[0]

    def body(i, carry):
        live, idx, ok = carry
        first = jnp.argmax(live)  # first live box = best score
        any_live = live[first]
        idx = idx.at[i].set(jnp.where(any_live, first, 0).astype(jnp.int32))
        ok = ok.at[i].set(any_live)
        ov = iou_matrix(boxes[first][None], boxes)[0]
        live = live & ~((ov > thresh) & any_live)
        live = live.at[first].set(False)
        return live, idx, ok

    _, idx, ok = lax.fori_loop(
        0, max_out, body,
        (valid, jnp.zeros((max_out,), jnp.int32), jnp.zeros((max_out,), bool)))
    return idx, ok


def _keep_random(mask, limit, key):
    """At most ``limit`` of the True entries, chosen by ranked uniform keys."""
    n = mask.shape[0]
    u = jnp.where(mask, jax.random.uniform(key, (n,)), 2.0)
    order = jnp.argsort(u)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    return mask & (rank < limit)


def _ranked(mask, key):
    n = mask.shape[0]
    u = jnp.where(mask, jax.random.uniform(key, (n,)), 2.0)
    return jnp.argsort(u).astype(jnp.int32), jnp.sum(mask.astype(jnp.int32))


# --------------------------------------------------------------------------
# targets, proposals, pooling
# --------------------------------------------------------------------------

def anchor_targets(anchors, gt_boxes, gt_valid, im_info, key, t):
    n = anchors.shape[0]
    k_fg, k_bg = jax.random.split(key)
    border = float(t["rpn_allowed_border"])
    inside = ((anchors[:, 0] >= -border) & (anchors[:, 1] >= -border)
              & (anchors[:, 2] < im_info[1] + border)
              & (anchors[:, 3] < im_info[0] + border))
    iou = jnp.where(gt_valid[None, :], iou_matrix(anchors, gt_boxes), -1.0)
    max_iou = jnp.max(iou, axis=1)
    arg = jnp.argmax(iou, axis=1)
    gt_best = jnp.max(jnp.where(inside[:, None], iou, -1.0), axis=0)
    is_best = jnp.any((jnp.abs(iou - gt_best[None]) < 1e-9)
                      & gt_valid[None] & (gt_best[None] > 0), axis=1)
    labels = jnp.full((n,), -1, jnp.int32)
    labels = jnp.where(inside & (max_iou < t["rpn_negative_overlap"]), 0,
                       labels)
    labels = jnp.where(
        inside & ((max_iou >= t["rpn_positive_overlap"]) | is_best), 1, labels)
    labels = jnp.where(jnp.any(gt_valid), labels, jnp.where(inside, 0, -1))
    batch = int(t["rpn_batch_size"])
    fg = _keep_random(labels == 1, int(batch * t["rpn_fg_fraction"]), k_fg)
    labels = jnp.where((labels == 1) & ~fg, -1, labels)
    bg = _keep_random(labels == 0, batch - jnp.sum(fg.astype(jnp.int32)), k_bg)
    labels = jnp.where((labels == 0) & ~bg, -1, labels)
    pos = (labels == 1)[:, None]
    targets = jnp.where(pos, encode(anchors, gt_boxes[arg]), 0.0)
    return labels, targets, pos.astype(jnp.float32)


def proposals(cls, box, anchors, im_info, pre_n, post_n, thresh, min_size, a):
    """RPN outputs of one image -> (post_n, 4) rois and their validity."""
    bg, fg = cls[..., :a].reshape(-1), cls[..., a:].reshape(-1)
    m = jnp.maximum(bg, fg)
    score = jnp.exp(fg - m) / (jnp.exp(bg - m) + jnp.exp(fg - m))
    boxes = clip(decode(anchors, box.reshape(-1, 4)), im_info[0], im_info[1])
    ws = boxes[:, 2] - boxes[:, 0] + 1.0
    hs = boxes[:, 3] - boxes[:, 1] + 1.0
    ok = (ws >= min_size * im_info[2]) & (hs >= min_size * im_info[2])
    score = jnp.where(ok, score, -1e10)
    k = min(pre_n, score.shape[0])
    top, idx = lax.top_k(score, k)
    cand, cand_ok = boxes[idx], top > -1e9
    keep, keep_ok = greedy_nms(cand, cand_ok, thresh, post_n)
    rois = jnp.where(keep_ok[:, None], cand[keep], cand[keep[0]][None])
    return rois, keep_ok, jnp.where(keep_ok, top[keep], 0.0)


def sample_rois(rois, roi_ok, gt_boxes, gt_classes, gt_valid, key, t, c):
    k_fg, k_bg = jax.random.split(key)
    cand = jnp.concatenate([rois, gt_boxes], axis=0)
    cand_ok = jnp.concatenate([roi_ok, gt_valid], axis=0)
    iou = jnp.where(gt_valid[None, :], iou_matrix(cand, gt_boxes), -1.0)
    max_iou = jnp.where(cand_ok, jnp.max(iou, axis=1), -1.0)
    arg = jnp.argmax(iou, axis=1)
    fg_c = cand_ok & (max_iou >= t["fg_thresh"])
    bg_c = cand_ok & (max_iou < t["bg_thresh_hi"]) & (max_iou >= t["bg_thresh_lo"])
    r = int(t["batch_rois"])
    fg_max = int(round(t["fg_fraction"] * r))
    fg_order, fg_n = _ranked(fg_c, k_fg)
    bg_order, bg_n = _ranked(bg_c, k_bg)
    n_fg = jnp.minimum(fg_n, fg_max)
    slot = jnp.arange(r, dtype=jnp.int32)
    is_fg = slot < n_fg
    fg_i = fg_order[jnp.minimum(slot, fg_n - 1)]
    bg_i = bg_order[jnp.where(bg_n > 0, (slot - n_fg) % jnp.maximum(bg_n, 1), 0)]
    any_bg = bg_n > 0
    take = jnp.where(is_fg, fg_i, jnp.where(any_bg, bg_i, fg_i))
    ok = (is_fg | (any_bg & ~is_fg)) & (fg_n + bg_n > 0)
    take = jnp.where(ok, take, 0)
    out = cand[take]
    matched = arg[take]
    fg_mask = is_fg & ok
    labels = jnp.where(fg_mask, gt_classes[matched].astype(jnp.int32), 0)
    tg = (encode(out, gt_boxes[matched]) - jnp.asarray(t["bbox_means"])) \
        / jnp.asarray(t["bbox_stds"])
    onehot = jax.nn.one_hot(labels, c, dtype=jnp.float32)
    targets = (onehot[:, :, None] * tg[:, None, :]).reshape(r, 4 * c)
    weights = jnp.broadcast_to(
        onehot[:, :, None] * fg_mask[:, None, None].astype(jnp.float32),
        (r, c, 4)).reshape(r, 4 * c)
    return out, labels, targets, weights, ok


def roi_align(feat, rois, size, scale, samples=2):
    """Bilinear point sampling, ``samples`` per bin axis, averaged; sample
    coordinates clamp to the map. feat (H, W, C), rois (R, 4) -> (R, P, P, C)."""
    h, w, _ = feat.shape
    x1, y1 = rois[:, 0] * scale, rois[:, 1] * scale
    rw = jnp.maximum(rois[:, 2] * scale - x1, 1.0)
    rh = jnp.maximum(rois[:, 3] * scale - y1, 1.0)
    grid = (jnp.arange(size * samples, dtype=jnp.float32) + 0.5) / samples
    ys = jnp.clip(y1[:, None] + grid[None] * (rh / size)[:, None], 0.0, h - 1.0)
    xs = jnp.clip(x1[:, None] + grid[None] * (rw / size)[:, None], 0.0, w - 1.0)
    y0, x0 = jnp.floor(ys), jnp.floor(xs)
    y1i = jnp.minimum(y0 + 1, h - 1).astype(jnp.int32)
    x1i = jnp.minimum(x0 + 1, w - 1).astype(jnp.int32)
    ly, lx = ys - y0, xs - x0
    y0i, x0i = y0.astype(jnp.int32), x0.astype(jnp.int32)

    def at(yi, xi):  # (R, S), (R, S) -> (R, S, S, C)
        return feat[yi[:, :, None], xi[:, None, :]]

    wy0, wy1 = (1 - ly)[:, :, None, None], ly[:, :, None, None]
    wx0, wx1 = (1 - lx)[:, None, :, None], lx[:, None, :, None]
    v = (at(y0i, x0i) * wy0 * wx0 + at(y0i, x1i) * wy0 * wx1
         + at(y1i, x0i) * wy1 * wx0 + at(y1i, x1i) * wy1 * wx1)
    r, c = rois.shape[0], feat.shape[-1]
    return v.reshape(r, size, samples, size, samples, c).mean(axis=(2, 4))


# --------------------------------------------------------------------------
# losses and the training step
# --------------------------------------------------------------------------

def smooth_l1(x, sigma):
    s2 = sigma * sigma
    ax = jnp.abs(x)
    return jnp.where(ax < 1.0 / s2, 0.5 * s2 * x * x, ax - 0.5 / s2)


def _ce_sum(logits, labels):
    ok = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], axis=1)[:, 0]
    return jnp.sum(jnp.where(ok, ce, 0.0)), jnp.sum(ok.astype(jnp.float32))


def image_losses(train_p, fixed_p, row, keys, spec, precision):
    """The four loss SUMS of one image (normalisers applied by the caller)
    and the count of valid sampled rois."""
    p = {**fixed_p, **train_p}
    t = spec["train"]
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    feat = trunk(p, row["image"], spec, precision)
    cls, box = rpn_head(p, feat, precision)
    anchors = jnp.asarray(anchor_grid(feat.shape[0], feat.shape[1], spec))
    labels, tg, wt = anchor_targets(anchors, row["gt_boxes"], row["gt_valid"],
                                    row["im_info"], keys[0], t)
    pair = jnp.stack([cls[..., :a].reshape(-1), cls[..., a:].reshape(-1)], -1)
    rpn_ce, _ = _ce_sum(pair, labels)
    rpn_l1 = jnp.sum(smooth_l1(box.reshape(-1, 4) - tg, 3.0) * wt)
    rois, roi_ok, _ = proposals(
        lax.stop_gradient(cls), lax.stop_gradient(box), anchors,
        row["im_info"], int(t["rpn_pre_nms_top_n"]),
        int(t["rpn_post_nms_top_n"]), t["rpn_nms_thresh"],
        float(t["rpn_min_size"]), a)
    s_rois, s_labels, s_tg, s_wt, s_ok = sample_rois(
        rois, roi_ok, row["gt_boxes"], row["gt_classes"], row["gt_valid"],
        keys[1], t, spec["num_classes"])
    pooled = roi_align(feat, s_rois, spec["roi_pool_size"],
                       1.0 / spec["feat_stride"])
    pooled = pooled * s_ok[:, None, None, None].astype(pooled.dtype)
    logits, deltas = box_head(p, pooled, spec, precision)
    rcnn_ce, n_ok = _ce_sum(logits, jnp.where(s_ok, s_labels, -1))
    rcnn_l1 = jnp.sum(smooth_l1(deltas - s_tg, 1.0) * s_wt)
    return jnp.stack([rpn_ce, rpn_l1, rcnn_ce, rcnn_l1]), n_ok


def rpn_valid_count(row, key, spec):
    """How many anchors of one image carry a label (needs no weights)."""
    h, w = row["image"].shape[0], row["image"].shape[1]
    s = spec["feat_stride"]
    anchors = jnp.asarray(anchor_grid(-(-h // s), -(-w // s), spec))
    labels, _, _ = anchor_targets(anchors, row["gt_boxes"], row["gt_valid"],
                                  row["im_info"], key, spec["train"])
    return jnp.sum((labels >= 0).astype(jnp.float32))


def step_keys(root_key, batch: int):
    """Per-image (anchor key, sampling key) of one step, from the step's key:
    split in three (anchors, sampling, dropout), each of the first two split
    over the rows of the global batch."""
    k_anchor, k_sample, _ = jax.random.split(root_key, 3)
    return jnp.stack([jax.random.split(k_anchor, batch),
                      jax.random.split(k_sample, batch)], axis=1)


class Trainer:
    """Three plain SGD-momentum steps, image by image."""

    def __init__(self, spec: dict, params: dict, precision: str = "f32"):
        self.spec, self.precision = spec, precision
        self.train = {k: v for k, v in params.items() if is_trainable(k)}
        self.fixed = {k: v for k, v in params.items() if not is_trainable(k)}
        self.trace = None
        self._grad = jax.jit(jax.value_and_grad(self._weighted, has_aux=True))
        self._count = jax.jit(partial(rpn_valid_count, spec=spec))

    def _weighted(self, train_p, fixed_p, row, keys, norm):
        sums, n_ok = image_losses(train_p, fixed_p, row, keys, self.spec,
                                  self.precision)
        return jnp.sum(sums * norm), (sums * norm, n_ok)

    def grads(self, batch: dict, key, rows=None):
        """(loss, four loss parts, {path: gradient}) of one step's batch.
        ``rows`` restricts the mean to some rows (the planted fault of
        ``tests/benchmarks``); None is the whole batch."""
        t = self.spec["train"]
        b = batch["image"].shape[0]
        keys = step_keys(key, b)
        rows = list(range(b)) if rows is None else list(rows)
        nb = len(rows)
        at = lambda i: {k: jnp.asarray(v[i]) for k, v in batch.items()}
        n_rpn = sum(float(self._count(at(i), keys[i, 0])) for i in rows)
        norm = jnp.asarray([1.0 / max(n_rpn, 1.0),
                            1.0 / (t["rpn_batch_size"] * nb),
                            1.0 / (t["batch_rois"] * nb),
                            1.0 / (t["batch_rois"] * nb)], jnp.float32)
        total, parts, n_ok = None, None, 0.0
        for i in rows:
            (_, (p_i, ok_i)), g_i = self._grad(self.train, self.fixed, at(i),
                                               keys[i], norm)
            n_ok += float(ok_i)
            total = g_i if total is None else jax.tree.map(jnp.add, total, g_i)
            parts = p_i if parts is None else parts + p_i
        if n_ok != t["batch_rois"] * nb:
            raise RuntimeError(
                f"reference: {n_ok} valid sampled rois, expected "
                f"{t['batch_rois'] * nb}; the class-loss normaliser assumed "
                "every slot valid")
        return float(jnp.sum(parts)), np.asarray(parts), total

    def update(self, grads):
        """clip elementwise, add the decay, momentum, step."""
        t = self.spec["train"]
        if self.trace is None:
            self.trace = jax.tree.map(jnp.zeros_like, self.train)
        self.train, self.trace = _sgd(self.train, grads, self.trace,
                                      t["lr"], t["momentum"], t["wd"],
                                      t["clip_gradient"])


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _sgd(params, grads, trace, lr, momentum, wd, clip_to):
    new_p, new_t = {}, {}
    for k, w in params.items():
        u = jnp.clip(grads[k], -clip_to, clip_to) + wd * w
        new_t[k] = u + momentum * trace[k]
        new_p[k] = w - lr * new_t[k]
    return new_p, new_t


# --------------------------------------------------------------------------
# input plane
# --------------------------------------------------------------------------

def prepare_image(pixels, flipped, scales, means, canvas):
    """uint8 (h, w, 3) RGB -> (canvas image float32, [h', w', scale]): the
    recipe's resize (short side to ``scales[0]``, long side capped at
    ``scales[1]``, bilinear), mirror, mean subtraction, zero pad."""
    import cv2

    h, w = pixels.shape[:2]
    scale = float(scales[0]) / min(h, w)
    if round(scale * max(h, w)) > scales[1]:
        scale = float(scales[1]) / max(h, w)
    nh, nw = int(round(h * scale)), int(round(w * scale))
    img = cv2.resize(pixels.astype(np.float32), (nw, nh),
                     interpolation=cv2.INTER_LINEAR)
    if flipped:
        img = img[:, ::-1]
    out = np.zeros((canvas[0], canvas[1], 3), np.float32)
    out[:nh, :nw] = img - np.asarray(means, np.float32)
    return out, np.asarray([nh, nw, scale], np.float32)


def prepare_boxes(boxes, width, flipped, scale, max_gt):
    b = np.asarray(boxes, np.float32).copy()
    if flipped:
        x1 = b[:, 0].copy()
        b[:, 0] = width - b[:, 2] - 1
        b[:, 2] = width - x1 - 1
    b *= scale
    out = np.zeros((max_gt, 4), np.float32)
    out[:len(b)] = b
    ok = np.zeros((max_gt,), bool)
    ok[:len(b)] = True
    return out, ok
