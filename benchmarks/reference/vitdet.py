"""Plain reference: ViTDet-B Mask R-CNN, the end-to-end training step in
straightforward float32 ``jax.numpy``.

Written from the published description (Li, Mao, Girshick, He 2022,
"Exploring Plain Vision Transformer Backbones for Object Detection",
section 3 and appendix A.1, the decomposed relative positions of MViTv2; the
recipe of Detectron2's ``projects/ViTDet/configs/COCO/
mask_rcnn_vitdet_b_100ep.py`` on ``common/models/mask_rcnn_vitdet.py``),
independent of ``mx_rcnn_tpu``: it imports nothing of the program and is
given nothing the program made. Weights come from ``benchmarks/weights.py``
by path, inputs from the traffic generator. One image at a time; every
product at ``highest``.

What it shares with the pyramid and mask detectors is imported from
``benchmarks/reference/fpn.py`` and ``mask.py`` (and, through them,
``c4.py``) and not written twice: anchors level by level, anchor labelling,
proposals, the sampler, the roi -> level rule, the mask branch's foreground
block, targets and loss, the box coding and the losses. What is ViTDet's own
is here: the plain trunk (patches, absolute positions, blocks of windowed or
global attention with the decomposed relative-position bias, MLPs), the
simple feature pyramid, the two-conv RPN head, the 4conv1fc box head and the
mask head (LayerNorm'd), ROIAlign with the half-pixel shift (ROIAlignV2),
and AdamW with ViTDet's layer-wise rates and no-decay set.

The trunk, as the appendix writes it: tokens of a window (or of the whole
grid) attend as ``softmax(q k^T / sqrt(d) + B) v`` with
``B[(i_h, i_w), (j_h, j_w)] = q_i . R_h[i_h - j_h] + q_i . R_w[i_w - j_w]``
(the query unscaled), ``R_h`` / ``R_w`` the rows of one table an axis for
each offset; windows tile the grid padded with zeros to whole windows (the
padded tokens attend and are attended to, as Detectron2 does), and are cut
away after.

Departures from the recipe, which the program shares, so the reference
follows them:

- bfloat16 compute where the recipe runs fp16 automatic mixed precision
  (this reference is float32 throughout);
- ROIAlign with 2 samples a bin axis, where Detectron2's
  ``sampling_ratio=0`` is adaptive (a data-dependent shape);
- one fixed scale (longest side 1024 in a 1024x1024 canvas), where
  training's large-scale jitter draws 0.1-2.0 and crops (both yield a fixed
  1024x1024 canvas);
- the absolute positions are a table of the token grid (64x64), where
  Detectron2 keeps MAE's 14x14 pretraining grid and interpolates it;
- the losses, the proposals' ``rpn_min_size`` 16, the anchors' border rule
  and the inclusive (+1) widths of Eq. 1 are ``fpn.py``'s (the program's);
  Detectron2 has L1 box losses, no minimum size and keeps border anchors;
- no learning-rate warm-up (Detectron2: 250 iterations from 0.001);
- stochastic depth is a ``keep`` argument (``drop_path``): the harness
  cannot hand the program's per-image draws to the reference, so the
  configuration trains at rate 0 (``assumed``) and a CPU test holds the two
  to the same draws.

``precision`` is ``c4.py``'s ("f32", or the stand-ins "bf16", "fp8",
"f32/rpn_bf16"), and the planted faults the cell's limits have to refuse
(``benchmarks/readings_vitdet.py``): "f32/no_relpos" (the bias left out:
its tables get no gradient), "f32/window8" (windows of 8 in place of 14),
"f32/all_window" (the four global blocks windowed), "f32/no_layer_decay"
(every leaf at the full rate) and "f32/group_norm" (GroupNorm of 32 groups
in place of LayerNorm in the box and mask heads).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference import c4, fpn, mask

# MAE's ImageNet normalisation, RGB: the recipe's pixel_std (its mean is
# the configuration's pixel_means)
PIXEL_STD = (58.395, 57.12, 57.375)
LN_EPS = 1e-6
HEAD = mask.HEAD
FAULTS = ("f32/no_relpos", "f32/window8", "f32/all_window",
          "f32/no_layer_decay", "f32/group_norm")


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def grid_of(spec: dict):
    """The token grid of the canvas: (rows, columns)."""
    patch = spec["vit_patch"]
    return tuple(-(-n // patch) for n in spec["canvas"])


def param_shapes(spec: dict) -> dict:
    """{path: shape} of every leaf, from the configuration's sizes, by the
    program's paths."""
    d, depth, heads = spec["vit_dim"], spec["vit_depth"], spec["vit_heads"]
    hd, ws, patch = d // heads, spec["vit_window"], spec["vit_patch"]
    gh, gw = grid_of(spec)
    mlp = d * spec["vit_mlp_ratio"]
    s = {"features/patch_embed/kernel": (patch, patch, 3, d),
         "features/patch_embed/bias": (d,),
         "features/pos_embed": (1, gh, gw, d)}
    for i in range(depth):
        b = f"features/block{i}"
        th, tw = (gh, gw) if i in spec["vit_global_blocks"] else (ws, ws)
        for name, shape in (("attn/qkv", (d, 3 * d)), ("attn/proj", (d, d)),
                            ("mlp1", (d, mlp)), ("mlp2", (mlp, d))):
            s[f"{b}/{name}/kernel"] = shape
            s[f"{b}/{name}/bias"] = shape[-1:]
        s[f"{b}/attn/rel_pos_h"] = (2 * th - 1, hd)
        s[f"{b}/attn/rel_pos_w"] = (2 * tw - 1, hd)
        for n in ("norm1", "norm2"):
            s[f"{b}/{n}/scale"] = s[f"{b}/{n}/bias"] = (d,)
    f = spec["fpn_channels"]
    for name, shape in (("up4_1", (2, 2, d, d // 2)),
                        ("up4_2", (2, 2, d // 2, d // 4)),
                        ("up8", (2, 2, d, d // 2))):
        s[f"neck/{name}/kernel"] = shape
        s[f"neck/{name}/bias"] = shape[-1:]
    s["neck/up4_ln/scale"] = s["neck/up4_ln/bias"] = (d // 2,)
    for lv, cin in ((2, d // 4), (3, d // 2), (4, d), (5, d)):
        s[f"neck/out{lv}_1/kernel"] = (1, 1, cin, f)
        s[f"neck/out{lv}_3/kernel"] = (3, 3, f, f)
        for n in (f"out{lv}_1_ln", f"out{lv}_3_ln"):
            s[f"neck/{n}/scale"] = s[f"neck/{n}/bias"] = (f,)
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    r = spec["rpn_channels"]
    for i in range(spec["rpn_convs"]):
        name = "rpn/rpn_conv" + (str(i + 1) if i else "")
        s[f"{name}/kernel"] = (3, 3, f if i == 0 else r, r)
        s[f"{name}/bias"] = (r,)
    for name, shape in (("rpn/rpn_cls_score", (1, 1, r, 2 * a)),
                        ("rpn/rpn_bbox_pred", (1, 1, r, 4 * a))):
        s[f"{name}/kernel"] = shape
        s[f"{name}/bias"] = shape[-1:]
    p, w, c = spec["roi_pool_size"], spec["head_width"], spec["num_classes"]
    for i in range(1, spec["box_head_convs"] + 1):
        s[f"head/conv{i}/kernel"] = (3, 3, f, f)
        s[f"head/conv{i}_norm/scale"] = s[f"head/conv{i}_norm/bias"] = (f,)
    for name, shape in (("head/fc1", (p * p * f, w)), ("cls_score", (w, c)),
                        ("bbox_pred", (w, 4 * c))):
        s[f"{name}/kernel"] = shape
        s[f"{name}/bias"] = shape[-1:]
    mw = spec["mask_head_width"]
    for i in range(spec["mask_convs"]):
        s[f"{HEAD}/mask_conv{i}/kernel"] = (3, 3, f if i == 0 else mw, mw)
        n = f"{HEAD}/mask_conv{i}_norm"
        s[f"{n}/scale"] = s[f"{n}/bias"] = (mw,)
    for name, shape in (("mask_deconv", (2, 2, mw, mw)),
                        ("mask_logits", (1, 1, mw, c))):
        s[f"{HEAD}/{name}/kernel"] = shape
        s[f"{HEAD}/{name}/bias"] = shape[-1:]
    return s


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def _mm(eq, a, b, precision):
    return jnp.einsum(eq, c4._round_to(a, precision),
                      c4._round_to(b, precision),
                      precision=lax.Precision.HIGHEST)


def layer_norm(x, p, prefix):
    """Over the last axis, eps 1e-6, then the leaf's scale and bias."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * lax.rsqrt(var + LN_EPS) * p[f"{prefix}/scale"]
            + p[f"{prefix}/bias"])


def group_norm(x, p, prefix, groups=32):
    """GroupNorm over (rows, columns, channels of a group), eps 1e-6: the
    planted fault's norm."""
    r, h, w, c = x.shape
    g = x.reshape(r, h, w, groups, c // groups)
    mu = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mu), axis=(1, 2, 4), keepdims=True)
    g = (g - mu) * lax.rsqrt(var + LN_EPS)
    return g.reshape(x.shape) * p[f"{prefix}/scale"] + p[f"{prefix}/bias"]


def head_norm(x, p, prefix, precision):
    if precision == "f32/group_norm":
        return group_norm(x, p, prefix)
    return layer_norm(x, p, prefix)


def linear(x, p, prefix, precision):
    return _mm("...i,io->...o", x, p[f"{prefix}/kernel"], precision) + p[
        f"{prefix}/bias"]


def resize_rows(table, rows):
    """A table linearly resampled to ``rows`` rows, half-pixel centres
    (torch's ``interpolate(mode="linear")``), for a window the table was
    not made for."""
    n = table.shape[0]
    if n == rows:
        return table
    at = np.clip((np.arange(rows) + 0.5) * n / rows - 0.5, 0, n - 1)
    lo = np.floor(at).astype(int)
    hi = np.minimum(lo + 1, n - 1)
    f = jnp.asarray((at - lo)[:, None], jnp.float32)
    return table[lo] * (1 - f) + table[hi] * f


def rel_positions(table, size):
    """(size, size, d): R[i, j] = the table's row for the offset i - j."""
    table = resize_rows(table, 2 * size - 1)
    i = np.arange(size)
    return table[i[:, None] - i[None, :] + size - 1]


def attention(x, p, prefix, size, spec, precision):
    """Multi-head self-attention of the (n, C) tokens of a (gh, gw) grid,
    row-major, with the decomposed relative-position bias."""
    gh, gw = size
    n, c = x.shape
    heads = spec["vit_heads"]
    d = c // heads
    qkv = linear(x, p, f"{prefix}/qkv", precision).reshape(n, 3, heads, d)
    q, k, v = (jnp.transpose(qkv[:, j], (1, 0, 2)) for j in range(3))
    logits = _mm("hqd,hkd->hqk", q * d ** -0.5, k, precision)
    if precision != "f32/no_relpos":
        r_h = rel_positions(p[f"{prefix}/rel_pos_h"], gh)  # (gh, gh, d)
        r_w = rel_positions(p[f"{prefix}/rel_pos_w"], gw)
        qg = q.reshape(heads, gh, gw, d)
        bias_h = _mm("hyxd,ykd->hyxk", qg, r_h, precision)  # key row k
        bias_w = _mm("hyxd,xkd->hyxk", qg, r_w, precision)  # key column k
        bias = bias_h[..., :, None] + bias_w[..., None, :]
        logits = logits + bias.reshape(heads, n, n)
    out = _mm("hqk,hkd->qhd", jax.nn.softmax(logits, axis=-1), v, precision)
    return linear(out.reshape(n, c), p, f"{prefix}/proj", precision)


def windowed(x, fn, ws):
    """``fn`` over each ws x ws window of the (H, W, C) grid, zero-padded
    to whole windows; the padding cut away after."""
    h, w, c = x.shape
    ph, pw = -h % ws, -w % ws
    xp = jnp.pad(x, ((0, ph), (0, pw), (0, 0)))
    nh, nw = (h + ph) // ws, (w + pw) // ws
    wins = xp.reshape(nh, ws, nw, ws, c).transpose(0, 2, 1, 3, 4)
    out = jax.vmap(fn)(wins.reshape(nh * nw, ws * ws, c))
    out = out.reshape(nh, nw, ws, ws, c).transpose(0, 2, 1, 3, 4)
    return out.reshape(h + ph, w + pw, c)[:h, :w]


def block(x, p, prefix, window, spec, precision, keep=(1.0, 1.0)):
    """Pre-LN: x + attn(LN(x)), then + MLP(LN(x)); ``keep`` the two
    branches' stochastic-depth factors for this image."""
    h, w, c = x.shape
    y = layer_norm(x, p, f"{prefix}/norm1")
    attn = partial(attention, p=p, prefix=f"{prefix}/attn", spec=spec,
                   precision=precision)
    if window:
        y = windowed(y, lambda t: attn(t, size=(window, window)), window)
    else:
        y = attn(y.reshape(h * w, c), size=(h, w)).reshape(h, w, c)
    x = x + keep[0] * y
    y = layer_norm(x, p, f"{prefix}/norm2")
    y = jax.nn.gelu(linear(y, p, f"{prefix}/mlp1", precision),
                    approximate=False)
    return x + keep[1] * linear(y, p, f"{prefix}/mlp2", precision)


def trunk(p, image, spec, precision="f32", keep=None):
    """(H, W, 3) normalised image -> (H/16, W/16, C), the last block's
    output. ``keep`` (depth, 2): the image's stochastic-depth factors."""
    patch = spec["vit_patch"]
    x = c4._conv(image[None], p["features/patch_embed/kernel"], patch, 0,
                 precision)[0] + p["features/patch_embed/bias"]
    if x.shape[:2] != p["features/pos_embed"].shape[1:3]:
        raise ValueError(f"token grid {x.shape[:2]} is not the table's "
                         f"{p['features/pos_embed'].shape[1:3]}")
    x = x + p["features/pos_embed"][0]
    ws = spec["vit_window"]
    if precision == "f32/window8":
        ws = 8
    for i in range(spec["vit_depth"]):
        glob = (i in spec["vit_global_blocks"]
                and precision != "f32/all_window")
        x = block(x, p, f"features/block{i}", 0 if glob else ws, spec,
                  precision, (1.0, 1.0) if keep is None else keep[i])
    return x


def deconv(x, p, prefix, precision):
    return mask.deconv2x2(x[None], p[f"{prefix}/kernel"], precision)[0] + p[
        f"{prefix}/bias"]


def pyramid(p, feat, precision="f32"):
    """The simple feature pyramid: stride 4 (two 2x2 stride-2 transposed
    convolutions, LN and GELU between), 8 (one), 16 (the map), 32 (a 2x2
    max-pool); each through a bias-free 1x1 and 3x3 convolution, each
    LayerNorm'd; P6 every second cell of P5. {level: (H_l, W_l, 256)}."""
    y4 = deconv(feat, p, "neck/up4_1", precision)
    y4 = jax.nn.gelu(layer_norm(y4, p, "neck/up4_ln"), approximate=False)
    y4 = deconv(y4, p, "neck/up4_2", precision)
    h, w, c = feat.shape
    y32 = feat.reshape(h // 2, 2, w // 2, 2, c).max(axis=(1, 3))
    out = {}
    for lv, y in ((2, y4), (3, deconv(feat, p, "neck/up8", precision)),
                  (4, feat), (5, y32)):
        y = c4._conv(y[None], p[f"neck/out{lv}_1/kernel"], 1, 0, precision)
        y = layer_norm(y, p, f"neck/out{lv}_1_ln")
        y = c4._conv(y, p[f"neck/out{lv}_3/kernel"], 1, 1, precision)
        out[lv] = layer_norm(y, p, f"neck/out{lv}_3_ln")[0]
    out[6] = out[5][::2, ::2]
    return out


def rpn_head(p, feat, spec, precision):
    """``rpn_convs`` 3x3 convolutions with ReLU, then the 1x1 objectness
    (2 a cell and anchor: background, foreground) and box deltas."""
    x = feat[None]
    for i in range(spec["rpn_convs"]):
        name = "rpn/rpn_conv" + (str(i + 1) if i else "")
        x = jax.nn.relu(c4._conv(x, p[f"{name}/kernel"], 1, 1, precision)
                        + p[f"{name}/bias"])
    cls = c4._conv(x, p["rpn/rpn_cls_score/kernel"], 1, 0, precision)
    box = c4._conv(x, p["rpn/rpn_bbox_pred/kernel"], 1, 0, precision)
    cls = (cls + p["rpn/rpn_cls_score/bias"])[0]
    box = (box + p["rpn/rpn_bbox_pred/bias"])[0]
    if precision == "f32/rpn_bf16":
        cls, box = c4._round_to(cls, "bf16"), c4._round_to(box, "bf16")
    return cls, box


def roi_align_v2(feat, rois, size, scale, samples=2):
    """ROIAlignV2: the roi's corners shifted by half a cell (``aligned``),
    its extent not held to one cell; ``samples`` points a bin axis, four
    taps a point, coordinates clamped to the map. (R, P, P, C)."""
    h, w, _ = feat.shape
    x1, y1 = rois[:, 0] * scale - 0.5, rois[:, 1] * scale - 0.5
    rw = rois[:, 2] * scale - 0.5 - x1
    rh = rois[:, 3] * scale - 0.5 - y1
    grid = (jnp.arange(size * samples, dtype=jnp.float32) + 0.5) / samples
    ys = jnp.clip(y1[:, None] + grid[None] * (rh / size)[:, None], 0.0, h - 1.0)
    xs = jnp.clip(x1[:, None] + grid[None] * (rw / size)[:, None], 0.0, w - 1.0)
    y0, x0 = jnp.floor(ys), jnp.floor(xs)
    fy, fx = ys - y0, xs - x0
    y0i, x0i = y0.astype(jnp.int32), x0.astype(jnp.int32)
    y1i, x1i = jnp.minimum(y0i + 1, h - 1), jnp.minimum(x0i + 1, w - 1)
    top = (feat[y0i[:, :, None], x0i[:, None, :]] * (1 - fx)[:, None, :, None]
           + feat[y0i[:, :, None], x1i[:, None, :]] * fx[:, None, :, None])
    bot = (feat[y1i[:, :, None], x0i[:, None, :]] * (1 - fx)[:, None, :, None]
           + feat[y1i[:, :, None], x1i[:, None, :]] * fx[:, None, :, None])
    v = top * (1 - fy)[:, :, None, None] + bot * fy[:, :, None, None]
    r, c = rois.shape[0], feat.shape[-1]
    return v.reshape(r, size, samples, size, samples, c).mean(axis=(2, 4))


def pool(pyr, rois, spec, size, samples):
    """Each roi from the level Eq. 1 assigns it, by ROIAlignV2."""
    levels = fpn.roi_levels(rois, spec)
    out = 0.0
    for lv in spec["roi_levels"]:
        got = roi_align_v2(pyr[lv], rois, size, 1.0 / 2 ** lv, samples)
        out = out + jnp.where((levels == lv)[:, None, None, None], got, 0.0)
    return out


def box_head(p, pooled, spec, precision):
    """4conv1fc: ``box_head_convs`` bias-free 3x3 convolutions, each
    LayerNorm'd and ReLU'd, one fully connected layer with ReLU, then the
    class logits and box deltas."""
    x = pooled
    for i in range(1, spec["box_head_convs"] + 1):
        x = c4._conv(x, p[f"head/conv{i}/kernel"], 1, 1, precision)
        x = jax.nn.relu(head_norm(x, p, f"head/conv{i}_norm", precision))
    x = jax.nn.relu(c4._dense(x.reshape(x.shape[0], -1),
                              p["head/fc1/kernel"], p["head/fc1/bias"],
                              precision))
    return (c4._dense(x, p["cls_score/kernel"], p["cls_score/bias"],
                      precision),
            c4._dense(x, p["bbox_pred/kernel"], p["bbox_pred/bias"],
                      precision))


def mask_head(p, pooled, spec, precision):
    """(R, 14, 14, C) -> (R, 28, 28, classes): four bias-free 3x3
    convolutions, each LayerNorm'd and ReLU'd, the 2x2 stride-2 transposed
    convolution with ReLU, the 1x1 convolution to a map a class."""
    x = pooled
    for i in range(spec["mask_convs"]):
        x = c4._conv(x, p[f"{HEAD}/mask_conv{i}/kernel"], 1, 1, precision)
        x = jax.nn.relu(head_norm(x, p, f"{HEAD}/mask_conv{i}_norm",
                                  precision))
    x = jax.nn.relu(mask.deconv2x2(x, p[f"{HEAD}/mask_deconv/kernel"],
                                   precision) + p[f"{HEAD}/mask_deconv/bias"])
    return (c4._conv(x, p[f"{HEAD}/mask_logits/kernel"], 1, 0, precision)
            + p[f"{HEAD}/mask_logits/bias"])


# --------------------------------------------------------------------------
# one image's losses
# --------------------------------------------------------------------------

def image_parts(p, row, keys, spec, precision="f32", keep=None):
    """Everything one image's five losses are made of, by name: the four
    loss SUMS of the detector, the mask loss's sum over the live
    foreground rois and their count (the caller normalises)."""
    t = spec["train"]
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    pyr = pyramid(p, trunk(p, row["image"], spec, precision, keep),
                  precision)
    rpn_out = {lv: rpn_head(p, pyr[lv], spec, precision)
               for lv in spec["rpn_levels"]}
    anchors = {lv: jnp.asarray(fpn.level_anchors(*pyr[lv].shape[:2], lv,
                                                 spec))
               for lv in spec["rpn_levels"]}
    every = jnp.concatenate(list(anchors.values()))
    labels, tg, wt = c4.anchor_targets(every, row["gt_boxes"],
                                       row["gt_valid"], row["im_info"],
                                       keys[0], t)
    pair = jnp.concatenate([
        jnp.stack([cls[..., :a].reshape(-1), cls[..., a:].reshape(-1)], -1)
        for cls, _ in rpn_out.values()])
    deltas = jnp.concatenate([box.reshape(-1, 4)
                              for _, box in rpn_out.values()])
    rpn_ce, _ = c4._ce_sum(pair, labels)
    rpn_l1 = jnp.sum(c4.smooth_l1(deltas - tg, 3.0) * wt)
    rois, roi_ok, _ = fpn.proposals(
        jax.tree.map(lax.stop_gradient, rpn_out), anchors, row["im_info"],
        t, a)
    s_rois, s_labels, s_tg, s_wt, s_ok = c4.sample_rois(
        rois, roi_ok, row["gt_boxes"], row["gt_classes"], row["gt_valid"],
        keys[1], t, spec["num_classes"])
    pooled = pool(pyr, s_rois, spec, spec["roi_pool_size"],
                  spec["roi_sampling_ratio"])
    pooled = pooled * s_ok[:, None, None, None].astype(pooled.dtype)
    logits, box = box_head(p, pooled, spec, precision)
    rcnn_ce, n_ok = c4._ce_sum(logits, jnp.where(s_ok, s_labels, -1))
    rcnn_l1 = jnp.sum(c4.smooth_l1(box - s_tg, 1.0) * s_wt)
    # the mask branch over the sampler's foreground block (mask.py)
    m_rois, live, m_labels, matched = mask.foreground(rois, roi_ok, row,
                                                      keys[1], spec)
    m_pooled = pool(pyr, m_rois, spec, spec["mask_pool_size"],
                    spec["mask_sampling_ratio"])
    m_pooled = m_pooled * live[:, None, None, None].astype(m_pooled.dtype)
    m_logits = mask_head(p, m_pooled, spec, precision)
    targets = mask.mask_targets(m_rois, matched, spec["mask_resolution"])
    own = jnp.take_along_axis(m_logits, m_labels[:, None, None, None],
                              axis=-1)[..., 0]
    per_roi = jnp.mean(mask.sigmoid_ce(own, targets), axis=(1, 2))
    return {"sums": jnp.stack([rpn_ce, rpn_l1, rcnn_ce, rcnn_l1]),
            "n_ok": n_ok, "pyramid": pyr, "rois": rois, "roi_ok": roi_ok,
            "sampled": s_rois, "sampled_ok": s_ok, "pooled": pooled,
            "mask_sum": jnp.sum(jnp.where(live, per_roi, 0.0)),
            "n_live": jnp.sum(live.astype(jnp.float32)),
            "mask_pooled": m_pooled, "mask_logits": m_logits}


# --------------------------------------------------------------------------
# the training step
# --------------------------------------------------------------------------

def no_decay(path: str, params: dict, names) -> bool:
    """Weight decay skips ``names``: leaf names, and "norm" for every leaf
    of a norm layer (a module with a ``scale`` leaf)."""
    module, leaf = path.rsplit("/", 1)
    if leaf in names:
        return True
    return "norm" in names and f"{module}/scale" in params


def layer_of(path: str, depth: int) -> int:
    """ViTDet's layer of a leaf: patches and absolute positions 0, block i
    at i + 1, the rest depth + 1."""
    parts = path.split("/")
    if parts[0] == "features":
        if parts[1] in ("patch_embed", "pos_embed"):
            return 0
        if parts[1].startswith("block"):
            return int(parts[1][5:]) + 1
    return depth + 1


class Trainer(mask.Trainer):
    """``mask.Trainer``'s five losses and normalisers over this model,
    every leaf trained; the update is AdamW (Loshchilov and Hutter) with the
    configuration's global-norm clip (none where it is infinite), weight
    decay off the ``no_decay`` leaves, each leaf's step times its layer's
    rate ``layer_decay ** (depth + 1 - layer)``."""

    def __init__(self, spec: dict, params: dict, precision: str = "f32"):
        super().__init__(spec, params, precision)
        self.train = dict(params)
        self.fixed = {}
        self.count, self.mu, self.nu, self.step = 0, None, None, None

    def _weighted(self, train_p, fixed_p, row, keys, norm):
        got = image_parts({**fixed_p, **train_p}, row, keys, self.spec,
                          self.precision)
        sums = jnp.append(got["sums"], got["mask_sum"]) * norm
        return jnp.sum(sums), (sums, got["n_ok"], got["n_live"])

    def update(self, grads):
        t, spec = self.spec["train"], self.spec
        b1, b2 = spec["adam_betas"]
        if self.mu is None:
            self.mu = {k: jnp.zeros_like(v) for k, v in self.train.items()}
            self.nu = {k: jnp.zeros_like(v) for k, v in self.train.items()}
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
        clip = float(t["clip_gradient"])
        if np.isfinite(clip):
            f = jnp.where(norm < clip, 1.0, clip / norm)
            grads = {k: g * f for k, g in grads.items()}
        self.count += 1
        rate = {k: (1.0 if self.precision == "f32/no_layer_decay" else
                    t["layer_decay"] ** (spec["vit_depth"] + 1 - layer_of(
                        k, spec["vit_depth"]))) for k in self.train}
        wd = {k: 0.0 if no_decay(k, self.train, t["no_decay"]) else t["wd"]
              for k in self.train}
        self.train, self.mu, self.nu, self.step = _adamw(
            self.train, grads, self.mu, self.nu,
            jnp.asarray(self.count, jnp.float32), float(t["lr"]), float(b1),
            float(b2), float(spec["adam_eps"]),
            {k: jnp.float32(v) for k, v in wd.items()},
            {k: jnp.float32(v) for k, v in rate.items()})


@partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _adamw(params, grads, mu, nu, count, lr, b1, b2, eps, wd, rate):
    """One AdamW step of every leaf: the moments, their bias correction,
    the decoupled decay (``wd`` a leaf), the step times ``rate`` a leaf.
    Returns the new parameters, moments and the step taken."""
    new_p, new_m, new_v, delta = {}, {}, {}, {}
    for k, w in params.items():
        g = grads[k]
        new_m[k] = b1 * mu[k] + (1.0 - b1) * g
        new_v[k] = b2 * nu[k] + (1.0 - b2) * g * g
        m_hat = new_m[k] / (1.0 - b1 ** count)
        v_hat = new_v[k] / (1.0 - b2 ** count)
        step = m_hat / (jnp.sqrt(v_hat) + eps) + wd[k] * w
        delta[k] = rate[k] * (lr * step)
        new_p[k] = w - delta[k]
    return new_p, new_m, new_v, delta


# --------------------------------------------------------------------------
# input plane
# --------------------------------------------------------------------------

def prepare_image(pixels, flipped, scales, means, canvas):
    """``c4.prepare_image`` (resize, mirror, mean subtraction, zero pad),
    then divided by the recipe's ``PIXEL_STD``: zero stays zero."""
    img, info = c4.prepare_image(pixels, flipped, scales, means, canvas)
    return img / np.asarray(PIXEL_STD, np.float32), info


prepare_boxes = c4.prepare_boxes
