"""Operations that ViTDet's Mask R-CNN REQUIRES, counted from the
configuration's shapes under ``benchmarks/flops.py``'s rules: 2 x MACs;
forward, data gradient and weight gradient of every trained layer (the
patch embedding has no data gradient: its input is the image); elementwise
ops, norms, softmax, the targets, the losses and the update not counted.

The trunk (Li et al. 2022; Detectron2's ViT): a windowed block's attention
runs on its grid padded to whole windows (64x64 tokens in 14x14 windows:
70x70 = 4900 tokens, 25 windows), its qkv and proj on the padded tokens as
well, its MLP on the 4096 of the grid; a global block's on the 4096. Each
attention is QK^T and PV, and the decomposed relative-position bias's two
products q . R_h and q . R_w (one table row a query row or column). The
pyramid's transposed convolutions read one input cell through one tap an
output cell. The heads: the RPN over P2-P6, the box head over the sampled
rois, the mask head over the sampler's foreground SLOTS (static shapes, as
``flops_mask.py`` counts them). ROIAlign is 4 taps a sample point.
"""

from __future__ import annotations

from benchmarks.flops import conv_flops
from benchmarks.flops_mask import head_flops, mask_rois


def grid(spec: dict) -> tuple:
    p = spec["vit_patch"]
    return tuple(-(-n // p) for n in spec["canvas"])


def _attention(spec: dict, tokens: int, groups: int, side: tuple) -> float:
    """Forward operations of one block's attention core: ``groups``
    windows (or 1) of ``tokens`` each, on a ``side`` (rows, columns)
    grid: QK^T, PV and the two bias products."""
    h = spec["vit_heads"]
    d = spec["vit_dim"] // h
    core = 2 * 2 * groups * h * tokens * tokens * d
    bias = 2 * groups * h * tokens * d * (side[0] + side[1])
    return float(core + bias)


def parts(spec: dict) -> dict:
    """Forward operations of one image, by part (the table PERF.md keeps)."""
    gh, gw = grid(spec)
    c, f = spec["vit_dim"], spec["fpn_channels"]
    mlp = c * spec["vit_mlp_ratio"]
    ws = spec["vit_window"]
    nh, nw = -(-gh // ws), -(-gw // ws)
    padded, tokens = nh * nw * ws * ws, gh * gw
    out = {"patch_embed": float(2 * tokens * spec["vit_patch"] ** 2 * 3 * c),
           "trunk_linears": 0.0, "global_attention": 0.0,
           "window_attention": 0.0}
    for i in range(spec["vit_depth"]):
        glob = i in spec["vit_global_blocks"]
        attn_tokens = tokens if glob else padded
        out["trunk_linears"] += 2 * (attn_tokens * c * 4 * c
                                     + tokens * 2 * c * mlp)
        if glob:
            out["global_attention"] += _attention(spec, tokens, 1, (gh, gw))
        else:
            out["window_attention"] += _attention(spec, ws * ws, nh * nw,
                                                  (ws, ws))
    # the simple feature pyramid: P2 <- two transposed convs, P3 <- one,
    # P4 the map, P5 its 2x2 max-pool; each a 1x1 and a 3x3 to f channels
    pyr = (conv_flops(2 * gh, 2 * gw, 1, 1, c, c // 2)
           + conv_flops(4 * gh, 4 * gw, 1, 1, c // 2, c // 4)
           + conv_flops(2 * gh, 2 * gw, 1, 1, c, c // 2))
    levels = {2: (4 * gh, 4 * gw, c // 4), 3: (2 * gh, 2 * gw, c // 2),
              4: (gh, gw, c), 5: (gh // 2, gw // 2, c)}
    for lv in spec["roi_levels"]:
        h, w, cin = levels[lv]
        pyr += conv_flops(h, w, 1, 1, cin, f) + conv_flops(h, w, 3, 3, f, f)
    out["pyramid"] = float(pyr)
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    r = spec["rpn_channels"]
    rpn = 0
    for lv in spec["rpn_levels"]:
        h, w = 4 * gh // 2 ** (lv - 2), 4 * gw // 2 ** (lv - 2)
        for i in range(spec["rpn_convs"]):
            rpn += conv_flops(h, w, 3, 3, f if i == 0 else r, r)
        rpn += conv_flops(h, w, 1, 1, r, 2 * a) + conv_flops(h, w, 1, 1, r,
                                                             4 * a)
    out["rpn"] = float(rpn)
    p, width = spec["roi_pool_size"], spec["head_width"]
    k = spec["num_classes"]
    box = spec["box_head_convs"] * conv_flops(p, p, 3, 3, f, f)
    box += 2 * (p * p * f * width + width * 5 * k)
    out["box_head"] = float(spec["train"]["batch_rois"] * box)
    out["mask_head"] = float(mask_rois(spec) * head_flops(spec))
    s, ms = spec["roi_sampling_ratio"], spec["mask_pool_size"]
    out["roi_align"] = float(
        spec["train"]["batch_rois"] * p * p * s * s * 4 * 2 * f
        + mask_rois(spec) * ms * ms * spec["mask_sampling_ratio"] ** 2
        * 4 * 2 * f)
    return out


def vitdet_flops(spec: dict, mode: str = "train") -> float:
    """Per image: the forward ('fwd'), or forward and required backward
    ('train': 3x every part, 2x the patch embedding and ROIAlign)."""
    got = parts(spec)
    if mode != "train":
        return float(sum(got.values()))
    twice = ("patch_embed", "roi_align")
    return float(sum((2 if k in twice else 3) * v for k, v in got.items()))


def global_attention_work(spec: dict) -> dict:
    """{"flops", "bytes"} of the global blocks' attention of one image in
    training, from the shapes alone, whatever implements it: QK^T, PV and
    the two bias products forward and twice again backward; the least
    bytes any implementation moves, bfloat16: q, k, v and the tables read
    and the output written forward, and backward q, k, v, the output, its
    cotangent and the tables read and q's, k's, v's and the tables'
    cotangents written (no N x N matrix)."""
    gh, gw = grid(spec)
    tokens, c = gh * gw, spec["vit_dim"]
    d = c // spec["vit_heads"]
    blocks = len(spec["vit_global_blocks"])
    act = tokens * c * 2
    tables = (2 * gh - 1 + 2 * gw - 1) * d * 2
    nbytes = (3 * act + tables + act) + (5 * act + tables + 3 * act + tables)
    return {"flops": 3 * blocks * _attention(spec, tokens, 1, (gh, gw)),
            "bytes": float(blocks * nbytes)}
