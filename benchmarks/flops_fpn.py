"""Operations that a pyramid (FPN) Faster R-CNN REQUIRES, counted from the
configuration's shapes under ``benchmarks/flops.py``'s rules: 2 x MACs;
forward, data gradient and weight gradient of every trained convolution and
matrix product; no weight gradient for a frozen layer, nothing at all below
the stop-gradient cut, no data gradient for the layers that read the cut;
recomputation, elementwise ops, the losses and the update are not counted.
ROIAlign is 4 taps a sample point on the ONE level Eq. 1 assigns a roi to,
whatever the program pools from.
"""

from __future__ import annotations

from benchmarks.flops import (STAGE_BLOCKS, _out, conv_flops, nms_work,
                              stage_flops)

STAGE_WIDTHS = (64, 128, 256, 512)


def level_cells(spec: dict) -> dict:
    """{level: (rows, columns, trunk channels)} of the canvas; the trunk has
    none at P6."""
    h, w = spec["canvas"]
    h, w = _out(h, 7, 2, 3), _out(w, 7, 2, 3)      # stem
    out = {}
    for lv in (2, 3, 4, 5, 6):
        h, w = _out(h, 3, 2, 1), _out(w, 3, 2, 1)  # pool, then each stage
        c = 4 * STAGE_WIDTHS[lv - 2] if lv <= 5 else 0
        out[lv] = (h, w, c)
    return out


def fpn_flops(spec: dict, mode: str, rois: int) -> float:
    """Per image. mode 'train': forward + required backward with ``rois``
    sampled rois through the head; 'fwd': the test forward."""
    blocks = STAGE_BLOCKS[spec["depth"]]
    cells = level_cells(spec)
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    f, k = spec["fpn_channels"], 3 if mode == "train" else 1
    h, w = spec["canvas"]
    total = conv_flops(_out(h, 7, 2, 3), _out(w, 7, 2, 3), 7, 7, 3, 64)
    h, w, _ = cells[2]
    got, (h, w, ch) = stage_flops(h, w, 64, 64, blocks[0], 1, "fwd")
    total += got                                   # stem and stage 1: frozen
    for i in (1, 2, 3):
        got, (h, w, ch) = stage_flops(h, w, ch, STAGE_WIDTHS[i], blocks[i], 2,
                                      mode, input_is_cut=i == 1)
        total += got
    for lv in spec["roi_levels"]:                  # the neck
        h, w, ch = cells[lv]
        reads_cut = lv == 2 and mode == "train"    # C2 carries no gradient
        total += (2 if reads_cut else k) * conv_flops(h, w, 1, 1, ch, f)
        total += k * conv_flops(h, w, 3, 3, f, f)
    r = spec["rpn_channels"]
    for lv in spec["rpn_levels"]:                  # one head, every level
        h, w, _ = cells[lv]
        total += k * (conv_flops(h, w, 3, 3, f, r)
                      + conv_flops(h, w, 1, 1, r, 2 * a)
                      + conv_flops(h, w, 1, 1, r, 4 * a))
    p, s = spec["roi_pool_size"], spec["roi_sampling_ratio"]
    # ROIAlign: 4 taps x (multiply + add) a sample point; the backward
    # scatters the same taps
    total += (2 if mode == "train" else 1) * rois * p * p * s * s * 4 * 2 * f
    width, c = spec["head_width"], spec["num_classes"]
    total += k * rois * 2 * (p * p * f * width + width * width
                             + width * (c + 4 * c))
    return float(total)


def nms_candidates(spec: dict) -> dict:
    """{level: candidates an image}: the per-level budget, or every anchor
    of a level that has fewer."""
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    budget = spec["train"]["fpn_rpn_pre_nms_per_level"]
    return {lv: min(budget, h * w * a)
            for lv, (h, w, _) in level_cells(spec).items()
            if lv in spec["rpn_levels"]}


def per_level_nms_work(spec: dict) -> dict:
    """The per-level NMS an image asks for: each level's candidates held
    against each other, every one of them free to survive."""
    work = [nms_work(n, n) for n in nms_candidates(spec).values()]
    return {key: sum(w[key] for w in work) for key in ("flops", "bytes")}
