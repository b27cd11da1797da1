"""Operations that Mask R-CNN on a pyramid REQUIRES, counted from the
configuration's shapes under ``benchmarks/flops.py``'s rules (2 x MACs;
forward, data gradient and weight gradient of every trained convolution;
ROIAlign 4 taps a sample point on the ONE level a roi is assigned to;
elementwise ops, the targets and the loss not counted):
``flops_fpn.fpn_flops`` for the detector, and the mask branch over the
``round(fg_fraction * batch_rois)`` foreground SLOTS an image the recipe gives
it (He et al. 2017, section 3; Detectron's ``mask_rcnn_fcn_head_v1up4convs``).

The count is of slots, not of live rois: the program's shapes are static, so
pooling and head run over every slot whatever the sampler filled it with, as
they do for a trained model, which fills them. With the benchmark's seeded
weights 3 / 9.4 / 24 (min / mean / max) of an image's 128 slots hold a live
roi (the ``mask_rois`` event; builder's chip run, PR 34): the branch's share
of ``mask_flops`` is then mostly work on zeroed rows.
"""

from __future__ import annotations

from benchmarks.flops import conv_flops
from benchmarks.flops_fpn import fpn_flops


def mask_rois(spec: dict) -> int:
    """Slots an image through the branch: the sampler's foreground block."""
    t = spec["train"]
    return int(round(t["fg_fraction"] * t["batch_rois"]))


def head_flops(spec: dict) -> int:
    """Forward operations of the head on ONE roi: ``mask_convs`` 3x3
    convolutions on the pooled grid, the 2x2 stride-2 transposed convolution
    (every output cell reads one input cell through one tap: a 1x1
    convolution's count on the doubled grid), the 1x1 convolution to a map
    a class."""
    p, m = spec["mask_pool_size"], spec["mask_resolution"]
    w, c = spec["mask_head_width"], spec["num_classes"]
    total, cin = 0, spec["fpn_channels"]
    for _ in range(spec["mask_convs"]):
        total += conv_flops(p, p, 3, 3, cin, w)
        cin = w
    return total + conv_flops(m, m, 1, 1, w, w) + conv_flops(m, m, 1, 1, w, c)


def branch_flops(spec: dict, mode: str, rois: int) -> float:
    """The mask branch over ``rois`` rois: pooling (the backward scatters the
    same taps) and the head (every layer trained: 3x in 'train')."""
    p, s = spec["mask_pool_size"], spec["mask_sampling_ratio"]
    align = rois * p * p * s * s * 4 * 2 * spec["fpn_channels"]
    if mode == "train":
        return float(2 * align + 3 * rois * head_flops(spec))
    return float(align + rois * head_flops(spec))


def mask_flops(spec: dict, mode: str, rois: int) -> float:
    """Per image: ``fpn_flops`` with ``rois`` sampled rois through the box
    head, and the branch over the foreground block."""
    return fpn_flops(spec, mode, rois) + branch_flops(spec, mode,
                                                      mask_rois(spec))
