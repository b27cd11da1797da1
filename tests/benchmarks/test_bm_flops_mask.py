"""``benchmarks/flops_mask.py`` against a hand count at the published sizes
(``mask_r101_fpn_coco``): the mask branch's head is 1.060 GFLOP a roi
forward, over 128 rois an image and three passes 0.407 TFLOP, on top of the
pyramid detector's 1.711."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import flops_fpn, flops_mask, manifest  # noqa: E402

SPEC = manifest.load_json("configs", "mask_r101_fpn_coco")["spec"]
CONV = 14 * 14 * 9 * 256 * 256 * 2          # one 3x3 convolution, a roi
DECONV = 28 * 28 * 256 * 256 * 2            # one tap an output cell
LOGITS = 28 * 28 * 256 * 81 * 2


def test_the_head_is_1_060_gflop_a_roi():
    assert flops_mask.head_flops(SPEC) == 4 * CONV + DECONV + LOGITS
    assert flops_mask.head_flops(SPEC) / 1e9 == pytest.approx(1.060, abs=5e-4)
    assert (4 * CONV / 1e6, DECONV / 1e6, LOGITS / 1e6) == pytest.approx(
        (924.8, 102.8, 32.5), abs=0.05)


def test_the_branch_runs_over_the_samplers_foreground_block():
    assert flops_mask.mask_rois(SPEC) == 128
    odd = dict(SPEC, train=dict(SPEC["train"], batch_rois=64))
    assert flops_mask.mask_rois(odd) == 16


@pytest.mark.parametrize("mode,passes,taps", [("train", 3, 2), ("fwd", 1, 1)])
def test_branch_count_by_hand(mode, passes, taps):
    """Pooling: 14 x 14 bins x 2 x 2 points x 4 taps x (multiply + add) x
    256 channels a roi, scattered back once in training; the head: every
    layer trained, so forward, data gradient and weight gradient."""
    align = 14 * 14 * 2 * 2 * 4 * 2 * 256
    want = 128 * (taps * align + passes * (4 * CONV + DECONV + LOGITS))
    assert flops_mask.branch_flops(SPEC, mode, 128) == want
    if mode == "train":
        assert want / 1e12 == pytest.approx(0.4075, abs=5e-4)


def test_the_cells_count_is_the_pyramids_and_the_branchs():
    whole = flops_mask.mask_flops(SPEC, "train", 512)
    assert whole == flops_fpn.fpn_flops(SPEC, "train", 512) + \
        flops_mask.branch_flops(SPEC, "train", 128)
    assert whole / 1e12 == pytest.approx(2.118, abs=1e-3)
    # the box-only configuration's detector is this configuration's
    fpn = manifest.load_json("configs", "fpn_r101_coco")["spec"]
    assert flops_fpn.fpn_flops(fpn, "train", 512) == flops_fpn.fpn_flops(
        SPEC, "train", 512)
    share = flops_mask.branch_flops(SPEC, "train", 128) / whole
    assert share == pytest.approx(0.19, abs=0.005)


@pytest.mark.parametrize("key,factor", [("mask_convs", 2), ("mask_head_width", 2),
                                        ("mask_pool_size", 2)])
def test_each_size_of_the_branch_is_counted(key, factor):
    """Twice the convolutions, the width or the bins an axis: more work, by
    what the layer's shape says."""
    more = dict(SPEC, **{key: SPEC[key] * factor})
    if key == "mask_pool_size":
        more["mask_resolution"] = SPEC["mask_resolution"] * factor
    got = flops_mask.head_flops(more) / flops_mask.head_flops(SPEC)
    want = {"mask_convs": (8 * CONV + DECONV + LOGITS) / (4 * CONV + DECONV + LOGITS),
            "mask_pool_size": 4.0}.get(key)
    if want is None:   # conv0 reads the pyramid's 256 channels at any width
        want = (CONV * 2 + 3 * CONV * 4 + DECONV * 4 + LOGITS * 2) / (
            4 * CONV + DECONV + LOGITS)
    assert got == pytest.approx(want)
