"""``benchmarks/flops_fpn.py`` held to counts made by hand, as
``test_bm_flops.py`` holds ``c4_flops``: one pyramid level's neck and RPN
head, the head over the rois, ROIAlign on one level only, and the per-level
NMS."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import flops, flops_fpn, manifest  # noqa: E402

SPEC = manifest.load_json("configs", "fpn_r101_coco")["spec"]


def test_the_levels_of_the_published_canvas():
    cells = flops_fpn.level_cells(SPEC)
    assert cells == {2: (208, 336, 256), 3: (104, 168, 512),
                     4: (52, 84, 1024), 5: (26, 42, 2048), 6: (13, 21, 0)}
    anchors = 3 * sum(h * w for h, w, _ in cells.values())
    assert anchors == 279279                      # "~280 k anchors"
    assert flops_fpn.nms_candidates(SPEC) == {2: 2000, 3: 2000, 4: 2000,
                                              5: 2000, 6: 819}


def _without(level):
    """The spec with one level taken out of every list that names it."""
    return dict(SPEC, rpn_levels=[lv for lv in SPEC["rpn_levels"]
                                  if lv != level],
                roi_levels=[lv for lv in SPEC["roi_levels"] if lv != level])


@pytest.mark.parametrize("mode,k", [("fwd", 1), ("train", 3)])
def test_one_level_by_hand(mode, k):
    """P3 (104x168 cells over C3's 512 channels), counted on paper: its
    lateral 1x1 (512 -> 256), its output 3x3 (256 -> 256), and the shared
    head on it (3x3 256 -> 256, 1x1 -> 6 and -> 12), 2 operations a MAC,
    three passes in training. The trunk and the box head do not change
    with the level taken out."""
    cells = 104 * 168
    lateral = 2 * cells * 512 * 256
    output = 2 * cells * 9 * 256 * 256
    head = 2 * cells * (9 * 256 * 256 + 256 * 6 + 256 * 12)
    by_hand = k * (lateral + output + head)
    got = (flops_fpn.fpn_flops(SPEC, mode, 512)
           - flops_fpn.fpn_flops(_without(3), mode, 512))
    assert got == by_hand
    assert by_hand == k * 45_962_821_632


def test_the_level_that_reads_the_cut_has_no_data_gradient():
    """P2's lateral reads C2, which carries no gradient (stage 1 is fixed):
    forward and weight gradient only; its output 3x3 and the head on P2 take
    all three passes."""
    cells = 208 * 336
    lateral = 2 * cells * 256 * 256
    rest = 2 * cells * (9 * 256 * 256 + 9 * 256 * 256 + 256 * 18)
    got = (flops_fpn.fpn_flops(SPEC, "train", 512)
           - flops_fpn.fpn_flops(_without(2), "train", 512))
    assert got == 2 * lateral + 3 * rest


def test_rois_cost_one_level_of_taps_and_two_wide_layers():
    """Per roi: 7x7 bins x 2x2 points x 4 taps x (multiply + add) x 256
    channels, forward and scatter; fc6 (12544 -> 1024), fc7 (1024 -> 1024)
    and the two output layers (1024 -> 81 + 324), three passes."""
    per_roi_align = 2 * (49 * 4 * 4 * 2 * 256)
    per_roi_head = 3 * 2 * (12544 * 1024 + 1024 * 1024 + 1024 * 405)
    got = (flops_fpn.fpn_flops(SPEC, "train", 513)
           - flops_fpn.fpn_flops(SPEC, "train", 512))
    assert got == per_roi_align + per_roi_head
    assert per_roi_align == 802_816               # not four levels' worth


def test_the_trunk_is_the_c4_count_plus_stage_four():
    """With no roi and no level the count is the trunk alone; its first
    three stages are ``c4_flops``' own (same helpers, same cut)."""
    bare = dict(SPEC, rpn_levels=[], roi_levels=[])
    c4 = manifest.load_json("configs", "c4_r101_coco")["spec"]
    c4_trunk = flops.c4_flops(dict(c4, canvas=SPEC["canvas"],
                                   anchor_ratios=[], anchor_scales=[]),
                              "train", 0)           # with no roi, no anchor:
    c4_trunk -= 3 * flops.conv_flops(52, 84, 3, 3, 1024, 512)  # its RPN 3x3
    stage4, _ = flops.stage_flops(52, 84, 1024, 512, 3, 2, "train")
    assert flops_fpn.fpn_flops(bare, "train", 0) == c4_trunk + stage4
    whole = flops_fpn.fpn_flops(SPEC, "train", 512)
    assert 1.70e12 < whole < 1.72e12              # PERF.md: 1.711 TFLOP


def test_per_level_nms_work_adds_the_levels():
    work = flops_fpn.per_level_nms_work(SPEC)
    assert work["flops"] == 16 * (4 * 2000 * 2000 + 819 * 819)
    assert work["bytes"] == (4 * 2000 + 819) * (20 + 4)
    one = flops.nms_work(12000, 2000)             # C4's one launch
    assert work["flops"] < one["flops"]
