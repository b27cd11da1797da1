"""benchmarks/flops.py against layers worked by hand."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import flops  # noqa: E402


def test_stem_convolution_by_hand():
    # 7x7x3 -> 64 at stride 2 on 640x1024: 320x512 outputs
    assert flops.conv_flops(320, 512, 7, 7, 3, 64) == 2 * 320 * 512 * 147 * 64
    assert flops.conv_flops(320, 512, 7, 7, 3, 64) == 3_082_813_440


def test_pointwise_convolution_by_hand():
    # the RPN's 1x1 score layer: 512 -> 18 on the 40x64 map
    assert flops.conv_flops(40, 64, 1, 1, 512, 18) == 2 * 2560 * 512 * 18


@pytest.mark.parametrize("mode,factor", [("fwd", 1), ("train", 3)])
def test_whole_res5_stage_by_hand(mode, factor):
    # res5 on one 14x14x1024 roi: three bottlenecks of width 512, the first
    # at stride 2 with a projection; every convolution trained
    px = 7 * 7
    b0 = (2 * 14 * 14 * 1024 * 512 + 2 * px * 9 * 512 * 512
          + 2 * px * 512 * 2048 + 2 * px * 1024 * 2048)
    b12 = 2 * (2 * px * 2048 * 512 + 2 * px * 9 * 512 * 512
               + 2 * px * 512 * 2048)
    got, shape = flops.stage_flops(14, 14, 1024, 512, 3, 2, mode)
    assert got == factor * (b0 + b12) and shape == (7, 7, 2048)


def test_the_cut_needs_no_data_gradient():
    plain, _ = flops.stage_flops(160, 256, 256, 128, 4, 2, "train")
    cut, _ = flops.stage_flops(160, 256, 256, 128, 4, 2, "train",
                               input_is_cut=True)
    first = 2 * 160 * 256 * 256 * 128 + 2 * 80 * 128 * 256 * 512
    assert plain - cut == first  # one of three passes of the two input convs


def test_whole_step_and_nms_work():
    with open(os.path.join(REPO, "benchmarks/configs/c4_r101_coco.json")) as f:
        spec = json.load(f)["spec"]
    train = flops.c4_flops(spec, "train", 128)
    fwd = flops.c4_flops(spec, "fwd", 128)
    assert 1.19e12 < train < 1.21e12 and fwd < train < 3 * fwd
    work = flops.nms_work(12000, 2000)
    assert work["flops"] == 2000 * 12000 * 16
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(work, peak)
    assert bound == "compute" and least == pytest.approx(384e6 / 197e12)
