"""Seed -> traffic, the copies of the program's generators, the weights."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, synth, traffic  # noqa: E402

MIX = dict(manifest.load_json("traffic", "train_packed_landscape"), images=10)


def test_same_seed_same_traffic():
    a, b = traffic.make_roidb(MIX, 2 ** 31 + 77), traffic.make_roidb(MIX, 2 ** 31 + 77)
    for x, y in zip(a, b):
        assert np.array_equal(x["image_data"], y["image_data"])
        assert np.array_equal(x["boxes"], y["boxes"])
        assert np.array_equal(x["gt_classes"], y["gt_classes"])


def test_mesh_mix_draws_the_one_chip_mix_images():
    """The four-chip cell has a mix of its own (a pair of configuration and
    traffic is one cell); its rate per chip compares with the one-chip
    cell's only while both mixes hand the generator the same parameters."""
    one = manifest.load_json("traffic", "train_packed_landscape")
    four = manifest.load_json("traffic", "train_packed_landscape_mesh4")
    assert one.pop("why") != four.pop("why")
    assert one == four


def test_every_seed_has_the_same_sizes_in_another_order():
    a, b = traffic.make_roidb(MIX, 1), traffic.make_roidb(MIX, 2)
    sizes = lambda r: sorted((x["height"], x["width"]) for x in r)
    assert sizes(a) == sizes(b)
    assert [x["height"] for x in a] != [x["height"] for x in b]
    assert not np.array_equal(a[0]["image_data"][:8, :8], b[0]["image_data"][:8, :8])


def test_landscape_mix_is_landscape_and_boxes_are_inside():
    for rec in traffic.make_roidb(MIX, 3):
        assert rec["height"] <= rec["width"]
        b = rec["boxes"]
        assert 1 <= len(b) <= 5 and (b[:, 2] >= b[:, 0]).all()
        assert b[:, 2].max() < rec["width"] and b[:, 3].max() < rec["height"]
        assert rec["image_data"].dtype == np.uint8


def test_portrait_share_is_the_mix_s():
    mix = dict(MIX, portrait_share=1.0)
    assert all(r["height"] >= r["width"] for r in traffic.make_roidb(mix, 4))


def test_generator_copy_agrees_with_the_program_s():
    from mx_rcnn_tpu.tools.gen_synthetic_coco import _COLORS, _gen_image

    assert np.array_equal(_COLORS, synth.COLORS)
    for seed in (0, 7):
        mine = synth.gen_image(np.random.RandomState(seed), 8)
        theirs = _gen_image(np.random.RandomState(seed), 8)
        assert np.array_equal(mine[0], theirs[0])
        assert mine[1] == theirs[1] and mine[2] == theirs[2]


def test_pad_waste_copy_agrees_with_the_program_s():
    from mx_rcnn_tpu.obs.costs import batch_pad_waste

    info = np.asarray([[600, 800, 1.2], [512, 1000, 1.6]], np.float32)
    batch = {"image": np.zeros((2, 640, 1024, 3), np.float32), "im_info": info}
    assert synth.pad_waste(info, (640, 1024), 2) == \
        batch_pad_waste(batch)["pad_waste"]


def test_weights_follow_the_seed_and_the_rules():
    from benchmarks import weights

    shapes = {"features/stage2/block0/conv1/kernel": (1, 1, 256, 128),
              "features/stage2/block0/bn3/gamma": (512,),
              "features/bn0/moving_var": (64,),
              "cls_score/kernel": (2048, 81), "cls_score/bias": (81,)}
    big = 2 ** 31 + 12345
    a, b, c = weights.make(big, shapes), weights.make(big, shapes), \
        weights.make(big + 1, shapes)
    k = "features/stage2/block0/conv1/kernel"
    assert np.array_equal(a[k], b[k]) and not np.array_equal(a[k], c[k])
    assert float(np.std(a[k])) == pytest.approx((2 / 256) ** 0.5, rel=0.05)
    assert float(np.std(a["cls_score/kernel"])) == pytest.approx(0.01, rel=0.05)
    assert float(a["features/stage2/block0/bn3/gamma"][0]) == 0.25
    assert float(a["features/bn0/moving_var"][0]) == 4096.0
    assert not np.any(a["cls_score/bias"])
