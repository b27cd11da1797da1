"""The pyramid path held to its plain reference (benchmarks/reference/fpn.py)
piece by piece, on seeded weights at a small size: R-50, a 128x192 canvas, 2
images, 64 candidates a level, 32 rois. The program computes in float32 here
(``train.compute_dtype=f32``), so what is left between the two sides is the
order of float32 sums; each tolerance says what it allows for."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import compare, manifest, traffic, weights  # noqa: E402
from benchmarks.drivers import train as driver  # noqa: E402
from bm_tiny_fpn import tiny_fpn  # noqa: E402

pytestmark = pytest.mark.compile_heavy
SEED = 2 ** 31 + 7


@pytest.fixture(scope="module")
def both():
    from mx_rcnn_tpu.models import fpn
    from mx_rcnn_tpu.models.zoo import build_model, init_params

    conf = manifest.load_json("configs", "fpn_r101_coco")
    t = tiny_fpn()
    spec = dict(conf["spec"], **t["spec_overrides"], compute_dtype="f32")
    spec["train"] = dict(conf["spec"]["train"], **t["spec_overrides"]["train"])
    cfg = driver._program_config(
        conf, dict(t["overrides"], **{"train.compute_dtype": "f32"}))
    driver.check_spec(cfg, spec)
    ref = manifest.load_module("reference", conf["reference"])
    mix = dict(manifest.load_json("traffic", "train_packed_landscape"),
               **t["mix_overrides"])
    raw = traffic.make_roidb(mix, SEED)
    batch = driver.reference_batch(ref, [(0, False), (1, True)], raw, spec)
    model = build_model(cfg)
    params = weights.fill_tree(SEED, jax.eval_shape(
        lambda k: init_params(model, cfg, k), jax.random.PRNGKey(0)))
    p_ref = weights.make(SEED, ref.param_shapes(spec))
    key = jax.random.PRNGKey(3)
    keys = ref.c4.step_keys(key, 2)
    parts = [jax.jit(lambda row, k: ref.image_parts(p_ref, row, k, spec))(
        {n: jnp.asarray(v[i]) for n, v in batch.items()}, keys[i])
        for i in range(2)]
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    pyramid, rpn_out = jax.jit(
        lambda p, x: fpn._pyramid_rpn(model, p, x, cfg)[:2])(
            params, jbatch["image"])
    return dict(fpn=fpn, cfg=cfg, spec=spec, ref=ref, model=model,
                params=params, p_ref=p_ref, batch=jbatch, key=key,
                parts=parts, pyramid=pyramid, rpn_out=rpn_out)


def test_both_sides_hold_the_same_leaves(both):
    prog = driver._flat(both["params"])
    assert set(prog) == set(both["p_ref"])
    for k, v in prog.items():
        np.testing.assert_array_equal(v, np.asarray(both["p_ref"][k]), k)
    assert sum(k.startswith("rpn/") for k in prog) == 6


def test_neck_outputs(both):
    """P2-P6 of both images. 2e-4 of the level's largest value: the same
    float32 products summed in another order through 50 layers."""
    for i, part in enumerate(both["parts"]):
        for lv, want in part["pyramid"].items():
            got = np.asarray(both["pyramid"][lv][i])
            assert got.shape == want.shape
            np.testing.assert_allclose(
                got, want, atol=2e-4 * float(np.abs(want).max()), rtol=0,
                err_msg=f"image {i} P{lv}")


def test_proposals_are_the_same_boxes_in_the_same_order(both):
    """Per-level top-k, NMS within the level, the union's best by score:
    the same rois in the same slots. 0.01 px and 1e-5 of a score: decode and
    softmax in float32 on both sides; a swapped pair would be whole boxes
    apart."""
    fpn, cfg = both["fpn"], both["cfg"]
    shapes = {lv: both["pyramid"][lv].shape[1:3] for lv in fpn.RPN_LEVELS}
    rois, ok, scores = jax.jit(lambda out, info: fpn.fpn_proposals(
        out, fpn.pyramid_anchors(shapes, cfg), info, cfg, train=True))(
            both["rpn_out"], both["batch"]["im_info"])
    for i, part in enumerate(both["parts"]):
        np.testing.assert_array_equal(np.asarray(ok[i]), part["roi_ok"])
        assert int(part["roi_ok"].sum()) > 16
        np.testing.assert_allclose(np.asarray(rois[i]), part["rois"],
                                   atol=1e-2, rtol=0)
        np.testing.assert_allclose(np.asarray(scores[i]),
                                   part["roi_scores"], atol=1e-5, rtol=0)


def test_roi_levels_and_pooled_features(both):
    """The reference's own sampled rois given to the program: Eq. 1 sends
    each to the same level (integers: equal), and the program's pooling from
    the four levels stacked in one canvas, each roi's weights laid at its own
    level's rows and columns (at this size the dense form: one pair of
    contractions), gives what four gathered taps a sample point on the one
    level give. 2e-4 of the largest pooled value: tent weights against
    gathered taps, float32 both."""
    fpn, spec = both["fpn"], both["spec"]
    rois = jnp.stack([p["sampled"] for p in both["parts"]])
    ok = jnp.stack([p["sampled_ok"] for p in both["parts"]])
    levels = np.asarray(fpn.roi_levels(rois))
    want_levels = np.stack([p["levels"] for p in both["parts"]])
    np.testing.assert_array_equal(levels, want_levels)
    assert len(np.unique(want_levels)) >= 2    # more than one level is met
    pooled = np.asarray(jax.jit(lambda pyr, r, v: fpn.pyramid_roi_align(
        pyr, r, v, spec["roi_pool_size"]))(both["pyramid"], rois, ok))
    want = np.concatenate([p["pooled"] for p in both["parts"]])
    np.testing.assert_allclose(pooled, want, rtol=0,
                               atol=2e-4 * float(np.abs(want).max()))


def test_the_four_losses_and_the_first_gradient(both):
    """The whole step's forward and backward on the same key. Losses to
    1e-4 relative (sums of ~300 and 64 float32 terms in another order);
    every leaf's gradient norm to 1e-3 of the reference's (or of the median
    leaf's: ``compare.leaf_gaps``), which 50 layers of float32 back
    propagation in another order stay well inside, and which a wrong level,
    a dropped image or a missing loss term would pass by orders of
    magnitude."""
    from mx_rcnn_tpu.models.zoo import forward_train

    ref, spec, cfg = both["ref"], both["spec"], both["cfg"]
    model, params = both["model"], both["params"]
    trainer = ref.Trainer(spec, both["p_ref"])
    np_batch = {k: np.asarray(v) for k, v in both["batch"].items()}
    _, want_parts, want_grads = trainer.grads(np_batch, both["key"])
    (_, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: forward_train(model, p, both["batch"], both["key"], cfg),
        has_aux=True))(params)
    got_parts = [float(aux[k]) for k in ("rpn_cls_loss", "rpn_bbox_loss",
                                         "rcnn_cls_loss", "rcnn_bbox_loss")]
    np.testing.assert_allclose(got_parts, want_parts, rtol=1e-4)
    share = np.asarray(aux["roi_level_counts"])
    assert share.sum() == 2 * spec["train"]["batch_rois"]
    want_levels = np.concatenate([p["levels"] for p in both["parts"]])
    np.testing.assert_array_equal(
        share, [(want_levels == lv).sum() for lv in spec["roi_levels"]])
    got = compare.norms({k: v for k, v in driver._flat(grads).items()
                         if k in want_grads})
    gaps = compare.leaf_gaps(got, compare.norms(want_grads))
    assert compare.worst(gaps)[0] < 1e-3, compare.worst(gaps)
