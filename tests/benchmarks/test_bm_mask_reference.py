"""The mask branch held to its plain reference (benchmarks/reference/mask.py)
piece by piece, on seeded weights at a small size: R-50, a 128x192 canvas, 2
images, 64 candidates a level, 64 rois of which the first 16 slots are the
branch's. The program computes in float32 here (``train.compute_dtype=f32``),
so what is left between the two sides is the order of float32 sums and the
target's rule (the reference's follows the publication); each tolerance says
what it allows for."""

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
from bm_tiny_mask import tiny_mask  # noqa: E402

pytestmark = pytest.mark.compile_heavy
SEED = 2 ** 31 + 7
MASK_LEAVES = [f"mask_head/{name}/{leaf}" for name in (
    "mask_conv0", "mask_conv1", "mask_conv2", "mask_conv3", "mask_deconv",
    "mask_logits") for leaf in ("kernel", "bias")]


@pytest.fixture(scope="module")
def both():
    from mx_rcnn_tpu.models import fpn
    from mx_rcnn_tpu.models.zoo import build_model, forward_train, init_params

    conf = manifest.load_json("configs", "mask_r101_fpn_coco")
    t = tiny_mask()
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
    # what the program's loader serves for a record without a mask
    m = cfg.train.mask_gt_resolution
    jbatch["gt_masks"] = jnp.ones((2, spec["max_gt_boxes"], m, m), jnp.uint8)
    trainer = ref.Trainer(spec, p_ref)
    want = trainer.grads(batch, key)
    (_, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: forward_train(model, p, jbatch, key, cfg),
        has_aux=True))(params)
    return dict(fpn=fpn, cfg=cfg, spec=spec, ref=ref, model=model,
                params=params, p_ref=p_ref, batch=jbatch, key=key,
                parts=parts, want=want, aux=aux, grads=driver._flat(grads))


def test_both_sides_hold_the_same_leaves(both):
    prog = driver._flat(both["params"])
    assert set(prog) == set(both["p_ref"]) >= set(MASK_LEAVES)
    for k in MASK_LEAVES:
        np.testing.assert_array_equal(prog[k], np.asarray(both["p_ref"][k]), k)
    assert prog["mask_head/mask_deconv/kernel"].shape == (2, 2, 256, 256)
    assert prog["mask_head/mask_logits/kernel"].shape == (1, 1, 256, 81)


def _program_targets(rois, boxes, m=56, size=28):
    from mx_rcnn_tpu.targets.mask_targets import mask_targets_for_rois

    n = len(rois)
    return np.asarray(mask_targets_for_rois(
        jnp.asarray(rois), jnp.arange(n), jnp.asarray(boxes),
        jnp.ones((n, m, m), jnp.uint8), resolution=size))


def test_the_two_target_rules_differ_only_on_the_ring_a_box_edge_crosses(
        both):
    """The reference: a cell is 1 where its centre lies inside the box. The
    program: an all-ones 56x56 box-frame mask resampled bilinearly with zero
    padding, thresholded at 0.5, which is the same rule but within 1/112 of
    the box's side of an edge on BOTH axes (a corner), where the product of
    two tent sums can fall under 0.5. On 4,000 seeded rois jittered about
    their boxes every differing cell lies in the ring of cells whose own
    extent an edge of the box crosses, they are under 0.1 % of the cells,
    and a roi that IS its box has none."""
    ref = both["ref"]
    rs = np.random.RandomState(5)
    tl = rs.uniform(0, 400, (4000, 2))
    boxes = np.concatenate([tl, tl + rs.uniform(20, 300, (4000, 2))], 1)
    rois = boxes + rs.uniform(-0.3, 0.3, (4000, 4)) * (
        boxes[:, 2:] - boxes[:, :2])[:, [0, 1, 0, 1]]
    rois[:200] = boxes[:200]
    rois, boxes = rois.astype(np.float32), boxes.astype(np.float32)
    want = np.asarray(ref.mask_targets(jnp.asarray(rois), jnp.asarray(boxes),
                                       28))
    got = _program_targets(rois, boxes)
    assert set(np.unique(want)) == set(np.unique(got)) == {0.0, 1.0}
    differ = want != got
    assert 0 < differ.sum() < 1e-3 * differ.size
    assert not differ[:200].any() and want[:200].all()
    # the ring: cells whose extent holds an edge of the box, on either axis
    edge = np.arange(29, dtype=np.float32) / 28
    ring = np.zeros(differ.shape, bool)
    for lo, hi, columns in ((0, 2, True), (1, 3, False)):
        side = np.maximum(rois[:, hi] - rois[:, lo] + 1, 1)
        cuts = rois[:, lo, None] + edge[None] * side[:, None]   # (R, 29)
        for at in (boxes[:, lo], boxes[:, hi] + 1):
            hit = (cuts[:, :-1] <= at[:, None]) & (at[:, None] <= cuts[:, 1:])
            ring |= hit[:, None, :] if columns else hit[:, :, None]
    assert not (differ & ~ring).any()


def test_the_branch_runs_over_the_same_rois_with_the_same_targets(both):
    """The reference's foreground block is the program's: the first 16 of
    64 slots, the live ones first, at least three live rois in the batch;
    and on THESE rois the two target rules agree in every cell (so the loss
    and gradient comparisons below are not blurred by the ring)."""
    spec = both["spec"]
    n = round(spec["train"]["fg_fraction"] * spec["train"]["batch_rois"])
    live_total = 0
    for part in both["parts"]:
        live = np.asarray(part["mask_live"])
        assert live.shape == (n,) == (16,)
        k = int(live.sum())
        assert live[:k].all() and not live[k:].any()
        np.testing.assert_array_equal(np.asarray(part["mask_rois"]),
                                      np.asarray(part["sampled"])[:n])
        got = _program_targets(np.asarray(part["mask_rois"]),
                               np.asarray(part["mask_matched"]))
        np.testing.assert_array_equal(got[live],
                                      np.asarray(part["mask_targets"])[live])
        live_total += k
    assert live_total >= 3
    counts = np.asarray(both["aux"]["mask_roi_counts"])
    per_image = [int(np.asarray(p["mask_live"]).sum()) for p in both["parts"]]
    np.testing.assert_allclose(counts[:3], [min(per_image),
                                            np.mean(per_image),
                                            max(per_image)])
    assert counts[3:].sum() == live_total


def test_pooled_14x14_features(both):
    """The reference's own foreground rois given to the program: its pooling
    from the four levels stacked in one canvas, each roi's weights laid at
    its own level's rows and columns, at 14x14 bins (the dense form: one
    pair of contractions), gives what four gathered taps a sample point on
    the one level give. 2e-4 of the largest pooled value: tent weights
    against gathered taps, float32 both."""
    fpn, spec, model = both["fpn"], both["spec"], both["model"]
    rois = jnp.stack([p["mask_rois"] for p in both["parts"]])
    live = jnp.stack([p["mask_live"] for p in both["parts"]])
    pyramid = {lv: jnp.stack([p["pyramid"][lv] for p in both["parts"]])
               for lv in spec["roi_levels"]}
    assert model.mask_pool_size == spec["mask_pool_size"] == 14
    pooled = np.asarray(jax.jit(lambda pyr, r, v: fpn.pyramid_roi_align(
        pyr, r, v, model.mask_pool_size))(pyramid, rois, live))
    want = np.concatenate([p["mask_pooled"] for p in both["parts"]])
    assert pooled.shape == want.shape == (32, 14, 14, 256)
    np.testing.assert_allclose(pooled, want, rtol=0,
                               atol=2e-4 * float(np.abs(want).max()))


def test_the_five_losses(both):
    """The whole step's forward on the same key: the four detection losses,
    the mask loss and their sum to 1e-4 relative (sums of ~300, 64 and
    784 x the live rois float32 terms in another order)."""
    aux = both["aux"]
    _, want_parts, _ = both["want"]
    got_parts = [float(aux[k]) for k in (
        "rpn_cls_loss", "rpn_bbox_loss", "rcnn_cls_loss", "rcnn_bbox_loss",
        "mask_loss")]
    assert want_parts.shape == (5,) and want_parts[4] > 0.1
    np.testing.assert_allclose(got_parts, want_parts, rtol=1e-4)
    np.testing.assert_allclose(float(aux["total_loss"]), both["want"][0],
                               rtol=1e-4)
    np.testing.assert_allclose(float(aux["total_loss"]), sum(got_parts),
                               rtol=1e-6)


@pytest.mark.parametrize("leaf", MASK_LEAVES)
def test_the_first_gradient_of_each_mask_head_leaf(leaf, both):
    """Each of the head's twelve leaves: the gradient itself, element by
    element, to 1e-3 of its largest element (float32 back propagation in
    another order), which a flipped kernel of the transposed convolution, a
    wrong class's map or a wrong normaliser would pass by orders of
    magnitude; it is not nought."""
    want = np.asarray(both["want"][2][leaf])
    got = both["grads"][leaf]
    assert float(np.abs(want).max()) > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-3 * float(np.abs(want).max()))


def test_the_first_gradient_of_every_other_leaf(both):
    """The neck, trunk, RPN and box head: every leaf's gradient norm to 1e-3
    of the reference's (or of the median leaf's: ``compare.leaf_gaps``), and
    the neck's leaves carry the mask loss's gradient: without it (the
    planted fault "f32/mask_off") their norms are over 1e-2 away."""
    want_grads = both["want"][2]
    got = compare.norms({k: v for k, v in both["grads"].items()
                         if k in want_grads})
    gaps = compare.leaf_gaps(got, compare.norms(want_grads))
    assert set(gaps) == set(want_grads)
    assert compare.worst(gaps)[0] < 1e-3, compare.worst(gaps)
    off = both["ref"].Trainer(both["spec"], both["p_ref"], "f32/mask_off")
    _, parts, without = off.grads(
        {k: np.asarray(v) for k, v in both["batch"].items()
         if k != "gt_masks"}, both["key"])
    assert parts[4] == 0.0
    gaps = compare.leaf_gaps(compare.norms(without),
                             compare.norms(want_grads))
    assert all(not np.asarray(without[k]).any() for k in MASK_LEAVES)
    assert max(gaps[k] for k in MASK_LEAVES) == pytest.approx(1.0)
    assert max(v for k, v in gaps.items() if k.startswith("neck/")) > 1e-2
