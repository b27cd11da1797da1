"""FPN detector: neck, anchors, level assignment, pyramid pooling, forwards.

Covers BASELINE.json configs 3-4 machinery (models/fpn.py,
targets/mask_targets.py). The reference repo has no FPN; semantics follow
Lin et al. (FPN) / He et al. (Mask R-CNN) as documented in the module.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.compile_heavy

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import fpn as F
from mx_rcnn_tpu.models import zoo
from mx_rcnn_tpu.ops.roi_align import roi_align
from mx_rcnn_tpu.targets.mask_targets import mask_targets_for_rois


def tiny_cfg(mask=False, **overrides):
    base = {
        "image.pad_shape": (128, 128),
        "train.batch_images": 1,
        "train.fpn_rpn_pre_nms_per_level": 64,
        "train.rpn_post_nms_top_n": 64,
        "train.batch_rois": 32,
        "train.max_gt_boxes": 8,
        "train.mask_gt_resolution": 28,
        "test.fpn_rpn_pre_nms_per_level": 32,
        "test.rpn_post_nms_top_n": 16,
    }
    base.update(overrides)
    net = "resnet50_fpn_mask" if mask else "resnet50_fpn"
    return generate_config(net, "synthetic", **base)


def tiny_batch(rng, mask=False):
    gm = np.zeros((1, 8, 28, 28), np.uint8)
    gm[0, :2, 6:22, 6:22] = 1
    batch = {
        "image": rng.randn(1, 128, 128, 3).astype(np.float32),
        "im_info": np.asarray([[128, 128, 1.0]], np.float32),
        "gt_boxes": np.asarray(
            [[[10, 10, 60, 90], [70, 20, 120, 70]] + [[0, 0, 0, 0]] * 6],
            np.float32),
        "gt_classes": np.asarray([[1, 2] + [0] * 6], np.int32),
        "gt_valid": np.asarray([[True, True] + [False] * 6]),
    }
    if mask:
        batch["gt_masks"] = gm
    return batch


def test_upsample2x():
    x = jnp.arange(4, dtype=jnp.float32).reshape(1, 2, 2, 1)
    y = F._upsample2x(x)
    assert y.shape == (1, 4, 4, 1)
    assert np.array_equal(np.asarray(y)[0, :, :, 0],
                          [[0, 0, 1, 1], [0, 0, 1, 1],
                           [2, 2, 3, 3], [2, 2, 3, 3]])


def test_neck_shapes():
    neck = F.FPNNeck(channels=32, dtype=jnp.float32)
    feats = [jnp.zeros((1, 32, 32, 8)), jnp.zeros((1, 16, 16, 16)),
             jnp.zeros((1, 8, 8, 32)), jnp.zeros((1, 4, 4, 64))]
    params = neck.init(jax.random.PRNGKey(0), feats)
    out = neck.apply(params, feats)
    assert set(out.keys()) == {2, 3, 4, 5, 6}
    assert out[2].shape == (1, 32, 32, 32)
    assert out[5].shape == (1, 4, 4, 32)
    assert out[6].shape == (1, 2, 2, 32)


def test_pyramid_anchor_sizes():
    cfg = tiny_cfg()
    shapes = {2: (32, 32), 3: (16, 16), 4: (8, 8), 5: (4, 4), 6: (2, 2)}
    anchors = F.pyramid_anchors(shapes, cfg)
    for lv in F.RPN_LEVELS:
        a = anchors[lv]
        assert a.shape == (shapes[lv][0] * shapes[lv][1] * 3, 4)
        # The 1:1-ratio anchor at each level is (scale*stride) square:
        # 8 * 2^lv px. Ratio enumeration rounds, so allow 1px.
        w = a[:, 2] - a[:, 0] + 1
        h = a[:, 3] - a[:, 1] + 1
        square = np.abs(w - h) < 1e-3
        assert square.any()
        np.testing.assert_allclose(w[square][0], 8 * 2 ** lv, atol=1.0)


def test_roi_levels_eq1():
    rois = jnp.asarray([
        [0, 0, 223, 223],    # 224x224 -> k0 = 4
        [0, 0, 111, 111],    # 112 -> 3
        [0, 0, 447, 447],    # 448 -> 5
        [0, 0, 20, 20],      # tiny -> clamp 2
        [0, 0, 2000, 2000],  # huge -> clamp 5
    ], jnp.float32)
    np.testing.assert_array_equal(np.asarray(F.roi_levels(rois)),
                                  [4, 3, 5, 2, 5])


def test_pyramid_roi_align_selects_assigned_level(rng):
    cfg = tiny_cfg()
    pyramid = {lv: jnp.asarray(
        rng.randn(1, 128 // 2 ** lv, 128 // 2 ** lv, 8).astype(np.float32))
        for lv in (2, 3, 4, 5)}
    # One roi per level: sizes 56 (k=2), 112 (k=3), 224->but image is 128...
    # use sizes mapping to levels 2 and 3 inside the image.
    rois = jnp.asarray([[[4, 4, 59, 59], [4, 4, 115, 115]]], jnp.float32)
    valid = jnp.ones((1, 2), bool)
    out = F.pyramid_roi_align(pyramid, rois, valid, pool_size=7)
    assert out.shape == (2, 7, 7, 8)
    lv_of = np.asarray(F.roi_levels(rois[0]))
    for i, lv in enumerate(lv_of):
        want = roi_align(pyramid[int(lv)], rois[:, i:i + 1], 7,
                         1.0 / 2 ** int(lv))
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(want[0, 0]),
                                   rtol=1e-5, atol=1e-5)


_EVEN = ((32, 48), (16, 24), (8, 12), (4, 6))   # a 128x192 image, halved
_ODD = ((13, 9), (7, 5), (4, 3), (2, 2))         # halving rounds up
# the side of a roi, px: Eq. 1 sends < 112 to P2, >= 448 to P5
_SPLITS = {"all_on_p2": (8, 100), "none_on_p2": (120, 900),
           "spread": (8, 900), "all_invalid": (8, 900)}
_POOLINGS = (
    [(split, bins, dt, _EVEN, 1) for split in _SPLITS for bins in (7, 14)
     for dt in ("bfloat16", "float32")]
    + [("spread", bins, dt, _EVEN, 2) for bins in (7, 14)
       for dt in ("bfloat16", "float32")]
    + [("spread", bins, dt, _ODD, 1) for bins in (7, 14)
       for dt in ("bfloat16", "float32")])


@pytest.mark.parametrize(
    "split,bins,dtype,sizes,per_plane", _POOLINGS,
    ids=[f"{s}-{b}-{d}-{'odd' if z is _ODD else 'even'}-{i}_a_plane"
         for s, b, d, z, i in _POOLINGS])
def test_pyramid_roi_align_is_roi_align_of_the_assigned_level(
        split, bins, dtype, sizes, per_plane):
    """One pooling from the canvas of all levels gives, roi by roi, what
    ``roi_align`` of the roi's Eq. 1 level gives, and zeros for an invalid
    roi: the values AND the gradient to each level's map, bfloat16 to the
    bit (the added terms are products with an exact zero), float32 to the
    order of a sum. Over the levels' splits, 7 and 14 bins, a packed batch
    (two images a plane, each clamped to its own placement window) and a
    pyramid whose halving rounds up (``_ODD`` packs onto three shelves)."""
    from mx_rcnn_tpu.ops.canvas import rois_by_plane

    dt = jnp.dtype(dtype)
    rs = np.random.RandomState(bins + len(split))
    planes, r = 2, 24
    b = planes * per_plane
    pyramid = {lv: jnp.asarray(rs.randn(planes, h, w, 8), dt)
               for lv, (h, w) in zip(F.ROI_LEVELS, sizes)}
    (_, wc), places = F.pack_placements(list(sizes), gap=0)
    assert wc == sizes[0][1]
    assert len({y for y, _, _, _ in places}) == (3 if sizes is _ODD else 2)
    ih, iw = 4 * sizes[0][0], 4 * sizes[0][1] // per_plane
    side = rs.uniform(*_SPLITS[split], size=(b, r))
    cy, cx = rs.uniform(0, ih, (b, r)), rs.uniform(0, iw, (b, r))
    windows = None
    if per_plane > 1:  # image i of a plane lies i * iw px to the right
        x0 = iw * (np.arange(b) % per_plane).astype(np.float32)
        windows = jnp.asarray(np.stack(
            [np.zeros(b), x0, np.full(b, ih), np.full(b, iw)], axis=1),
            jnp.float32)
        cx = cx + x0[:, None]
    rois = jnp.asarray(np.stack([cx - side / 2, cy - side / 2,
                                 cx + side / 2, cy + side / 2], axis=-1),
                       jnp.float32)
    valid = jnp.asarray(rs.uniform(size=(b, r)) > 0.2
                        if split != "all_invalid" else np.zeros((b, r), bool))
    levels = np.asarray(F.roi_levels(rois))
    on_p2 = (levels == 2).mean()
    assert {"all_on_p2": on_p2 == 1, "none_on_p2": on_p2 == 0}.get(
        split, 0 < on_p2 < 1 and len(np.unique(levels)) == 4)

    def level_by_level(pyr):
        grouped, win = rois_by_plane(planes, rois, windows)
        out = 0.0
        for lv in F.ROI_LEVELS:
            pooled = roi_align(pyr[lv], grouped, bins, 1.0 / 2 ** lv,
                               windows=win).reshape(b, r, bins, bins, -1)
            mine = (F.roi_levels(rois) == lv) & valid
            out = out + jnp.where(mine[..., None, None, None], pooled, 0)
        return out.reshape(b * r, bins, bins, -1)

    def once(pyr):
        return F.pyramid_roi_align(pyr, rois, valid, bins, windows=windows)

    def value_and_map_grads(pool):
        def weighed(pyr):
            out = pool(pyr)
            wave = jnp.cos(jnp.arange(out.size, dtype=jnp.float32))
            return jnp.sum(out.astype(jnp.float32)
                           * wave.reshape(out.shape)), out
        (_, out), grads = jax.jit(
            jax.value_and_grad(weighed, has_aux=True))(pyramid)
        return [np.asarray(a, np.float32)
                for a in [out] + [grads[lv] for lv in F.ROI_LEVELS]]

    got, want = value_and_map_grads(once), value_and_map_grads(level_by_level)
    assert got[0].shape == (b * r, bins, bins, 8)
    if split == "all_invalid":
        assert not got[0].any() and not any(g.any() for g in got[1:])
    else:
        assert np.abs(want[0]).max() > 0.5
    for g, w in zip(got, want):
        if dt == jnp.bfloat16:
            np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_allclose(
                g, w, rtol=0, atol=1e-6 * max(1.0, np.abs(w).max()))


def test_per_level_nms_union_suppression():
    """Direct check of the per-level scope on constructed candidates:
    same-level near-duplicates ARE suppressed, cross-level near-duplicates
    are NOT (Detectron semantics), and the union is score-ranked."""
    # level A: two heavy-overlap boxes (IoU ~0.9) + one separate
    la = jnp.asarray([[[0, 0, 100, 100], [2, 2, 102, 102],
                       [200, 200, 300, 300]]], jnp.float32)
    sa = jnp.asarray([[0.9, 0.8, 0.6]], jnp.float32)
    # level B: a near-duplicate of level A's best box
    lb = jnp.asarray([[[1, 1, 101, 101], [400, 0, 500, 80],
                       [0, 0, 0, 0]]], jnp.float32)
    sb = jnp.asarray([[0.7, 0.5, 0.0]], jnp.float32)
    valid = jnp.asarray([[True, True, True]])
    vb = jnp.asarray([[True, True, False]])

    rois, kv, scores = F.per_level_nms_union(
        [la, lb], [sa, sb], [valid, vb], thresh=0.5, post=6)
    rois, kv, scores = map(np.asarray, (rois, kv, scores))
    got = {tuple(r) for r in rois[0][kv[0]]}
    # within level A, (2,2,102,102) suppressed by (0,0,100,100)
    assert (2, 2, 102, 102) not in got
    # across levels, the near-duplicate from level B survives
    assert (1, 1, 101, 101) in got
    assert (0, 0, 100, 100) in got and (200, 200, 300, 300) in got
    assert (400, 0, 500, 80) in got
    assert kv[0].sum() == 4
    s = scores[0][kv[0]]
    assert (np.diff(s) <= 1e-6).all()  # union score-ranked
    np.testing.assert_allclose(sorted(s, reverse=True),
                               [0.9, 0.7, 0.6, 0.5], rtol=1e-6)


def test_per_level_nms_semantics(rng):
    """fpn_nms_per_level (Detectron-lineage default): within one level no
    two kept rois overlap past the threshold, the union is score-ranked,
    and the joint variant (False) still runs and returns valid rois."""
    from functools import partial

    from mx_rcnn_tpu.ops.boxes import bbox_overlaps

    cfg = tiny_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    images = jnp.asarray(batch["image"])
    info = jnp.asarray(batch["im_info"])

    def props(p, x, i, per_level):
        c = cfg.with_updates(train=__import__("dataclasses").replace(
            cfg.train, fpn_nms_per_level=per_level))
        _, rpn_out, anchors = F._pyramid_rpn(model, p, x, c)
        return F.fpn_proposals(rpn_out, anchors, i, c, train=True)

    rois_pl, valid_pl, scores_pl = jax.jit(
        partial(props, per_level=True))(params, images, info)
    rois_j, valid_j, scores_j = jax.jit(
        partial(props, per_level=False))(params, images, info)

    for rois, valid, scores in ((rois_pl, valid_pl, scores_pl),
                                (rois_j, valid_j, scores_j)):
        rois, valid, scores = map(np.asarray, (rois, valid, scores))
        assert valid.any()
        v = rois[0][valid[0]]
        assert np.isfinite(v).all()
        assert (v[:, 2] >= v[:, 0]).all() and (v[:, 3] >= v[:, 1]).all()
        # scores of valid rois are sorted descending (top-k output order)
        s = scores[0][valid[0]]
        assert (np.diff(s) <= 1e-6).all()

    # joint NMS guarantees global non-overlap; per-level only guarantees
    # it within a level — so the joint survivors must pairwise clear the
    # threshold, which pins the two variants really do differ in scope.
    vj = np.asarray(rois_j)[0][np.asarray(valid_j)[0]]
    iou = np.array(bbox_overlaps(vj, vj))  # copy: jax view is read-only
    np.fill_diagonal(iou, 0.0)
    assert iou.max() <= cfg.train.rpn_nms_thresh + 1e-5


def test_forward_train_finite_and_jit(rng):
    cfg = tiny_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    loss, aux = jax.jit(
        lambda p, b, r: zoo.forward_train(model, p, b, r, cfg)
    )(params, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    for k in ("rpn_cls_loss", "rpn_bbox_loss", "rcnn_cls_loss",
              "rcnn_bbox_loss"):
        assert np.isfinite(float(aux[k])), k


def test_forward_train_grads_reach_all_parts(rng):
    cfg = tiny_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    grads = jax.jit(jax.grad(
        lambda p: zoo.forward_train(model, p, batch,
                                    jax.random.PRNGKey(1), cfg)[0]
    ))(params)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]

    def norm_of(substr):
        tot = 0.0
        for path, leaf in flat:
            if substr in jax.tree_util.keystr(path):
                tot += float(jnp.sum(jnp.abs(leaf)))
        return tot

    for part in ("neck", "rpn", "head", "cls_score", "bbox_pred", "stage3"):
        assert norm_of(part) > 0, f"no gradient reached {part}"
    # Frozen prefix: stage1 gradient is structurally zero.
    assert norm_of("stage1") == 0


def test_forward_test_contract(rng):
    cfg = tiny_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    rois, rv, scores, boxes = jax.jit(
        lambda p, i, ii: zoo.forward_test(model, p, i, ii, cfg)
    )(params, batch["image"], batch["im_info"])
    r = cfg.test.rpn_post_nms_top_n
    c = cfg.dataset.num_classes
    assert rois.shape == (1, r, 4)
    assert scores.shape == (1, r, c)
    assert boxes.shape == (1, r, 4 * c)
    # Scores on invalid rois are zeroed.
    s = np.asarray(scores)
    v = np.asarray(rv)
    assert (s[~v] == 0).all()


def test_mask_targets_identity_roi():
    # ROI == gt box: the target must reproduce the gt mask at 28x28.
    gt_boxes = jnp.asarray([[10.0, 20.0, 65.0, 75.0]])
    gm = np.zeros((1, 28, 28), np.float32)
    gm[0, 7:21, 7:21] = 1
    t = mask_targets_for_rois(
        jnp.asarray([[10.0, 20.0, 65.0, 75.0]]), jnp.asarray([0]),
        gt_boxes, jnp.asarray(gm), resolution=28)
    np.testing.assert_array_equal(np.asarray(t)[0], gm[0])


def test_mask_targets_half_roi():
    # ROI = left half of the gt box: target is the left half of the mask,
    # stretched to full resolution.
    gt_boxes = jnp.asarray([[0.0, 0.0, 55.0, 55.0]])
    gm = np.zeros((1, 28, 28), np.float32)
    gm[0, :, :14] = 1  # left half on
    t = mask_targets_for_rois(
        jnp.asarray([[0.0, 0.0, 27.0, 55.0]]), jnp.asarray([0]),
        gt_boxes, jnp.asarray(gm), resolution=28)
    got = np.asarray(t)[0]
    # Almost all columns should be on (right boundary cell may waver).
    assert got[:, :26].all()


def test_mask_targets_outside_gt_box_is_zero():
    gt_boxes = jnp.asarray([[0.0, 0.0, 27.0, 27.0]])
    gm = np.ones((1, 28, 28), np.float32)
    t = mask_targets_for_rois(
        jnp.asarray([[100.0, 100.0, 127.0, 127.0]]), jnp.asarray([0]),
        gt_boxes, jnp.asarray(gm), resolution=28)
    assert np.asarray(t).sum() == 0


def test_mask_forward_train(rng):
    cfg = tiny_cfg(mask=True)
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng, mask=True)
    loss, aux = jax.jit(
        lambda p, b, r: zoo.forward_train(model, p, b, r, cfg)
    )(params, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    assert np.isfinite(float(aux["mask_loss"]))
    assert float(aux["mask_loss"]) > 0


def _mask_loss_over_every_slot(model, params, pyramid, samples, gt_boxes,
                               gt_masks):
    """The branch as it stood before PR 34, kept here only: pooling, head,
    targets and loss over ALL sampled slots, the loss masked by
    ``fg_mask``, the class's map by a gather."""
    b, r = samples.rois.shape[:2]
    live = samples.valid & samples.fg_mask
    pooled = F.pyramid_roi_align(pyramid, samples.rois, live,
                                 model.mask_pool_size)
    logits = model.apply(params, pooled, method="mask_forward")
    m = logits.shape[1]
    targets = jax.vmap(
        lambda ro, g, gb, gm: mask_targets_for_rois(ro, g, gb, gm,
                                                    resolution=m)
    )(samples.rois, samples.matched_gt, gt_boxes, gt_masks)
    labels = jnp.where(samples.valid, samples.labels, -1).reshape(-1)
    per_roi = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[:, None, None, None], axis=-1)[..., 0]
    bce = F.optax_sigmoid_bce(per_roi, targets.reshape(b * r, m, m))
    fg = live.reshape(-1).astype(jnp.float32)
    return (jnp.sum(jnp.mean(bce, axis=(1, 2)) * fg)
            / jnp.maximum(jnp.sum(fg), 1.0))


@pytest.fixture(scope="module")
def mask_branch_pair():
    """Both forms jitted once at the published slot counts (512 slots an
    image, 128 of them the branch's), on a small seeded pyramid, float32."""
    from mx_rcnn_tpu.targets.rcnn_targets import RoiSamples

    cfg = tiny_cfg(mask=True, **{"train.batch_rois": 512,
                                 "train.compute_dtype": "f32"})
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    rs = np.random.RandomState(7)
    pyramid = {lv: jnp.asarray(rs.randn(1, 128 >> lv, 128 >> lv, 256)
                               .astype(np.float32)) for lv in F.ROI_LEVELS}
    tl = rs.uniform(0, 90, (1, 512, 2))
    # sides of 4-120 px: Eq. 1 sends these to P2 and P3
    rois = jnp.asarray(np.concatenate(
        [tl, np.minimum(tl + rs.uniform(4, 120, (1, 512, 2)), 127)],
        axis=-1).astype(np.float32))
    batch = tiny_batch(rs, mask=True)
    classes = jnp.asarray(rs.randint(1, model.num_classes, (1, 512)))
    matched = jnp.asarray(rs.randint(0, 2, (1, 512)).astype(np.int32))

    def samples_of(n_fg, valid):
        fg = (jnp.arange(512) < n_fg)[None] & valid
        return RoiSamples(rois=rois, labels=jnp.where(fg, classes, 0),
                          bbox_targets=None, bbox_weights=None, valid=valid,
                          fg_mask=fg, matched_gt=matched)

    def both(n_fg, valid):
        s = samples_of(n_fg, valid)
        gb, gm = jnp.asarray(batch["gt_boxes"]), jnp.asarray(batch["gt_masks"])

        def prefix(p2):
            return F.mask_branch(model, params, {**pyramid, 2: p2}, s,
                                 gb, gm, None, cfg)

        def every(p2):
            return _mask_loss_over_every_slot(
                model, params, {**pyramid, 2: p2}, s, gb, gm)

        (lp, counts), gp = jax.value_and_grad(prefix, has_aux=True)(pyramid[2])
        le, ge = jax.value_and_grad(every)(pyramid[2])
        return lp, gp, counts, le, ge

    return jax.jit(both)


@pytest.mark.parametrize("n_fg", [0, 1, 128])
def test_mask_loss_over_the_foreground_prefix_equals_every_slot(
        n_fg, mask_branch_pair):
    """The sampler lays the foreground out as a prefix of the slots, so the
    branch over the first round(fg_fraction * batch_rois) = 128 of 512 slots
    gives the loss (and the gradient into P2) of the branch over every slot
    with the loss masked: to float32 round-off (a sum of 128 terms against
    one of 512, 384 of them zeros; the dense select against the gather is
    exact). One slot inside the prefix is invalid: both forms leave it
    out."""
    valid = jnp.ones((1, 512), bool).at[0, 0].set(n_fg < 128)
    lp, gp, counts, le, ge = mask_branch_pair(n_fg, valid)
    live = n_fg if n_fg < 128 else 127
    assert float(le) > 0 or live == 0
    np.testing.assert_allclose(float(lp), float(le), rtol=1e-6, atol=0)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(ge), rtol=1e-5,
                               atol=1e-7 * float(np.abs(ge).max() + 1e-30))
    counts = np.asarray(counts)
    np.testing.assert_array_equal(counts[:3], [live] * 3)  # one image
    assert counts[3:].sum() == live and (live == 0 or counts[3] > 0)


def test_mask_inference_contract(rng):
    cfg = tiny_cfg(mask=True)
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng, mask=True)
    det_boxes = jnp.asarray([[[10, 10, 60, 90], [70, 20, 120, 70]]],
                            jnp.float32)
    det_classes = jnp.asarray([[1, 2]], jnp.int32)
    det_valid = jnp.asarray([[True, False]])
    probs = jax.jit(lambda p: F.forward_test_masks(
        model, p, batch["image"], det_boxes, det_classes, det_valid))(params)
    assert probs.shape == (1, 2, 28, 28)
    p = np.asarray(probs)
    assert (p[0, 1] == 0).all()  # invalid detection zeroed
    assert ((p >= 0) & (p <= 1)).all()


def test_fpn_dp_parity(rng):
    """FPN train step: 2-way DP == single device on the same 2-image batch
    (the pattern of tests/test_train_step.py::test_dp_grads_match_single_device)."""
    from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
    from mx_rcnn_tpu.train.optimizer import build_optimizer
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step

    if jax.device_count() < 2:
        pytest.skip("needs 2 virtual devices")
    cfg = tiny_cfg(**{"train.batch_images": 2})
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=10)

    one = tiny_batch(rng)
    batch = {k: np.repeat(v, 2, axis=0) for k, v in one.items()}
    key = jax.random.PRNGKey(3)

    def fwd(mdl, p, b, r, c):
        return zoo.forward_train(mdl, p, b, r, c)

    s1 = create_train_state(params, tx)
    f1 = make_train_step(model, cfg, forward_fn=fwd, donate=False)
    s1b, m1 = f1(s1, batch, key)

    mesh = create_mesh("2")
    s2 = create_train_state(params, tx)
    f2 = make_train_step(model, cfg, mesh=mesh, forward_fn=fwd, donate=False)
    s2b, m2 = f2(s2, shard_batch(batch, mesh), key)

    assert np.isclose(float(m1["TotalLoss"]), float(m2["TotalLoss"]),
                      rtol=1e-4)
    l1 = jax.tree.leaves(s1b.params)
    l2 = jax.tree.leaves(s2b.params)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-5)


def test_train_step_defaults_to_the_family_forward(rng):
    """``make_train_step(model, cfg)`` with no ``forward_fn`` lowers the
    pyramid step (the default is models/zoo.py's dispatcher, not the C4
    forward), and its metrics carry the sampled rois' level shares."""
    from mx_rcnn_tpu.parallel.mesh import create_mesh
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    cfg = tiny_cfg()
    model = zoo.build_model(cfg)
    mesh = create_mesh("1")
    step = make_train_step(model, cfg, mesh=mesh, donate=False)
    args = abstract_step_inputs(model, cfg, mesh, 1)
    assert "neck" in step.lower(*args).as_text()
    _, metrics = jax.eval_shape(step, *args)
    assert metrics["RoiLevelShare"].shape == (len(F.ROI_LEVELS),)
    assert metrics["TotalLoss"].shape == ()


def test_pack_placements_gaps_and_bounds():
    """Shelf packing: every rectangle in bounds, pairwise >=1px separated."""
    shapes = [(40, 64), (20, 32), (10, 16), (5, 8), (3, 4)]
    (hc, wc), places = F.pack_placements(shapes)
    assert wc == 64
    rects = []
    for (h, w), (y, x, ph, pw) in zip(shapes, places):
        assert (ph, pw) == (h, w)
        assert 0 <= y and y + h <= hc and 0 <= x and x + w <= wc
        rects.append((y, x, h, w))
    for i in range(len(rects)):
        for j in range(i + 1, len(rects)):
            yi, xi, hi, wi = rects[i]
            yj, xj, hj, wj = rects[j]
            # grow rect i by the 1px gap; it must not intersect rect j
            sep = (yi + hi + 1 <= yj or yj + hj + 1 <= yi
                   or xi + wi + 1 <= xj or xj + wj + 1 <= xi)
            assert sep, (rects[i], rects[j])


def test_pack_levels_roundtrip(rng):
    """Canvas slices reproduce the packed tensors; gaps are zero."""
    shapes = [(16, 32), (8, 16), (4, 8)]
    tensors = [jnp.asarray(rng.randn(2, h, w, 3), jnp.float32)
               for h, w in shapes]
    canvas, places = F.pack_levels(tensors)
    total = 0.0
    for t, (y, x, h, w) in zip(tensors, places):
        np.testing.assert_array_equal(
            np.asarray(canvas[:, y:y + h, x:x + w, :]), np.asarray(t))
        total += float(jnp.sum(jnp.abs(t)))
    assert np.isclose(float(jnp.sum(jnp.abs(canvas))), total, rtol=1e-6)


def test_rpn_forward_packed_matches_per_level(rng):
    """The fused one-canvas head application == five per-level applications
    (same params; 3x3 SAME borders see zeros either way)."""
    cfg = tiny_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    images = jnp.asarray(rng.randn(1, 128, 128, 3), jnp.float32)
    pyramid = jax.jit(
        lambda p, im: model.apply(p, im, method="extract"))(params, images)
    per_level = jax.jit(lambda p, pyr: model.apply(
        p, pyr, method="rpn_forward"))(params, pyramid)
    packed = jax.jit(lambda p, pyr: model.apply(
        p, pyr, method="rpn_forward_packed"))(params, pyramid)
    for lv in F.RPN_LEVELS:
        for a, b in zip(per_level[lv], packed[lv]):
            assert a.shape == b.shape, lv
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-2, atol=2e-3)


def test_forward_train_packed_vs_unpacked_rpn(rng):
    """End-to-end train loss with the packed head == per-level head."""
    from dataclasses import replace

    cfg = tiny_cfg()
    assert cfg.network.fpn_packed_rpn_head  # default on
    cfg_off = cfg.with_updates(network=replace(
        cfg.network, fpn_packed_rpn_head=False))
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    key = jax.random.PRNGKey(1)
    loss_on, _ = jax.jit(
        lambda p, b, r: zoo.forward_train(model, p, b, r, cfg)
    )(params, batch, key)
    loss_off, _ = jax.jit(
        lambda p, b, r: zoo.forward_train(model, p, b, r, cfg_off)
    )(params, batch, key)
    np.testing.assert_allclose(float(loss_on), float(loss_off),
                               rtol=1e-4, atol=1e-5)


def test_packed_head_requires_spatial_radius():
    """apply_rpn_head_packed sizes its inter-level gap from the head's
    declared SPATIAL_RADIUS (1 for RPNHead's single 3x3 conv); a head
    class that declares none fails loudly instead of silently leaking
    activations across packed levels (advisor r5)."""
    from mx_rcnn_tpu.models.rpn import RPNHead

    assert RPNHead.SPATIAL_RADIUS == 1

    class NoRadiusHead:
        def __call__(self, x):
            return x, x

    pyramid = {lv: jnp.zeros((1, 4, 4, 8)) for lv in F.RPN_LEVELS}
    with pytest.raises(ValueError, match="SPATIAL_RADIUS"):
        F.apply_rpn_head_packed(NoRadiusHead(), pyramid)
