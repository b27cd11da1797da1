"""graftcanvas — whole-batch canvas packing (data/canvas.py, ops/canvas.py,
the packed loader path and the packed model forwards).

The three acceptance gates of the feature, all on CPU:
- packed forward == per-image bucketed forward (loss rtol well under 1e-4
  for C4 and FPN — in fact f32-rounding-level, because placement masking
  reproduces the bucketed canvas-edge zero padding exactly);
- border isolation: no proposal crosses a placement border;
- compile collapse: a multi-scale config trains through ONE compiled
  train-step shape (the orientation x scale pad-bucket zoo is gone).

Budget notes: module-scope model/params fixtures, numpy perturbation,
64-128 px shapes, tiny proposal budgets (memory: tier-1 is budget-bound).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytestmark = pytest.mark.compile_heavy

from mx_rcnn_tpu.config import Config, ImageConfig, generate_config
from mx_rcnn_tpu.data import canvas as dcanvas
from mx_rcnn_tpu.data.loader import AnchorLoader, ROIIter
from mx_rcnn_tpu.models import faster_rcnn as c4
from mx_rcnn_tpu.models import fpn as F
from mx_rcnn_tpu.obs import compile_track
from mx_rcnn_tpu.ops.anchors import anchor_grid
from mx_rcnn_tpu.ops.proposal import generate_proposals_packed


# ---------------------------------------------------------------------------
# Planner / config contract (pure host)
# ---------------------------------------------------------------------------


def test_plan_plane_aligned_and_separated():
    offs = dcanvas.plan_plane([(64, 96), (96, 128)], (192, 128),
                              gap=16, align=16)
    assert offs is not None
    for (y, x) in offs:
        assert y % 16 == 0 and x % 16 == 0
    # FFD puts the taller rect first; both fit with a >= gap separation.
    (y0, x0), (y1, x1) = offs
    assert {(y0, x0), (y1, x1)} == {(112, 0), (0, 0)}
    # overflow → None
    assert dcanvas.plan_plane([(160, 96), (96, 96)], (192, 128),
                              gap=16, align=16) is None


def test_plan_batch_scale_to_fit_and_hopeless():
    spec = dcanvas.CanvasSpec((128, 128), gap=16, align=16, images=2)

    def sizes_at(fit):
        return [(int(100 * fit), int(100 * fit)),
                (int(100 * fit), int(100 * fit))]

    placements, fit, sizes = dcanvas.plan_batch(sizes_at, 2, spec)
    assert fit < 1.0  # two 100px squares cannot share a 128px canvas
    assert len(placements) == 2
    for (pl, y, x), (h, w) in zip(placements, sizes):
        assert pl == 0 and y + h <= 128 and x + w <= 128
    # a canvas that can never fit raises with the real cause
    tiny = dcanvas.CanvasSpec((16, 16), gap=16, align=16, images=1)
    with pytest.raises(ValueError, match="mis-sized"):
        dcanvas.plan_batch(lambda f: [(400, 400)], 1, tiny)


def _canvas_cfg(net="resnet50", **over):
    base = {
        "image.scales": ((64, 96),),
        "image.pad_shape": (64, 96),
        "image.canvas_pack": True,
        "image.canvas_shape": (160, 96),
        "image.canvas_images": 2,
        "train.batch_images": 2,
    }
    base.update(over)
    return generate_config(net, "synthetic", **base)


def test_validate_accepts_groupnorm_from_scratch():
    """Regression: --from-scratch flips norm to GroupNorm — canvas_pack's
    validate must ACCEPT it (canvas-pooled stats are the same
    approximation class as the zero padding already in the bucketed
    GroupNorm stats), not refuse the whole from-scratch profile."""
    cfg = _canvas_cfg(**{"network.norm": "group", "network.freeze_at": 0})
    spec = dcanvas.validate_canvas_pack(cfg)
    assert spec.shape == (160, 96) and spec.images == 2
    # ...and the loader (which validates on construction) builds too.
    loader = AnchorLoader(_mixed_roidb(4), cfg, num_shards=1)
    assert loader._canvas_spec is not None


def test_validate_rejections():
    with pytest.raises(ValueError, match="DETR"):
        dcanvas.validate_canvas_pack(
            _canvas_cfg("detr_r50", **{"image.canvas_shape": (192, 96)}))
    with pytest.raises(ValueError, match="multiple"):
        dcanvas.validate_canvas_pack(
            _canvas_cfg(**{"image.canvas_shape": (150, 96)}))
    with pytest.raises(ValueError, match="short side"):
        dcanvas.validate_canvas_pack(
            _canvas_cfg(**{"image.canvas_shape": (32, 32)}))
    with pytest.raises(ValueError, match="positive multiple"):
        # -16 % 16 == 0: without the sign check a negative gap would
        # validate and the planner would emit OVERLAPPING placements
        dcanvas.validate_canvas_pack(_canvas_cfg(**{"image.canvas_gap": -16}))
    with pytest.raises(NotImplementedError, match="ROIIter"):
        ROIIter(_mixed_roidb(4), _canvas_cfg(), num_shards=1)


# ---------------------------------------------------------------------------
# Packed loader (host assembly + pad counters)
# ---------------------------------------------------------------------------


def _mixed_roidb(n):
    """Landscape-ish mixed-size synthetic entries, content well below the
    square pad bucket — the measured-pad-waste shape of the ROADMAP item."""
    rs = np.random.RandomState(0)
    dims = [(48, 80), (64, 96), (48, 96), (56, 88)]
    out = []
    for i in range(n):
        h, w = dims[i % len(dims)]
        out.append({
            "image_data": rs.uniform(0, 255, (h, w, 3)).astype(np.float32),
            "height": h, "width": w,
            "boxes": np.asarray([[4.0, 4.0, w // 2, h // 2]], np.float32),
            "gt_classes": np.asarray([1 + i % 3], np.int32),
        })
    return out


def _loader_cfg(packed: bool):
    over = {
        "image.scales": ((48, 96),),
        "image.pad_shape": (96, 96),
        "train.batch_images": 2,
        "train.max_gt_boxes": 4,
        "train.shuffle": False,
    }
    if packed:
        over.update({"image.canvas_pack": True,
                     "image.canvas_shape": (128, 96),
                     "image.canvas_images": 2})
    return generate_config("resnet50", "synthetic", **over)


def test_packed_loader_batch_contract():
    cfg = _loader_cfg(packed=True)
    with AnchorLoader(_mixed_roidb(4), cfg, num_shards=1) as loader:
        batch = next(iter(loader))
    assert batch["image"].shape == (1, 128, 96, 3)
    assert batch["im_info"].shape == (1, 2, 5)
    assert batch["gt_boxes"].shape == (1, 2, 4, 4)
    for slot in range(2):
        h, w, scale, y0, x0 = batch["im_info"][0, slot]
        assert y0 % 16 == 0 and x0 % 16 == 0
        assert y0 + h <= 128 and x0 + w <= 96
        assert scale > 0
        # gt boxes live inside the placement rect (canvas coordinates)
        gtb = batch["gt_boxes"][0, slot][batch["gt_valid"][0, slot]]
        assert np.all(gtb[:, 0] >= x0) and np.all(gtb[:, 1] >= y0)
        assert np.all(gtb[:, 2] <= x0 + w) and np.all(gtb[:, 3] <= y0 + h)
    # placements are disjoint and gap pixels are exactly zero
    m = np.zeros((128, 96), np.int32)
    for slot in range(2):
        h, w, _, y0, x0 = batch["im_info"][0, slot].astype(int)
        m[y0:y0 + h, x0:x0 + w] += 1
    assert m.max() == 1
    assert np.all(batch["image"][0][m == 0] == 0.0)


def test_packed_pad_waste_below_bucketed():
    """Acceptance: on the same mixed-size roidb the packed loader's
    measured canvas waste is below the bucketed loader's bucket waste."""
    roidb = _mixed_roidb(8)
    with AnchorLoader(roidb, _loader_cfg(False), num_shards=1) as lb:
        for _ in lb:
            pass
        bucketed = lb.pad_waste_stats()
    with AnchorLoader(roidb, _loader_cfg(True), num_shards=1) as lp:
        for _ in lp:
            pass
        packed = lp.pad_waste_stats()
    assert bucketed is not None and packed is not None
    assert packed["pad_waste"] < bucketed["pad_waste"] - 0.05
    # graftprof's batch accountant agrees with the loader's counters on
    # the packed contract (planes counted once, not per im_info row)
    from mx_rcnn_tpu.obs.costs import batch_pad_waste

    cfg = _loader_cfg(True)
    with AnchorLoader(roidb, cfg, num_shards=1) as lp2:
        batch = next(iter(lp2))
    pw = batch_pad_waste(batch)
    assert pw["canvas_px"] == 128 * 96  # ONE plane
    assert 0.0 < pw["pad_waste"] < 1.0


# ---------------------------------------------------------------------------
# Border isolation (packed proposals)
# ---------------------------------------------------------------------------


def test_packed_proposals_stay_inside_placements():
    rs = np.random.RandomState(3)
    anchors = jnp.asarray(anchor_grid(10, 6, stride=16, base_size=16,
                                      ratios=(0.5, 1.0, 2.0), scales=(2, 4)))
    n = anchors.shape[0]
    # two images in one plane: rects (64x96 @ 0,0) and (64x96 @ 96,0)
    info = jnp.asarray([[64, 96, 1.0, 0, 0], [64, 96, 1.0, 96, 0]],
                       jnp.float32)
    scores = jnp.asarray(rs.uniform(size=(2, n)), jnp.float32)
    deltas = jnp.asarray(rs.normal(0, 0.5, (2, n, 4)), jnp.float32)
    rois, valid, _ = generate_proposals_packed(
        scores, deltas, info, anchors, pre_nms_top_n=128,
        post_nms_top_n=32, nms_thresh=0.7, min_size=4)
    rois, valid = np.asarray(rois), np.asarray(valid)
    assert valid.any()
    for i, (h, w, _, y0, x0) in enumerate(np.asarray(info)):
        r = rois[i][valid[i]]
        assert len(r)
        assert np.all(r[:, 0] >= x0) and np.all(r[:, 2] <= x0 + w - 1)
        assert np.all(r[:, 1] >= y0) and np.all(r[:, 3] <= y0 + h - 1)


def test_fpn_packed_proposals_stay_inside_placements():
    rs = np.random.RandomState(4)
    cfg = generate_config("resnet50_fpn", "synthetic", **{
        "image.scales": ((64, 128),), "image.pad_shape": (64, 128),
        "network.anchor_scales": (2,), "network.proposal_topk": "exact",
        "train.fpn_rpn_pre_nms_per_level": 64,
        "train.rpn_post_nms_top_n": 16,
    })
    shapes = {lv: (256 // 2 ** lv, 128 // 2 ** lv) for lv in F.RPN_LEVELS}
    anchors = F.pyramid_anchors(shapes, cfg)
    rpn_out = {}
    for lv, (h, w) in shapes.items():
        rpn_out[lv] = (
            jnp.asarray(rs.normal(0, 1, (1, h, w, 6)), jnp.float32),
            jnp.asarray(rs.normal(0, 0.5, (1, h, w, 12)), jnp.float32))
    info = jnp.asarray([[64, 128, 1.0, 0, 0], [64, 128, 1.0, 128, 0]],
                       jnp.float32)
    plane_of = jnp.zeros((2,), jnp.int32)
    rois, valid, _ = F.fpn_proposals_packed(rpn_out, anchors, info,
                                            plane_of, cfg, train=True)
    rois, valid = np.asarray(rois), np.asarray(valid)
    assert valid.any()
    for i, (h, w, _, y0, x0) in enumerate(np.asarray(info)):
        r = rois[i][valid[i]]
        assert np.all(r[:, 0] >= x0) and np.all(r[:, 2] <= x0 + w - 1)
        assert np.all(r[:, 1] >= y0) and np.all(r[:, 3] <= y0 + h - 1)


# ---------------------------------------------------------------------------
# Exactness: packed forward == bucketed forward (C4 + FPN)
# ---------------------------------------------------------------------------


def _perturb(params, seed=1, sigma=0.02):
    """Numpy param perturbation (per-leaf jax.random costs seconds on
    big trees). Exactness holds for ARBITRARY frozen-BN parameters —
    placements see implicit-zero boundaries exactly like bucket edges —
    so every leaf is perturbed, norms included."""
    rs = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x) + rs.normal(0, sigma, x.shape)
        .astype(x.dtype), params)


def _pair_batches(hw, align):
    """Two same-bucket images + their packed single-plane counterpart.
    Content fills the bucket exactly, so the bucketed forward has no pad
    cells — the geometry where packed == bucketed is provable (and
    gated here) bit-for-bit; mixed-size placements are covered by the
    border-isolation tests above."""
    h, w = hw
    g = 8
    rs = np.random.RandomState(2)
    imgs = rs.randn(2, h, w, 3).astype(np.float32)
    gtb = np.zeros((2, g, 4), np.float32)
    gtb[0, :2] = [[10, 10, w - 45, h - 20], [40, 20, w - 5, h - 4]]
    gtb[1, :2] = [[5, 8, 30, 30], [w // 2, h // 2, w - 8, h - 6]]
    gtc = np.zeros((2, g), np.int32)
    gtc[:, :2] = [[1, 2], [2, 1]]
    gtv = np.zeros((2, g), bool)
    gtv[:, :2] = True
    bucketed = {
        "image": imgs,
        "im_info": np.asarray([[h, w, 1.0]] * 2, np.float32),
        "gt_boxes": gtb, "gt_classes": gtc, "gt_valid": gtv,
    }
    off = dcanvas.align_up(h + align, align)
    canvas = np.zeros((1, off + dcanvas.align_up(h, align), w, 3),
                      np.float32)
    canvas[0, :h] = imgs[0]
    canvas[0, off:off + h] = imgs[1]
    info = np.zeros((1, 2, 5), np.float32)
    info[0, 0] = (h, w, 1.0, 0, 0)
    info[0, 1] = (h, w, 1.0, off, 0)
    gtb_p = gtb.copy()
    gtb_p[1, :, 1] += off
    gtb_p[1, :, 3] += off
    packed = {
        "image": canvas, "im_info": info, "gt_boxes": gtb_p[None],
        "gt_classes": gtc[None], "gt_valid": gtv[None],
    }
    return bucketed, packed


@pytest.fixture(scope="module")
def c4_cfg():
    return _canvas_cfg(**{
        "train.compute_dtype": "f32",
        "network.anchor_scales": (2, 4),
        "train.rpn_batch_size": 1024,  # keep-all: neutralizes the anchor
        # subsample's grid-size-dependent uniform draws (canvas grid !=
        # bucket grid); everything downstream is then bit-comparable.
        "train.rpn_pre_nms_top_n": 300,
        "train.rpn_post_nms_top_n": 32,
        "train.batch_rois": 32,
        "train.max_gt_boxes": 8,
    })


@pytest.fixture(scope="module")
def c4_model_params(c4_cfg):
    model = c4.build_model(c4_cfg)
    params = _perturb(c4.init_params(model, c4_cfg, jax.random.PRNGKey(0)))
    return model, params


def test_packed_matches_bucketed_c4(c4_cfg, c4_model_params):
    model, params = c4_model_params
    bucketed, packed = _pair_batches((64, 96), align=16)
    rng = jax.random.PRNGKey(7)
    fwd = jax.jit(lambda p, b, r: c4.forward_train(model, p, b, r, c4_cfg))
    lb, auxb = fwd(params, bucketed, rng)
    lp, auxp = fwd(params, packed, rng)
    assert float(auxb["rpn_cls_loss"]) > 0  # live RPN targets, not a 0==0
    np.testing.assert_allclose(float(lb), float(lp), rtol=1e-4)
    for k in ("rpn_cls_loss", "rpn_bbox_loss",
              "rcnn_cls_loss", "rcnn_bbox_loss"):
        np.testing.assert_allclose(float(auxb[k]), float(auxp[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)


@pytest.fixture(scope="module")
def fpn_cfg():
    return generate_config("resnet50_fpn", "synthetic", **{
        "image.scales": ((64, 128),),
        "image.pad_shape": (64, 128),
        "image.pad_shapes": (),
        "image.canvas_pack": True,
        "image.canvas_shape": (256, 128),
        "image.canvas_images": 2,
        "train.compute_dtype": "f32",
        "network.anchor_scales": (2,),
        "network.proposal_topk": "exact",  # approx_max_k membership is
        # grid-size-dependent; exactness needs the deterministic top-k
        "train.batch_images": 2,
        "train.rpn_batch_size": 4096,
        "train.fpn_rpn_pre_nms_per_level": 128,
        "train.rpn_post_nms_top_n": 32,
        "train.batch_rois": 32,
        "train.max_gt_boxes": 8,
    })


def test_packed_matches_bucketed_fpn(fpn_cfg):
    model = F.build_fpn_model(fpn_cfg)
    params = _perturb(F.init_fpn_params(model, fpn_cfg,
                                        jax.random.PRNGKey(0)))
    bucketed, packed = _pair_batches((64, 128), align=64)
    rng = jax.random.PRNGKey(7)
    fwd = jax.jit(lambda p, b, r: F.forward_train(model, p, b, r, fpn_cfg))
    lb, auxb = fwd(params, bucketed, rng)
    lp, auxp = fwd(params, packed, rng)
    assert float(auxb["rpn_cls_loss"]) > 0
    np.testing.assert_allclose(float(lb), float(lp), rtol=1e-4)
    for k in ("rpn_cls_loss", "rpn_bbox_loss",
              "rcnn_cls_loss", "rcnn_bbox_loss"):
        np.testing.assert_allclose(float(auxb[k]), float(auxp[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)


# ---------------------------------------------------------------------------
# Compile collapse: one train-step shape across the scale zoo
# ---------------------------------------------------------------------------


def test_multiscale_canvas_single_compiled_shape(c4_cfg, c4_model_params):
    """Two scale buckets, orientation-mixed roidb — the bucketed loader
    would compile one step per (scale x orientation) bucket; the packed
    loader feeds ONE canvas shape, so the whole multi-scale stream runs
    through a single compiled train step (compile_track.count())."""
    from mx_rcnn_tpu.parallel.mesh import (create_mesh, place_replicated,
                                           shard_batch)
    from mx_rcnn_tpu.train.optimizer import build_optimizer
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step

    cfg = _canvas_cfg(**{
        "image.scales": ((48, 96), (64, 96)),
        "image.pad_shapes": (),
        "image.canvas_shape": (160, 96),
        "train.compute_dtype": "f32",
        "network.anchor_scales": (2, 4),
        "train.rpn_pre_nms_top_n": 64,
        "train.rpn_post_nms_top_n": 16,
        "train.batch_rois": 16,
        "train.max_gt_boxes": 4,
        "train.shuffle": False,
    })
    roidb = _mixed_roidb(8)
    with AnchorLoader(roidb, cfg, num_shards=1, seed=0) as loader:
        loader.set_epoch(0)
        batches = list(loader)
    # multi-scale draw happened, yet every batch has the ONE canvas shape
    shapes = {tuple(b["image"].shape) for b in batches}
    assert shapes == {(1, 160, 96, 3)}
    scales = {round(float(b["im_info"][0, 0, 2]), 3) for b in batches}
    assert len(scales) > 1  # genuinely different scale draws
    # ...while the BUCKETED loader over the same roidb/scales feeds the
    # shape zoo this feature collapses (>= one bucket per scale draw).
    bcfg = cfg.with_updates(image=ImageConfig(
        scales=cfg.image.scales, pad_shape=(96, 96),
        pad_shapes=((48, 96), (64, 96))))
    with AnchorLoader(roidb, bcfg, num_shards=1, seed=0) as bl:
        bl.set_epoch(0)
        bucket_shapes = {tuple(b["image"].shape) for b in bl}
    assert len(bucket_shapes) > 1

    model, params = c4_model_params  # same tree; cfg drives the forward
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    mesh = create_mesh("1")
    # fit_detector's placement: state on the mesh before the first step
    state = place_replicated(create_train_state(params, tx), mesh)
    step_fn = make_train_step(model, cfg, mesh=mesh, donate=False)
    # Two dispatches cover both scale draws (seed-0 order starts 0, 1);
    # the remaining batches add no coverage, only tier-1 wall time.
    two = [batches[0], next(b for b in batches[1:]
                            if float(b["im_info"][0, 0, 2])
                            != float(batches[0]["im_info"][0, 0, 2]))]
    with compile_track.count() as cc:
        for i, batch in enumerate(two):
            sharded = shard_batch(batch, mesh)
            state, metrics = step_fn(state, sharded,
                                     jax.random.PRNGKey(10 + i))
        float(np.asarray(metrics["TotalLoss"]))
    # ONE train-step program for the whole multi-scale stream — never one
    # per scale bucket, and never a second one for the step's own output
    # coming back as input: jax 0.9 types an array by its mesh, so state
    # that is NOT placed on the mesh first (place_replicated) is traced
    # and compiled twice. Counted by name: helpers that jax compiles
    # around the step are not train steps.
    assert cc.count_of("jit(step)") == 1, cc.programs
    assert step_fn._cache_size() == 1
