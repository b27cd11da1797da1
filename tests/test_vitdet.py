"""ViTDet (models/vit.py): backbone, SFP, detector forwards, ring option.

BASELINE.json config 5 (stretch). The reference has no transformer models
(SURVEY.md §3.2); semantics follow Li et al. (ViTDet) as documented in the
module. The detector reuses the fpn.py functional forwards via the shared
pyramid method surface (models/zoo.py duck dispatch).
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.compile_heavy

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import zoo
from mx_rcnn_tpu.models.vit import SimpleFeaturePyramid, ViTBackbone, ViTDet
from mx_rcnn_tpu.parallel.mesh import create_mesh


def tiny_cfg(mask=False, **overrides):
    base = {
        "image.pad_shape": (128, 128),
        "train.batch_images": 1,
        "network.vit_dim": 32,
        "network.vit_depth": 2,
        "network.vit_heads": 2,
        "network.vit_window": 4,
        "train.fpn_rpn_pre_nms_per_level": 64,
        "train.rpn_post_nms_top_n": 64,
        "train.batch_rois": 32,
        "train.max_gt_boxes": 8,
        "train.mask_gt_resolution": 28,
        "test.fpn_rpn_pre_nms_per_level": 32,
        "test.rpn_post_nms_top_n": 16,
    }
    base.update(overrides)
    return generate_config("vitdet_b_mask" if mask else "vitdet_b",
                           "synthetic", **base)


def tiny_batch(rng, mask=False):
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
        gm = np.zeros((1, 8, 28, 28), np.uint8)
        gm[0, :2, 6:22, 6:22] = 1
        batch["gt_masks"] = gm
    return batch


def test_backbone_shapes_and_window_padding(rng):
    # 80x112 image -> 5x7 token grid: not divisible by window 4, exercises
    # the window pad/unpad path.
    vit = ViTBackbone(patch=16, dim=32, depth=2, heads=2, window=4,
                      dtype=jnp.float32)
    x = jnp.asarray(rng.randn(1, 80, 112, 3).astype(np.float32))
    params = vit.init(jax.random.PRNGKey(0), x)
    out = vit.apply(params, x)
    assert out.shape == (1, 5, 7, 32)
    assert np.isfinite(np.asarray(out)).all()


def test_sfp_levels(rng):
    sfp = SimpleFeaturePyramid(channels=16, dtype=jnp.float32)
    feat = jnp.asarray(rng.randn(1, 8, 8, 32).astype(np.float32))
    params = sfp.init(jax.random.PRNGKey(0), feat)
    out = sfp.apply(params, feat)
    assert set(out.keys()) == {2, 3, 4, 5, 6}
    assert out[2].shape == (1, 32, 32, 16)
    assert out[3].shape == (1, 16, 16, 16)
    assert out[4].shape == (1, 8, 8, 16)
    assert out[5].shape == (1, 4, 4, 16)
    assert out[6].shape == (1, 2, 2, 16)


def test_forward_train_and_test(rng):
    cfg = tiny_cfg()
    model = zoo.build_model(cfg)
    assert isinstance(model, ViTDet)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    loss, aux = jax.jit(
        lambda p, b, r: zoo.forward_train(model, p, b, r, cfg)
    )(params, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    rois, rv, scores, boxes = jax.jit(
        lambda p, i, ii: zoo.forward_test(model, p, i, ii, cfg)
    )(params, batch["image"], batch["im_info"])
    r, c = cfg.test.rpn_post_nms_top_n, cfg.dataset.num_classes
    assert rois.shape == (1, r, 4)
    assert scores.shape == (1, r, c)
    assert boxes.shape == (1, r, 4 * c)


def test_grads_reach_vit(rng):
    cfg = tiny_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    grads = jax.jit(jax.grad(
        lambda p: zoo.forward_train(model, p, batch,
                                    jax.random.PRNGKey(1), cfg)[0]))(params)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]

    def norm_of(substr):
        return sum(float(jnp.sum(jnp.abs(leaf)))
                   for path, leaf in flat
                   if substr in jax.tree_util.keystr(path))

    for part in ("patch_embed", "block0", "block1", "neck", "rpn",
                 "cls_score"):
        assert norm_of(part) > 0, f"no gradient reached {part}"


def test_mask_variant(rng):
    cfg = tiny_cfg(mask=True)
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(rng, mask=True)
    loss, aux = jax.jit(
        lambda p, b, r: zoo.forward_train(model, p, b, r, cfg)
    )(params, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    assert np.isfinite(float(aux["mask_loss"]))


def test_ring_attention_matches_dense(rng):
    """ViTDet with ring attention over a 4-way model axis == dense."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = tiny_cfg(**{"network.use_ring_attention": True})
    mesh = create_mesh("1x4")
    model_ring = zoo.build_model(cfg, mesh=mesh)
    cfg_dense = cfg.with_updates(
        network=replace(cfg.network, use_ring_attention=False))
    model_dense = zoo.build_model(cfg_dense)
    params = zoo.init_params(model_dense, cfg_dense, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    key = jax.random.PRNGKey(1)
    l_ring, _ = jax.jit(lambda p, b, r: zoo.forward_train(
        model_ring, p, b, r, cfg))(params, batch, key)
    l_dense, _ = jax.jit(lambda p, b, r: zoo.forward_train(
        model_dense, p, b, r, cfg_dense))(params, batch, key)
    assert np.isclose(float(l_ring), float(l_dense), rtol=1e-4)


def test_train_step_under_dp_mesh(rng):
    """One ViTDet train step over a 2-way data mesh (the dryrun shape)."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    from mx_rcnn_tpu.parallel.mesh import shard_batch
    from mx_rcnn_tpu.train.optimizer import build_optimizer
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step

    cfg = tiny_cfg(**{"train.batch_images": 2})
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    state = create_train_state(params, tx)
    mesh = create_mesh("2")
    step = make_train_step(model, cfg, mesh=mesh,
                           forward_fn=zoo.forward_train, donate=False)
    one = tiny_batch(rng)
    batch = {k: np.repeat(v, 2, axis=0) for k, v in one.items()}
    state, metrics = step(state, shard_batch(batch, mesh),
                          jax.random.PRNGKey(3))
    assert np.isfinite(float(metrics["TotalLoss"]))


def test_window_block_nondivisible_grid(rng):
    """Window attention pads a 5x7 grid to 8x8 windows and unpads exactly;
    small depths make every BACKBONE block global, so the window path is
    pinned here at the Block level."""
    from mx_rcnn_tpu.models.vit import Block

    blk = Block(dim=16, heads=2, window=4, dtype=jnp.float32)
    x = jnp.asarray(rng.randn(2, 5, 7, 16).astype(np.float32))
    params = blk.init(jax.random.PRNGKey(0), x)
    out = blk.apply(params, x)
    assert out.shape == (2, 5, 7, 16)
    assert np.isfinite(np.asarray(out)).all()


def test_global_block_pattern_vitb():
    """Depth 12 → globals end each quarter: blocks 2, 5, 8, 11 (ViTDet)."""
    depth = 12
    global_blocks = {depth * k // 4 - 1 for k in range(1, 5)}
    assert global_blocks == {2, 5, 8, 11}


def test_ulysses_attention_matches_dense(rng):
    """ViTDet with all-to-all (Ulysses) SP over a 2-way model axis ==
    dense (network.sp_mode='ulysses'; tiny_cfg has 2 heads, so the axis
    size must divide 2)."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    # float32 end-to-end: the Ulysses op is exact, but bf16 attention
    # rounding can flip discrete top-k/NMS selections on some platforms,
    # making an rtol comparison of the post-selection losses flaky.
    cfg = tiny_cfg(**{"network.use_ring_attention": True,
                      "network.sp_mode": "ulysses",
                      "train.compute_dtype": "f32"})
    mesh = create_mesh("1x2")
    model_sp = zoo.build_model(cfg, mesh=mesh)
    cfg_dense = cfg.with_updates(
        network=replace(cfg.network, use_ring_attention=False,
                        sp_mode="ring"))
    model_dense = zoo.build_model(cfg_dense)
    params = zoo.init_params(model_dense, cfg_dense, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    key = jax.random.PRNGKey(1)
    l_sp, _ = jax.jit(lambda p, b, r: zoo.forward_train(
        model_sp, p, b, r, cfg))(params, batch, key)
    l_dense, _ = jax.jit(lambda p, b, r: zoo.forward_train(
        model_dense, p, b, r, cfg_dense))(params, batch, key)
    assert np.isclose(float(l_sp), float(l_dense), rtol=1e-4)


def test_streaming_attn_impl_matches_dense(rng):
    """network.attn_impl='streaming' routes the global blocks through the
    flash-style streaming-softmax kernel with identical numerics (r5; a
    small kv_chunk forces a real multi-block scan at tiny token counts)."""
    cfg = tiny_cfg(**{"network.attn_impl": "streaming",
                      "network.attn_kv_chunk": 8})
    model_s = zoo.build_model(cfg)
    cfg_d = cfg.with_updates(
        network=replace(cfg.network, attn_impl="dense"))
    model_d = zoo.build_model(cfg_d)
    params = zoo.init_params(model_d, cfg_d, jax.random.PRNGKey(0))
    batch = tiny_batch(rng)
    key = jax.random.PRNGKey(1)
    l_s, _ = jax.jit(lambda p, b, r: zoo.forward_train(
        model_s, p, b, r, cfg))(params, batch, key)
    l_d, _ = jax.jit(lambda p, b, r: zoo.forward_train(
        model_d, p, b, r, cfg_d))(params, batch, key)
    assert np.isclose(float(l_s), float(l_d), rtol=1e-4), (l_s, l_d)


@pytest.mark.parametrize("value", ["dense", "streaming", "stream"])
def test_attn_impl_is_validated(value):
    """network.attn_impl outside {'dense','streaming'} fails at build
    time for every family (mirrors the sp_mode validation) instead of
    being silently treated as dense (advisor r5): a typo one letter
    short of a valid value is refused, the two valid values build."""
    cfg = generate_config("resnet50", "synthetic",
                          **{"network.attn_impl": value})
    if value == "stream":
        with pytest.raises(ValueError, match="attn_impl"):
            zoo.build_model(cfg)
    else:
        assert zoo.build_model(cfg) is not None


def test_attn_impl_streaming_superseded_by_sp_warns(caplog):
    """'streaming' + a sequence-parallel build: the SP kernels manage
    their own attention, so the knob is accepted with a supersede
    warning (mirrors the pp_stages warning)."""
    import logging

    cfg = tiny_cfg(**{"network.attn_impl": "streaming",
                      "network.use_ring_attention": True})
    mesh = create_mesh("1x2")
    with caplog.at_level(logging.WARNING, logger="mx_rcnn_tpu"):
        zoo.build_model(cfg, mesh=mesh)
    assert any("superseded" in r.getMessage() for r in caplog.records)
