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


def _explicit_attention(q, k, v, rel_h_table, rel_w_table, gh, gw):
    """Attention with the decomposed bias built as an explicit N x N matrix
    from the tables, loop by loop: bias[(ih, iw), (jh, jw)] = q_i .
    R_h[ih - jh + gh - 1] + q_i . R_w[iw - jw + gw - 1]. numpy, float64."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    rh, rw = (np.asarray(t, np.float64) for t in (rel_h_table, rel_w_table))
    b, n, h, d = q.shape
    out = np.zeros_like(q)
    for bi in range(b):
        for hi in range(h):
            bias = np.zeros((n, n))
            for i in range(n):
                for j in range(n):
                    ih, iw, jh, jw = i // gw, i % gw, j // gw, j % gw
                    bias[i, j] = (q[bi, i, hi] @ rh[ih - jh + gh - 1]
                                  + q[bi, i, hi] @ rw[iw - jw + gw - 1])
            s = q[bi, :, hi] @ k[bi, :, hi].T / np.sqrt(d) + bias
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[bi, :, hi] = p @ v[bi, :, hi]
    return out


@pytest.mark.parametrize("grid", [(4, 4), (3, 5)])
def test_decomposed_bias_is_the_explicit_one(grid, rng):
    """Dense attention with the decomposed relative-position bias, and the
    streaming form over key blocks of 4 (grid 3x5: a padded tail), against
    the explicit N x N bias of the tables: 1e-5 (float32 against
    float64)."""
    from mx_rcnn_tpu.models.vit import decomposed_rel_pos
    from mx_rcnn_tpu.ops.ring_attention import (dense_attention,
                                                streaming_attention)

    gh, gw = grid
    q, k, v = (jnp.asarray(rng.randn(2, gh * gw, 2, 8), jnp.float32)
               for _ in range(3))
    th = jnp.asarray(rng.randn(2 * gh - 1, 8), jnp.float32)
    tw = jnp.asarray(rng.randn(2 * gw - 1, 8), jnp.float32)
    want = _explicit_attention(q, k, v, th, tw, gh, gw)
    rel = decomposed_rel_pos(q, th, tw, grid)
    for got in (dense_attention(q, k, v, rel=rel),
                streaming_attention(q, k, v, kv_chunk=4, rel=rel)):
        np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-5)
    plain = dense_attention(q, k, v)
    assert np.abs(np.asarray(plain) - want).max() > 1e-2


def _vitdet_abstract(network="vitdet_b_mask"):
    cfg = generate_config(network, "coco")
    model = zoo.build_model(cfg)
    return cfg, jax.eval_shape(lambda k: zoo.init_params(model, cfg, k),
                               jax.random.PRNGKey(0))


def _paths(tree):
    return ["/".join(str(getattr(p, "key", p)) for p in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def test_layer_rates_follow_the_formula():
    """``train/optimizer.py::scale_by_layer`` at ViT-B's depth, leaf by
    leaf, against ViTDet's get_vit_lr_decay_rate: patch_embed and pos_embed
    0.7^13, block i 0.7^(12 - i), the pyramid and the heads 1."""
    from mx_rcnn_tpu.train.optimizer import scale_by_layer

    cfg, abstract = _vitdet_abstract()
    assert (cfg.train.layer_decay, cfg.network.vit_depth) == (0.7, 12)
    ones = jax.tree.map(lambda a: jnp.ones((), jnp.float32), abstract)
    got, _ = scale_by_layer(0.7, 12).update(ones, None)
    seen = set()
    for path, leaf in zip(_paths(abstract), jax.tree.leaves(got)):
        parts = path.split("/")
        if parts[1:3] in (["features", "patch_embed"],
                          ["features", "pos_embed"]):
            layer = 0
        elif parts[1] == "features":
            layer = int(parts[2][len("block"):]) + 1
        else:
            layer = 13
        seen.add(layer)
        assert float(leaf) == pytest.approx(0.7 ** (13 - layer), rel=1e-6), \
            path
    assert seen == set(range(14))


def test_the_no_decay_set():
    """AdamW's weight decay skips exactly the norm layers' leaves (every
    LayerNorm's scale and bias: 41 norms) and pos_embed; the relative-
    position tables, every kernel and every other bias decay."""
    from mx_rcnn_tpu.train.optimizer import decay_mask

    cfg, abstract = _vitdet_abstract()
    assert cfg.train.no_decay == ("norm", "pos_embed")
    mask = dict(zip(_paths(abstract),
                    jax.tree.leaves(decay_mask(abstract, cfg.train.no_decay))))
    skipped = sorted(p for p, m in mask.items() if not m)
    norms = {p.rsplit("/", 1)[0] for p in mask if p.endswith("/scale")}
    assert len(norms) == 41
    assert skipped == sorted([f"{n}/{leaf}" for n in norms
                              for leaf in ("scale", "bias")]
                             + ["params/features/pos_embed"])
    assert mask["params/features/block2/attn/rel_pos_h"]
    assert mask["params/rpn/rpn_conv2/bias"]


@pytest.mark.parametrize("network", ["resnet101_fpn", "resnet101_fpn_mask"])
def test_the_pyramid_presets_keep_their_heads_and_update(network, rng):
    """The FPN and Mask presets read none of ViTDet's head options: the
    parameter tree is the two-FC head's, the one-conv RPN head's and the
    unnormed mask head's, the pooling unshifted; and two steps of their
    optimizer equal, bit for bit, the chain they always built (elementwise
    clip, coupled decay, SGD momentum, frozen leaves zeroed)."""
    import optax

    from mx_rcnn_tpu.train.optimizer import (build_optimizer,
                                             effective_fixed_patterns,
                                             lr_schedule, trainable_mask)

    cfg = generate_config(network, "coco", **{
        "network.depth": 50, "image.pad_shape": (128, 128)})
    model = zoo.build_model(cfg)
    assert not model.roi_aligned
    abstract = jax.eval_shape(lambda k: zoo.init_params(model, cfg, k),
                              jax.random.PRNGKey(0))
    paths = _paths(abstract)
    assert "params/head/fc7/kernel" in paths
    assert not [p for p in paths if "rpn_conv2" in p or "_norm/" in p
                or p.endswith("/scale")]
    if cfg.network.use_mask:
        assert "params/mask_head/mask_conv0/bias" in paths
    params = jax.tree.map(
        lambda a: jnp.asarray(rng.randn(*a.shape) * 0.1, jnp.float32),
        abstract)
    grads = [jax.tree.map(
        lambda a: jnp.asarray(rng.randn(*a.shape) * 7.0, jnp.float32),
        abstract) for _ in range(2)]
    labels = jax.tree.map(lambda t: "train" if t else "frozen",
                          trainable_mask(params,
                                         effective_fixed_patterns(cfg)))
    old = optax.multi_transform({
        "train": optax.chain(
            optax.clip(cfg.train.clip_gradient),
            optax.add_decayed_weights(cfg.train.wd),
            optax.sgd(learning_rate=lr_schedule(cfg, 1000),
                      momentum=cfg.train.momentum)),
        "frozen": optax.set_to_zero()}, labels)
    new = build_optimizer(cfg, params)
    s_old, s_new = old.init(params), new.init(params)
    for g in grads:
        u_old, s_old = jax.jit(old.update)(g, s_old, params)
        u_new, s_new = jax.jit(new.update)(g, s_new, params)
        for a, b in zip(jax.tree.leaves(u_old), jax.tree.leaves(u_new)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_vitdet_presets_are_the_recipe():
    """``vitdet_b`` and ``vitdet_b_mask``: window 14 with the global blocks
    2, 5, 8, 11, LN heads, a two-conv RPN head, 4conv1fc, exact top-k, the
    1024x1024 canvas with MAE's normalisation, AdamW at lr 1e-4 (the
    recipe's 64 images), weight decay 0.1, layer decay 0.7, drop path 0.1,
    no clip; no final norm after the last block; ROIAlignV2."""
    from mx_rcnn_tpu.models.vit import _global_block_indices

    for name in ("vitdet_b", "vitdet_b_mask"):
        cfg, abstract = _vitdet_abstract(name)
        n = cfg.network
        assert (n.vit_window, n.norm, n.rpn_convs, n.box_head_convs,
                n.proposal_topk, n.vit_drop_path) == (
                    14, "layer", 2, 4, "exact", 0.1)
        assert _global_block_indices(n.vit_depth) == {2, 5, 8, 11}
        assert cfg.image.pad_shape == (1024, 1024)
        assert cfg.image.pixel_stds == (58.395, 57.12, 57.375)
        t = cfg.train
        assert (t.optimizer, t.lr, t.wd, t.layer_decay) == (
            "adamw", 1e-4, 0.1, 0.7)
        assert t.clip_gradient == float("inf")
        paths = _paths(abstract)
        assert not [p for p in paths if p.startswith("params/features/norm")]
        assert "params/features/block2/attn/rel_pos_h" in paths
        assert zoo.build_model(cfg).roi_aligned


@pytest.mark.parametrize("size", [1, 4, 14])
def test_rel_pos_table_reads_each_offsets_row(size, rng):
    """Entry (i, j) of ``rel_pos_table`` is the table's row for the offset
    i - j, row i - j + size - 1 (Detectron2's ``get_rel_pos``)."""
    from mx_rcnn_tpu.models.vit import rel_pos_table

    table = jnp.asarray(rng.randn(2 * size - 1, 5), jnp.float32)
    got = np.asarray(rel_pos_table(table, size))
    assert got.shape == (size, size, 5)
    for i in range(size):
        for j in range(size):
            np.testing.assert_array_equal(got[i, j], table[i - j + size - 1])


def test_rel_pos_table_resizes_a_table_of_another_length(rng):
    """A table made for 8 positions read at 4 is resampled to 7 rows first
    (what the init pass at a smaller grid meets); one of the right length
    is taken as it is."""
    from mx_rcnn_tpu.models.vit import rel_pos_table

    table = jnp.asarray(rng.randn(15, 3), jnp.float32)
    assert rel_pos_table(table, 4).shape == (4, 4, 3)
    np.testing.assert_array_equal(np.asarray(rel_pos_table(table, 8))[7, 7],
                                  table[7])


@pytest.mark.parametrize("rate", [0.0, 0.1, 0.5])
def test_drop_path_keep_factors(rate):
    """The factors of ``drop_path_keep``: block 0 never drops (its rate is
    0), every factor is 0 or 1/(1 - its block's rate), and at rate 0 all
    are 1."""
    from mx_rcnn_tpu.models.vit import drop_path_keep

    keep = np.asarray(drop_path_keep(jax.random.PRNGKey(5), 64, 12, rate))
    assert keep.shape == (12, 2, 64)
    assert (keep[0] == 1.0).all()
    scale = 1.0 / (1.0 - np.linspace(0.0, rate, 12))[:, None, None]
    assert np.all((keep == 0.0) | np.isclose(keep, scale))
    assert (keep == 0.0).any() == (rate > 0.0)


@pytest.mark.parametrize("path,layer", [
    ("params/features/patch_embed/kernel", 0),
    ("params/features/pos_embed", 0),
    ("params/features/block0/attn/qkv/kernel", 1),
    ("params/features/block11/mlp2/bias", 12),
    ("params/neck/out2_1/kernel", 13),
    ("params/rpn/rpn_conv2/kernel", 13),
    ("params/mask_head/mask_logits/bias", 13)])
def test_vit_layer_of_each_kind_of_leaf(path, layer):
    """``get_vit_lr_decay_rate``'s layer ids at ViT-B's depth: the patch
    embedding and positions 0, block i at i + 1, the rest 13."""
    from mx_rcnn_tpu.train.optimizer import vit_layer

    assert vit_layer(path.split("/"), 12) == layer


def test_decay_mask_on_a_small_tree():
    """``decay_mask`` by module: a LayerNorm's and a GroupNorm's leaves
    (``scale`` beside ``bias``) skip the decay under "norm", a named leaf
    skips it by name, a dense layer's bias and kernel decay."""
    from mx_rcnn_tpu.train.optimizer import decay_mask

    tree = {"params": {"ln": {"scale": 1.0, "bias": 0.0},
                       "gn": {"scale": 1.0, "bias": 0.0},
                       "fc": {"kernel": 1.0, "bias": 0.0},
                       "pos_embed": 0.0}}
    got = decay_mask(tree, ("norm", "pos_embed"))["params"]
    assert not any([got["ln"]["scale"], got["ln"]["bias"],
                    got["gn"]["scale"], got["gn"]["bias"], got["pos_embed"]])
    assert got["fc"]["kernel"] and got["fc"]["bias"]
    assert all(jax.tree.leaves(decay_mask(tree, ())))


def test_scale_by_layer_at_decay_one_is_the_identity(rng):
    """At decay 1.0 every layer's factor is 1: the updates pass through."""
    from mx_rcnn_tpu.train.optimizer import scale_by_layer

    u = {"params": {"features": {"block3": {"mlp1": {"kernel": jnp.asarray(
        rng.randn(4, 4), jnp.float32)}}}, "head": {"fc1": {"bias": jnp.ones(
            (3,))}}}}
    got, _ = scale_by_layer(1.0, 12).update(u, None)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(u)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
