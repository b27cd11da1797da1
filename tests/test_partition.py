"""Tensor parallelism (parallel/partition.py): spec rules, placement,
and TP-vs-replicated train-step parity.

The reference has no model parallelism (SURVEY.md §3.2); these tests pin
the TPU-native TP extension: Megatron-split weights over the mesh `model`
axis with GSPMD-inserted collectives, composed with DP on the `data` axis.
Run on the conftest 8-device CPU mesh; comparisons use float32 compute so
shard-order summation noise stays inside tight tolerances (the bf16
lesson from test_ulysses_attention_matches_dense).
"""

from functools import partial

import jax
import numpy as np
import optax
import pytest

pytestmark = pytest.mark.compile_heavy
from jax.sharding import PartitionSpec as P

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import zoo
from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
from mx_rcnn_tpu.parallel.partition import (
    shard_params,
    shard_train_state,
    tp_param_specs,
)
from mx_rcnn_tpu.train.optimizer import build_optimizer
from mx_rcnn_tpu.train.step import create_train_state, make_train_step


def _vit_cfg(**overrides):
    base = {
        "image.pad_shape": (128, 128),
        "train.batch_images": 2,
        "network.vit_dim": 32,
        "network.vit_depth": 2,
        "network.vit_heads": 2,
        "network.vit_window": 4,
        "train.compute_dtype": "f32",
        "network.tensor_parallel": True,
        # the replicated and the sharded step are held to each other after
        # an update: by SGD at lr 0.01, which moves the parameters by ~1e-3
        # and keeps the two sides' rounding at its own size; the preset's
        # AdamW turns a gradient's rounding into a whole lr wherever the
        # gradient is near nought (the second step's loss 1e-4 to 6e-4
        # apart, params a step apart; AdamW under TP is DETR's test below),
        # and no stochastic depth
        "network.vit_drop_path": 0.0,
        "train.optimizer": "sgd",
        "train.lr": 0.01,
        "train.clip_gradient": 5.0,
        "train.fpn_rpn_pre_nms_per_level": 64,
        "train.rpn_post_nms_top_n": 64,
        "train.batch_rois": 32,
        "train.max_gt_boxes": 8,
    }
    base.update(overrides)
    return generate_config("vitdet_b", "synthetic", **base)


def _batch(rng, b=2, size=128):
    one = {
        "image": rng.randn(1, size, size, 3).astype(np.float32),
        "im_info": np.asarray([[size, size, 1.0]], np.float32),
        "gt_boxes": np.asarray(
            [[[10, 10, 60, 90], [70, 20, 120, 70]] + [[0, 0, 0, 0]] * 6],
            np.float32),
        "gt_classes": np.asarray([[1, 2] + [0] * 6], np.int32),
        "gt_valid": np.asarray([[True, True] + [False] * 6]),
    }
    return {k: np.repeat(v, b, axis=0) for k, v in one.items()}


def _flat(tree):
    return {
        "/".join(str(getattr(k, "key", k)) for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def test_spec_rules_match_expected_leaves():
    cfg = _vit_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    specs = _flat(tp_param_specs(params))
    assert specs["params/features/block0/attn/qkv/kernel"] == P(None, "model")
    assert specs["params/features/block0/attn/proj/kernel"] == P("model", None)
    assert specs["params/features/block0/mlp1/kernel"] == P(None, "model")
    assert specs["params/features/block0/mlp2/kernel"] == P("model", None)
    # ViTDet's box head is 4conv1fc: its one FC layer has no partner to
    # split against, so it stays replicated, as everything conv-ish does;
    # the paired FC head (fc6/fc7) of the FPN presets is split.
    assert specs["params/head/fc1/kernel"] == P()
    assert specs["params/head/conv1/kernel"] == P()
    assert specs["params/features/patch_embed/kernel"] == P()
    assert specs["params/cls_score/kernel"] == P()
    fpn_cfg = generate_config("resnet50_fpn", "synthetic",
                              **{"image.pad_shape": (128, 128)})
    fpn_model = zoo.build_model(fpn_cfg)
    fpn_specs = _flat(tp_param_specs(jax.eval_shape(
        lambda k: zoo.init_params(fpn_model, fpn_cfg, k),
        jax.random.PRNGKey(0))))
    assert fpn_specs["params/head/fc6/kernel"] == P(None, "model")
    assert fpn_specs["params/head/fc7/kernel"] == P("model", None)


def test_shard_params_places_on_model_axis():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = _vit_cfg()
    mesh = create_mesh("2x2")
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    sharded, shardings = shard_params(params, mesh)
    flat = _flat(sharded)
    qkv = flat["params/features/block0/attn/qkv/kernel"]
    assert not qkv.sharding.is_fully_replicated
    # 32x96 kernel split on the 2-way model axis → 32x48 shards.
    assert qkv.addressable_shards[0].data.shape == (32, 48)
    assert flat["params/features/patch_embed/kernel"].sharding.is_fully_replicated
    # Values survive placement bit-exactly.
    np.testing.assert_array_equal(
        np.asarray(qkv), np.asarray(_flat(params)["params/features/block0/attn/qkv/kernel"]))


def test_indivisible_dims_fall_back_to_replicated():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    # vit_dim 24, model axis 4: qkv out = 72 ≡ 0 mod 4 but mlp hidden
    # 96/4 ok; use heads=3/dim=24 with model=4 → 24*3=72/4=18 fine...
    # pick dims that do NOT divide: dim 20 → qkv 60, 60 % 8.
    cfg = _vit_cfg(**{"network.vit_dim": 20, "network.vit_heads": 2})
    mesh = create_mesh("1x8")
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    sharded, _ = shard_params(params, mesh)
    flat = _flat(sharded)
    # 20x60 qkv: 60 % 8 != 0 → replicated, not padded.
    assert flat["params/features/block0/attn/qkv/kernel"].sharding.is_fully_replicated


def _run_steps(cfg, params, batch, mesh=None, tp=False, n_steps=2):
    model = zoo.build_model(cfg)
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    state = create_train_state(params, tx)
    specs = None
    if tp:
        specs = tp_param_specs(state.params)
        state = shard_train_state(state, mesh, specs)
    step = make_train_step(model, cfg, mesh=mesh, donate=False,
                           forward_fn=zoo.forward_train, param_specs=specs)
    losses = []
    for i in range(n_steps):
        b = shard_batch(batch, mesh) if mesh is not None else batch
        state, metrics = step(state, b, jax.random.PRNGKey(7 + i))
        losses.append(float(metrics["TotalLoss"]))
    return losses, jax.device_get(state)


def test_vitdet_tp_step_matches_replicated(rng):
    """DP×TP (2x2 mesh) reproduces the single-device step: same losses,
    same updated params — GSPMD collectives change only the schedule."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = _vit_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = _batch(rng)

    ref_losses, ref_state = _run_steps(cfg, params, batch)
    mesh = create_mesh("2x2")
    tp_losses, tp_state = _run_steps(cfg, params, batch, mesh=mesh, tp=True)

    np.testing.assert_allclose(tp_losses, ref_losses, rtol=2e-4)
    ref_flat, tp_flat = _flat(ref_state.params), _flat(tp_state.params)
    for name in ("params/features/block0/mlp1/kernel",
                 "params/head/fc1/kernel",
                 "params/features/patch_embed/kernel"):
        np.testing.assert_allclose(tp_flat[name], ref_flat[name],
                                   rtol=1e-3, atol=1e-5, err_msg=name)


def _first_moment(opt_state) -> dict:
    """AdamW's mu, flat by parameter path (frozen leaves left out)."""
    adam = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)]
    assert len(adam) == 1
    return {k: v for k, v in _flat(adam[0].mu).items()
            if isinstance(v, np.ndarray)}


def test_vitdet_tp_adamw_step_matches_replicated(rng):
    """The ViT preset's own update under DP x TP (2x2 mesh): AdamW with
    layer-wise decay and the no-decay set, one step, compared where it is
    well conditioned. The losses (2e-4, as above). The first moment
    mu = (1 - b1) g, the gradient as each side sums it, to 2e-2 of its
    norm: the two sides read 0.5-0.75 % apart on every trunk leaf at
    float32, one upstream difference of summation order carried through
    the whole backward (a lost reduction reads 50 % or more). And the step
    itself on entries whose gradient stands clear of that difference
    (above 5e-2 of the leaf's largest, so no sign flips), where AdamW's
    first step is lr · rate · (sign(g) + wd · w): the two sides, and that
    formula with the layer's rate 0.7 ** (depth + 1 - layer) and no decay
    on norms and pos_embed, to 1e-3 (the step is read as w0 - w1, whose
    float32 rounding is up to 1e-4 of a 3e-5 step)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    preset = generate_config("vitdet_b", "synthetic").train
    cfg = _vit_cfg(**{"train.optimizer": preset.optimizer,
                      "train.lr": preset.lr,
                      "train.clip_gradient": preset.clip_gradient})
    t = cfg.train
    assert (t.optimizer, t.layer_decay, t.no_decay) == (
        "adamw", 0.7, ("norm", "pos_embed"))
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = _batch(rng)
    ref_losses, ref = _run_steps(cfg, params, batch, n_steps=1)
    mesh = create_mesh("2x2")
    tp_losses, tp = _run_steps(cfg, params, batch, mesh=mesh, tp=True,
                               n_steps=1)
    np.testing.assert_allclose(tp_losses, ref_losses, rtol=2e-4)
    w0 = _flat(jax.device_get(params))
    ref_mu, tp_mu = _first_moment(ref.opt_state), _first_moment(tp.opt_state)
    ref_w, tp_w = _flat(ref.params), _flat(tp.params)
    depth = cfg.network.vit_depth
    checked = {
        # path: (layer, decayed)
        "params/features/pos_embed": (0, False),
        "params/features/patch_embed/kernel": (0, True),
        "params/features/block0/mlp1/kernel": (1, True),
        "params/features/block0/norm1/scale": (1, False),
        "params/features/block1/attn/qkv/kernel": (2, True),
        "params/features/block1/attn/rel_pos_h": (2, True),
        "params/head/fc1/kernel": (depth + 1, True),
        "params/neck/out2_3_ln/bias": (depth + 1, False),
    }
    for name, (layer, decayed) in checked.items():
        g = ref_mu[name]
        assert (np.linalg.norm(tp_mu[name] - g)
                < 2e-2 * np.linalg.norm(g)), name
        big = np.abs(g) > 5e-2 * np.abs(g).max()
        assert big.sum() >= 8, name
        step_ref = (w0[name] - ref_w[name])[big]
        step_tp = (w0[name] - tp_w[name])[big]
        np.testing.assert_allclose(step_tp, step_ref, rtol=1e-3, err_msg=name)
        rate = t.layer_decay ** (depth + 1 - layer)
        want = t.lr * rate * (np.sign(g[big])
                              + (t.wd * w0[name][big] if decayed else 0.0))
        np.testing.assert_allclose(step_ref, want, rtol=1e-3, err_msg=name)


def _detr_tp_cfg(**overrides):
    base = {
        "image.pad_shape": (128, 128),
        "train.batch_images": 2,
        "network.detr_queries": 20,
        "network.detr_hidden": 64,
        "network.detr_heads": 4,
        "network.detr_enc_layers": 2,
        "network.detr_dec_layers": 2,
        "network.norm": "group",
        "network.freeze_at": 0,
        "train.compute_dtype": "f32",
        "network.tensor_parallel": True,
        "train.max_gt_boxes": 8,
    }
    base.update(overrides)
    return generate_config("detr_r50", "synthetic", **base)


def test_detr_tp_step_matches_replicated(rng):
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = _detr_tp_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    specs = _flat(tp_param_specs(params))
    assert specs["params/enc0/self_attn/q/kernel"] == P(None, "model")
    assert specs["params/dec0/cross_attn/proj/kernel"] == P("model", None)
    # The FFN pair holds the largest DETR matrices — it MUST be split.
    assert specs["params/enc0/ffn1/kernel"] == P(None, "model")
    assert specs["params/enc0/ffn2/kernel"] == P("model", None)
    assert specs["params/dec0/ffn1/kernel"] == P(None, "model")
    batch = _batch(rng)

    ref_losses, _ = _run_steps(cfg, params, batch)
    mesh = create_mesh("2x2")
    tp_losses, _ = _run_steps(cfg, params, batch, mesh=mesh, tp=True)
    np.testing.assert_allclose(tp_losses, ref_losses, rtol=5e-4)


def test_shard_train_state_keeps_opt_state_values(rng):
    """A restored (nonzero) opt_state survives TP placement bit-exactly —
    the resume path shards, never re-initializes."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = _vit_cfg()
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    state = create_train_state(params, tx)
    # One plain step gives nonzero momentum slots.
    step = make_train_step(model, cfg, donate=False,
                           forward_fn=zoo.forward_train)
    state, _ = step(state, _batch(rng), jax.random.PRNGKey(3))
    before = jax.device_get(state.opt_state)

    mesh = create_mesh("2x2")
    sharded = shard_train_state(state, mesh)
    after = jax.device_get(sharded.opt_state)
    jax.tree.map(np.testing.assert_array_equal, before, after)
    assert int(sharded.step) == int(state.step)


def test_fpn_fc_head_tp_runs(rng):
    """The classic-family TP surface: TwoFCHead fc6/fc7 split under a
    2x2 mesh trains one finite step (conv trunk replicated)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = generate_config(
        "resnet50_fpn", "synthetic",
        **{
            "image.pad_shape": (128, 128),
            "train.batch_images": 2,
            "train.compute_dtype": "f32",
            "network.tensor_parallel": True,
            "network.norm": "group",
            "network.freeze_at": 0,
            "train.fpn_rpn_pre_nms_per_level": 64,
            "train.rpn_post_nms_top_n": 64,
            "train.batch_rois": 32,
            "train.max_gt_boxes": 8,
        })
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    specs = _flat(tp_param_specs(params))
    assert specs["params/head/fc6/kernel"] == P(None, "model")
    mesh = create_mesh("2x2")
    losses, _ = _run_steps(cfg, params, _batch(rng), mesh=mesh, tp=True,
                           n_steps=1)
    assert np.isfinite(losses[0])


def test_fit_detector_tp_smoke(tmp_path, rng):
    """The full train loop (loader → TP shard → jitted step → checkpoint)
    with tensor_parallel on a 2x2 mesh — covers the fit_detector wiring,
    not just the bare step."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset
    from mx_rcnn_tpu.tools.train import fit_detector

    cfg = _detr_tp_cfg(**{
        "image.scales": ((128, 128),),
        "train.compute_dtype": "bf16",  # the production dtype path
        "train.batch_images": 1,
        "train.flip": False,
        "train.lr_step": (100,),
    })
    ds = SyntheticDataset("train", num_images=4, image_size=128,
                          max_objects=2, min_size_frac=4, max_size_frac=2)
    history = []
    fit_detector(cfg, ds.gt_roidb(), prefix=str(tmp_path / "tp"),
                 end_epoch=1, frequent=1000, seed=0, mesh_spec="2x2",
                 epoch_callback=lambda e, s, b: history.append(
                     b.get()["TotalLoss"]))
    assert len(history) == 1 and np.isfinite(history).all(), history
    assert (tmp_path / "tp" / "0001").exists()
