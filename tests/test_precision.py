"""graftcast (train/precision.py) gates, on the path the cells run: a tree
TrainState of float32 leaves, bf16 compute by the modules' dtype.

The acceptance contract of the bf16-compute / f32-master-weight policy:

- the optimizer update is BIT-exact across policies given identical
  gradients (masters are f32 and the update never sees bf16);
- checkpoints are f32 and interchange between bf16 and f32 runs in BOTH
  directions, bit-exact at the master-weight level;
- after a bf16 step every parameter and optimizer-slot leaf is float32,
  the gradients that reach ``apply_gradients`` are float32, and the
  step is deterministic (two runs from one seed are bitwise equal);
- norm statistics reach the forward in float32 (the island contract);
- the bf16 tiny-config train loss curve tracks f32 within a calibrated
  tolerance for C4 AND FPN (bf16 lowers fine on CPU XLA).
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.compile_heavy

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.train import precision
from mx_rcnn_tpu.train.optimizer import build_optimizer
from mx_rcnn_tpu.train.step import (TrainState, create_train_state,
                                    make_train_step)


def _c4_cfg(compute, **train_over):
    """A 64^2 C4 micro-config, policy selectable."""
    cfg = generate_config(
        "resnet50", "synthetic",
        **{
            "train.rpn_pre_nms_top_n": 128,
            "train.rpn_post_nms_top_n": 32,
            "train.batch_rois": 16,
            "train.max_gt_boxes": 4,
            "train.batch_images": 1,
            "network.anchor_scales": (2, 4),
            "image.pad_shape": (64, 64),
        })
    return cfg.with_updates(
        train=replace(cfg.train, **{"compute_dtype": compute, **train_over}))


def _fpn_cfg(compute):
    """tests/test_fpn.py's 128^2 tiny FPN config, policy selectable."""
    cfg = generate_config(
        "resnet50_fpn", "synthetic",
        **{
            "image.pad_shape": (128, 128),
            "train.batch_images": 1,
            "train.fpn_rpn_pre_nms_per_level": 64,
            "train.rpn_post_nms_top_n": 64,
            "train.batch_rois": 32,
            "train.max_gt_boxes": 8,
        })
    return cfg.with_updates(
        train=replace(cfg.train, compute_dtype=compute))


def _c4_batch():
    rs = np.random.RandomState(3)
    gt = np.zeros((1, 4, 4), np.float32)
    gt[:, 0] = [8, 8, 40, 40]
    valid = np.zeros((1, 4), bool)
    valid[:, 0] = True
    classes = np.zeros((1, 4), np.int32)
    classes[:, 0] = 1
    return {
        "image": jnp.asarray(rs.randn(1, 64, 64, 3).astype(np.float32)),
        "im_info": jnp.asarray([[64, 64, 1.0]], np.float32),
        "gt_boxes": jnp.asarray(gt),
        "gt_classes": jnp.asarray(classes),
        "gt_valid": jnp.asarray(valid),
    }


def _fpn_batch():
    rs = np.random.RandomState(5)
    return {
        "image": jnp.asarray(rs.randn(1, 128, 128, 3).astype(np.float32)),
        "im_info": jnp.asarray([[128, 128, 1.0]], np.float32),
        "gt_boxes": jnp.asarray(
            [[[10, 10, 60, 90], [70, 20, 120, 70]] + [[0, 0, 0, 0]] * 6],
            np.float32),
        "gt_classes": jnp.asarray([[1, 2] + [0] * 6], np.int32),
        "gt_valid": jnp.asarray([[True, True] + [False] * 6]),
    }


def _fake_params(layers=4):
    """A hand-built tree: frozen conv0/norm + trainable layers;
    bbox_pred 8-wide = 2 classes x 4 (checkpoint fold/unfold)."""
    rs = np.random.RandomState(0)
    tree = {"conv0": {"kernel": rs.randn(3, 3, 3, 8).astype(np.float32)}}
    for i in range(layers):
        tree[f"layer{i:02d}"] = {
            "kernel": rs.randn(8, 8).astype(np.float32),
            "bias": rs.randn(8).astype(np.float32),
        }
    tree["norm"] = {"gamma": np.ones(8, np.float32),
                    "beta": np.zeros(8, np.float32)}
    tree["bbox_pred"] = {"kernel": rs.randn(8, 8).astype(np.float32),
                         "bias": rs.randn(8).astype(np.float32)}
    return {"params": tree}


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _fake_grads(params, seed):
    rs = np.random.RandomState(seed)
    return jax.tree.map(
        lambda p: jnp.asarray(rs.randn(*p.shape).astype(p.dtype) * 1e-3),
        params)


def _fresh_state(cfg, params):
    return create_train_state(
        params, build_optimizer(cfg, params, steps_per_epoch=10))


# ---------------------------------------------------------------------------
# policy units (no compiles)
# ---------------------------------------------------------------------------

def test_policy_normalization_and_validation():
    assert precision.normalize_compute_dtype("bf16") == "bfloat16"
    assert precision.normalize_compute_dtype("BFloat16") == "bfloat16"
    assert precision.normalize_compute_dtype("f32") == "float32"
    assert precision.normalize_compute_dtype("float32") == "float32"
    with pytest.raises(ValueError, match="compute_dtype"):
        precision.normalize_compute_dtype("fp16")

    cfg = _c4_cfg("bf16")
    pol = precision.policy_of(cfg)
    assert pol.compute == "bfloat16" and pol.short == "bf16"
    assert precision.model_dtype(cfg) == jnp.bfloat16
    assert precision.policy_of(_c4_cfg("f32")).compute == "float32"
    # a typo'd knob fails loudly at policy resolution (fit_detector
    # resolves it before any device work)
    bad = _c4_cfg("f32")
    bad = bad.with_updates(train=replace(bad.train, compute_dtype="f16"))
    with pytest.raises(ValueError):
        precision.policy_of(bad)


def test_island_param_predicate():
    # norm statistics/affine: FrozenBN leaves, bn*/downsample_bn and
    # norm*/dec_norm module params reach the forward in f32
    for path in ("params/features/bn0/gamma",
                 "params/features/stage2/block0/bn1/moving_var",
                 "params/features/stage2/block0/downsample_bn/scale",
                 "params/features/block3/norm1/bias",
                 "params/dec_norm/scale"):
        assert precision.is_island_param(path), path
    # DETR's set-prediction heads are dtype=f32 Denses over island(hs):
    # flax computes them with UNCAST f32 weights (models/detr.py)
    for path in ("params/class_embed/kernel",
                 "params/bbox_mlp0/kernel",
                 "params/bbox_mlp1/bias",
                 "params/bbox_out/kernel"):
        assert precision.is_island_param(path), path
    # pos_embed is bilinearly RESIZED before its per-use cast (cast does
    # not commute with resize), and the SFP up4_ln is norm affine like
    # any other LayerNorm (models/vit.py)
    for path in ("params/features/pos_embed",
                 "params/neck/up4_ln/scale"):
        assert precision.is_island_param(path), path
    # conv/dense kernels and biases are cast to the compute dtype at
    # use — including query_embed (a per-use .astype(x.dtype))
    for path in ("params/features/stage2/block0/conv1/kernel",
                 "params/rpn/rpn_conv/bias",
                 "params/head/fc6/kernel",
                 "params/cls_score/bias",
                 "params/query_embed"):
        assert not precision.is_island_param(path), path


def test_island_params_reach_the_forward_in_float32():
    """FrozenBN statistics are combined in float32 BEFORE the one cast to
    the compute dtype: beta 300 and mean 300.5 fold to a bias of exactly
    -0.5; cast to bfloat16 first (spacing 2 at 300) they would both read
    300 and fold to 0."""
    from mx_rcnn_tpu.models.backbones import FrozenBatchNorm

    bn = {"gamma": jnp.ones(1), "beta": jnp.full(1, 300.0),
          "moving_mean": jnp.full(1, 300.5),
          "moving_var": jnp.full(1, 1.0 - 1e-5)}
    assert all(precision.is_island_param(f"params/bn1/{k}") for k in bn)
    y = FrozenBatchNorm(1, dtype=jnp.bfloat16).apply(
        {"params": bn}, jnp.zeros((1, 1), jnp.bfloat16))
    assert y.dtype == jnp.bfloat16 and float(y[0, 0]) == -0.5


# ---------------------------------------------------------------------------
# update bit-exactness + checkpoint interchange (no model compiles)
# ---------------------------------------------------------------------------

def test_update_bit_exact_across_policies_given_equal_grads():
    """The acceptance claim: masters are f32 and the optimizer update
    never sees bf16 — with gradients FORCED equal, the bf16-policy
    update is bit-for-bit the f32-policy update."""
    params = _fake_params()
    grads = _fake_grads(params, 7)
    s_b = _fresh_state(_c4_cfg("bf16"), params)
    s_f = _fresh_state(_c4_cfg("f32"), params)
    update = jax.jit(lambda s, g: s.apply_gradients(g))
    for _ in range(3):
        s_b, s_f = update(s_b, grads), update(s_f, grads)
    _leaves_equal(s_b.params, s_f.params)
    _leaves_equal(s_b.opt_state, s_f.opt_state)
    assert int(s_b.step) == 3


def test_checkpoint_interchange_bf16_f32_both_directions(tmp_path):
    """Checkpoints stay f32: a bf16 run's save restores into an f32 run
    bit-exact at the master-weight level, and an f32 save restores into
    a bf16 run."""
    from mx_rcnn_tpu.train.checkpoint import load_checkpoint, save_checkpoint

    params = _fake_params()
    grads = _fake_grads(params, 9)
    # power-of-two stds: the checkpoint's bbox_pred unnormalize/
    # renormalize round-trip is bit-exact only then (the graftguard
    # parity convention, tests/_resilience_driver.py)
    cfg = _c4_cfg("f32")
    kw = dict(means=cfg.train.bbox_means, stds=(0.5, 0.5, 0.25, 0.25),
              num_classes=2)
    for saver, loader in (("bf16", "f32"), ("f32", "bf16")):
        # the saving run trains a step — on-disk form must be f32
        saved = _fresh_state(_c4_cfg(saver), params).apply_gradients(grads)
        assert all(np.asarray(x).dtype == np.float32
                   for x in jax.tree_util.tree_leaves(saved.params))
        prefix = str(tmp_path / f"{saver}run")
        save_checkpoint(prefix, 1, saved.params, saved.opt_state, **kw)
        # -> the other policy's run: loaded masters and slots bit-exact
        fresh = _fresh_state(_c4_cfg(loader), params)
        p_l, o_l = load_checkpoint(
            prefix, 1, template={"params": params},
            opt_state_template=fresh.opt_state, **kw)
        _leaves_equal(p_l, saved.params)
        _leaves_equal(o_l, saved.opt_state)


# ---------------------------------------------------------------------------
# compiled-step gates: dtypes, determinism, loss-curve parity (C4, FPN)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def c4_steps():
    """Shared C4 fixtures: the batch, the parameters, and one jitted
    step and fresh state per policy."""
    from mx_rcnn_tpu.models.faster_rcnn import build_model, init_params

    cfg_f, cfg_b = _c4_cfg("f32"), _c4_cfg("bf16")
    model_f, model_b = build_model(cfg_f), build_model(cfg_b)
    params = init_params(model_f, cfg_f, jax.random.PRNGKey(0))
    return {"batch": _c4_batch(), "params": params,
            "state_f": _fresh_state(cfg_f, params),
            "state_b": _fresh_state(cfg_b, params),
            "step_f": make_train_step(model_f, cfg_f, donate=False),
            "step_b": make_train_step(model_b, cfg_b, donate=False)}


def test_bf16_step_leaves_every_state_leaf_float32(c4_steps):
    """Masters and slots never leave float32 under bf16 compute."""
    state, _ = c4_steps["step_b"](c4_steps["state_b"], c4_steps["batch"],
                                  jax.random.PRNGKey(11))
    leaves = jax.tree_util.tree_leaves((state.params, state.opt_state))
    floats = [x for x in leaves if jnp.issubdtype(x.dtype, jnp.floating)]
    assert len(floats) > 200
    assert all(x.dtype == jnp.float32 for x in floats)


def test_bf16_step_hands_float32_grads_to_apply_gradients(c4_steps):
    """The cast's transpose casts each cotangent up: what the optimizer
    (and the DP psum before it) sees is float32, leaf for leaf. Traced,
    not compiled: a TrainState that records what it is handed."""
    seen = []

    class Spy(TrainState):
        def apply_gradients(self, grads):
            seen.append(grads)
            return super().apply_gradients(grads)

    s = c4_steps["state_b"]
    spy = Spy(step=s.step, params=s.params, opt_state=s.opt_state, tx=s.tx)
    jax.eval_shape(c4_steps["step_b"], spy, c4_steps["batch"],
                   jax.random.PRNGKey(11))
    (grads,) = seen
    assert (jax.tree.structure(grads) == jax.tree.structure(s.params))
    assert all(g.dtype == jnp.float32 and g.shape == p.shape
               for g, p in zip(jax.tree.leaves(grads),
                               jax.tree.leaves(s.params)))


def test_bf16_step_is_deterministic(c4_steps):
    """Two runs of the bf16 step from one seed are bitwise equal: what
    every kill/heal/resume parity gate rests on."""
    args = (c4_steps["state_b"], c4_steps["batch"], jax.random.PRNGKey(11))
    (s1, m1), (s2, m2) = c4_steps["step_b"](*args), c4_steps["step_b"](*args)
    _leaves_equal(s1.params, s2.params)
    _leaves_equal(s1.opt_state, s2.opt_state)
    assert float(m1["TotalLoss"]) == float(m2["TotalLoss"])


def test_bf16_loss_curve_matches_f32_c4(c4_steps):
    """3-step tiny-config loss curve, bf16 vs f32. Calibrated gate:
    observed per-step relative gap <= ~6e-3 on CPU XLA (discrete
    proposal/sampling selections may flip under bf16 scores, so this is
    a tolerance, not bit-exactness); 3x margin -> 2e-2."""
    batch = c4_steps["batch"]
    s_f, s_b = c4_steps["state_f"], c4_steps["state_b"]
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    gaps = []
    for i in range(3):
        k = keys[i]
        s_f, m_f = c4_steps["step_f"](s_f, batch, k)
        s_b, m_b = c4_steps["step_b"](s_b, batch, k)
        lf, lb = float(m_f["TotalLoss"]), float(m_b["TotalLoss"])
        assert np.isfinite(lf) and np.isfinite(lb)
        gaps.append(abs(lb - lf) / max(abs(lf), 1e-6))
    assert max(gaps) < 2e-2, gaps


def test_bf16_loss_curve_matches_f32_fpn():
    """Same gate for the FPN family (multi-level proposals + approx
    top-k preset): 2 steps at the tests/test_fpn.py tiny geometry.
    Tolerance is looser than C4 — the per-level top-k membership at
    k=64 of ~3k scores is more selection-sensitive under bf16."""
    from mx_rcnn_tpu.models.zoo import build_model, forward_train, init_params

    cfg_f, cfg_b = _fpn_cfg("f32"), _fpn_cfg("bf16")
    model_f, model_b = build_model(cfg_f), build_model(cfg_b)
    params = init_params(model_f, cfg_f, jax.random.PRNGKey(0))
    batch = _fpn_batch()
    step_f = make_train_step(model_f, cfg_f, donate=False,
                             forward_fn=forward_train)
    step_b = make_train_step(model_b, cfg_b, donate=False,
                             forward_fn=forward_train)
    s_f, s_b = _fresh_state(cfg_f, params), _fresh_state(cfg_b, params)
    keys = jax.random.split(jax.random.PRNGKey(13), 2)
    gaps = []
    for i in range(2):
        k = keys[i]
        s_f, m_f = step_f(s_f, batch, k)
        s_b, m_b = step_b(s_b, batch, k)
        lf, lb = float(m_f["TotalLoss"]), float(m_b["TotalLoss"])
        assert np.isfinite(lf) and np.isfinite(lb)
        gaps.append(abs(lb - lf) / max(abs(lf), 1e-6))
    assert max(gaps) < 5e-2, gaps
