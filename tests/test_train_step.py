"""Train-step integration: full fwd+bwd+update on an 8-device CPU mesh.

The DP analog of the reference's multi-GPU path (MutableModule over a context
list + KVStore 'device' allreduce) — SURVEY.md §5 says test it on
host-simulated devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.compile_heavy

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models.faster_rcnn import build_model, forward_train, init_params
from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
from mx_rcnn_tpu.train.optimizer import build_optimizer, trainable_mask
from mx_rcnn_tpu.train.step import create_train_state, make_train_step

PAD = 128


def tiny_cfg(batch_images=1):
    return generate_config(
        "resnet50", "synthetic",
        **{
            "train.rpn_pre_nms_top_n": 256,
            "train.rpn_post_nms_top_n": 64,
            "train.batch_rois": 32,
            "train.max_gt_boxes": 8,
            "train.batch_images": batch_images,
            # Small anchors so some are inside the tiny test image.
            "network.anchor_scales": (2, 4, 8),
            "image.pad_shape": (PAD, PAD),
        },
    )


def tiny_batch(b):
    rs = np.random.RandomState(3)
    gt = np.zeros((b, 8, 4), np.float32)
    gt[:, 0] = [10, 10, 70, 60]
    gt[:, 1] = [50, 40, 110, 100]
    valid = np.zeros((b, 8), bool)
    valid[:, :2] = True
    classes = np.zeros((b, 8), np.int32)
    classes[:, :2] = [1, 3]
    return {
        "image": jnp.asarray(rs.randn(b, PAD, PAD, 3).astype(np.float32)),
        "im_info": jnp.asarray([[PAD, PAD, 1.0]] * b, np.float32),
        "gt_boxes": jnp.asarray(gt),
        "gt_classes": jnp.asarray(classes),
        "gt_valid": jnp.asarray(valid),
    }


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    return cfg, model, params


def test_forward_train_losses_finite_and_nonzero(setup):
    cfg, model, params = setup
    loss, aux = jax.jit(
        lambda p, b, k: forward_train(model, p, b, k, cfg)
    )(params, tiny_batch(1), jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    # With small anchors the RPN must see positives and negatives.
    assert float(aux["rpn_cls_loss"]) > 0
    assert float(aux["rcnn_cls_loss"]) > 0


def test_train_step_updates_trainable_only(setup):
    cfg, model, params = setup
    tx = build_optimizer(cfg, params, steps_per_epoch=100)
    state = create_train_state(params, tx)
    step_fn = make_train_step(model, cfg, mesh=None, donate=False)
    new_state, metrics = step_fn(state, tiny_batch(1), jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["TotalLoss"]))

    mask = trainable_mask(params, cfg.network.fixed_param_patterns)
    flat_old = jax.tree_util.tree_leaves_with_path(params)
    flat_new = dict(jax.tree_util.tree_leaves_with_path(new_state.params))
    flat_mask = dict(jax.tree_util.tree_leaves_with_path(mask))
    changed_any = False
    for path, old in flat_old:
        new = flat_new[path]
        trainable = flat_mask[path]
        if not trainable:
            np.testing.assert_array_equal(
                np.asarray(old), np.asarray(new),
                err_msg=f"frozen param changed: {path}")
        elif not np.allclose(np.asarray(old), np.asarray(new)):
            changed_any = True
    assert changed_any, "no trainable parameter changed"


def test_frozen_trunk_with_live_grads_stays_fixed():
    """Freeze via optimizer mask where grads are NONZERO (no stop_gradient
    cut): the alternate-training stages 4/6 case. optax.masked would pass
    raw gradients through as updates here (gradient ascent on the 'frozen'
    trunk — the bug test_stages caught); the optimizer must hard-zero
    them."""
    from dataclasses import replace

    cfg = tiny_cfg()
    cfg = cfg.with_updates(network=replace(
        cfg.network, norm="group", freeze_at=0,
        fixed_param_patterns=("features",)))
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))

    # Sanity: grads through the trunk really are nonzero in this config.
    # (jitted: the eager 128^2 backward costs ~30 s of tier-1 wall time.)
    grads = jax.jit(jax.grad(lambda p: forward_train(
        model, p, tiny_batch(1), jax.random.PRNGKey(2), cfg)[0]))(params)
    g = grads["params"]["features"]["stage3"]["block0"]["conv1"]["kernel"]
    assert float(jnp.abs(g).max()) > 0.0

    tx = build_optimizer(cfg, params, steps_per_epoch=100)
    state = create_train_state(params, tx)
    step_fn = make_train_step(model, cfg, mesh=None, donate=False)
    new_state, _ = step_fn(state, tiny_batch(1), jax.random.PRNGKey(2))
    old = params["params"]["features"]["stage3"]["block0"]["conv1"]["kernel"]
    new = new_state.params["params"]["features"]["stage3"]["block0"]["conv1"]["kernel"]
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    # ...while the heads trained.
    assert not np.array_equal(
        np.asarray(params["params"]["rpn"]["rpn_conv"]["kernel"]),
        np.asarray(new_state.params["params"]["rpn"]["rpn_conv"]["kernel"]))


def test_adamw_optimizer_knob():
    """train.optimizer='adamw' (the DETR/ViTDet preset): builds, steps,
    and still hard-zeros frozen leaves."""
    from dataclasses import replace

    cfg = tiny_cfg()
    cfg = cfg.with_updates(train=replace(cfg.train, optimizer="adamw",
                                         lr=1e-4, clip_gradient=0.1))
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    state = create_train_state(params, tx)
    step_fn = make_train_step(model, cfg, mesh=None, donate=False)
    new_state, metrics = step_fn(state, tiny_batch(1), jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["TotalLoss"]))
    # frozen stem stays fixed under adamw too
    old = params["params"]["features"]["conv0"]["kernel"]
    new = new_state.params["params"]["features"]["conv0"]["kernel"]
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    # trainable heads moved
    assert not np.array_equal(
        np.asarray(params["params"]["rpn"]["rpn_conv"]["kernel"]),
        np.asarray(new_state.params["params"]["rpn"]["rpn_conv"]["kernel"]))

    with pytest.raises(ValueError, match="sgd.*adamw|adamw.*sgd"):
        bad = cfg.with_updates(train=replace(cfg.train, optimizer="lion"))
        build_optimizer(bad, params)


def test_transformer_presets_use_adamw():
    from mx_rcnn_tpu.config import generate_config as gc

    assert gc("detr_r50", "coco").train.optimizer == "adamw"
    assert gc("vitdet_b", "coco").train.optimizer == "adamw"
    assert gc("resnet101", "coco").train.optimizer == "sgd"
    assert gc("resnet101_fpn", "coco").train.optimizer == "sgd"


def test_frozen_mask_covers_reference_prefixes(setup):
    cfg, model, params = setup
    mask = trainable_mask(params, cfg.network.fixed_param_patterns)
    flat = jax.tree_util.tree_leaves_with_path(mask)

    def joined(path):
        return "/".join(str(getattr(p, "key", p)) for p in path)

    for path, trainable in flat:
        j = joined(path)
        if "conv0" in j or "stage1" in j or "bn0" in j:
            assert not trainable, f"{j} should be frozen"
        if j.endswith("gamma") or j.endswith("beta"):
            assert not trainable, f"{j} (BN affine) should be frozen"
        if "rpn" in j or "cls_score" in j or "bbox_pred" in j:
            assert trainable, f"{j} should be trainable"


def test_multichip_dp_step_runs():
    """8-device CPU mesh: batch sharded, grads allreduced, one step."""
    assert jax.device_count() >= 8, "conftest must force 8 CPU devices"
    cfg = tiny_cfg(batch_images=8)
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    mesh = create_mesh("8")
    tx = build_optimizer(cfg, params, steps_per_epoch=100)
    state = create_train_state(params, tx)
    step_fn = make_train_step(model, cfg, mesh=mesh, donate=False)
    batch = shard_batch(tiny_batch(8), mesh)
    new_state, metrics = step_fn(state, batch, jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["TotalLoss"]))


def test_dp_grads_match_single_device():
    """DP over 2 virtual devices == single device on the same 2-image batch
    (the KVStore-allreduce correctness check the reference never had)."""
    cfg = tiny_cfg(batch_images=2)
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    batch = tiny_batch(2)
    rng = jax.random.PRNGKey(5)

    tx = build_optimizer(cfg, params, steps_per_epoch=100)
    s1 = create_train_state(params, tx)
    single = make_train_step(model, cfg, mesh=None, donate=False)
    s1_new, m1 = single(s1, batch, rng)

    mesh = create_mesh("2")
    s2 = create_train_state(params, tx)
    dp = make_train_step(model, cfg, mesh=mesh, donate=False)
    s2_new, m2 = dp(s2, shard_batch(batch, mesh), rng)

    loss_rtol = 1e-4
    param_rtol, param_atol = 2e-3, 2e-5
    assert np.allclose(float(m1["TotalLoss"]), float(m2["TotalLoss"]),
                       rtol=loss_rtol)
    l1 = jax.tree.leaves(s1_new.params)
    l2 = jax.tree.leaves(s2_new.params)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=param_rtol, atol=param_atol)


def test_remat_matches_no_remat():
    """network.remat=True (jax.checkpoint on ResNet stages) must give the
    same loss and gradients as the plain backbone, with an identical
    parameter tree (checkpoints are interchangeable)."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models import zoo

    def cfg_for(remat):
        return generate_config("resnet50", "synthetic", **{
            "image.pad_shape": (128, 128),
            "network.norm": "group",
            "network.freeze_at": 0,
            "network.remat": remat,
            "network.anchor_scales": (2, 4, 8),
            "train.rpn_pre_nms_top_n": 256,
            "train.rpn_post_nms_top_n": 64,
            "train.batch_rois": 16,
            "train.max_gt_boxes": 8,
        })

    rs = np.random.RandomState(0)
    batch = {
        "image": jnp.asarray(rs.randn(1, 128, 128, 3).astype(np.float32)),
        "im_info": jnp.asarray([[128, 128, 1.0]], np.float32),
        "gt_boxes": jnp.asarray(
            [[[10, 10, 60, 90], [70, 20, 120, 70]] + [[0, 0, 0, 0]] * 6],
            np.float32),
        "gt_classes": jnp.asarray([[1, 2] + [0] * 6], np.int32),
        "gt_valid": jnp.asarray([[True, True] + [False] * 6]),
    }
    cfg_plain, cfg_remat = cfg_for(False), cfg_for(True)
    model_plain = zoo.build_model(cfg_plain)
    model_remat = zoo.build_model(cfg_remat)
    params = zoo.init_params(model_plain, cfg_plain, jax.random.PRNGKey(0))
    # identical parameter tree -> same params load into the remat model
    params_r = zoo.init_params(model_remat, cfg_remat, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(params_r)

    key = jax.random.PRNGKey(1)

    def loss_fn(model, cfg):
        return lambda p: zoo.forward_train(model, p, batch, key, cfg)[0]

    # jit both graphs: eager per-op dispatch of the 128^2 fwd+bwd costs
    # ~45 s of tier-1 wall time; the jitted pair rides the persistent
    # compile cache (same numerics — the parity being gated).
    l_plain, g_plain = jax.jit(
        jax.value_and_grad(loss_fn(model_plain, cfg_plain)))(params)
    l_remat, g_remat = jax.jit(
        jax.value_and_grad(loss_fn(model_remat, cfg_remat)))(params)
    assert np.isclose(float(l_plain), float(l_remat), rtol=1e-5)
    flat_p = jax.tree.leaves(g_plain)
    flat_r = jax.tree.leaves(g_remat)
    for a, b in zip(flat_p, flat_r):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


def _accum_cfg(**train_over):
    """64^2 micro-config: the accum tests compile fresh f32 graphs, so
    every shape is minimized (the 128^2 version costs ~45 min on CPU)."""
    from dataclasses import replace

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
        train=replace(cfg.train, **{"compute_dtype": "f32",
                                    "grad_accum_steps": 2, **train_over}))


def _accum_batch(b):
    rs = np.random.RandomState(3)
    gt = np.zeros((b, 4, 4), np.float32)
    gt[:, 0] = [8, 8, 40, 40]
    valid = np.zeros((b, 4), bool)
    valid[:, 0] = True
    classes = np.zeros((b, 4), np.int32)
    classes[:, 0] = 1
    return {
        "image": jnp.asarray(rs.randn(b, 64, 64, 3).astype(np.float32)),
        "im_info": jnp.asarray([[64, 64, 1.0]] * b, np.float32),
        "gt_boxes": jnp.asarray(gt),
        "gt_classes": jnp.asarray(classes),
        "gt_valid": jnp.asarray(valid),
    }


def test_grad_accum_matches_manual_average():
    """accum=2 over a 2-image batch reproduces (g0 + g1)/2 applied once —
    the unrolled micro-step loop is an exact re-ordering of the big-batch
    gradient math."""
    cfg = _accum_cfg()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    batch = _accum_batch(2)
    rng = jax.random.PRNGKey(11)

    accum_step = make_train_step(model, cfg, donate=False)
    new_state, metrics = accum_step(
        create_train_state(params, tx), batch, rng)
    assert np.isfinite(float(metrics["TotalLoss"]))

    # Manual: per-chunk grads with the same split keys, averaged, applied.
    keys = jax.random.split(rng, 2)

    @jax.jit
    def grads_of(chunk, key):
        def loss_fn(p):
            loss, _ = forward_train(model, p, chunk, key, cfg)
            return loss

        return jax.grad(loss_fn)(params)

    chunk = lambda i: {k: v[i:i + 1] for k, v in batch.items()}
    g = jax.tree.map(lambda a, b: (a + b) / 2,
                     grads_of(chunk(0), keys[0]),
                     grads_of(chunk(1), keys[1]))
    manual = create_train_state(params, tx).apply_gradients(g)

    flat_a = jax.tree_util.tree_leaves(new_state.params)
    flat_m = jax.tree_util.tree_leaves(manual.params)
    for a, b in zip(flat_a, flat_m):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_grad_accum_under_dp_mesh():
    """accum=2 composes with the data mesh (the reshaped micro-batch axis
    reshards; semantics hold)."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg = _accum_cfg()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    mesh = create_mesh("2")
    step = make_train_step(model, cfg, mesh=mesh, donate=False)
    # accum(2) x data(2) x batch_images(1) = 4 images per optimizer step.
    state, metrics = step(create_train_state(params, tx),
                          shard_batch(_accum_batch(4), mesh),
                          jax.random.PRNGKey(5))
    assert np.isfinite(float(metrics["TotalLoss"]))


def test_opt_state_dtype_bf16_slots():
    """train.opt_state_dtype=bfloat16 stores the momentum slot in bf16
    (HBM lever, PERF.md r4) and still trains: one step moves params and
    the bf16-slot trajectory tracks the f32 one closely."""
    import jax.numpy as jnp

    cfg32 = _accum_cfg(grad_accum_steps=1)
    cfg16 = _accum_cfg(grad_accum_steps=1, opt_state_dtype="bfloat16")
    model = build_model(cfg32)
    params = init_params(model, cfg32, jax.random.PRNGKey(0))
    batch = _accum_batch(1)
    rng = jax.random.PRNGKey(3)

    outs = {}
    for tag, cfg in (("f32", cfg32), ("bf16", cfg16)):
        tx = build_optimizer(cfg, params, steps_per_epoch=10)
        state = create_train_state(params, tx)
        if tag == "bf16":
            dtypes = {l.dtype for l in jax.tree.leaves(state.opt_state)
                      if hasattr(l, "dtype") and l.ndim > 0}
            assert jnp.dtype(jnp.bfloat16) in dtypes, dtypes
        step = make_train_step(model, cfg, donate=False)
        state, m = step(state, batch, rng)
        outs[tag] = (state, float(m["TotalLoss"]))
    assert np.isfinite(outs["bf16"][1])
    np.testing.assert_allclose(outs["bf16"][1], outs["f32"][1], rtol=1e-4)
    a = jax.tree.leaves(outs["bf16"][0].params)[0]
    b = jax.tree.leaves(outs["f32"][0].params)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.fixture(scope="module")
def accum_fit(tmp_path_factory):
    """One tiny fit_detector run: what its epoch_callback was handed."""
    from dataclasses import replace

    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset
    from mx_rcnn_tpu.tools.train import fit_detector

    cfg = _accum_cfg(flip=False, lr_step=(100,))
    cfg = cfg.with_updates(
        image=replace(cfg.image, scales=((64, 64),)))
    ds = SyntheticDataset("train", num_images=4, image_size=64,
                          max_objects=1, min_size_frac=3, max_size_frac=2)
    calls = []
    final = fit_detector(
        cfg, ds.gt_roidb(), prefix=str(tmp_path_factory.mktemp("ga") / "ga"),
        end_epoch=1, frequent=1000, seed=0,
        epoch_callback=lambda e, s, b: calls.append(
            (e, s, b.get()["TotalLoss"])))
    return calls, final


def test_grad_accum_fit_smoke(accum_fit):
    """fit_detector sizes the loader at accum x batch_images and trains."""
    calls, _ = accum_fit
    history = [loss for _, _, loss in calls]
    assert len(history) == 1 and np.isfinite(history).all(), history


def test_epoch_callback_gets_the_train_state(accum_fit):
    """The callback's state is the loop's TrainState: `.params` is the
    parameter tree (benchmarks/drivers/train.py reads exactly that) and
    `.opt_state` the optax state, one step a dispatch."""
    from mx_rcnn_tpu.train.step import TrainState

    (epoch, state, _), = accum_fit[0]
    final = accum_fit[1]
    assert epoch == 0 and isinstance(state, TrainState)
    assert int(state.step) == 2  # 4 images, accum 2 x batch 1: 2 steps
    assert (jax.tree.structure(state.params) == jax.tree.structure(final))
    for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(final)):
        np.testing.assert_array_equal(np.asarray(a), b)
    assert jax.tree.leaves(state.opt_state)


@pytest.mark.parametrize("network", ["resnet50", "resnet50_fpn_mask", "vgg"])
def test_a_fixed_pattern_is_the_prefix_of_a_path_segment(network):
    """``fixed_param_patterns`` name modules as the reference's
    ``fixed_param_prefix`` did: ``conv0`` fixes the stem's
    ``features/conv0`` and NOT the mask head's ``mask_conv0`` (as a bare
    substring of the path it did, and the Mask presets never trained that
    layer); every other leaf of every family is decided as before."""
    from mx_rcnn_tpu.models.zoo import build_model, init_params
    from mx_rcnn_tpu.train.optimizer import effective_fixed_patterns

    cfg = generate_config(network, "synthetic")
    model = build_model(cfg)
    abstract = jax.eval_shape(lambda k: init_params(model, cfg, k),
                              jax.random.PRNGKey(0))
    pats = effective_fixed_patterns(cfg)
    flat = jax.tree_util.tree_leaves_with_path(trainable_mask(abstract, pats))
    got = {"/".join(str(getattr(k, "key", k)) for k in path): v
           for path, v in flat}

    def as_a_substring(path):
        if "moving_" in path or path.rsplit("/", 1)[-1] in ("gamma", "beta"):
            return False
        return not any(p in path for p in pats)

    moved = {k for k, v in got.items() if v != as_a_substring(k)}
    if network.endswith("_mask"):
        assert moved == {"params/mask_head/mask_conv0/kernel",
                         "params/mask_head/mask_conv0/bias"}
        assert all(v for k, v in got.items() if "/mask_head/" in k)
        assert sum("/mask_head/" in k for k in got) == 12
    else:
        assert not moved
    frozen = [k for k, v in got.items() if not v]
    assert frozen and all(v for k, v in got.items() if "/rpn/" in k)
    if network != "vgg":
        assert not got["params/features/conv0/kernel"]
        assert not any(v for k, v in got.items() if "/stage1/" in k)
