"""The first gradient as the optimizer got it, read from its state after one
step (benchmarks/drivers/train.py::first_gradient), for either optimizer the
program has: an SGD state gives the read the cells have always made, bit for
bit; an AdamW state, after one step of the program's own train step, gives
the gradient of ``jax.grad`` behind the optimizer's global-norm clip. And the
reference's gradient is clipped as each optimizer clips it."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, weights  # noqa: E402
from benchmarks.drivers import train as driver  # noqa: E402

pytestmark = pytest.mark.compile_heavy


def _frozen_trace_leaves(opt_state):
    """The SGD read as it stood before ``first_gradient``, copied."""
    flat, _ = jax.tree_util.tree_flatten_with_path(opt_state)
    out = {}
    for p, v in flat:
        keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in p]
        if "trace" in keys and "params" in keys:
            out["/".join(keys[keys.index("params") + 1:])] = np.asarray(v)
    return out


def _tree(rng):
    """A few leaves of the C4 tree's kinds: trained kernels and biases,
    frozen BN (no slot), a stem the configuration fixes."""
    shapes = {"features/conv0/kernel": (7, 7, 3, 8),
              "features/bn0/gamma": (8,), "features/bn0/moving_var": (8,),
              "features/stage2/block0/conv1/kernel": (1, 1, 8, 16),
              "rpn_cls_score/kernel": (1, 1, 16, 6),
              "rpn_cls_score/bias": (6,)}
    w0 = {p: np.asarray(v) for p, v in weights.make(7, shapes).items()}
    grads = {p: (rng.randn(*s) * 4).astype(np.float32)
             for p, s in shapes.items()}

    def nest(flat):
        out = {}
        for p, v in flat.items():
            d = out
            for k in p.split("/")[:-1]:
                d = d.setdefault(k, {})
            d[p.split("/")[-1]] = jnp.asarray(v)
        return {"params": out}

    return w0, grads, nest


def test_an_sgd_state_gives_the_read_the_cells_made(rng):
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.train.optimizer import build_optimizer

    conf = manifest.load_json("configs", "c4_r101_coco")
    cfg = generate_config(conf["network"], conf["dataset"])
    w0, grads, nest = _tree(rng)
    params = nest(w0)
    tx = build_optimizer(cfg, params)
    _, state = tx.update(nest(grads), tx.init(params), params)
    wd = conf["spec"]["train"]["wd"]
    want = {p: t - wd * np.asarray(w0[p])
            for p, t in _frozen_trace_leaves(state).items()}
    got = driver.first_gradient(jax.device_get(state), w0, conf["spec"])
    assert sorted(got) == sorted(want) == [
        "features/stage2/block0/conv1/kernel", "rpn_cls_score/bias",
        "rpn_cls_score/kernel"]
    for p in want:
        assert np.array_equal(got[p], want[p]), p
    # what it is: the elementwise clip of the gradient, to rounding
    c = conf["spec"]["train"]["clip_gradient"]
    for p in want:
        np.testing.assert_allclose(got[p], np.clip(grads[p], -c, c),
                                   rtol=0, atol=1e-5)


def test_an_sgd_read_of_another_optimizers_state_raises(rng):
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.train.optimizer import build_optimizer

    cfg = generate_config("vitdet_b", "coco")
    w0, grads, nest = _tree(rng)
    params = nest(w0)
    tx = build_optimizer(cfg, params)
    _, state = tx.update(nest(grads), tx.init(params), params)
    with pytest.raises(RuntimeError, match="'trace' slot"):
        driver.first_gradient(state, w0, {"train": {"wd": 1e-4}})


def test_the_b1_read_is_the_programs():
    """``optax.adamw``'s decay, which the program's optimizer takes."""
    import inspect

    from mx_rcnn_tpu.config import generate_config

    b1 = driver.program_b1(generate_config("vitdet_b", "coco"))
    want = inspect.signature(optax.adamw).parameters["b1"].default
    assert b1 == pytest.approx(want, abs=1e-7)


def test_an_adamw_state_after_the_programs_step_gives_the_clipped_gradient(
        rng):
    """One step of ``train/step.py`` on a tiny ``vitdet_b`` (float32 compute,
    so that both sides round alike) against ``jax.grad`` of the same loss at
    the same key, behind ``optax.clip_by_global_norm`` over the trainable
    leaves. Tolerance: 1e-6 of each leaf's largest entry, about eight
    float32 roundings (the worst leaf reads under 1e-7 here): the two
    gradients come from two compiled programs that may sum in another
    order, and ``mu / (1 - b1)`` rounds twice more."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models import zoo
    from mx_rcnn_tpu.train.optimizer import (build_optimizer,
                                             effective_fixed_patterns,
                                             trainable_mask)
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step

    cfg = generate_config("vitdet_b", "synthetic", **{
        "image.pad_shape": (128, 128), "train.batch_images": 1,
        "train.compute_dtype": "f32", "network.vit_dim": 32,
        "network.vit_depth": 2, "network.vit_heads": 2,
        "network.vit_window": 4, "train.fpn_rpn_pre_nms_per_level": 64,
        "train.rpn_post_nms_top_n": 64, "train.batch_rois": 32,
        "train.max_gt_boxes": 8})
    assert cfg.train.optimizer == "adamw"
    model = zoo.build_model(cfg)
    params = zoo.init_params(model, cfg, jax.random.PRNGKey(0))
    batch = {
        "image": rng.randn(1, 128, 128, 3).astype(np.float32),
        "im_info": np.asarray([[128, 128, 1.0]], np.float32),
        "gt_boxes": np.asarray(
            [[[10, 10, 60, 90], [70, 20, 120, 70]] + [[0, 0, 0, 0]] * 6],
            np.float32),
        "gt_classes": np.asarray([[1, 2] + [0] * 6], np.int32),
        "gt_valid": np.asarray([[True, True] + [False] * 6])}
    key = jax.random.PRNGKey(3)
    tx = build_optimizer(cfg, params)
    state = create_train_state(params, tx)
    new, _ = make_train_step(model, cfg, donate=False)(state, batch, key)
    spec = {"train": {"optimizer": "adamw", "wd": cfg.train.wd,
                      "clip_gradient": cfg.train.clip_gradient}}
    got = driver.first_gradient(jax.device_get(new.opt_state), None, spec,
                                driver.program_b1(cfg))

    grads = jax.jit(jax.grad(lambda p: zoo.forward_train(
        model, p, batch, key, cfg)[0]))(params)
    mask = trainable_mask(params, effective_fixed_patterns(cfg))
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    keep, _ = jax.tree_util.tree_flatten(mask)
    trained = {weights.path_of(p): g for (p, g), k in zip(flat, keep) if k}
    clipped, _ = optax.clip_by_global_norm(cfg.train.clip_gradient).update(
        trained, optax.EmptyState())
    assert sorted(got) == sorted(clipped) and len(got) > 20
    norm = float(optax.global_norm(trained))
    assert norm > cfg.train.clip_gradient     # the clip is in force
    for p, want in clipped.items():
        want = np.asarray(want)
        np.testing.assert_allclose(got[p], want, rtol=0,
                                   atol=1e-6 * max(np.abs(want).max(), 1e-30),
                                   err_msg=p)


@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
@pytest.mark.parametrize("scale", [1e-3, 30.0])
def test_the_references_clip_is_the_optimizers(optimizer, scale, rng):
    """``follow``'s clip against optax's own, under and over the limit."""
    grads = {f"leaf{i}": (rng.randn(*s) * scale).astype(np.float32)
             for i, s in enumerate([(3, 5), (7,), (2, 2, 4)])}
    train = {"clip_gradient": 0.1 if optimizer == "adamw" else 5.0}
    if optimizer == "adamw":
        train["optimizer"] = "adamw"
        tx = optax.clip_by_global_norm(train["clip_gradient"])
    else:
        tx = optax.clip(train["clip_gradient"])
    want, _ = tx.update({k: jnp.asarray(v) for k, v in grads.items()},
                        tx.init(grads))
    got = driver.clip_like(grads, train)
    for k in grads:
        np.testing.assert_allclose(got[k], np.asarray(want[k]), rtol=1e-6,
                                   atol=0)
        assert got[k].dtype == np.float32
