"""The first gradient read from the AdamW state of the ViTDet recipe's own
step (benchmarks/drivers/train.py::first_gradient): layer-wise decay and
the no-decay set scale the update after Adam's moments, so the read is
still ``jax.grad`` behind the clip. Two clips: none, as the recipe sets
(``clip_gradient`` inf, which the program skips), and a global-norm clip
in force (DETR's 0.1), so that the read is held where it clips too."""

import os
import sys

import jax
import numpy as np
import optax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import weights  # noqa: E402
from benchmarks.drivers import train as driver  # noqa: E402

pytestmark = pytest.mark.compile_heavy


@pytest.mark.parametrize("clip", [float("inf"), 0.1], ids=["recipe", "0.1"])
def test_the_recipes_adamw_state_gives_the_clipped_gradient(clip, rng):
    """One step of ``train/step.py`` on a tiny ``vitdet_b`` (float32
    compute, the preset's layer decay 0.7 and no-decay set) against
    ``jax.grad`` of the same loss at the same key, behind the clip.
    Tolerance as the pre-recipe test's: 1e-6 of each leaf's largest
    entry, about eight float32 roundings."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models import zoo
    from mx_rcnn_tpu.train.optimizer import (build_optimizer,
                                             effective_fixed_patterns,
                                             trainable_mask)
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step

    over = {"image.pad_shape": (128, 128), "train.batch_images": 1,
            "train.compute_dtype": "f32", "network.vit_dim": 32,
            "network.vit_depth": 2, "network.vit_heads": 2,
            "network.vit_window": 4, "train.fpn_rpn_pre_nms_per_level": 64,
            "train.rpn_post_nms_top_n": 64, "train.batch_rois": 32,
            "train.max_gt_boxes": 8}
    if np.isfinite(clip):
        over["train.clip_gradient"] = clip
    cfg = generate_config("vitdet_b", "synthetic", **over)
    assert cfg.train.optimizer == "adamw" and cfg.train.layer_decay == 0.7
    assert cfg.train.clip_gradient == clip
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
    clipped, _ = optax.clip_by_global_norm(clip).update(
        trained, optax.EmptyState())
    assert sorted(got) == sorted(clipped) and len(got) > 20
    norm = float(optax.global_norm(trained))
    assert (norm > clip) == np.isfinite(clip)   # the clip in force, or none
    for p, want in clipped.items():
        want = np.asarray(want)
        np.testing.assert_allclose(got[p], want, rtol=0,
                                   atol=1e-6 * max(np.abs(want).max(), 1e-30),
                                   err_msg=p)
