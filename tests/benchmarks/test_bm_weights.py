"""Weights from the seed (benchmarks/weights.py): the three benchmarked
configurations' trees keep their values bit for bit against the rules as
they stood before the transformer leaves had any, and the attention trunk's
and the set-prediction decoder's trees find a rule for every leaf."""

import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, weights  # noqa: E402

SEED = 2 ** 31 + 9


def _frozen_leaf(key, path, shape):
    """The rules before ``scale``, ``pos_embed``, ``rel_pos_*`` and
    ``query_embed`` had any, copied as they were."""
    head_std = {"rpn_cls_score": 0.01, "rpn_bbox_pred": 0.01,
                "cls_score": 0.01, "bbox_pred": 0.001}
    parts = path.split("/")
    leaf, module = parts[-1], parts[-2] if len(parts) > 1 else ""
    if leaf == "kernel":
        if module in head_std:
            std = head_std[module]
        else:
            fan_in = 1
            for d in shape[:-1]:
                fan_in *= d
            std = (2.0 / fan_in) ** 0.5
        k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
        return std * jax.random.normal(k, shape, jnp.float32)
    if leaf == "gamma":
        return jnp.full(shape, 0.25 if module == "bn3" else 1.0, jnp.float32)
    if leaf == "moving_var":
        return jnp.full(shape, 4096.0 if module == "bn0" else 1.0,
                        jnp.float32)
    if leaf in ("bias", "beta", "moving_mean"):
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(f"no weight rule for leaf {path!r}")


def _abstract(network, dataset="coco", **over):
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.zoo import build_model, init_params

    cfg = generate_config(network, dataset, **over)
    model = build_model(cfg)
    return jax.eval_shape(lambda k: init_params(model, cfg, k),
                          jax.random.PRNGKey(0))


def _paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(weights.path_of(p), tuple(v.shape)) for p, v in flat]


@pytest.mark.parametrize("config", ["c4_r101_coco", "fpn_r101_coco",
                                    "mask_r101_fpn_coco"])
def test_the_benchmarked_trees_keep_every_value(config):
    conf = manifest.load_json("configs", config)
    abstract = _abstract(conf["network"], conf["dataset"])
    filled = weights.fill_tree(SEED, abstract)
    items = sorted(_paths(abstract))

    @jax.jit
    def frozen(key):
        return {p: _frozen_leaf(key, p, s) for p, s in items}

    want = frozen(weights.seed_key(SEED))
    flat, _ = jax.tree_util.tree_flatten_with_path(filled)
    assert len(flat) == len(want)
    for p, v in flat:
        path = weights.path_of(p)
        assert np.array_equal(np.asarray(v), np.asarray(want[path])), path


@pytest.mark.parametrize("network,kinds", [
    ("vitdet_b_mask", {"scale": 26, "pos_embed": 1}),
    ("detr_r50", {"scale": 31, "query_embed": 1})])
def test_the_transformer_trees_have_a_rule_for_every_leaf(network, kinds):
    """At the presets' own sizes, abstractly (no memory): every leaf of
    ``vitdet_b_mask`` (109.5M parameters) and ``detr_r50`` finds its rule."""
    items = _paths(_abstract(network))
    got = jax.eval_shape(lambda k: {p: weights._leaf(k, p, s)
                                    for p, s in items},
                         jax.random.PRNGKey(0))
    assert {p: tuple(v.shape) for p, v in got.items()} == dict(items)
    for leaf, n in kinds.items():
        assert sum(p.split("/")[-1] == leaf for p, _ in items) == n


def test_the_transformer_rules_give_their_published_values():
    """A tiny ``vitdet_b_mask`` tree filled for real, with the decomposed
    relative positions the published ViTDet adds to each block."""
    abstract = _abstract("vitdet_b_mask", "synthetic", **{
        "image.pad_shape": (128, 128), "network.vit_dim": 32,
        "network.vit_depth": 2, "network.vit_heads": 2,
        "network.vit_window": 4})
    shapes = dict(_paths(abstract))
    shapes.update({"features/block0/attn/rel_pos_h": (15, 16),
                   "features/block0/attn/rel_pos_w": (15, 16),
                   "query_embed": (100, 256)})
    made = weights.make(SEED, shapes)
    for p, v in made.items():
        v = np.asarray(v)
        leaf = p.split("/")[-1]
        if leaf == "scale":
            assert (v == 1.0).all(), p
        elif leaf in ("rel_pos_h", "rel_pos_w"):
            assert (v == 0.0).all(), p
        elif leaf == "pos_embed":
            assert v.std() == pytest.approx(0.02, rel=0.1)
        elif leaf == "query_embed":
            assert v.std() == pytest.approx(1.0, rel=0.05)
    again = weights.make(SEED, shapes)
    assert all(np.array_equal(made[p], again[p]) for p in shapes)
    other = weights.make(SEED + 1, shapes)
    assert not np.array_equal(made["features/pos_embed"],
                              other["features/pos_embed"])


def test_a_leaf_of_no_kind_still_raises():
    with pytest.raises(ValueError, match="no weight rule"):
        weights.make(SEED, {"features/block0/attn/unknown": (2,)})
