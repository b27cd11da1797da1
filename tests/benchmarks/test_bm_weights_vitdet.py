"""Weights from the seed (benchmarks/weights.py) for the ViTDet recipe's
tree at the preset's own size, abstractly (no memory): every leaf finds
its rule, and the tree has the recipe's norms and position tables."""

import os
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import weights  # noqa: E402


def test_the_recipe_tree_has_a_rule_for_every_leaf():
    """``vitdet_b_mask`` (113.9M parameters): LayerNorm ``scale`` leaves,
    24 in the blocks (no norm after the last), 9 in the pyramid (the 4x
    path's and each level's two convs), 8 in the heads (4conv1fc and the
    mask head's four convs); one ``pos_embed``; a ``rel_pos_h`` table in
    each of the 12 blocks."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.zoo import build_model, init_params

    cfg = generate_config("vitdet_b_mask", "coco")
    model = build_model(cfg)
    tree = jax.eval_shape(lambda k: init_params(model, cfg, k),
                          jax.random.PRNGKey(0))
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    items = [(weights.path_of(p), tuple(v.shape)) for p, v in flat]
    got = jax.eval_shape(lambda k: {p: weights._leaf(k, p, s)
                                    for p, s in items},
                         jax.random.PRNGKey(0))
    assert {p: tuple(v.shape) for p, v in got.items()} == dict(items)
    kinds = {"scale": 41, "pos_embed": 1, "rel_pos_h": 12, "rel_pos_w": 12}
    for leaf, n in kinds.items():
        assert sum(p.split("/")[-1] == leaf for p, _ in items) == n, leaf
    assert 113.5e6 < sum(v.size for _, v in flat) < 114.5e6
