"""Weights from ``--seed``: one rule per kind of leaf, keyed by the leaf's
path, made on the device in one jitted call.

The harness fills the program's parameter tree with these (by path) and the
plain reference asks for the same paths on its own, so neither takes a weight
from the other. The recipe is the one users run (ImageNet-style trunk, frozen
BN); what the offline sandbox cannot load is assumed, and listed under
``assumed`` in the configuration files:

- conv / dense kernels: He normal (std = sqrt(2 / fan_in));
- the detection heads' output layers at the program's own init scale
  (``rpn_cls_score``, ``rpn_bbox_pred``, ``cls_score``: 0.01; ``bbox_pred``:
  0.001); biases zero;
- frozen BN: identity statistics, except that ``bn0`` carries the pixels'
  variance (64**2), as a pretrained stem does, and each block's last BN
  (``bn3``) has gamma 0.25, so that activations stay O(1..10) through the 33
  residual blocks in bfloat16;
- the transformer trees' leaves, each at its published initializer:
  ``scale`` (LayerNorm, and GroupNorm, which flax names alike) 1.0;
  ``pos_embed`` normal with std 0.02 (ViT's absolute positions, as
  ``models/vit.py`` initializes them); ``rel_pos_h`` / ``rel_pos_w`` 0
  (ViTDet's decomposed relative positions, ``rel_pos_zero_init``, Li et al.
  2022); ``query_embed`` normal with std 1.0 (DETR's object queries, a
  ``torch.nn.Embedding``, Carion et al. 2020).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

HEAD_STD = {"rpn_cls_score": 0.01, "rpn_bbox_pred": 0.01,
            "cls_score": 0.01, "bbox_pred": 0.001}
# leaves drawn from a normal of a fixed std, by leaf name
NORMAL_STD = {"pos_embed": 0.02, "query_embed": 1.0}
# leaves of a constant value, by leaf name
CONSTANT = {"scale": 1.0, "rel_pos_h": 0.0, "rel_pos_w": 0.0}


def _normal(key, path: str, shape, std: float):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return std * jax.random.normal(k, shape, jnp.float32)


def seed_key(seed: int):
    """A key from any whole number a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, path: str, shape):
    parts = path.split("/")
    leaf, module = parts[-1], parts[-2] if len(parts) > 1 else ""
    if leaf == "kernel":
        if module in HEAD_STD:
            std = HEAD_STD[module]
        else:
            fan_in = 1
            for d in shape[:-1]:
                fan_in *= d
            std = (2.0 / fan_in) ** 0.5
        return _normal(key, path, shape, std)
    if leaf in NORMAL_STD:
        return _normal(key, path, shape, NORMAL_STD[leaf])
    if leaf in CONSTANT:
        return jnp.full(shape, CONSTANT[leaf], jnp.float32)
    if leaf == "gamma":
        return jnp.full(shape, 0.25 if module == "bn3" else 1.0, jnp.float32)
    if leaf == "moving_var":
        return jnp.full(shape, 4096.0 if module == "bn0" else 1.0,
                        jnp.float32)
    if leaf in ("bias", "beta", "moving_mean"):
        return jnp.zeros(shape, jnp.float32)
    raise ValueError(f"no weight rule for leaf {path!r}")


def make(seed: int, shapes: dict) -> dict:
    """{path: shape} -> {path: float32 array}, one jitted call."""
    items = sorted((p, tuple(s)) for p, s in shapes.items())

    @jax.jit
    def build(key):
        return {p: _leaf(key, p, s) for p, s in items}

    return build(seed_key(seed))


def fill_tree(seed: int, abstract_tree):
    """The program's tree (from ``jax.eval_shape`` of its own init) filled by
    path: same structure, the benchmark's values."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    paths = [path_of(p) for p, _ in flat]
    made = make(seed, {p: leaf.shape for p, (_, leaf) in zip(paths, flat)})
    return jax.tree_util.tree_unflatten(treedef, [made[p] for p in paths])


def path_of(key_path) -> str:
    """'features/stage2/block0/conv1/kernel' (the leading 'params' dropped)."""
    keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in key_path]
    if keys and keys[0] == "params":
        keys = keys[1:]
    return "/".join(keys)
