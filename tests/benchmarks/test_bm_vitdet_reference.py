"""ViTDet's Mask R-CNN held to its plain reference (benchmarks/reference/
vitdet.py) on seeded weights at a small size (tests/benchmarks/
bm_tiny_vitdet.py: width 64, 8 blocks alternating windowed and global, 2
heads, an 8x8 token grid, 2 images, 64 rois). The relative-position tables
are seeded NON-zero (the benchmark's weights make them 0, ViTDet's init), so
the decomposed bias moves every forward compared here. The program computes
in float32 (``train.compute_dtype=f32``), so what is left between the two
sides is the order of float32 sums; each tolerance says what it allows for.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import compare, manifest, traffic, weights  # noqa: E402
from benchmarks.drivers import train as driver  # noqa: E402
from bm_tiny_vitdet import tiny_vitdet  # noqa: E402

pytestmark = pytest.mark.compile_heavy
SEED = 2 ** 31 + 11


def _tables_nonzero(tree: dict) -> dict:
    """The relative-position leaves drawn from a normal of std 0.3, by
    path, the same arrays whoever asks."""
    out = dict(tree)
    for k in sorted(tree):
        if k.rsplit("/", 1)[-1] in ("rel_pos_h", "rel_pos_w"):
            rs = np.random.RandomState(zlib_crc(k))
            out[k] = jnp.asarray(0.3 * rs.randn(*tree[k].shape), jnp.float32)
    return out


def zlib_crc(text: str) -> int:
    import zlib

    return zlib.crc32(text.encode()) & 0x7FFFFFFF


def _unflat(tree, flat: dict):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[weights.path_of(p)] for p, _ in leaves])


def _sides(window: int):
    from mx_rcnn_tpu.models.zoo import build_model, init_params

    conf = manifest.load_json("configs", "vitdet_b_mask_coco")
    t = tiny_vitdet()
    over = dict(t["overrides"], **{"train.compute_dtype": "f32",
                                   "network.vit_window": window})
    spec = dict(conf["spec"], **t["spec_overrides"])
    spec.update(compute_dtype="f32", vit_window=window)
    spec["train"] = dict(conf["spec"]["train"], **t["spec_overrides"]["train"])
    cfg = driver._program_config(conf, over)
    driver.check_spec(cfg, spec)
    ref = manifest.load_module("reference", conf["reference"])
    mix = dict(manifest.load_json("traffic", "train_packed_landscape"),
               **t["mix_overrides"])
    raw = traffic.make_roidb(mix, SEED)
    batch = driver.reference_batch(ref, [(0, False), (1, True)], raw, spec)
    model = build_model(cfg)
    abstract = jax.eval_shape(lambda k: init_params(model, cfg, k),
                              jax.random.PRNGKey(0))
    flat = _tables_nonzero(driver._flat(weights.fill_tree(SEED, abstract)))
    params = _unflat(abstract, {k: jnp.asarray(v) for k, v in flat.items()})
    p_ref = _tables_nonzero(weights.make(SEED, ref.param_shapes(spec)))
    return dict(cfg=cfg, spec=spec, ref=ref, model=model, params=params,
                p_ref=p_ref, batch=batch)


@pytest.fixture(scope="module")
def both():
    from mx_rcnn_tpu.models.zoo import forward_train

    s = _sides(4)
    jbatch = {k: jnp.asarray(v) for k, v in s["batch"].items()}
    m = s["cfg"].train.mask_gt_resolution
    jbatch["gt_masks"] = jnp.ones((2, s["spec"]["max_gt_boxes"], m, m),
                                  jnp.uint8)
    key = jax.random.PRNGKey(3)
    trainer = s["ref"].Trainer(s["spec"], s["p_ref"])
    want = trainer.grads(s["batch"], key)
    (_, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: forward_train(s["model"], p, jbatch, key, s["cfg"]),
        has_aux=True))(s["params"])
    return dict(s, jbatch=jbatch, key=key, trainer=trainer, want=want,
                aux=aux, grads=driver._flat(grads))


def test_both_sides_hold_the_same_leaves_and_the_tables_are_not_nought(both):
    prog = driver._flat(both["params"])
    assert set(prog) == set(both["p_ref"])
    for k, v in prog.items():
        assert v.shape == tuple(both["p_ref"][k].shape), k
    tables = [k for k in prog if k.endswith(("rel_pos_h", "rel_pos_w"))]
    assert len(tables) == 16
    assert prog["features/block1/attn/rel_pos_h"].shape == (15, 32)  # 8x8
    assert prog["features/block0/attn/rel_pos_h"].shape == (7, 32)   # 4x4
    for k in tables:
        np.testing.assert_array_equal(prog[k], np.asarray(both["p_ref"][k]))
        assert np.abs(prog[k]).max() > 0.5


@pytest.mark.parametrize("window", [4, 3])
def test_the_pyramid(window):
    """Trunk and simple feature pyramid of the first image, windows of 4
    (the grid divides) and 3 (the 8x8 grid padded to 9x9): every level to
    1e-4 of its largest value (float32 sums of 64-wide products in
    another order through 8 blocks and the pyramid's LayerNorms); the
    same with the tables zeroed is off by more than 1e-2, so the bias is
    what is compared."""
    s = _sides(window)
    image = jnp.asarray(s["batch"]["image"][:1])
    got = jax.jit(lambda p, x: s["model"].apply(p, x, method="extract"))(
        s["params"], image)
    ref, spec = s["ref"], s["spec"]
    want = jax.jit(lambda p, x: ref.pyramid(p, ref.trunk(p, x, spec)))(
        s["p_ref"], image[0])
    for lv in (2, 3, 4, 5, 6):
        w = np.asarray(want[lv])
        np.testing.assert_allclose(np.asarray(got[lv][0]), w, rtol=0,
                                   atol=1e-4 * float(np.abs(w).max()))
    flat = {k: (jnp.zeros_like(v) if k.endswith(("rel_pos_h", "rel_pos_w"))
                else v) for k, v in s["p_ref"].items()}
    off = jax.jit(lambda p, x: ref.pyramid(p, ref.trunk(p, x, spec)))(
        flat, image[0])
    w = np.asarray(want[4])
    assert np.abs(np.asarray(off[4]) - w).max() > 1e-2 * np.abs(w).max()


def test_the_five_losses(both):
    """The whole step's forward on the same key: the four detection losses,
    the mask loss and their sum to 1e-4 relative (float32 terms summed in
    another order)."""
    aux = both["aux"]
    _, want_parts, _ = both["want"]
    got_parts = [float(aux[k]) for k in (
        "rpn_cls_loss", "rpn_bbox_loss", "rcnn_cls_loss", "rcnn_bbox_loss",
        "mask_loss")]
    assert want_parts.shape == (5,) and want_parts[4] > 0.1
    np.testing.assert_allclose(got_parts, want_parts, rtol=1e-4)
    np.testing.assert_allclose(float(aux["total_loss"]), both["want"][0],
                               rtol=1e-4)


def test_the_first_gradient_of_every_leaf(both):
    """Every leaf's gradient norm to 1e-3 of the reference's (or of the
    median leaf's: ``compare.leaf_gaps``), the tables' among them: not
    nought, and with the bias left out (the planted fault "f32/no_relpos")
    they read nought: the largest of their gaps 1.0 (the others are their
    norm over the median leaf's)."""
    want_grads = both["want"][2]
    got = compare.norms({k: v for k, v in both["grads"].items()
                         if k in want_grads})
    gaps = compare.leaf_gaps(got, compare.norms(want_grads))
    assert set(gaps) == set(want_grads)
    assert compare.worst(gaps)[0] < 1e-3, compare.worst(gaps)
    tables = [k for k in want_grads if k.endswith(("rel_pos_h", "rel_pos_w"))]
    assert all(np.abs(np.asarray(want_grads[k])).max() > 0 for k in tables)
    off = both["ref"].Trainer(both["spec"], both["p_ref"], "f32/no_relpos")
    _, _, without = off.grads(both["batch"], both["key"])
    gaps = compare.leaf_gaps(compare.norms(without),
                             compare.norms(want_grads))
    assert all(not np.asarray(without[k]).any() for k in tables)
    assert max(gaps[k] for k in tables) == pytest.approx(1.0)
    assert min(gaps[k] for k in tables) > 1e-2


def test_one_update_is_the_references(both):
    """The program's optimizer (``build_optimizer``: AdamW, no clip, weight
    decay 0.1 off the norms and pos_embed, layer-wise rates 0.7) on the
    reference's first gradient, against the reference's update of it: every
    leaf's step to 1e-6 of its largest element (float32 AdamW in another
    order of multiplications); a step of block 0's norm (no decay, layer 1
    of 8 + 1) is lr x 0.7^8."""
    from mx_rcnn_tpu.train.optimizer import build_optimizer

    trainer = both["ref"].Trainer(both["spec"], both["p_ref"])
    grads = {k: jnp.asarray(v) for k, v in both["want"][2].items()}
    trainer.update(grads)
    tx = build_optimizer(both["cfg"], both["params"])
    g_tree = _unflat(both["params"], grads)
    updates, _ = jax.jit(tx.update)(g_tree, tx.init(both["params"]),
                                    both["params"])
    got = driver._flat(updates)
    for k, step in trainer.step.items():
        want = -np.asarray(step)
        np.testing.assert_allclose(got[k], want, rtol=0,
                                   atol=1e-6 * float(np.abs(want).max()),
                                   err_msg=k)
    lr = both["spec"]["train"]["lr"]
    ln = np.abs(got["features/block0/norm1/scale"]).max()
    assert ln == pytest.approx(lr * 0.7 ** 8, rel=1e-3)  # |m/sqrt(v)| = 1


def test_drop_path_given_the_same_draws():
    """Stochastic depth at rate 0.5: the program's trunk and pyramid given
    ``drop_path_keep``'s factors against the
    reference's trunk given the same factors, to 1e-4 of the largest value;
    the draws drop some branches (a factor 0) and scale the kept ones by
    1/(1 - their block's rate)."""
    from mx_rcnn_tpu.models.vit import drop_path_keep

    s = _sides(4)
    keep = drop_path_keep(jax.random.PRNGKey(9), 1, 8, 0.5)
    rates = np.linspace(0, 0.5, 8)
    k = np.asarray(keep)
    assert (k == 0).any() and (k[0] == 1).all()
    kept = k > 0
    np.testing.assert_allclose(k[kept], np.broadcast_to(
        1 / (1 - rates)[:, None, None], k.shape)[kept], rtol=1e-6)
    image = jnp.asarray(s["batch"]["image"][:1])
    got = jax.jit(lambda p, x, kp: s["model"].apply(
        p, x, kp, method=lambda m, x, kp: m.neck(m.features(x, None, kp))))(
            s["params"], image, keep)
    ref, spec = s["ref"], s["spec"]
    want = jax.jit(lambda p, x, kp: ref.pyramid(p, ref.trunk(
        p, x, spec, keep=kp)))(s["p_ref"], image[0], keep[:, :, 0])
    w = np.asarray(want[4])
    np.testing.assert_allclose(np.asarray(got[4][0]), w, rtol=0,
                               atol=1e-4 * float(np.abs(w).max()))


@pytest.mark.parametrize("size", [3, 4, 14])
def test_the_offsets_tables_agree(size):
    """The reference's ``rel_positions`` and the program's
    ``rel_pos_table`` read the same row for every (query, key) offset of a
    window or a grid."""
    from mx_rcnn_tpu.models.vit import rel_pos_table

    ref = manifest.load_module("reference", "vitdet")
    table = jnp.asarray(np.random.RandomState(size).randn(2 * size - 1, 4),
                        jnp.float32)
    np.testing.assert_array_equal(np.asarray(ref.rel_positions(table, size)),
                                  np.asarray(rel_pos_table(table, size)))


def test_the_references_resampling_keeps_the_ends_and_a_same_length_table():
    """``resize_rows`` (the planted faults' window 8 reads a table made for
    14) takes a table of the asked length as it is, and keeps the first
    and last rows' values within the linear ramp between them."""
    ref = manifest.load_module("reference", "vitdet")
    table = jnp.asarray(np.arange(27 * 2, dtype=np.float32).reshape(27, 2))
    assert ref.resize_rows(table, 27) is table
    got = np.asarray(ref.resize_rows(table, 15))
    assert got.shape == (15, 2)
    assert np.all(np.diff(got[:, 0]) > 0)
    assert got[0, 0] >= table[0, 0] and got[-1, 0] <= table[-1, 0]
