"""flatcore (train/flatcore.py): flat parameter/optimizer-state storage.

Parity gates for the flat update path: the flat-mode train step must match
tree mode (f32 CPU, bit-for-bit for SGD — the update is purely elementwise
— and to reduction-order tolerance for AdamW's global-norm clip), frozen
params must stay bit-identical, TP/PP configs must route back to the
per-leaf path, and checkpoints must interchange between modes bit-for-bit.
The structural kernel-count proof (the ~6 ms many-buffer floor's fix,
PERF.md r6) runs on the CPU backend so it survives TPU outages.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.compile_heavy

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models.faster_rcnn import build_model, forward_train, init_params
from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
from mx_rcnn_tpu.train import flatcore
from mx_rcnn_tpu.train.optimizer import build_optimizer, trainable_mask
from mx_rcnn_tpu.train.step import create_train_state, make_train_step


def _cfg(**train_over):
    """64^2 f32 micro-config (the test_train_step accum-test shapes)."""
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
        train=replace(cfg.train, **{"compute_dtype": "f32", **train_over}))


def _batch(b):
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


def _fake_params(layers=4, frozen=True):
    """Small hand-built tree with frozen (conv0/bn gamma-beta) and
    trainable leaves — update-only tests need no model build."""
    rs = np.random.RandomState(0)
    tree = {"conv0": {"kernel": rs.randn(3, 3, 3, 8).astype(np.float32)}} \
        if frozen else {}
    for i in range(layers):
        tree[f"layer{i:02d}"] = {
            "kernel": rs.randn(8, 8).astype(np.float32),
            "bias": rs.randn(8).astype(np.float32),
        }
    if frozen:
        tree["norm"] = {"gamma": np.ones(8, np.float32),
                        "beta": np.zeros(8, np.float32)}
    tree["bbox_pred"] = {"kernel": rs.randn(8, 8).astype(np.float32),
                         "bias": rs.randn(8).astype(np.float32)}
    return {"params": tree}


def _grads_like(params, scale=1e-2):
    key = jax.random.PRNGKey(7)
    return jax.tree_util.tree_map(
        lambda p: (jax.random.normal(
            jax.random.fold_in(key, p.size), p.shape) * scale
        ).astype(p.dtype), params)


def _leaves_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# segment table
# ---------------------------------------------------------------------------

def test_segment_table_round_trip_and_dtype_segregation():
    params = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
              "b": np.arange(4, dtype=np.int32),
              "c": {"d": np.ones((3, 2), np.float32)}}
    mask = {"a": True, "b": False, "c": {"d": True}}
    table = flatcore.SegmentTable(params, mask)
    bufs = table.flatten(params)
    assert set(bufs) == {"float32", "int32"}
    assert bufs["float32"].shape == (12,) and bufs["int32"].shape == (4,)
    _leaves_equal(table.unflatten(bufs), params)
    # offsets follow the canonical flatten spec order ('a' before 'c/d')
    np.testing.assert_array_equal(
        table.segment_view(bufs, "a"), params["a"])
    np.testing.assert_array_equal(
        table.segment_view(bufs, "c/d"), params["c"]["d"])
    masks = table.mask_buffers()
    assert masks["float32"].sum() == 12  # both f32 leaves trainable
    assert masks["int32"].sum() == 0     # 'b' frozen


def test_segment_table_rejects_mismatched_tree():
    params = {"a": np.ones((2, 2), np.float32)}
    table = flatcore.SegmentTable(params, {"a": True})
    with pytest.raises(ValueError, match="leaves"):
        table.flatten({"a": np.ones((2, 2), np.float32),
                       "b": np.ones(3, np.float32)})


# ---------------------------------------------------------------------------
# update-only parity (no forward — fast)
# ---------------------------------------------------------------------------

def test_flat_sgd_update_bit_exact_and_state_round_trip():
    cfg = _cfg()
    params = _fake_params()
    grads = _grads_like(params)
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    core = flatcore.FlatCore(cfg, params, steps_per_epoch=10)

    s_tree = create_train_state(params, tx)
    s_flat = core.init_state(params)
    fgrads = {d: jnp.asarray(b) for d, b in core.table.flatten(grads).items()}
    for _ in range(3):
        s_tree = s_tree.apply_gradients(grads)
        s_flat = s_flat.apply_gradients(fgrads)

    p_flat, o_flat = core.tree_state(s_flat)
    _leaves_equal(s_tree.params, p_flat)       # params bit-for-bit
    _leaves_equal(s_tree.opt_state, o_flat)    # momentum + count bit-for-bit

    # frozen leaves (conv0 kernel, gamma/beta) never moved
    for name in ("conv0", "norm"):
        _leaves_equal(params["params"][name], p_flat["params"][name])

    # tree -> flat -> tree is the identity
    rt_p, rt_o = core.tree_state(core.flatten_state(s_tree))
    _leaves_equal(s_tree.params, rt_p)
    _leaves_equal(s_tree.opt_state, rt_o)


def test_flat_adamw_update_matches_tree():
    """AdamW differs from the tree path only in the global-norm reduction
    order (per-buffer partial sums vs per-leaf) — float-rounding-level."""
    cfg = _cfg(optimizer="adamw", lr=1e-4, clip_gradient=0.1)
    params = _fake_params()
    grads = _grads_like(params)
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    core = flatcore.FlatCore(cfg, params, steps_per_epoch=10)

    s_tree = create_train_state(params, tx)
    s_flat = core.init_state(params)
    fgrads = {d: jnp.asarray(b) for d, b in core.table.flatten(grads).items()}
    for _ in range(3):
        s_tree = s_tree.apply_gradients(grads)
        s_flat = s_flat.apply_gradients(fgrads)

    p_flat, o_flat = core.tree_state(s_flat)
    for a, b in zip(jax.tree_util.tree_leaves(s_tree.params),
                    jax.tree_util.tree_leaves(p_flat)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    # frozen leaves are EXACT even under adamw (hard-zero update)
    _leaves_equal(params["params"]["conv0"], p_flat["params"]["conv0"])
    # moments/counts line up leaf-for-leaf
    for a, b in zip(jax.tree_util.tree_leaves(s_tree.opt_state),
                    jax.tree_util.tree_leaves(o_flat)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-7)


def test_flat_bf16_slot_dtype():
    """opt_state_dtype=bfloat16 flows into the flat trace buffer (the
    memory lever survives the flat layout)."""
    cfg = _cfg(opt_state_dtype="bfloat16")
    params = _fake_params()
    core = flatcore.FlatCore(cfg, params, steps_per_epoch=10)
    s_flat = core.init_state(params)
    assert s_flat.slots[0]["float32"].dtype == jnp.bfloat16
    fgrads = {d: jnp.asarray(b)
              for d, b in core.table.flatten(_grads_like(params)).items()}
    s_flat = s_flat.apply_gradients(fgrads)
    # conversion reproduces optax's cast-stored trace bit-for-bit
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    s_tree = create_train_state(params, tx).apply_gradients(
        _grads_like(params))
    _, o_flat = core.tree_state(s_flat)
    _leaves_equal(s_tree.opt_state, o_flat)


# ---------------------------------------------------------------------------
# full-step parity (forward + backward through the flat buffers)
# ---------------------------------------------------------------------------

def test_flat_full_step_bit_exact_sgd():
    """The exactness gate: a full fwd+bwd+update train step in flat mode
    reproduces tree mode bit-for-bit on the f32 CPU backend (like the
    multi-step-dispatch gate), frozen-BN/stem params included."""
    cfg = _cfg()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    core = flatcore.FlatCore(cfg, params, steps_per_epoch=10)
    batch = _batch(1)
    rng = jax.random.PRNGKey(11)

    tree_step = make_train_step(model, cfg, donate=False)
    s_tree, m_tree = tree_step(create_train_state(params, tx), batch, rng)
    s_tree, _ = tree_step(s_tree, batch, jax.random.PRNGKey(12))

    flat_step = make_train_step(model, cfg, donate=False, flat_core=core)
    s_flat, m_flat = flat_step(core.init_state(params), batch, rng)
    s_flat, _ = flat_step(s_flat, batch, jax.random.PRNGKey(12))

    np.testing.assert_allclose(float(m_tree["TotalLoss"]),
                               float(m_flat["TotalLoss"]), rtol=1e-6)
    p_flat, o_flat = core.tree_state(s_flat)
    _leaves_equal(s_tree.params, p_flat)
    _leaves_equal(s_tree.opt_state, o_flat)
    assert int(s_flat.step) == 2 and int(s_flat.count) == 2

    # frozen-mask coverage on the real model: every frozen leaf identical
    mask = trainable_mask(params, cfg.network.fixed_param_patterns)
    for (path, old), m in zip(jax.tree_util.tree_leaves_with_path(params),
                              jax.tree_util.tree_leaves(mask)):
        if not m:
            new = p_flat
            for entry in path:
                new = new[entry.key]
            np.testing.assert_array_equal(np.asarray(old), np.asarray(new),
                                          err_msg=f"frozen moved: {path}")


def test_flat_multi_step_dispatch_matches_sequential():
    """multi_step_dispatch scans the FLAT state: K=2 stacked batches
    reproduce two sequential flat dispatches bit-for-bit (f32 CPU)."""
    cfg1 = _cfg()
    cfgK = _cfg(multi_step_dispatch=2)
    model = build_model(cfg1)
    params = init_params(model, cfg1, jax.random.PRNGKey(0))
    core = flatcore.FlatCore(cfg1, params, steps_per_epoch=10)
    rng = jax.random.PRNGKey(9)
    b0, b1 = _batch(1), _batch(1)
    b1 = {**b1, "image": b1["image"] + 0.5}

    multi_step = make_train_step(model, cfgK, donate=False, flat_core=core)
    stacked = {k: jnp.stack([b0[k], b1[k]]) for k in b0}
    s_multi, _ = multi_step(core.init_state(params), stacked, rng)

    single = make_train_step(model, cfg1, donate=False, flat_core=core)
    keys = jax.random.split(rng, 2)
    s_seq = core.init_state(params)
    s_seq, _ = single(s_seq, b0, keys[0])
    s_seq, _ = single(s_seq, b1, keys[1])

    assert int(s_multi.step) == 2
    for d in s_multi.flat:
        np.testing.assert_allclose(np.asarray(s_multi.flat[d]),
                                   np.asarray(s_seq.flat[d]),
                                   rtol=1e-5, atol=1e-6)


def test_flat_dp_step_matches_single_device():
    """2-way DP over flat buffers == single device on the same batch: the
    gradient allreduce is ONE psum per buffer and changes nothing."""
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    cfg = _cfg(batch_images=2)
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    core = flatcore.FlatCore(cfg, params, steps_per_epoch=10)
    batch = _batch(2)
    rng = jax.random.PRNGKey(5)

    single = make_train_step(model, cfg, donate=False, flat_core=core)
    s1, m1 = single(core.init_state(params), batch, rng)

    mesh = create_mesh("2")
    dp = make_train_step(model, cfg, mesh=mesh, donate=False,
                         flat_core=core)
    s2, m2 = dp(core.init_state(params), shard_batch(batch, mesh), rng)

    np.testing.assert_allclose(float(m1["TotalLoss"]),
                               float(m2["TotalLoss"]), rtol=1e-4)
    for d in s1.flat:
        np.testing.assert_allclose(np.asarray(s1.flat[d]),
                                   np.asarray(s2.flat[d]),
                                   rtol=2e-3, atol=2e-5)


# ---------------------------------------------------------------------------
# routing: TP/PP keep the per-leaf path
# ---------------------------------------------------------------------------

def test_flat_mode_routing():
    from dataclasses import replace

    from jax.sharding import PartitionSpec as P

    from mx_rcnn_tpu.parallel.partition import flat_segment_specs

    cfg = _cfg(flat_params=True)
    assert flatcore.flat_mode_for(cfg)
    assert not flatcore.flat_mode_for(_cfg())  # knob off
    tp = cfg.with_updates(network=replace(cfg.network, tensor_parallel=True))
    assert not flatcore.flat_mode_for(tp)
    pp = cfg.with_updates(network=replace(cfg.network, pp_stages=2))
    assert not flatcore.flat_mode_for(pp)

    params = _fake_params()
    repl = jax.tree_util.tree_map(lambda _: P(), params)
    specs = flat_segment_specs(params, repl)
    assert specs == {"float32": P()}  # replicated tree -> flat buffers ok
    assert flatcore.flat_mode_for(cfg, params=params, param_specs=repl)

    sharded = jax.tree_util.tree_map(lambda _: P(), params)
    sharded["params"]["layer00"]["kernel"] = P(None, "model")
    assert flat_segment_specs(params, sharded) is None
    assert not flatcore.flat_mode_for(cfg, params=params,
                                      param_specs=sharded)


# ---------------------------------------------------------------------------
# structural proof: kernel-count collapse (CPU backend, outage-proof)
# ---------------------------------------------------------------------------

_ARITH = {"fusion", "multiply", "add", "subtract", "divide", "sqrt",
          "rsqrt", "power", "select", "clamp", "maximum", "minimum",
          "reduce", "negate"}


def _module_arith(text):
    """Arithmetic instructions across the whole compiled module (fusion
    bodies included — on CPU the per-leaf structure lives inside them)."""
    n = 0
    for m in re.finditer(r"=\s*[a-z0-9_\[\],\. ]*?\b([a-z][a-z0-9\-]*)\(",
                         text):
        if m.group(1) in _ARITH:
            n += 1
    return n


def _entry_fusions(text):
    m = re.search(r"^ENTRY [^{]*\{(.*?)^\}", text, re.S | re.M)
    return sum(1 for line in m.group(1).splitlines() if " fusion(" in line)


@pytest.mark.parametrize("opt", ["sgd", "adamw"])
def test_flat_update_kernel_count_collapses(opt):
    """The compiled flat update is O(1) kernels in the leaf count — a
    dozen or so fused kernels at the program's top level and a few dozen
    arithmetic instructions total — while the per-leaf path scales with
    the tree (hundreds of instructions for a ~100-leaf tree). Same method
    as the packed-RPN 5-conv→1-conv HLO count: structure of the COMPILED
    program on the CPU backend — a count, not a speed."""
    over = {"optimizer": opt}
    if opt == "adamw":
        over.update(lr=1e-4, clip_gradient=0.1)
    cfg = _cfg(**over)
    params = _fake_params(layers=48)  # ~100 leaves: 'hundreds' per-leaf
    grads = _grads_like(params)
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    core = flatcore.FlatCore(cfg, params, steps_per_epoch=10)
    fgrads = {d: jnp.asarray(b) for d, b in core.table.flatten(grads).items()}

    tree_fn = jax.jit(lambda s, g: s.apply_gradients(g), donate_argnums=(0,))
    flat_fn = jax.jit(lambda s, g: s.apply_gradients(g), donate_argnums=(0,))
    tree_txt = tree_fn.lower(create_train_state(params, tx),
                             grads).compile().as_text()
    flat_txt = flat_fn.lower(core.init_state(params),
                             fgrads).compile().as_text()

    flat_arith = _module_arith(flat_txt)
    tree_arith = _module_arith(tree_txt)
    # The absolute bound is what THIS XLA emits (jaxlib 0.9.0, CPU): 4
    # entry fusions for flat SGD, 13 for flat AdamW (its clip-by-global-
    # norm and bias-correction scalars are no longer folded into the
    # update fusions) against 198 / 490 per-leaf. What it guards is the
    # collapse — a flat update that is O(1) in the leaf count — so the
    # ratio is asserted beside it.
    flat_fus, tree_fus = _entry_fusions(flat_txt), _entry_fusions(tree_txt)
    assert flat_fus <= 16, flat_txt[:2000]
    assert tree_fus >= 10 * flat_fus, (tree_fus, flat_fus)
    assert flat_arith <= 40, f"flat update grew: {flat_arith} arith ops"
    assert tree_arith >= 200, f"per-leaf baseline changed: {tree_arith}"
    assert tree_arith >= 10 * flat_arith, (tree_arith, flat_arith)


# ---------------------------------------------------------------------------
# checkpoint interchange (tree form on disk, both directions, sync + async)
# ---------------------------------------------------------------------------

def test_checkpoint_round_trip_between_modes(tmp_path):
    """A checkpoint saved from a flat-mode run loads into a tree-mode run
    bit-for-bit and vice versa — including the async (orbax) writer. Both
    modes run the identical SGD trajectory, save, and the loaded states
    are compared cross-mode."""
    from mx_rcnn_tpu.train.checkpoint import (
        CheckpointWriter, load_checkpoint, save_checkpoint)

    cfg = _cfg()
    params = _fake_params()
    grads = _grads_like(params)
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    core = flatcore.FlatCore(cfg, params, steps_per_epoch=10)
    kw = dict(means=cfg.train.bbox_means, stds=cfg.train.bbox_stds,
              num_classes=2)  # bbox_pred kernel is 8-wide = 2 classes x 4

    s_tree = create_train_state(params, tx).apply_gradients(grads)
    fgrads = {d: jnp.asarray(b) for d, b in core.table.flatten(grads).items()}
    s_flat = core.init_state(params).apply_gradients(fgrads)

    # flat-mode save goes through tree_state: async writer, tree form
    writer = CheckpointWriter()
    p_save, o_save = core.tree_state(s_flat)
    writer.save(str(tmp_path / "flat"), 1, p_save, o_save, **kw)
    writer.close()
    # tree-mode save: the unchanged sync path
    save_checkpoint(str(tmp_path / "tree"), 1, s_tree.params,
                    s_tree.opt_state, **kw)

    tmpl = {"params": params}
    p_from_flat, o_from_flat = load_checkpoint(
        str(tmp_path / "flat"), 1, template=tmpl,
        opt_state_template=tx.init(params), **kw)
    p_from_tree, o_from_tree = load_checkpoint(
        str(tmp_path / "tree"), 1, template=tmpl,
        opt_state_template=tx.init(params), **kw)

    # on-disk forms are interchangeable: both loads are bit-identical
    _leaves_equal(p_from_flat, p_from_tree)
    _leaves_equal(o_from_flat, o_from_tree)

    # flat-saved checkpoint resumes a TREE run == the live tree state
    # (modulo the bbox_pred fold/unfold both loads share)
    resumed_tree = create_train_state(p_from_flat, tx).replace(
        opt_state=o_from_flat)
    _leaves_equal(resumed_tree.opt_state, s_tree.opt_state)

    # tree-saved checkpoint resumes a FLAT run == the live flat state
    resumed_flat = core.flatten_state(
        create_train_state(p_from_tree, tx).replace(
            opt_state=o_from_tree, step=jnp.asarray(1, jnp.int32)))
    for d in s_flat.slots[0]:
        np.testing.assert_array_equal(
            np.asarray(resumed_flat.slots[0][d]),
            np.asarray(s_flat.slots[0][d]))
    assert int(resumed_flat.count) == int(s_flat.count)


def test_fit_detector_flat_smoke(tmp_path):
    """End-to-end: fit_detector with train.flat_params trains, saves a
    TREE-form checkpoint (loadable with plain load_checkpoint + an optax
    template), and returns a host param tree."""
    from dataclasses import replace

    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset
    from mx_rcnn_tpu.tools.train import fit_detector
    from mx_rcnn_tpu.train.checkpoint import load_checkpoint

    cfg = _cfg(flat_params=True, flip=False, lr_step=(100,))
    cfg = cfg.with_updates(image=replace(cfg.image, scales=((64, 64),)))
    ds = SyntheticDataset("train", num_images=3, image_size=64,
                          max_objects=1, min_size_frac=3, max_size_frac=2)
    history = []
    final = fit_detector(cfg, ds.gt_roidb(), prefix=str(tmp_path / "flat"),
                         end_epoch=1, frequent=1000, seed=0, mesh_spec="1",
                         epoch_callback=lambda e, s, b: history.append(
                             (int(s.step), b.get()["TotalLoss"])))
    assert len(history) == 1 and history[0][0] == 3, history
    assert np.isfinite(history[0][1])
    # checkpoint is in tree form: restores against a tree template
    model = build_model(cfg)
    tmpl = init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, tmpl, steps_per_epoch=3)
    loaded, opt = load_checkpoint(
        str(tmp_path / "flat"), 1, template={"params": tmpl},
        opt_state_template=tx.init(tmpl),
        means=cfg.train.bbox_means, stds=cfg.train.bbox_stds,
        num_classes=cfg.dataset.num_classes)
    assert opt is not None
    _leaves_equal(jax.tree_util.tree_map(lambda x: np.asarray(x).shape,
                                         loaded),
                  jax.tree_util.tree_map(lambda x: np.asarray(x).shape,
                                         final))
