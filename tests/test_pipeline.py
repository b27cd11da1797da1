"""Pipeline parallelism (parallel/pipeline.py + the staged ViT backbone).

The GPipe schedule must be a pure re-ordering: pipelined forward AND
backward match the sequential stage composition exactly (float32). The
reference has no model parallelism (SURVEY.md §3.2) — this is TPU-native
surface like TP/SP.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.compile_heavy

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import zoo
from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
from mx_rcnn_tpu.parallel.pipeline import pipeline_apply


def _toy(rng, s=4):
    w = jnp.asarray(rng.randn(s, 16, 16) * 0.3, jnp.float32)
    b = jnp.asarray(rng.randn(s, 16) * 0.1, jnp.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    def sequential(params, x):
        y = x
        for i in range(s):
            y = stage_fn(jax.tree.map(lambda a: a[i], params), y)
        return y

    return {"w": w, "b": b}, stage_fn, sequential


def test_toy_pipeline_matches_sequential(rng):
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = create_mesh("2x4")
    params, stage_fn, sequential = _toy(rng)
    x = jnp.asarray(rng.randn(8, 5, 16), jnp.float32)
    out = jax.jit(
        lambda p, x: pipeline_apply(stage_fn, p, x, mesh, "model"))(params, x)
    np.testing.assert_allclose(out, sequential(params, x), rtol=1e-6)


def test_toy_pipeline_gradients_match(rng):
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = create_mesh("2x4")
    params, stage_fn, sequential = _toy(rng)
    x = jnp.asarray(rng.randn(8, 5, 16), jnp.float32)

    g_pp = jax.jit(jax.grad(
        lambda p: jnp.sum(pipeline_apply(stage_fn, p, x, mesh, "model") ** 2)
    ))(params)
    g_seq = jax.jit(jax.grad(
        lambda p: jnp.sum(sequential(p, x) ** 2)))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        g_pp, g_seq)


def test_more_microbatches_shrink_nothing_numerically(rng):
    """m=8 over 4 stages (smaller bubble) is still exact."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = create_mesh("2x4")
    params, stage_fn, sequential = _toy(rng)
    x = jnp.asarray(rng.randn(16, 5, 16), jnp.float32)
    out = jax.jit(lambda p, x: pipeline_apply(
        stage_fn, p, x, mesh, "model", microbatches=8))(params, x)
    np.testing.assert_allclose(out, sequential(params, x), rtol=1e-6)


def test_microbatch_data_shard_mismatch_raises(rng):
    """Microbatch size must still divide over the data axis (DP x PP)."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = create_mesh("2x4")
    params, stage_fn, _ = _toy(rng)
    x = jnp.asarray(rng.randn(8, 5, 16), jnp.float32)
    with pytest.raises(ValueError, match="data axis"):
        pipeline_apply(stage_fn, params, x, mesh, "model", microbatches=8)


def test_stage_axis_mesh_mismatch_raises(rng):
    """S=8 stacked stages over a 4-way axis would silently compose only
    every other stage via shard_map slicing — must hard-error."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = create_mesh("2x4")
    params, stage_fn, _ = _toy(rng, s=8)
    x = jnp.asarray(rng.randn(8, 5, 16), jnp.float32)
    with pytest.raises(ValueError, match="stage_params leading axis"):
        pipeline_apply(stage_fn, params, x, mesh, "model")


def test_indivisible_microbatch_raises(rng):
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = create_mesh("2x4")
    params, stage_fn, _ = _toy(rng)
    x = jnp.asarray(rng.randn(6, 5, 16), jnp.float32)
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_apply(stage_fn, params, x, mesh, "model")


def _vit_pp_cfg(pp_stages=2, **overrides):
    base = {
        "image.pad_shape": (128, 128),
        "train.batch_images": 4,
        "network.vit_dim": 32,
        "network.vit_depth": 4,
        "network.vit_heads": 2,
        "network.vit_window": 4,
        "train.compute_dtype": "f32",
        "network.pp_stages": pp_stages,
        # the staged backbone runs no stochastic depth (models/vit.py)
        "network.vit_drop_path": 0.0,
        "train.fpn_rpn_pre_nms_per_level": 64,
        "train.rpn_post_nms_top_n": 64,
        "train.batch_rois": 32,
        "train.max_gt_boxes": 8,
    }
    base.update(overrides)
    return generate_config("vitdet_b", "synthetic", **base)


def _batch(rng, b=4):
    one = {
        "image": rng.randn(1, 128, 128, 3).astype(np.float32),
        "im_info": np.asarray([[128, 128, 1.0]], np.float32),
        "gt_boxes": np.asarray(
            [[[10, 10, 60, 90], [70, 20, 120, 70]] + [[0, 0, 0, 0]] * 6],
            np.float32),
        "gt_classes": np.asarray([[1, 2] + [0] * 6], np.int32),
        "gt_valid": np.asarray([[True, True] + [False] * 6]),
    }
    return {k: np.repeat(v, b, axis=0) for k, v in one.items()}


def test_vitdet_pp_train_step_matches_sequential(rng):
    """Two DP x PP train steps reproduce the single-device staged run —
    the pipeline is a schedule, not a numerics change."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from mx_rcnn_tpu.train.optimizer import build_optimizer
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step

    cfg = _vit_pp_cfg()
    batch = _batch(rng)
    model_seq = zoo.build_model(cfg)  # no mesh: sequential staged backbone
    params = zoo.init_params(model_seq, cfg, jax.random.PRNGKey(0))

    def run(model, mesh):
        tx = build_optimizer(cfg, params, steps_per_epoch=10)
        state = create_train_state(params, tx)
        step = make_train_step(model, cfg, mesh=mesh, donate=False,
                               forward_fn=zoo.forward_train)
        losses = []
        for i in range(2):
            b = shard_batch(batch, mesh) if mesh is not None else batch
            state, metrics = step(state, b, jax.random.PRNGKey(7 + i))
            losses.append(float(metrics["TotalLoss"]))
        return losses

    ref = run(model_seq, None)
    mesh = create_mesh("2x2")
    pp = run(zoo.build_model(cfg, mesh=mesh), mesh)
    np.testing.assert_allclose(pp, ref, rtol=2e-4)


def test_pp_and_tp_are_mutually_exclusive():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = _vit_pp_cfg(**{"network.tensor_parallel": True})
    with pytest.raises(ValueError, match="model' axis"):
        zoo.build_model(cfg, mesh=create_mesh("2x2"))


def test_pp_and_sp_are_mutually_exclusive():
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    cfg = _vit_pp_cfg(**{"network.use_ring_attention": True})
    with pytest.raises(ValueError, match="model' axis"):
        zoo.build_model(cfg, mesh=create_mesh("2x2"))


def test_pp_mesh_size_mismatch_raises():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    cfg = _vit_pp_cfg(pp_stages=4)
    with pytest.raises(ValueError, match="pp_stages"):
        zoo.build_model(cfg, mesh=create_mesh("4x2"))


def test_pp_depth_not_divisible_raises():
    cfg = _vit_pp_cfg(pp_stages=3)
    with pytest.raises(ValueError, match="divide"):
        zoo.build_model(cfg).init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 64, 64, 3), jnp.float32),
            jnp.asarray([[0.0, 0, 0, 31, 31]], jnp.float32))

def test_fit_detector_pp_smoke(tmp_path, rng):
    """The full train loop with the pipelined staged encoder on a 2x2
    mesh (DP x PP) — covers loader batch shapes, microbatch divisibility,
    and checkpointing of the stacked stage params."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset
    from mx_rcnn_tpu.tools.train import fit_detector

    cfg = _vit_pp_cfg(**{
        "image.scales": ((128, 128),),
        "train.batch_images": 2,  # global 4 → 2 microbatches × 2 data shards
        "train.flip": False,
        "train.lr_step": (100,),
    })
    ds = SyntheticDataset("train", num_images=8, image_size=128,
                          max_objects=2, min_size_frac=4, max_size_frac=2)
    history = []
    fit_detector(cfg, ds.gt_roidb(), prefix=str(tmp_path / "pp"),
                 end_epoch=1, frequent=1000, seed=0, mesh_spec="2x2",
                 epoch_callback=lambda e, s, b: history.append(
                     b.get()["TotalLoss"]))
    assert len(history) == 1 and np.isfinite(history).all(), history
    assert (tmp_path / "pp" / "0001").exists()


@pytest.fixture(scope="module")
def seq_vit8():
    """Depth-8 sequential ViTDet (cfg, model, params) — shared by both
    stage-count parametrizations of the conversion gate (identical
    across them; the test never mutates the tree)."""
    cfg_seq = _vit_pp_cfg(pp_stages=0, **{"network.vit_depth": 8,
                                          "train.batch_images": 1})
    model_seq = zoo.build_model(cfg_seq)
    params_seq = zoo.init_params(model_seq, cfg_seq, jax.random.PRNGKey(0))
    return cfg_seq, model_seq, params_seq


@pytest.mark.parametrize("stages_n", [2, 4])
def test_sequential_to_staged_checkpoint_conversion(rng, seq_vit8, stages_n):
    """A sequentially-trained ViTDet param tree converts to the staged/PP
    layout with identical numerics (and back, bit-exact round trip) for
    EVERY supported stage count — the staged model preserves the
    sequential global-attention placement (depth 8: globals {1,3,5,7} →
    in-stage {1,3} per half at stages_n=2, {1} per quarter at 4)."""
    from mx_rcnn_tpu.models.vit import (
        sequential_to_staged, staged_to_sequential)

    cfg_seq, model_seq, params_seq = seq_vit8
    cfg_pp = _vit_pp_cfg(pp_stages=stages_n, **{"network.vit_depth": 8,
                                                "train.batch_images": 1})
    staged = sequential_to_staged(params_seq, stages_n)

    model_pp = zoo.build_model(cfg_pp)  # no mesh: sequential staged exec
    batch = _batch(rng, b=1)
    l_seq, _ = jax.jit(
        lambda p, b, r: zoo.forward_train(model_seq, p, b, r, cfg_seq)
    )(params_seq, batch, jax.random.PRNGKey(3))
    l_pp, _ = jax.jit(
        lambda p, b, r: zoo.forward_train(model_pp, p, b, r, cfg_pp)
    )(staged, batch, jax.random.PRNGKey(3))
    np.testing.assert_allclose(float(l_pp), float(l_seq), rtol=1e-6)

    # Bit-exact round trip.
    back = staged_to_sequential(staged)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 params_seq, back)


def test_sequential_to_staged_rejects_mismatched_layout(rng, seq_vit8):
    from mx_rcnn_tpu.models.vit import (
        sequential_to_staged, staged_to_sequential)

    _, _, params_seq = seq_vit8
    # 8 stages over depth 8 (per=1): sequential globals {1,3,5,7} give
    # alternating empty/global per-stage patterns — not preservable.
    with pytest.raises(ValueError, match="preserve"):
        sequential_to_staged(params_seq, 8)
    with pytest.raises(ValueError, match="divide"):
        sequential_to_staged(params_seq, 3)
    # Wrong tree kind, both directions.
    with pytest.raises(ValueError, match="block"):
        sequential_to_staged(
            sequential_to_staged(params_seq, 4), 4)
    with pytest.raises(ValueError, match="staged-backbone"):
        staged_to_sequential(params_seq)
    # Hand-built stages_n=8/per=1 staged tree over depth 8, every block's
    # relative-position tables given block 0's (window) shape so the
    # blocks stack: the converter must reject on architecture
    # (alternating placement), not shape.
    feats = params_seq["params"]["features"]
    tables = {k: feats["block0"]["attn"][k] for k in ("rel_pos_h",
                                                      "rel_pos_w")}
    blocks = [{**feats[f"block{i}"],
               "attn": {**feats[f"block{i}"]["attn"], **tables}}
              for i in range(8)]
    bad = {
        **params_seq,
        "params": {
            **params_seq["params"],
            "features": {
                k: v for k, v in feats.items() if not k.startswith("block")
            } | {"stages": {"b0": jax.tree.map(
                lambda *xs: jnp.stack(xs), *blocks)}},
        },
    }
    with pytest.raises(ValueError, match="architectures differ"):
        staged_to_sequential(bad)
