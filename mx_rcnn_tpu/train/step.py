"""The pjit-able train step — MutableModule.fit's hot loop, TPU-style.

Reference: rcnn/core/module.py MutableModule + the per-batch loop in
train_end2end.py (SURVEY.md §4.1): forward_backward → KVStore push/pull →
update. Here the whole thing is ONE jitted SPMD program: loss+grad,
XLA-inserted gradient allreduce over the mesh `data` axis, optax update.

No rebinding: the reference rebinds executors when a batch outgrows the bound
shapes (MutableModule's raison d'être); static padded shapes make that
machinery unnecessary — one compilation per config, period.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.faster_rcnn import FasterRCNN
from mx_rcnn_tpu.obs.profile import stage
from mx_rcnn_tpu.resilience import chaos
from mx_rcnn_tpu.train import health as health_mod


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads) -> "TrainState":
        updates, new_opt = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt,
        )


def create_train_state(params, tx: optax.GradientTransformation) -> TrainState:
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        tx=tx,
    )


def abstract_step_inputs(model, cfg: Config, mesh: Mesh, n_images: int):
    """``(state, batch, key)`` as ShapeDtypeStructs with the shardings the
    mesh step expects (state and key replicated, batch over ``data``):
    what ``make_train_step(...).lower()`` needs to lower or compile the
    step from shapes alone — no array, no attached device, so it works
    for a chip that is only described (tests/test_chip_compile.py) as
    well as for the one a process owns (chip_smoke.py)."""
    from mx_rcnn_tpu.models.zoo import init_params
    from mx_rcnn_tpu.train.optimizer import build_optimizer

    def fresh_state(key):
        params = init_params(model, cfg, key)
        return create_train_state(
            params, build_optimizer(cfg, params, steps_per_epoch=100))

    def spec(tree, sharding):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    repl = NamedSharding(mesh, P())
    h, w = cfg.image.pad_shape
    g = cfg.train.max_gt_boxes
    batch = {
        "image": jax.ShapeDtypeStruct((n_images, h, w, 3), jnp.float32),
        "im_info": jax.ShapeDtypeStruct((n_images, 3), jnp.float32),
        "gt_boxes": jax.ShapeDtypeStruct((n_images, g, 4), jnp.float32),
        "gt_classes": jax.ShapeDtypeStruct((n_images, g), jnp.int32),
        "gt_valid": jax.ShapeDtypeStruct((n_images, g), jnp.bool_)}
    if cfg.network.use_mask:  # box-frame masks, as data/loader.py serves them
        m = cfg.train.mask_gt_resolution
        batch["gt_masks"] = jax.ShapeDtypeStruct((n_images, g, m, m),
                                                 jnp.uint8)
    return (spec(jax.eval_shape(fresh_state, jax.random.PRNGKey(0)), repl),
            spec(batch, NamedSharding(mesh, P("data"))),
            spec(jax.eval_shape(lambda: jax.random.PRNGKey(0)), repl))


def _metric_parts(aux: Dict[str, jnp.ndarray]) -> Dict[str, tuple]:
    """The reference's 6 metrics (rcnn/core/metric.py) as (num, den) pairs
    so they pool EXACTLY across micro-steps: losses are (value, 1) means;
    accuracies are (correct-count, valid-count) — summing parts then
    dividing gives the big-batch value, which a mean-of-ratios would not.

    RPNAcc/RCNNAcc ignore label −1 exactly as the reference metrics mask
    ignore labels. Tolerant of partial aux (rpn-only / rcnn-only stages
    emit their half; DETR emits rcnn_* losses without logits).
    """
    one = jnp.ones((), jnp.float32)
    out = {"TotalLoss": (aux["total_loss"], one)}
    if "rpn_cls_loss" in aux:
        out["RPNLogLoss"] = (aux["rpn_cls_loss"], one)
        out["RPNL1Loss"] = (aux["rpn_bbox_loss"], one)
    if "rpn_logits" in aux:
        rpn_pred = jnp.argmax(aux["rpn_logits"], axis=-1)
        rpn_valid = aux["rpn_labels"] >= 0
        rpn_correct = (rpn_pred == aux["rpn_labels"]) & rpn_valid
        out["RPNAcc"] = (jnp.sum(rpn_correct).astype(jnp.float32),
                         jnp.sum(rpn_valid).astype(jnp.float32))
    if "rcnn_cls_loss" in aux:
        out["RCNNLogLoss"] = (aux["rcnn_cls_loss"], one)
        out["RCNNL1Loss"] = (aux["rcnn_bbox_loss"], one)
    if "rcnn_logits" in aux:
        rcnn_pred = jnp.argmax(aux["rcnn_logits"], axis=-1)
        rcnn_valid = aux["rcnn_labels"] >= 0
        rcnn_correct = (rcnn_pred == aux["rcnn_labels"]) & rcnn_valid
        out["RCNNAcc"] = (jnp.sum(rcnn_correct).astype(jnp.float32),
                          jnp.sum(rcnn_valid).astype(jnp.float32))
    if "rpn_target_counts" in aux:  # targets/rpn_targets.py::RpnTargets
        out["RpnTargetCounts"] = (aux["rpn_target_counts"], one)
    if "roi_level_counts" in aux:  # pyramid families: a vector, P2..P5
        counts = aux["roi_level_counts"]
        out["RoiLevelShare"] = (counts, jnp.sum(counts))
        # static: the pooling canvas's rows, columns, poolings a call
        out["RoiPoolingForm"] = (aux["roi_pooling_form"], one)
    if "mask_roi_counts" in aux:  # models/fpn.py::mask_branch
        out["MaskRoiCounts"] = (aux["mask_roi_counts"], one)
    return out


def _finalize_metrics(parts: Dict[str, tuple]) -> Dict[str, jnp.ndarray]:
    return {k: num / (den + 1e-12) for k, (num, den) in parts.items()}


def _metrics_from_aux(aux: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    return _finalize_metrics(_metric_parts(aux))


def make_train_step(
    model: FasterRCNN,
    cfg: Config,
    mesh: Optional[Mesh] = None,
    donate: bool = True,
    forward_fn: Optional[Callable] = None,
    param_specs=None,
    health: bool = False,
) -> Callable[[TrainState, Dict[str, jnp.ndarray], jax.Array],
              Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Build the jitted train step.

    With a mesh: params/opt_state replicated, batch sharded on `data` —
    XLA inserts the gradient all-reduce over ICI (the KVStore replacement).
    Without: plain single-device jit. forward_fn selects the training graph
    (end2end / rpn-only / rcnn-only — the reference's get_*_train symbol
    variants); None is the model family's own end2end forward
    (models/zoo.py::forward_train, resolved here: zoo imports every family).

    graftcanvas (image.canvas_pack): packed batches shard/accumulate
    UNCHANGED through this machinery — every leaf's leading dim is the
    plane count P (one-plus planes per data shard; im_info/gt tensors are
    (P, I, ...)), so the P('data') sharding and the accum inner-reshape
    slice whole planes. The forward detects the packed contract from the
    batch itself (ops/canvas.py).

    param_specs (parallel/partition.py): tensor-parallel weight shardings.
    The state must then arrive PRE-PLACED (shard_train_state) — shardings
    are inferred from the committed inputs and propagated by GSPMD, which
    inserts the TP collectives alongside the data-axis gradient psum.

    graftcast (train.compute_dtype=bf16): parameters and optimizer slots
    stay float32 leaves; the modules compute in bfloat16 by flax's
    per-leaf promotion (train/precision.py), so gradients reach
    apply_gradients float32.

    graftpulse (health=True, obs.health_every > 0): the step RETURNS a
    third output — the numerics health dict of train/health.py
    (whole-tree nonfinite counts and squared norms of grads, params and
    the update delta, plus the pooled loss) — computed in-graph and
    fused into the same executable, so the cadenced host read
    (obs/health.py) adds no per-step sync and no extra compile.
    health=False keeps the exact two-output program (bit-identical HLO
    to pre-graftpulse). Chaos ``nan_at_step=K`` (resilience/chaos.py)
    poisons step K's final gradients IN-GRAPH here, after the accum
    fold — the registered "grad_inject" site, traced in at build time.
    """

    if forward_fn is None:
        from mx_rcnn_tpu.models.zoo import forward_train as forward_fn

    accum = max(1, int(getattr(cfg.train, "grad_accum_steps", 1)))
    # graftpulse chaos: the spec is env-carried and static per process —
    # parse once at build time; the injection (if armed) is traced into
    # the step at the registered "grad_inject" site below.
    _spec = chaos.from_env()
    nan_at = int(_spec.nan_at_step)
    if _spec.active:
        _spec.fire("grad_inject")

    def _grads_of(params, chunk, key):
        def loss_fn(p):
            loss, aux = forward_fn(model, p, chunk, key, cfg)
            return loss, aux

        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        return grads, _metric_parts(aux)

    def step(state: TrainState, batch, rng):
        if accum == 1:
            grads, parts = _grads_of(state.params, batch, rng)
        else:
            # Micro-step accumulation: the batch's leading dim is
            # accum x micro-batch; grads average and metric PARTS sum
            # (pooled accuracies = big-batch values) — identical gradient
            # semantics to the big batch (per-image-normalized losses;
            # frozen-BN / GroupNorm have no cross-batch coupling) at
            # 1/accum of the activation memory. Accum is the INNER dim of
            # the reshape so every chunk keeps one-row-per-device under
            # the data mesh (outer would hand each chunk to a device
            # subset and reshard every micro-step). The loop is UNROLLED
            # (accum is a small static int): a lax.scan body holding the
            # full fwd+bwd makes the SPMD partitioner pathologically slow
            # to compile (measured >12 min for accum=2 at 64^2 on CPU;
            # unrolled: seconds).
            chunks = jax.tree.map(
                lambda x: x.reshape(x.shape[0] // accum, accum,
                                    *x.shape[1:]), batch)
            keys = jax.random.split(rng, accum)
            g_tot, p_tot = None, None
            for i in range(accum):
                chunk = jax.tree.map(lambda x: x[:, i], chunks)
                g, p = _grads_of(state.params, chunk, keys[i])
                if g_tot is None:
                    g_tot, p_tot = g, p
                else:
                    g_tot = jax.tree.map(jnp.add, g_tot, g)
                    p_tot = jax.tree.map(jnp.add, p_tot, p)
            grads = jax.tree.map(lambda g: g / accum, g_tot)
            parts = p_tot
        if nan_at:
            # chaos nan_at_step: poison the FINAL gradients (post accum
            # fold) of the armed optimizer step, in-graph.
            grads = chaos.poison_grads(grads, state.step, nan_at)
        with stage("update"):  # tx.update + apply_updates
            new_state = state.apply_gradients(grads)
        metrics = _finalize_metrics(parts)
        if not health:
            return new_state, metrics
        return new_state, metrics, health_mod.step_health(
            state, grads, new_state, metrics["TotalLoss"])

    if mesh is None:
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    # Trace under the mesh: what GSPMD cannot partition by itself (the
    # Pallas NMS, ops/nms.py::nms_dispatch) finds there the axes to
    # shard_map over.
    meshless_step = step

    def step(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return meshless_step(*args)

    if param_specs is not None:
        # TP: respect the committed shardings of state (mixed sharded/
        # replicated leaves) and batch; outputs keep propagated layouts.
        return jax.jit(step, donate_argnums=(0,) if donate else ())

    repl = NamedSharding(mesh, P())
    data_sh = NamedSharding(mesh, P("data"))
    return jax.jit(
        step,
        in_shardings=(repl, data_sh, repl),
        out_shardings=(repl, repl, repl) if health else (repl, repl),
        donate_argnums=(0,) if donate else (),
    )
