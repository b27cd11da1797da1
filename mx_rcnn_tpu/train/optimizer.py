"""Optimizer: SGD + momentum with the reference's exact knobs.

Reference: the fit kwargs in train_end2end.py —
``optimizer='sgd', momentum 0.9, wd 5e-4, clip_gradient 5,
MultiFactorScheduler(lr_step), rescale_grad=1/batch_size`` — plus parameter
freezing via ``fixed_param_prefix`` handed to MutableModule.

Mapping:
- clip_gradient: MXNet clips ELEMENTWISE to [−c, c] → optax.clip.
- wd: MXNet SGD couples weight decay into the gradient → add_decayed_weights
  before the momentum step.
- rescale_grad 1/batch: our losses already normalize per local image and DP
  gradients are mean-reduced, so no extra rescale is needed (documented
  equivalence — see models/losses.py).
- MultiFactorScheduler: piecewise-constant LR dropped by ``lr_factor`` at
  ``lr_step`` epoch boundaries.
- freezing: a boolean mask — frozen leaves receive zero updates AND no weight
  decay (MXNet's fixed_param_names are simply absent from the executor's
  grad list).
"""

from __future__ import annotations

from typing import Sequence

import jax
import optax

from mx_rcnn_tpu.config import Config


def trainable_mask(params, patterns: Sequence[str]):
    """True for trainable leaves; False where any pattern is the PREFIX of
    a segment of the leaf's path (the reference's ``fixed_param_prefix``,
    a module at a time: ``conv0`` fixes ``features/conv0/kernel`` and not
    the mask head's ``mask_conv0`` - as a bare substring it did, and the
    Mask presets never trained that layer; PERF.md section 6, PR 34).

    Frozen-BN params (gamma/beta/moving_*) are always frozen in this
    framework (reference: use_global_stats + fixed gamma/beta).
    """
    always_frozen = ("moving_mean", "moving_var")

    def decide(path) -> bool:
        keys = [getattr(p, "key", str(p)) for p in path]
        joined = "/".join(str(k) for k in keys)
        if any(f in joined for f in always_frozen):
            return False
        # BN affine anywhere: frozen (gamma/beta leaf names).
        leaf = keys[-1] if keys else ""
        if leaf in ("gamma", "beta"):
            return False
        return not any(str(k).startswith(pat) for k in keys
                       for pat in patterns)

    return jax.tree_util.tree_map_with_path(lambda p, _: decide(p), params)


def lr_schedule(cfg: Config, steps_per_epoch: int,
                begin_step: int = 0) -> optax.Schedule:
    """MultiFactorScheduler analog: lr × lr_factor at each lr_step epoch.

    begin_step offsets the schedule for restarts whose opt_state (and with
    it optax's internal step count) was not restored — e.g. --begin_epoch
    with only a params checkpoint. With a restored opt_state the count
    resumes by itself and begin_step must stay 0.
    """
    boundaries = {
        int(e * steps_per_epoch): cfg.train.lr_factor for e in cfg.train.lr_step
    }
    base = optax.piecewise_constant_schedule(cfg.train.lr, boundaries)
    if begin_step:
        return lambda step: base(step + begin_step)
    return base


def effective_fixed_patterns(cfg: Config) -> tuple:
    """The optimizer-mask patterns implied by the config as a whole.

    The ResNet stem/stage1 patterns exist to mirror the reference's
    fixed_param_prefix, whose forward-side twin is the freeze_at
    stop_gradient cut (models/backbones.py). With freeze_at=0 (the
    from-scratch profile) there is no cut and the stem is MEANT to train —
    keeping the patterns would freeze it at random init. One knob, one
    freeze."""
    pats = tuple(cfg.network.fixed_param_patterns)
    if cfg.network.freeze_at < 2:
        # the stage1 cut exists only from freeze_at=2 up
        pats = tuple(p for p in pats if p != "stage1")
    if cfg.network.freeze_at == 0:
        # ResNet stem AND the VGG conv1-2 prefix both unfreeze
        pats = tuple(p for p in pats
                     if p not in ("conv0", "bn0")
                     and not p.startswith(("conv1_", "conv2_")))
    return pats


def build_optimizer(cfg: Config, params, steps_per_epoch: int = 1000,
                    begin_step: int = 0):
    mask = trainable_mask(params, effective_fixed_patterns(cfg))
    sched = lr_schedule(cfg, steps_per_epoch, begin_step)
    # Optional bf16 storage for the momentum / first-moment slot: halves
    # one full-size tree's bytes. f32 default.
    slot_dtype = (None if cfg.train.opt_state_dtype == "float32"
                  else cfg.train.opt_state_dtype)
    if cfg.train.optimizer == "adamw":
        # Transformer families (DETR/ViTDet): AdamW + global-norm clip,
        # per their papers. Weight decay is decoupled (inside adamw).
        inner = optax.chain(
            optax.clip_by_global_norm(cfg.train.clip_gradient),
            optax.adamw(learning_rate=sched, weight_decay=cfg.train.wd,
                        mu_dtype=slot_dtype),
        )
    elif cfg.train.optimizer == "sgd":
        inner = optax.chain(
            optax.clip(cfg.train.clip_gradient),
            optax.add_decayed_weights(cfg.train.wd),
            optax.sgd(learning_rate=sched, momentum=cfg.train.momentum,
                      accumulator_dtype=slot_dtype),
        )
    else:
        raise ValueError(
            f"train.optimizer must be 'sgd' or 'adamw', got "
            f"{cfg.train.optimizer!r}")
    # Freezing is a HARD ZERO on the update, not optax.masked: masked()
    # passes the RAW GRADIENT through for masked-out leaves (optax's
    # contract), which apply_updates would then ADD to the frozen params —
    # gradient ascent. Harmless only when the frozen grads are
    # structurally zero (the stop_gradient-cut C4 prefix), actively wrong
    # for the alternate-training frozen-trunk stages where grads through
    # `features` are real (caught by test_stages.py's trunk-sharing
    # assertion).
    # One code path for DP and TP. On the chip the per-leaf chain has no
    # device time of its own: XLA fuses each leaf's update into the
    # convolution that produces its weight gradient
    # (`stage.update_ms.train` 0.0001 ms, ledger, PR 29).
    labels = jax.tree_util.tree_map(
        lambda t: "train" if t else "frozen", mask)
    return optax.multi_transform(
        {"train": inner, "frozen": optax.set_to_zero()}, labels)


def rebase_schedule_count(opt_state, step: int):
    """Rewrite every scalar integer count leaf of an optax state to
    ``step`` (host-side; returns a new tree).

    Elastic cross-topology resume (graftheal): a restored opt_state's
    schedule/Adam counters are in the SAVING run's optimizer-step units.
    Once the dispatch skip has been converted through the images-consumed
    invariant, this run counts steps in its OWN units (its
    steps_per_epoch, its LR schedule) — left unrebased, every schedule
    read (warmup/decay boundaries) would happen at the old run's
    position, silently bending the LR trajectory. Scalar integer leaves
    are exactly optax's counts."""
    import numpy as np

    def _fix(leaf):
        arr = np.asarray(leaf)
        if arr.ndim == 0 and np.issubdtype(arr.dtype, np.integer):
            return np.asarray(step, arr.dtype)
        return leaf

    return jax.tree_util.tree_map(_fix, opt_state)
