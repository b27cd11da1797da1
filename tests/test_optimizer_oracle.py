"""``build_optimizer``'s update and ``lr_schedule`` against plain references
written here: numpy for the update (the reference's SGD and the transformer
families' AdamW, as train/optimizer.py's header maps them onto optax), plain
Python for the schedule. The only check of the update's arithmetic: every
other test takes the optax chain's word for it.

Tolerances, not bit equality: XLA may contract a multiply-add into one
rounding where numpy takes two. Frozen leaves ARE compared bit for bit."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.train.optimizer import build_optimizer, lr_schedule
from mx_rcnn_tpu.train.step import create_train_state

F32 = np.float32
TRAINABLE = (("fc", "kernel"), ("fc", "bias"))
FROZEN = (("conv0", "kernel"), ("bn1", "gamma"))  # by pattern; BN affine


def _cfg(optimizer, slot="float32", **train):
    cfg = generate_config("resnet50", "synthetic")
    over = dict(optimizer=optimizer, opt_state_dtype=slot, lr=0.05,
                lr_factor=0.1, lr_step=(2,), momentum=0.9, wd=0.01,
                clip_gradient=5.0)
    return cfg.with_updates(train=replace(cfg.train, **{**over, **train}))


def _tree(seed, scale=1.0):
    rs = np.random.RandomState(seed)
    return {"params": {
        "conv0": {"kernel": (scale * rs.randn(3, 3)).astype(F32)},
        "bn1": {"gamma": (scale * rs.randn(3)).astype(F32)},
        "fc": {"kernel": (scale * rs.randn(4, 3)).astype(F32),
               "bias": (scale * rs.randn(3)).astype(F32)}}}


def _leaf(tree, path):
    return np.asarray(tree["params"][path[0]][path[1]])


def _lr(cfg, count, steps_per_epoch, begin_step=0):
    """MultiFactorScheduler: lr x lr_factor once per boundary reached."""
    lr = cfg.train.lr
    for e in cfg.train.lr_step:
        if count + begin_step >= int(e * steps_per_epoch):
            lr *= cfg.train.lr_factor
    return F32(lr)


def _run_program(cfg, params, grads_seq):
    """The program: the optimizer fit_detector builds, stepped under jit
    (one optimizer step an epoch, so lr_step=(2,) lands inside the run)."""
    state = create_train_state(
        jax.tree.map(jnp.asarray, params),
        build_optimizer(cfg, params, steps_per_epoch=1))
    step = jax.jit(lambda s, g: s.apply_gradients(g))
    for g in grads_seq:
        state = step(state, jax.tree.map(jnp.asarray, g))
    return state


def _run_sgd_reference(cfg, params, grads_seq):
    """clip each element to [-c, c]; add wd x p; t = u + momentum x t;
    p -= lr x t. The slot is stored in the slot dtype and the step uses
    the unrounded value. jax types the Python scalar weakly, so under
    bfloat16 slots the momentum itself is read in bfloat16 (0.8984);
    whether the product is rounded to bfloat16 too is the backend's
    choice (XLA's CPU fusion does not, op-by-op execution does), which
    _check's tolerance for such runs covers."""
    t = cfg.train
    slot = ml_dtypes.bfloat16 if t.opt_state_dtype == "bfloat16" else F32
    out = {path: _leaf(params, path).copy() for path in TRAINABLE}
    trace = {path: np.zeros_like(out[path]).astype(slot) for path in TRAINABLE}
    for count, grads in enumerate(grads_seq):
        for path in TRAINABLE:
            u = np.clip(_leaf(grads, path), -F32(t.clip_gradient),
                        F32(t.clip_gradient))
            u = u + F32(t.wd) * out[path]
            new = u + F32(slot(t.momentum)) * trace[path].astype(F32)
            out[path] = out[path] - _lr(cfg, count, 1) * new
            trace[path] = new.astype(slot)
    return out, trace


def _run_adamw_reference(cfg, params, grads_seq, b1=0.9, b2=0.999, eps=1e-8):
    """Scale the trainable gradients to a joint norm of at most c; Adam
    moments with bias correction; decoupled decay wd x p; p -= lr x u.
    The first moment is stored in the slot dtype, and b1 is read in it,
    as the momentum in the SGD reference."""
    t = cfg.train
    slot = ml_dtypes.bfloat16 if t.opt_state_dtype == "bfloat16" else F32
    out = {path: _leaf(params, path).copy() for path in TRAINABLE}
    mu = {path: np.zeros_like(out[path]).astype(slot) for path in TRAINABLE}
    nu = {path: np.zeros_like(out[path]) for path in TRAINABLE}
    for count, grads in enumerate(grads_seq):
        norm = np.sqrt(sum(np.sum(np.square(_leaf(grads, p)), dtype=F32)
                           for p in TRAINABLE), dtype=F32)
        n = count + 1
        for path in TRAINABLE:
            g = _leaf(grads, path)
            if norm >= F32(t.clip_gradient):
                g = g / norm * F32(t.clip_gradient)
            m = F32(1 - b1) * g + F32(slot(b1)) * mu[path].astype(F32)
            v = F32(1 - b2) * g * g + F32(b2) * nu[path]
            u = (m / F32(1 - b1 ** n)) / (np.sqrt(v / F32(1 - b2 ** n))
                                          + F32(eps))
            u = u + F32(t.wd) * out[path]
            out[path] = out[path] - _lr(cfg, count, 1) * u
            mu[path], nu[path] = m.astype(slot), v
    return out, mu


REFERENCE = {"sgd": _run_sgd_reference, "adamw": _run_adamw_reference}


def _slots(state, name):
    """The optimizer's slot of that name, by trainable leaf."""
    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state.opt_state)[0]:
        keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
        if name in keys and "params" in keys:
            found[tuple(keys[keys.index("params") + 1:])] = np.asarray(leaf)
    return found


def _check(cfg, params, grads_seq):
    """The program's parameters and slots after the steps, against the
    reference's. What is compared is the CHANGE of each parameter (a
    parameter is ~1, a step ~1e-2: on the value itself a wrong term could
    hide under float32's rounding of the sum), to 1e-4 of a step. A run
    with bfloat16 slots is held to two bfloat16 ulps of its largest slot
    value, a step, instead: the slot's rounding, and the product's."""
    state = _run_program(cfg, params, grads_seq)
    want, want_slot = REFERENCE[cfg.train.optimizer](cfg, params, grads_seq)
    slots = _slots(state, "trace" if cfg.train.optimizer == "sgd" else "mu")
    assert set(slots) == set(TRAINABLE)  # no slot for a frozen leaf
    for path in TRAINABLE:
        assert slots[path].dtype == want_slot[path].dtype
        if slots[path].dtype == F32:
            tol = dict(rtol=1e-4, atol=1e-7)
            step_tol = dict(rtol=1e-4, atol=5e-7)
        else:
            ulps = 2.0 ** -7 * np.abs(want_slot[path].astype(F32)).max()
            tol = dict(rtol=0, atol=ulps)
            scale = 1.0 if cfg.train.optimizer == "sgd" else 10.0  # 1/sqrt(nu)
            step_tol = dict(rtol=0, atol=len(grads_seq) * cfg.train.lr
                            * scale * ulps)
        p0 = _leaf(params, path)
        np.testing.assert_allclose(_leaf(state.params, path) - p0,
                                   want[path] - p0, err_msg=str(path),
                                   **step_tol)
        np.testing.assert_allclose(slots[path].astype(F32),
                                   want_slot[path].astype(F32), **tol)
    return state


@pytest.mark.parametrize("slot", ["float32", "bfloat16"])
@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
def test_update_matches_reference(optimizer, slot):
    """Three steps with fresh gradients each, the learning rate dropping
    at the third: momentum, bias correction, the schedule's count and the
    slot's storage dtype all show in the parameters."""
    cfg = _cfg(optimizer, slot)
    state = _check(cfg, _tree(0), [_tree(s, 0.1) for s in (1, 2, 3)])
    assert int(state.step) == 3


@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
def test_frozen_leaf_with_a_live_gradient_stays_bit_identical(optimizer):
    """Freezing is a hard zero on the update, not optax.masked (which
    hands a masked leaf's RAW gradient to apply_updates): a frozen leaf
    whose gradient is large comes out bit for bit as it went in, with no
    weight decay either."""
    params = _tree(0)
    state = _run_program(_cfg(optimizer), params,
                         [_tree(s, 10.0) for s in (1, 2)])
    for path in FROZEN:
        np.testing.assert_array_equal(_leaf(state.params, path),
                                      _leaf(params, path))
    for path in TRAINABLE:
        assert not np.array_equal(_leaf(state.params, path),
                                  _leaf(params, path))


@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
def test_clip_engaged(optimizer):
    """Gradients far over the limit: sgd clips element by element (the
    reference's clip_gradient), adamw scales the TRAINABLE leaves' joint
    norm (a frozen leaf's gradient does not enter the norm)."""
    cfg = _cfg(optimizer, clip_gradient=0.5, wd=0.0)
    grads = [_tree(s, 10.0) for s in (1, 2)]
    assert max(np.abs(_leaf(grads[0], p)).max() for p in TRAINABLE) > 5.0
    _check(cfg, _tree(0), grads)
    # the clip bounds the step: |dp| <= lr x c for sgd's first step
    if optimizer == "sgd":
        state = _run_program(cfg, _tree(0), grads[:1])
        dp = np.concatenate([
            np.abs(_leaf(state.params, p) - _leaf(_tree(0), p)).ravel()
            for p in TRAINABLE])
        assert dp.max() <= 0.05 * 0.5 * (1 + 1e-4)
        assert dp.min() < 0.05 * 0.5 * 0.99  # and not everything is cut


@pytest.mark.parametrize("optimizer", ["sgd", "adamw"])
def test_weight_decay_where_each_chain_applies_it(optimizer):
    """Zero gradients isolate the decay: sgd couples wd x p into the
    gradient before momentum (p -= lr x wd x p at the first step); adamw's
    is decoupled, added after the (zero) Adam direction: the same first
    step, and from then on sgd's momentum carries it while adamw's does
    not. Frozen leaves do not decay."""
    cfg = _cfg(optimizer, wd=0.1, lr_step=())
    params = _tree(0)
    zero = jax.tree.map(np.zeros_like, params)
    state = _check(cfg, params, [zero, zero])
    p0 = _leaf(params, TRAINABLE[0])
    shrink = F32(0.05 * 0.1)
    after_one = p0 * (1 - shrink)
    if optimizer == "sgd":  # second step: u = wd x p1 + momentum x (wd x p0)
        want = after_one - F32(0.05) * (F32(0.1) * after_one
                                        + F32(0.9) * F32(0.1) * p0)
    else:
        want = after_one * (1 - shrink)
    np.testing.assert_allclose(_leaf(state.params, TRAINABLE[0]), want,
                               rtol=1e-5)
    for path in FROZEN:
        np.testing.assert_array_equal(_leaf(state.params, path),
                                      _leaf(params, path))


def test_fresh_slots_with_begin_step_step_at_the_schedules_later_rate():
    """A restart without its optimizer state (fit_detector: a params-only
    checkpoint, or a heal whose session starts mid-run) builds fresh slots
    and offsets the schedule by begin_step instead: its FIRST update is
    taken at the rate of the run's position, not at the initial one."""
    cfg = _cfg("sgd", wd=0.0, momentum=0.0)  # lr_step=(2,): 0.05 -> 0.005
    params, grads = _tree(0), _tree(1, 0.1)
    late = create_train_state(
        jax.tree.map(jnp.asarray, params),
        build_optimizer(cfg, params, steps_per_epoch=1, begin_step=2))
    late = late.apply_gradients(jax.tree.map(jnp.asarray, grads))
    for path in TRAINABLE:
        np.testing.assert_allclose(
            _leaf(late.params, path) - _leaf(params, path),
            -F32(0.005) * _leaf(grads, path), rtol=1e-4, atol=1e-7)


def test_unknown_optimizer_is_refused():
    with pytest.raises(ValueError, match="'sgd' or 'adamw'"):
        build_optimizer(_cfg("adam"), _tree(0))


@pytest.mark.parametrize("lr_step, steps_per_epoch, begin_step", [
    ((), 5, 0), ((2,), 5, 0), ((1, 3), 4, 0), ((1, 3), 4, 6), ((1.5,), 4, 0),
], ids=["no_boundary", "one", "two", "begin_step_offset", "half_epoch"])
def test_lr_schedule_against_plain_python(lr_step, steps_per_epoch,
                                          begin_step):
    """lr x lr_factor from the first step of each lr_step epoch on;
    begin_step shifts the whole schedule (a restart without its optimizer
    state)."""
    cfg = _cfg("sgd", lr_step=lr_step)
    sched = lr_schedule(cfg, steps_per_epoch, begin_step)
    for count in range(5 * steps_per_epoch):
        want = _lr(cfg, count, steps_per_epoch, begin_step)
        assert float(sched(count)) == pytest.approx(want, rel=1e-6), count
