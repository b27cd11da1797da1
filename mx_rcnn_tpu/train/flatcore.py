"""flatcore: persistent flat parameter/optimizer-state storage.

The r4 roofline (PERF.md item 3) left ONE formulation-invariant non-conv
cost in the train step: the optimizer update's ~6 ms floor, immune to five
different implementations because it is a serialization cost of launching
hundreds of per-leaf kernels (params → grads → momentum/moments, one tiny
kernel per leaf per transform), not HBM bandwidth. This module removes the
many-buffer shape itself:

- All trainable leaves live in ONE contiguous dtype-segregated buffer per
  tree (params / trace / Adam mu+nu), described by a precomputed STATIC
  segment table (path, shape, dtype, offset) built from the model's
  canonical flatten spec (models/zoo.py::param_flatten_spec).
- The param tree the forward sees is materialized INSIDE the compiled step
  as zero-copy views — static `buf[off:off+size].reshape(shape)` slices
  that XLA fuses into their consumers. Gradients are taken with respect to
  the BUFFER, so the backward accumulates straight into one flat gradient
  per dtype — no step-time ravel/unravel (optax.flatten's measured 10.2 ms
  failure mode: ~300 slice ops each way, every step).
- The update (train/optimizer.py::flat_sgd_update / flat_adamw_update) is
  a handful of fused elementwise kernels over the flat buffers; under a
  data mesh the gradient allreduce is ONE psum per buffer instead of one
  per leaf (the Horovod-fusion / ZeRO-flat-state shape, ready for the
  v5e-16 DP north star).
- Freezing is a precomputed per-segment 0/1 scale buffer carried in the
  state (NOT a baked-in constant — a params-sized literal would bloat the
  executable), preserving the r3 hard-zero fix: frozen elements update by
  exactly 0.0 and bit-retain their values.

graftcast (train/precision.py): under ``train.compute_dtype=bf16`` the
f32 buffers above are MASTER weights, and the state additionally carries
a bf16 COMPUTE SHADOW per float buffer (``FlatTrainState.compute``):
the update writes the masters in f32 (bit-exact vs the f32 policy given
equal grads) and re-materializes the shadow with ONE ``convert`` per
dtype buffer — a program output, so XLA cannot fold it away or
re-duplicate it into consumer fusions (``optimization_barrier`` is
dropped by the CPU pipeline). The
forward's param views slice the shadow — except the f32 islands (norm
statistics/affine, ``precision.is_island_param``), which stay views of
the master — and the loss differentiates w.r.t. the (master, shadow)
pair, so the backward yields one bf16 cotangent per buffer that is cast
UP once and summed into the f32 master gradient before the DP psum and
the optimizer update. Same values as flax's per-leaf promotion (cast
commutes with slicing); the per-leaf cast tree is simply gone.

Mode routing: `train.flat_params` opts in; TP/PP trees keep the per-leaf
path (parallel/partition.py::flat_segment_specs — a sharded leaf has no
contiguous image inside a replicated flat buffer).

Checkpoint contract: the on-disk form is ALWAYS the tree form —
`FlatCore.tree_state` reconstructs the exact optax opt_state structure
(slot layout discovered positionally from `jax.eval_shape(tx.init)`), so
checkpoints are bit-for-bit interchangeable between modes and with every
earlier round's checkpoints (tests/test_flatcore.py round-trips both
directions, sync and async).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.train import precision
from mx_rcnn_tpu.train.optimizer import (
    build_optimizer,
    effective_fixed_patterns,
    flat_adamw_update,
    flat_sgd_update,
    lr_schedule,
    trainable_mask,
)


@dataclass(frozen=True)
class Segment:
    """One leaf's image inside its dtype buffer. Static metadata only."""

    path: str
    dtype: str  # buffer key (param dtype name)
    offset: int
    size: int
    shape: Tuple[int, ...]
    trainable: bool


class SegmentTable:
    """Static (path, shape, dtype, offset) table for one param tree.

    Built once per (model, cfg) from the canonical flatten spec; segments
    within a dtype buffer follow the spec's order, so offsets are a pure
    function of the tree structure — two processes (or two rounds) with
    the same model agree on every offset without communicating.
    """

    def __init__(self, params, mask_tree):
        from mx_rcnn_tpu.models.zoo import param_flatten_spec

        spec = param_flatten_spec(params)
        self.treedef = jax.tree_util.tree_structure(params)
        mask_leaves = [bool(m) for m in jax.tree_util.tree_leaves(mask_tree)]
        if len(mask_leaves) != len(spec):
            raise ValueError(
                f"trainable mask has {len(mask_leaves)} leaves for a "
                f"{len(spec)}-leaf param tree")
        segments = []
        offsets: Dict[str, int] = {}
        for (path, shape, dtype), trainable in zip(spec, mask_leaves):
            size = int(np.prod(shape, dtype=np.int64)) if shape else 1
            off = offsets.get(dtype, 0)
            segments.append(Segment(path, dtype, off, size, shape, trainable))
            offsets[dtype] = off + size
        self.segments: Tuple[Segment, ...] = tuple(segments)
        self.sizes: Dict[str, int] = dict(offsets)

    def flatten(self, tree) -> Dict[str, np.ndarray]:
        """Tree → {dtype: flat buffer}. Host-side (state creation and
        checkpoint conversion); the hot path never calls it — gradients
        are produced flat by construction."""
        leaves = jax.tree_util.tree_leaves(tree)
        if len(leaves) != len(self.segments):
            raise ValueError(
                f"tree has {len(leaves)} leaves, table has "
                f"{len(self.segments)} segments")
        groups: Dict[str, list] = {d: [] for d in self.sizes}
        for seg, leaf in zip(self.segments, leaves):
            arr = np.asarray(jax.device_get(leaf))
            if arr.shape != seg.shape:
                raise ValueError(
                    f"leaf {seg.path}: shape {arr.shape} != table "
                    f"{seg.shape}")
            groups[seg.dtype].append(arr.reshape(-1).astype(seg.dtype))
        return {d: (np.concatenate(parts) if parts
                    else np.zeros((0,), d))
                for d, parts in groups.items()}

    def unflatten(self, bufs) -> Any:
        """{dtype: buffer} → param tree of static slice/reshape views.
        Trace-safe: under jit each leaf is a zero-copy view XLA fuses into
        its consumer; on host (numpy buffers) it is plain slicing."""
        leaves = [bufs[s.dtype][s.offset:s.offset + s.size].reshape(s.shape)
                  for s in self.segments]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def unflatten_mixed(self, master, compute,
                        use_compute: Tuple[bool, ...]) -> Any:
        """Two-source view assembly (graftcast): segment ``i`` slices the
        COMPUTE shadow when ``use_compute[i]`` (conv/dense weights — the
        bf16 fast path) and the MASTER buffer otherwise (f32 islands:
        norm statistics/affine, plus any non-float dtype group). Same
        static slice/reshape views as ``unflatten`` — only the source
        buffer differs per segment."""
        if len(use_compute) != len(self.segments):
            raise ValueError(
                f"use_compute has {len(use_compute)} flags for "
                f"{len(self.segments)} segments")
        leaves = [
            (compute if uc else master)[s.dtype]
            [s.offset:s.offset + s.size].reshape(s.shape)
            for s, uc in zip(self.segments, use_compute)]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def mask_buffers(self) -> Dict[str, np.ndarray]:
        """Per-dtype 0/1 trainability scale, materialized host-side once
        (it rides in the state so it is program INPUT, not a params-sized
        compile-time literal)."""
        out = {}
        for d, total in self.sizes.items():
            vals = np.concatenate([
                np.full(s.size, 1.0 if s.trainable else 0.0, d)
                for s in self.segments if s.dtype == d]) if total else \
                np.zeros((0,), d)
            out[d] = vals
        return out

    def segment_view(self, bufs, path: str):
        """Named lookup — THE way host code reads one leaf out of a flat
        buffer (the flat-state-access lint rule points here)."""
        for s in self.segments:
            if s.path == path:
                return bufs[s.dtype][s.offset:s.offset + s.size].reshape(
                    s.shape)
        raise KeyError(path)


@dataclass(frozen=True)
class _SlotSpec:
    """One optimizer slot (trace / mu / nu): which template-leaf positions
    it owns and its per-param-dtype accumulator dtype."""

    indices: Tuple[int, ...]
    dtypes: Tuple[Tuple[str, str], ...]  # ((param-dtype, slot-dtype), ...)

    def dtype_map(self) -> Dict[str, str]:
        return dict(self.dtypes)


class FlatTrainState(struct.PyTreeNode):
    """TrainState twin for flat mode: one buffer per dtype per tree.

    `masks` is carried (and returned unchanged) rather than closed over so
    donation aliases it instead of embedding a params-sized constant.
    `count` mirrors optax's schedule/Adam step count — it can differ from
    `step` on --begin_epoch restarts whose schedule is offset by
    begin_step instead (see fit_detector's resume logic).

    graftcast: `compute` is the compute-dtype shadow of every FLOAT
    master buffer ({master-dtype-name: bf16 buffer} — the key stays the
    GROUP name), refreshed by `apply` with one cast per buffer; `{}`
    under the f32 policy (no extra leaves, no behavior change). Being
    state, it is a program OUTPUT — the one reliable way to pin the cast
    as a single materialized kernel — and donation recycles it like any
    other buffer.
    """

    step: jnp.ndarray
    count: jnp.ndarray
    flat: Any                       # {dtype: f32 master params buffer}
    slots: Any                      # tuple of {dtype: slot buffer}
    masks: Any                      # {dtype: 0/1 buffer}
    compute: Any                    # {dtype: compute shadow} | {} (f32)
    core: "FlatCore" = struct.field(pytree_node=False)

    def apply_gradients(self, grad_bufs) -> "FlatTrainState":
        return self.core.apply(self, grad_bufs)

    @property
    def params(self):
        """Host-owned param tree — np COPIES, never zero-copy views of the
        donated device buffers (the FlatCore.tree_state use-after-free
        hazard). This is the read-only surface epoch callbacks and the
        fit_detector return path share with tree-mode TrainState; traced
        code reads the flat buffers directly and never calls it."""
        return self.core.table.unflatten(
            {d: np.array(jax.device_get(b))
             for d, b in self.flat.items()})


class FlatCore:
    """Per-(cfg, model) flat-storage engine: segment table + slot layout +
    the fused update. Static — closed over by the jitted step exactly like
    optax's tx (hashed by identity)."""

    def __init__(self, cfg: Config, params, steps_per_epoch: int = 1000,
                 begin_step: int = 0):
        self.kind = cfg.train.optimizer
        # The tree-mode twin: provides the opt_state structure template for
        # checkpoint interchange AND stays the authority on masking/
        # schedule semantics (build_optimizer validates cfg).
        self.tx = build_optimizer(cfg, params, steps_per_epoch, begin_step)
        self.sched = lr_schedule(cfg, steps_per_epoch, begin_step)
        self.clip = float(cfg.train.clip_gradient)
        self.wd = float(cfg.train.wd)
        self.momentum = float(cfg.train.momentum)
        mask_tree = trainable_mask(params, effective_fixed_patterns(cfg))
        self.table = SegmentTable(params, mask_tree)
        self._discover_slots(params)
        # graftcast policy (train/precision.py): which segments read the
        # compute shadow vs the f32 master. Islands (norm statistics and
        # affine — precision.is_island_param) and non-float groups stay
        # master views; everything else takes the one-cast bf16 path.
        self.policy = precision.policy_of(cfg)
        self.use_compute: Tuple[bool, ...] = tuple(
            self.policy.mixed
            and jnp.issubdtype(jnp.dtype(s.dtype), jnp.floating)
            and jnp.dtype(s.dtype) != self.policy.compute_jnp
            and not precision.is_island_param(s.path)
            for s in self.table.segments)

    # -- slot layout -------------------------------------------------------

    def _discover_slots(self, params):
        """Positional slot discovery from the optax state template.

        `tx.init` flattens to: zero or more scalar int32 counts, plus m
        contiguous groups of k array leaves, where k = number of trainable
        segments and each group matches their shapes in order (frozen
        leaves are optax.MaskedNode — no leaves). sgd → 1 group (trace);
        adamw → 2 (mu, nu). Anything else is an optimizer layout this
        module does not know how to flatten — fail loudly.
        """
        template = jax.eval_shape(self.tx.init, params)
        leaves, self.opt_treedef = jax.tree_util.tree_flatten(template)
        self._tmpl_n = len(leaves)
        train_segs = [s for s in self.table.segments if s.trainable]
        self.train_segments = tuple(train_segs)
        count_pos, array_pos = [], []
        for i, leaf in enumerate(leaves):
            if (getattr(leaf, "ndim", None) == 0
                    and jnp.issubdtype(leaf.dtype, jnp.integer)):
                count_pos.append(i)
            else:
                array_pos.append(i)
        k = len(train_segs)
        if k == 0 or len(array_pos) % k:
            raise ValueError(
                f"cannot map optimizer state onto flat slots: "
                f"{len(array_pos)} array leaves over {k} trainable segments")
        slots = []
        for j in range(len(array_pos) // k):
            idxs = array_pos[j * k:(j + 1) * k]
            per_dtype: Dict[str, set] = {}
            for seg, i in zip(train_segs, idxs):
                leaf = leaves[i]
                if tuple(leaf.shape) != seg.shape:
                    raise ValueError(
                        f"slot {j} leaf {i} shape {tuple(leaf.shape)} does "
                        f"not match segment {seg.path} {seg.shape}")
                per_dtype.setdefault(
                    seg.dtype, set()).add(jnp.dtype(leaf.dtype).name)
            dtypes = []
            for d, names in sorted(per_dtype.items()):
                if len(names) != 1:
                    raise ValueError(
                        f"slot {j} mixes dtypes {sorted(names)} within the "
                        f"{d} param group")
                dtypes.append((d, names.pop()))
            slots.append(_SlotSpec(tuple(idxs), tuple(dtypes)))
        expected = {"sgd": 1, "adamw": 2}[self.kind]
        if len(slots) != expected:
            raise ValueError(
                f"{self.kind} template yielded {len(slots)} slots, "
                f"expected {expected}")
        self.slots: Tuple[_SlotSpec, ...] = tuple(slots)
        self.count_pos = tuple(count_pos)

    def _slot_buffers(self, spec: _SlotSpec,
                      fill=None) -> Dict[str, np.ndarray]:
        """Full-size per-dtype slot buffers (frozen regions stay zero);
        `fill` maps trainable segments to leaf arrays (None → zeros)."""
        out = {d: np.zeros(self.table.sizes[d], spec.dtype_map()[d])
               for d in self.table.sizes if d in spec.dtype_map()}
        # dtype groups with no trainable segments still need a buffer so
        # the update's dict zip stays total
        for d in self.table.sizes:
            out.setdefault(d, np.zeros(self.table.sizes[d], d))
        if fill:
            for seg, leaf in fill:
                arr = np.asarray(jax.device_get(leaf))
                out[seg.dtype][seg.offset:seg.offset + seg.size] = (
                    arr.reshape(-1))
        return out

    # -- state construction / conversion -----------------------------------

    def compute_shadow(self, flat) -> Dict[str, Any]:
        """The compute-dtype shadow of the float master buffers — ONE
        cast per buffer ({} under the f32 policy)."""
        if not self.policy.mixed:
            return {}
        return {d: buf for d, buf in precision.cast_buffers(
            flat, self.policy.compute_jnp).items()
            if buf.dtype != jnp.dtype(d)}

    def params_view(self, flat, compute):
        """The param tree a forward should see for (master, shadow)
        buffers: compute views for the fast path, master views for the
        f32 islands (and for everything under the f32 policy)."""
        if not self.policy.mixed:
            return self.table.unflatten(flat)
        return self.table.unflatten_mixed(flat, compute, self.use_compute)

    def master_grads(self, grads) -> Dict[str, Any]:
        """Backward output → f32 master-gradient buffers.

        Under the bf16 policy the loss is differentiated w.r.t. the
        (flat, compute) pair, so ``grads`` arrives as that pair: the
        master cotangent (island leaves, already f32) plus the shadow
        cotangent (bf16). The shadow grad is cast UP once per buffer —
        the transpose twin of ``compute_shadow``'s cast — and summed, so
        everything downstream (DP psum, optimizer update) is float32.
        Under f32 the buffers pass through untouched."""
        if not self.policy.mixed:
            return grads
        g_master, g_compute = grads
        out = dict(g_master)
        for d, g in g_compute.items():
            out[d] = out[d] + g.astype(jnp.dtype(d))
        return out

    def init_state(self, params) -> FlatTrainState:
        """Fresh flat state (the create_train_state analog)."""
        flat = {d: jnp.asarray(b)
                for d, b in self.table.flatten(params).items()}
        slots = tuple({d: jnp.asarray(b)
                       for d, b in self._slot_buffers(spec).items()}
                      for spec in self.slots)
        masks = {d: jnp.asarray(b)
                 for d, b in self.table.mask_buffers().items()}
        return FlatTrainState(
            step=jnp.zeros((), jnp.int32), count=jnp.zeros((), jnp.int32),
            flat=flat, slots=slots, masks=masks,
            compute=self.compute_shadow(flat), core=self)

    def flatten_state(self, state) -> FlatTrainState:
        """TrainState (tree mode, fresh or checkpoint-restored) → flat."""
        flat = {d: jnp.asarray(b)
                for d, b in self.table.flatten(state.params).items()}
        opt_leaves, treedef = jax.tree_util.tree_flatten(state.opt_state)
        if treedef != self.opt_treedef:
            raise ValueError(
                "opt_state structure does not match this FlatCore's "
                "optimizer template — rebuild the core from the same cfg")
        slots = []
        for spec in self.slots:
            fill = [(seg, opt_leaves[i])
                    for seg, i in zip(self.train_segments, spec.indices)]
            slots.append({d: jnp.asarray(b) for d, b in
                          self._slot_buffers(spec, fill).items()})
        count = (jnp.asarray(opt_leaves[self.count_pos[0]], jnp.int32)
                 if self.count_pos else jnp.asarray(state.step, jnp.int32))
        masks = {d: jnp.asarray(b)
                 for d, b in self.table.mask_buffers().items()}
        return FlatTrainState(
            step=jnp.asarray(state.step, jnp.int32), count=count,
            flat=flat, slots=tuple(slots), masks=masks,
            compute=self.compute_shadow(flat), core=self)

    def tree_state(self, fstate: FlatTrainState):
        """Flat state → (params tree, exact optax opt_state) — the
        checkpoint form. Inverse of flatten_state bit-for-bit: trainable
        slot elements round-trip; frozen regions are zeros on both sides
        (tree mode stores no slot at all for frozen leaves).

        The host buffers are OWNED COPIES (np.array), never zero-copy
        views of the device buffers: on the CPU backend `np.asarray(jax
        array)` aliases the XLA buffer, and the train step DONATES the
        flat state — an async checkpoint writer still reading an aliased
        view when the next step reuses that memory is a use-after-free
        (observed as heap corruption crashing at unrelated sites)."""
        params = self.table.unflatten(
            {d: np.array(jax.device_get(b))
             for d, b in fstate.flat.items()})
        leaves: list = [None] * self._tmpl_n
        count = np.int32(jax.device_get(fstate.count))
        for i in self.count_pos:
            leaves[i] = np.asarray(count)
        for spec, bufs in zip(self.slots, fstate.slots):
            host = {d: np.array(jax.device_get(b))
                    for d, b in bufs.items()}
            for seg, i in zip(self.train_segments, spec.indices):
                leaves[i] = (host[seg.dtype]
                             [seg.offset:seg.offset + seg.size]
                             .reshape(seg.shape))
        opt_state = jax.tree_util.tree_unflatten(self.opt_treedef, leaves)
        return params, opt_state

    # -- the update --------------------------------------------------------

    def apply(self, state: FlatTrainState, grads) -> FlatTrainState:
        """One optimizer step over flat buffers (trace-safe; the jitted
        step calls this through FlatTrainState.apply_gradients).

        ``grads``: f32 master-gradient buffers ({dtype: buffer}). Under
        the bf16 policy the backward yields a (master, shadow) cotangent
        pair — the CALLER combines it via ``master_grads`` before the
        DP psum / accumulation (train/step.py::_grads_of), so the update
        itself always runs on f32 buffers, bit-exact across policies
        given equal gradients. The compute shadow is re-materialized
        from the NEW masters at the end — the one cast per dtype buffer,
        pinned by being a program output."""
        lr = self.sched(state.count)
        # optax's safe_int32_increment, computed ONCE: AdamW's bias
        # correction and the stored schedule count share this value.
        bump = jnp.where(state.count < jnp.iinfo(jnp.int32).max,
                         state.count + 1, state.count).astype(jnp.int32)
        if self.kind == "sgd":
            new_flat, new_trace = flat_sgd_update(
                state.flat, grads, state.slots[0], state.masks,
                lr=lr, momentum=self.momentum, wd=self.wd,
                clip_delta=self.clip,
                trace_dtypes=self._full_dtype_map(self.slots[0]))
            new_slots = (new_trace,)
        else:
            new_flat, new_mu, new_nu = flat_adamw_update(
                state.flat, grads, state.slots[0], state.slots[1],
                state.masks, bump,
                lr=lr, wd=self.wd, max_norm=self.clip,
                mu_dtypes=self._full_dtype_map(self.slots[0]))
            new_slots = (new_mu, new_nu)
        return state.replace(step=state.step + 1, count=bump,
                             flat=new_flat, slots=new_slots,
                             compute=self.compute_shadow(new_flat))

    def _full_dtype_map(self, spec: _SlotSpec) -> Dict[str, str]:
        out = {d: d for d in self.table.sizes}  # identity for sloteless dts
        out.update(spec.dtype_map())
        return out


def flat_mode_for(cfg: Config, params=None, param_specs=None) -> bool:
    """Should this run take the flat path? TP/PP (and any explicitly
    sharded param tree) route back to per-leaf — the warning names why."""
    if not getattr(cfg.train, "flat_params", False):
        return False
    if cfg.network.tensor_parallel or cfg.network.pp_stages:
        logger.warning(
            "train.flat_params ignored: %s shards param leaves over the "
            "model axis — a sharded leaf has no contiguous image in a flat "
            "buffer; keeping the per-leaf update path",
            "tensor_parallel" if cfg.network.tensor_parallel else
            f"pp_stages={cfg.network.pp_stages}")
        return False
    if param_specs is not None:
        from mx_rcnn_tpu.parallel.partition import flat_segment_specs

        if params is None or flat_segment_specs(params, param_specs) is None:
            logger.warning(
                "train.flat_params ignored: param tree carries non-"
                "replicated shardings; keeping the per-leaf update path")
            return False
    return True
