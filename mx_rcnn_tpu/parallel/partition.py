"""Tensor-parallel parameter partitioning over the mesh ``model`` axis.

The reference has NO model parallelism (SURVEY.md §3.2 — KVStore data
parallelism is its only strategy), so this module is pure TPU-native
surface: Megatron-style weight sharding for the dense/transformer layers,
expressed as PartitionSpec rules and realized by GSPMD. The recipe is the
scaling-book one: assign shardings to the weights, place the arrays, and
let XLA insert the collectives — no hand-written all-reduces.

What gets sharded (the ``model`` axis):

- transformer MLPs (ViTDet ``mlp1``/``mlp2``, DETR ``ffn1``/``ffn2``) and
  the paired detection FC heads (``fc6``/``fc7`` in TwoFCHead/VGGHead):
  the classic column-parallel → row-parallel split — the up-projection's
  output dim and the down-projection's input dim are sharded, so the
  pointwise nonlinearity runs on shards and XLA places ONE all-reduce at
  the row-parallel output;
- attention projections (ViTDet fused ``qkv``, DETR ``q``/``k``/``v``,
  and both families' ``proj``): column-parallel in, row-parallel out.
  The head-split reshape between them may cost GSPMD a resharding —
  semantics are guaranteed either way; the head-aligned fast path for
  long sequences is the Ulysses/ring SP formulation
  (ops/ring_attention.py), which composes with this module on the same
  axis.

Everything unmatched (convs, norms, small output heads) stays replicated:
for a detector the conv trunk dominates FLOPs but its weights are tiny —
DP handles it; TP pays off exactly where weight matrices are large
(VGG's 25088x4096 fc6 is the classic case, and the transformer families).

Optimizer slots mirror the params tree inside the optax state, so each is
matched to its param by path suffix and placed on that param's sharding —
momentum/Adam slots co-locate with their shards, including restored
(resume) opt_states.
"""

from __future__ import annotations

from fnmatch import fnmatchcase
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from mx_rcnn_tpu.logger import logger

# (path glob, spec) — first match wins; paths are "/"-joined tree keys,
# e.g. "params/features/block0/attn/qkv/kernel".
TP_RULES: Tuple[Tuple[str, P], ...] = (
    # ViTDet encoder blocks (models/vit.py).
    ("*/attn/qkv/kernel", P(None, "model")),
    ("*/attn/qkv/bias", P("model")),
    ("*/attn/proj/kernel", P("model", None)),
    ("*/mlp1/kernel", P(None, "model")),
    ("*/mlp1/bias", P("model")),
    ("*/mlp2/kernel", P("model", None)),
    # DETR encoder/decoder (models/detr.py): separate q/k/v Dense modules
    # under self_attn/cross_attn, paired ffn1/ffn2.
    ("*_attn/q/kernel", P(None, "model")),
    ("*_attn/q/bias", P("model")),
    ("*_attn/k/kernel", P(None, "model")),
    ("*_attn/k/bias", P("model")),
    ("*_attn/v/kernel", P(None, "model")),
    ("*_attn/v/bias", P("model")),
    ("*_attn/proj/kernel", P("model", None)),
    ("*/ffn1/kernel", P(None, "model")),
    ("*/ffn1/bias", P("model")),
    ("*/ffn2/kernel", P("model", None)),
    # Paired FC detection heads: TwoFCHead (models/fpn.py) and VGGHead
    # (models/backbones.py fc6/fc7 — reference symbol_vgg.py's 4096-wide
    # pair, the one genuinely large dense matrix in the classic family).
    ("*/fc6/kernel", P(None, "model")),
    ("*/fc6/bias", P("model")),
    ("*/fc7/kernel", P("model", None)),
)


def elastic_mesh_spec(data: int, model: int, n_devices: int,
                      micro_batch: int, mode: str = "shrink") -> str:
    """Re-derive a mesh spec when the backend comes back with a different
    device count (graftheal shrink / elastic resume / elastic phase 2).

    The default contract is GLOBAL-BATCH INVARIANCE: the run's
    hyperparameters (batch, LR schedule, epoch order) describe the run,
    not the hardware, so a (data, model) mesh re-cut onto fewer devices
    keeps the model axis intact (a TP/PP-sharded weight cannot change
    its partition count mid-run without a resharding story) and shrinks
    the DATA axis to the largest size that still divides ``micro_batch``
    (the per-micro-step global image count) — each surviving device
    simply carries more batch rows, and the loss trajectory continues up
    to psum reassociation.

    ``mode`` is elastic phase 2 (``resilience.elastic_mode``):

    - ``"shrink"`` — the phase 1 behavior above; with ``n_devices`` at
      or above the original footprint the original shape is kept (extra
      devices idle; growth stays a scheduling decision).
    - ``"grow"`` — additionally GROW the data axis onto devices beyond
      the nominal footprint when the re-acquire returns more, to the
      largest micro-batch divisor that fits (still batch-invariant:
      each device carries FEWER rows).
    - ``"rescale"`` — grow, and when a shrink cannot hold the global
      batch the caller rescales it instead: the data axis takes ALL
      available slots (no divisor constraint) and the caller keeps
      rows-per-device constant, shrinking the global batch and rebasing
      the LR schedule in images-seen terms (rebase_schedule_count).
      This function only picks the axis size; the batch/schedule math
      lives in the trainer.
    """
    if mode not in ("shrink", "grow", "rescale"):
        raise ValueError(f"unknown elastic mode {mode!r}; expected "
                         "shrink | grow | rescale")
    if n_devices >= data * model:
        if mode == "shrink":
            grown = data
        else:
            # GROW: the largest micro-batch divisor the returned devices
            # can seat (>= the nominal data axis; falls back to nominal
            # when no larger divisor fits).
            avail = n_devices // model
            grown = next((k for k in range(avail, data, -1)
                          if micro_batch % k == 0), data)
        if grown != data:
            logger.warning(
                "elastic mesh: backend returned %d device(s) above the "
                "%dx%d footprint; growing data axis %d -> %d "
                "(global micro-batch %d invariant, fewer rows per device)",
                n_devices, data, model, data, grown, micro_batch)
        return f"{grown}x{model}"
    if n_devices < model:
        raise ValueError(
            f"backend came back with {n_devices} device(s), fewer than the "
            f"model axis ({model}) — a model-sharded run cannot shrink "
            "below one data shard; resume from checkpoint on a matching "
            "topology instead")
    avail = n_devices // model
    if mode == "rescale" and micro_batch % avail:
        # Too deep for a batch-invariant shrink: take every slot and let
        # the trainer rescale the global batch instead of idling devices.
        logger.warning(
            "elastic mesh: %dx%d does not fit %d device(s) and %d does "
            "not divide the micro-batch %d; rescale mode takes all %d "
            "data slots (rows-per-device constant, global batch scales)",
            data, model, n_devices, avail, micro_batch, avail)
        return f"{avail}x{model}"
    new_data = next(k for k in range(min(avail, data), 0, -1)
                    if micro_batch % k == 0)
    logger.warning(
        "elastic mesh: %dx%d does not fit %d device(s); re-sharding data "
        "axis %d -> %d (model axis kept, global micro-batch %d invariant)",
        data, model, n_devices, data, new_data, micro_batch)
    return f"{new_data}x{model}"


def _path_str(path) -> str:
    parts = []
    for entry in path:
        key = getattr(entry, "key", None)
        if key is None:
            key = getattr(entry, "idx", entry)
        parts.append(str(key))
    return "/".join(parts)


def tp_param_specs(params, rules: Sequence[Tuple[str, P]] = TP_RULES):
    """Params pytree → PartitionSpec pytree (unmatched leaves → P())."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, _ in flat:
        name = _path_str(path)
        spec = P()
        for pattern, rule_spec in rules:
            if fnmatchcase(name, pattern):
                spec = rule_spec
                break
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def _validated_sharding(mesh: Mesh, spec: P, shape,
                        name: str = "?") -> NamedSharding:
    """Spec → NamedSharding; drop to replicated if a sharded dim is not
    divisible by its mesh-axis size (GSPMD would pad, but for the small
    test/head dims an even split either exists or the layer is too small
    for TP to matter — replicate and say so)."""
    for dim, axes in enumerate(spec):
        if axes is None:
            continue
        size = mesh.shape[axes] if isinstance(axes, str) else 1
        if dim >= len(shape) or shape[dim] % size != 0:
            logger.warning(
                "TP rule %s for param %s (shape %s) dropped: dim %d not "
                "divisible by mesh axis %r (size %d) — replicating",
                spec, name, tuple(shape), dim, axes, size)
            return NamedSharding(mesh, P())
    return NamedSharding(mesh, spec)


def shard_params(params, mesh: Mesh, specs=None):
    """Place a (host or replicated) params tree per the spec tree."""
    specs = specs if specs is not None else tp_param_specs(params)
    flat_p, treedef = jax.tree_util.tree_flatten_with_path(params)
    flat_s, spec_def = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    if spec_def != treedef:  # stale/mismatched spec tree must not
        raise ValueError(    # silently misalign shardings
            f"spec tree does not match params tree: {spec_def} vs {treedef}")
    shardings = jax.tree_util.tree_unflatten(treedef, [
        _validated_sharding(mesh, spec, leaf.shape, _path_str(path))
        for (path, leaf), spec in zip(flat_p, flat_s)])
    return jax.device_put(params, shardings), shardings


def shard_train_state(state, mesh: Mesh, specs=None):
    """Place a TrainState for tensor-parallel training.

    Params go to their rule shardings; step is replicated; opt_state leaves
    (fresh OR restored-from-checkpoint) are suffix-matched to their params
    and placed on the same shardings, so Adam/momentum slots always
    co-locate with their param shards.
    """
    params, shardings = shard_params(state.params, mesh, specs)
    n_sharded = sum(
        1 for s in jax.tree.leaves(
            shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
        if not s.is_fully_replicated)
    logger.info("tensor parallel: %d param leaves sharded over model axis "
                "(size %d)", n_sharded, mesh.shape["model"])
    # Optimizer slots (momentum/Adam moments, fresh OR restored) mirror the
    # params tree inside the optax state — e.g. ...mu/params/head/fc6/kernel.
    # Match each opt leaf to its param by path suffix and co-locate it on
    # that param's sharding; everything else (schedule counts, scalars) is
    # replicated. (Running tx.init over sharded params does NOT work:
    # zeros_like has no data dependence on the params, so GSPMD has nothing
    # to propagate and XLA picks arbitrary single-device placements.)
    p_flat = jax.tree_util.tree_flatten_with_path(params)[0]
    s_flat = jax.tree_util.tree_flatten_with_path(shardings)[0]
    param_info = [
        (_path_str(pp), leaf.shape, sh)
        for (pp, leaf), (_, sh) in zip(p_flat, s_flat)
        if not sh.is_fully_replicated]
    repl = NamedSharding(mesh, P())

    def _opt_sharding(path, leaf):
        name = _path_str(path)
        for pname, pshape, sh in param_info:
            if ((name == pname or name.endswith("/" + pname))
                    and getattr(leaf, "shape", None) == pshape):
                return sh
        return repl

    flat, treedef = jax.tree_util.tree_flatten_with_path(state.opt_state)
    opt_shardings = jax.tree_util.tree_unflatten(
        treedef, [_opt_sharding(p, leaf) for p, leaf in flat])
    opt_state = jax.device_put(state.opt_state, opt_shardings)
    step = jax.device_put(state.step, NamedSharding(mesh, P()))
    return state.replace(step=step, params=params, opt_state=opt_state)
