"""graftpulse in-graph numerics probes — the device half of health telemetry.

PR 11 made bf16 the default compute dtype, and nothing watched numerical
health: graftscope/graftprof say how FAST a run is, but a run that
overflows in bf16, diverges after a heal, or silently trains on NaNs was
invisible until the epoch metric. This module computes the health signal
INSIDE the compiled train step:

- ``finite_stats`` is ONE fused pass over a buffer: nonfinite count plus
  the finite-masked squared sum (XLA fuses both reductions with the
  ``isfinite`` mask into a single sweep). Masking keeps the norm
  informative when a few elements have overflowed — "3 nonfinite, norm
  unchanged" localizes a blowup far better than an all-NaN norm.
- ``step_health`` probes the three tensors that tell the mixed-precision
  story (grads, params, the update delta), each as one whole-tree fold
  (one count + one norm per kind).
- The result is a dict of SCALARS returned as extra step outputs
  (train/step.py ``health=True``): the cadenced device→host read
  (obs/health.py HealthMonitor, ``obs.health_every``) piggybacks on the
  step's existing output fetch — zero added host syncs per step and zero
  new compiled executables. With ``obs.health_every=0`` the step program
  is bit-identical to the pre-graftpulse one.

Key schema (the contract obs/health.py folds): ``{kind}/{group}/nf``
(nonfinite count, int32) and ``{kind}/{group}/sq`` (finite-masked squared
sum, f32) for kind ∈ {grad, param, update}, group = the literal
``tree``; plus ``loss`` (the step's pooled mean total loss, f32).

This file is the sanctioned home of jit-reachable ``jnp.isfinite``-style
probe reductions — the ``health-host-pull`` lint rule flags them
anywhere else (route new probes through here instead).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

#: key suffixes (obs/health.py parses on these)
NF_SUFFIX = "/nf"
SQ_SUFFIX = "/sq"


def finite_stats(x: jnp.ndarray):
    """One fused pass: ``(nonfinite count, finite-masked squared sum)``.
    The squared sum accumulates in f32 regardless of the buffer dtype
    (a bf16 square would overflow at ~2^64 where the f32 sum does not
    even notice)."""
    finite = jnp.isfinite(x)
    nf = jnp.asarray(x.size, jnp.int32) - jnp.sum(finite.astype(jnp.int32))
    xf = jnp.where(finite, x, 0).astype(jnp.float32)
    return nf, jnp.sum(xf * xf)


def probe_tree(kind: str, tree: Any) -> Dict[str, Any]:
    """The whole-tree fold — per-leaf stats summed into ONE (count,
    squared-sum) pair under the group name ``tree``."""
    nf_tot = jnp.zeros((), jnp.int32)
    sq_tot = jnp.zeros((), jnp.float32)
    for leaf in jax.tree_util.tree_leaves(tree):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            continue
        nf, sq = finite_stats(leaf)
        nf_tot = nf_tot + nf
        sq_tot = sq_tot + sq
    return {f"{kind}/tree{NF_SUFFIX}": nf_tot,
            f"{kind}/tree{SQ_SUFFIX}": sq_tot}


def step_health(old_state, grads, new_state, loss) -> Dict[str, Any]:
    """The per-optimizer-step health dict (train/step.py calls this inside
    the traced step, after the update).

    ``grads`` are the FINAL gradients the update consumed. The update
    delta is probed as ``new − old`` per leaf: a nonfinite delta with
    finite grads localizes the fault to the optimizer math rather than
    the backward."""
    out: Dict[str, Any] = {"loss": jnp.asarray(loss, jnp.float32)}
    out.update(probe_tree("grad", grads))
    out.update(probe_tree("param", new_state.params))
    delta = jax.tree_util.tree_map(
        lambda a, b: a - b, new_state.params, old_state.params)
    out.update(probe_tree("update", delta))
    return out
