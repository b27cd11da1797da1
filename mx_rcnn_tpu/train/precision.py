"""graftcast — the central mixed-precision policy (one knob).

``train.compute_dtype`` says in one place which numerics run in which
dtype:

- **f32 master weights.** Parameters, gradients and optimizer slots are
  float32 tree leaves, always — in the train state and in the checkpoint
  (bit-for-bit interchangeable between ``f32`` and ``bf16`` runs in both
  directions).
- **bf16 compute.** With ``train.compute_dtype=bf16`` every flax module
  carries ``dtype=bfloat16`` (:func:`model_dtype`) and casts its own
  float32 leaves down at use (flax's per-leaf promotion): activations
  and the conv/matmul weights are bf16, and matmuls/convs accumulate f32
  via XLA's MXU default plus the explicit ``preferred_element_type``
  sites (ops/ring_attention.py, ops/roi_align.py, ops/nms_pallas.py).
- **f32 islands.** The numerics that f16-family dtypes demonstrably
  break stay float32 regardless of the knob: all norm statistics
  (:func:`is_island_param` names the frozen-BN/GroupNorm/LayerNorm
  parameters; flax's norm layers already compute their statistics in
  f32), the losses, ``bbox_transform`` encode/decode, and NMS scores —
  model code routes those casts through :func:`island` (the
  ``dtype-cast-in-jit`` lint rule points here).
- **f32 gradients.** The cast's transpose hands every leaf a float32
  cotangent, so the DP psum and the optimizer update run float32 — the
  update is bit-exact against the ``f32`` path given identical gradients
  (tests/test_precision.py).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

#: accepted ``train.compute_dtype`` spellings → canonical numpy-dtype name
_CANON = {
    "f32": "float32",
    "float32": "float32",
    "bf16": "bfloat16",
    "bfloat16": "bfloat16",
}

#: canonical short spelling (config docs, bench/ledger rows)
SHORT = {"float32": "f32", "bfloat16": "bf16"}

#: leaf names that ARE norm statistics / affine (FrozenBatchNorm) — plus
#: ``pos_embed`` (models/vit.py): it is bilinearly RESIZED in float32
#: before its per-use cast
_ISLAND_LEAVES = frozenset(
    {"gamma", "beta", "moving_mean", "moving_var", "pos_embed"})
#: module-name fragments of the repo's norm layers: make_norm's ``bn*`` /
#: ``downsample_bn`` (FrozenBN + GroupNorm) and the transformer
#: ``norm*`` / ``dec_norm`` LayerNorms (models/vit.py, models/detr.py) —
#: plus DETR's set-prediction heads (``class_embed`` / ``bbox_mlp*`` /
#: ``bbox_out``), which are declared ``dtype=jnp.float32`` Denses over
#: ``island(hs)``: flax computes them with UNCAST f32 weights.
#: ``_ln`` covers the SFP upsampling LayerNorm (models/vit.py up4_ln)
_ISLAND_MODULES = ("bn", "norm", "_ln", "class_embed", "bbox_mlp",
                   "bbox_out")


def normalize_compute_dtype(value: str) -> str:
    """Knob spelling → canonical dtype name; raises on anything else."""
    key = str(value).strip().lower()
    if key not in _CANON:
        raise ValueError(
            f"train.compute_dtype must be one of "
            f"{sorted(set(_CANON))}, got {value!r}")
    return _CANON[key]


@dataclass(frozen=True)
class Policy:
    """Resolved dtype policy: ``compute`` is what the forward/backward
    run in. Parameters, gradients and optimizer state are stored and
    updated in float32 always (bf16 master weights are a different,
    accuracy-risky regime this repo does not offer)."""

    compute: str  # canonical dtype name ("float32" | "bfloat16")

    @property
    def compute_jnp(self):
        return jnp.dtype(self.compute)

    @property
    def short(self) -> str:
        """Ledger/bench row spelling ("f32" / "bf16")."""
        return SHORT[self.compute]


def policy_of(cfg) -> Policy:
    """The run's policy from ``cfg.train.compute_dtype`` (validated)."""
    return Policy(compute=normalize_compute_dtype(cfg.train.compute_dtype))


def model_dtype(cfg):
    """The flax-module ``dtype`` the policy implies — every build_model
    variant reads the knob through here (models/*.py)."""
    return policy_of(cfg).compute_jnp


def island(x: jnp.ndarray) -> jnp.ndarray:
    """THE sanctioned f32 island cast for model code: losses, norm
    statistics, bbox_transform encode/decode, NMS scores. Routing the
    cast through here (instead of a scattered ``.astype(jnp.float32)``)
    keeps the island set auditable — the ``dtype-cast-in-jit`` lint rule
    flags hard-coded float dtype literals in model code."""
    return x.astype(jnp.float32)


def is_island_param(path: str) -> bool:
    """True for param leaves that reach the forward in float32 under a
    bf16 policy: norm statistics and norm affine terms.

    ``path`` is the "/"-joined tree keys of the leaf (e.g.
    ``params/features/stage2/block0/bn1/scale``). Everything else (conv/
    dense kernels and biases) is cast to the compute dtype at use."""
    parts = path.split("/")
    if parts and parts[-1] in _ISLAND_LEAVES:
        return True
    # the owning module: norm layers are named bn*/downsample_bn (ResNet/
    # VGG families) and norm*/dec_norm (ViT/DETR LayerNorms)
    if len(parts) >= 2:
        module = parts[-2]
        if any(frag in module for frag in _ISLAND_MODULES):
            return True
    return False
