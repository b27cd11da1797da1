"""Backbones: ResNet-50/101 C4 and VGG-16, TPU-native.

Replaces the reference's symbolic graph builders (rcnn/symbol/symbol_resnet.py
``residual_unit``/``get_resnet_conv`` and rcnn/symbol/symbol_vgg.py
``get_vgg_conv``) with flax modules. Deliberate deltas from the reference,
chosen for TPU:

- NHWC layout (MXU-native) instead of the reference's NCHW (cuDNN-native).
- bfloat16 compute / float32 params via flax ``dtype``/``param_dtype``.
- Frozen BatchNorm is an affine constant (reference: BN with
  ``use_global_stats=True`` and fixed gamma/beta) — params carry
  ``stop_gradient`` in the forward so the backward pass is structurally free,
  and the trainer additionally masks them out of the optimizer.
- The frozen prefix (reference ``fixed_param_prefix``: ResNet conv0+stage1,
  VGG conv1-conv2) is a ``stop_gradient`` cut on the activation at the freeze
  boundary, so XLA never materializes the early backward graph at all —
  cheaper than the reference's approach of computing and discarding nothing
  (MXNet skips those grads too via fixed_param_names; we keep parity).
- ResNet block is the post-activation v1.5 bottleneck (stride on the 3x3).
  The reference uses the tornadomeet v2 pre-act variant; since pretrained
  MXNet checkpoints cannot be loaded in this environment the standard
  detection (Detectron-lineage) block is used and documented here.
- ``norm="group"`` swaps FrozenBatchNorm for GroupNorm(32) (Wu & He).
  Frozen BN is only sound when restoring PRETRAINED statistics — the
  reference always fine-tunes from an ImageNet checkpoint
  (train_end2end.py --pretrained). Training from scratch (the only option
  in this offline environment) with identity-initialized frozen BN is
  numerically unstable; GroupNorm is the batch-independent, SPMD-friendly
  alternative detection codebases use for from-scratch runs. Default stays
  "frozen_bn" for reference parity.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from flax import linen as nn

Dtype = Any

STAGE_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


class FrozenBatchNorm(nn.Module):
    """BN with frozen statistics AND frozen affine (reference semantics).

    Reference: rcnn/symbol/symbol_resnet.py BatchNorm(use_global_stats=True,
    fixed gamma/beta via fixed_param_prefix). At train and test time this is
    y = gamma * (x - mean) * rsqrt(var + eps) + beta with every tensor
    constant, which XLA folds into the preceding conv.
    """

    features: int
    eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        f = self.features
        gamma = self.param("gamma", nn.initializers.ones, (f,), jnp.float32)
        beta = self.param("beta", nn.initializers.zeros, (f,), jnp.float32)
        mean = self.param("moving_mean", nn.initializers.zeros, (f,), jnp.float32)
        var = self.param("moving_var", nn.initializers.ones, (f,), jnp.float32)
        # Fold to a single scale/bias pair; stop_gradient makes freezing
        # structural (no backward graph through BN params).
        scale = jax.lax.stop_gradient(gamma * jax.lax.rsqrt(var + self.eps))
        bias = jax.lax.stop_gradient(beta - mean * scale)
        return x * scale.astype(self.dtype) + bias.astype(self.dtype)


def make_norm(norm: str, features: int, dtype: Dtype, name: str):
    """Norm-layer factory: "frozen_bn" (reference parity), "group", or
    "layer" (LayerNorm over the channels, eps 1e-6: ViTDet's heads)."""
    if norm == "frozen_bn":
        return FrozenBatchNorm(features, dtype=dtype, name=name)
    if norm == "group":
        return nn.GroupNorm(num_groups=min(32, features), dtype=dtype,
                            param_dtype=jnp.float32, name=name)
    if norm == "layer":
        return nn.LayerNorm(epsilon=1e-6, dtype=dtype,
                            param_dtype=jnp.float32, name=name)
    raise ValueError(f"unknown norm {norm!r}")


class Bottleneck(nn.Module):
    """ResNet v1.5 bottleneck: 1x1 -> 3x3(stride) -> 1x1, post-activation.

    graftcanvas masks: `mask_in` (input stride) re-zeros packed-canvas
    gap cells on the 3x3 conv's INPUT — the 1x1 conv + norm turn masked
    zeros into a bias value (frozen-BN beta, GroupNorm bias), and the
    3x3 is the block's only cross-cell read, so masking exactly there
    makes every spatial window see zeros beyond the content boundary,
    identical to the bucketed path's implicit SAME padding. `mask_out`
    (output stride) re-zeros the block output so the NEXT cross-cell
    consumer (the following block's 3x3, the RPN head, ROIAlign border
    taps) reads clean gaps too. None = no-op (bucketed path HLO
    unchanged)."""

    filters: int  # inner width; output is 4*filters
    stride: int = 1
    norm: str = "frozen_bn"
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask_in=None,
                 mask_out=None) -> jnp.ndarray:
        needs_proj = x.shape[-1] != self.filters * 4 or self.stride != 1
        residual = x
        y = nn.Conv(self.filters, (1, 1), use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32, name="conv1")(x)
        y = make_norm(self.norm, self.filters, self.dtype, "bn1")(y)
        y = nn.relu(y)
        if mask_in is not None:
            y = y * mask_in.astype(y.dtype)
        y = nn.Conv(self.filters, (3, 3), strides=(self.stride, self.stride),
                    padding=[(1, 1), (1, 1)], use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32, name="conv2")(y)
        y = make_norm(self.norm, self.filters, self.dtype, "bn2")(y)
        y = nn.relu(y)
        y = nn.Conv(self.filters * 4, (1, 1), use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32, name="conv3")(y)
        y = make_norm(self.norm, self.filters * 4, self.dtype, "bn3")(y)
        if needs_proj:
            residual = nn.Conv(self.filters * 4, (1, 1),
                               strides=(self.stride, self.stride),
                               use_bias=False, dtype=self.dtype,
                               param_dtype=jnp.float32, name="downsample_conv")(x)
            residual = make_norm(self.norm, self.filters * 4, self.dtype,
                                 "downsample_bn")(residual)
        out = nn.relu(y + residual)
        if mask_out is not None:
            out = out * mask_out.astype(out.dtype)
        return out


class ResNetStage(nn.Module):
    """graftcanvas masks (ops/canvas.py::placement_masks): `mask_in` at
    the stage's INPUT stride and `mask` at its OUTPUT stride. Block 0
    (which may downsample) reads mask_in for its 3x3 input, every later
    block the output-stride mask; all blocks re-zero their outputs
    (Bottleneck.mask_out) so packed-canvas gap cells stay exactly zero
    through the stage. None = no-op (identical HLO to the pre-canvas
    code)."""

    blocks: int
    filters: int
    stride: int
    norm: str = "frozen_bn"
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, mask_in=None, mask=None) -> jnp.ndarray:
        for i in range(self.blocks):
            x = Bottleneck(self.filters, stride=self.stride if i == 0 else 1,
                           norm=self.norm, dtype=self.dtype,
                           name=f"block{i}")(x, mask_in if i == 0 else mask,
                                             mask)
        return x


class ResNetC4(nn.Module):
    """ResNet conv0 + stages 1-3 -> stride-16, 1024-channel C4 features.

    Reference: rcnn/symbol/symbol_resnet.py get_resnet_conv (units for 50/101
    layers, ends at the stage-4-in-torch-numbering res4 block). ``freeze_at=2``
    reproduces fixed_param_prefix=['conv0','stage1'] via an activation
    stop_gradient cut.
    """

    depth: int = 50
    freeze_at: int = 2  # 0=no freeze, 1=stem, 2=stem+stage1 (reference default)
    norm: str = "frozen_bn"
    dtype: Dtype = jnp.bfloat16
    remat: bool = False  # rematerialize stage activations in the backward

    @nn.compact
    def __call__(self, x: jnp.ndarray, masks=None) -> jnp.ndarray:
        """masks (graftcanvas): {stride: (B, H/s, W/s, 1)} placement
        masks of a packed canvas; gap cells are re-zeroed after the stem
        (before AND after the max-pool — the pool's post-relu window max
        over zero gap cells matches the bucketed -inf edge padding only
        when its inputs are masked) and after every residual block
        (ResNetStage.mask). None = the classic bucketed path."""
        blocks = STAGE_BLOCKS[self.depth]
        m = masks or {}
        # jax.checkpoint per stage: trades ~1/3 extra FLOPs for not keeping
        # every block's activations live through the backward — the HBM
        # lever for big images / batch > 1 (network.remat).
        Stage = nn.remat(ResNetStage) if self.remat else ResNetStage
        x = x.astype(self.dtype)
        x = nn.Conv(64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                    use_bias=False, dtype=self.dtype, param_dtype=jnp.float32,
                    name="conv0")(x)
        x = make_norm(self.norm, 64, self.dtype, "bn0")(x)
        x = nn.relu(x)
        if 2 in m:
            x = x * m[2].astype(x.dtype)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        if 4 in m:
            x = x * m[4].astype(x.dtype)
        if self.freeze_at >= 1:
            x = jax.lax.stop_gradient(x)
        x = Stage(blocks[0], 64, stride=1, norm=self.norm,
                  dtype=self.dtype, name="stage1")(x, m.get(4), m.get(4))
        if self.freeze_at >= 2:
            x = jax.lax.stop_gradient(x)
        x = Stage(blocks[1], 128, stride=2, norm=self.norm,
                  dtype=self.dtype, name="stage2")(x, m.get(4), m.get(8))
        x = Stage(blocks[2], 256, stride=2, norm=self.norm,
                  dtype=self.dtype, name="stage3")(x, m.get(8), m.get(16))
        return x  # (B, H/16, W/16, 1024)


class ResNetStages(nn.Module):
    """All four stages with per-stage outputs — the FPN backbone variant.

    Returns (C2, C3, C4, C5) at strides (4, 8, 16, 32).
    """

    depth: int = 50
    freeze_at: int = 2
    norm: str = "frozen_bn"
    dtype: Dtype = jnp.bfloat16
    remat: bool = False  # see ResNetC4.remat

    @nn.compact
    def __call__(self, x: jnp.ndarray, masks=None) -> Sequence[jnp.ndarray]:
        """masks: packed-canvas placement masks (see ResNetC4)."""
        blocks = STAGE_BLOCKS[self.depth]
        m = masks or {}
        Stage = nn.remat(ResNetStage) if self.remat else ResNetStage
        x = x.astype(self.dtype)
        x = nn.Conv(64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                    use_bias=False, dtype=self.dtype, param_dtype=jnp.float32,
                    name="conv0")(x)
        x = make_norm(self.norm, 64, self.dtype, "bn0")(x)
        x = nn.relu(x)
        if 2 in m:
            x = x * m[2].astype(x.dtype)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)])
        if 4 in m:
            x = x * m[4].astype(x.dtype)
        if self.freeze_at >= 1:
            x = jax.lax.stop_gradient(x)
        c2 = Stage(blocks[0], 64, stride=1, norm=self.norm,
                   dtype=self.dtype, name="stage1")(x, m.get(4), m.get(4))
        if self.freeze_at >= 2:
            c2 = jax.lax.stop_gradient(c2)
        c3 = Stage(blocks[1], 128, stride=2, norm=self.norm,
                   dtype=self.dtype, name="stage2")(c2, m.get(4), m.get(8))
        c4 = Stage(blocks[2], 256, stride=2, norm=self.norm,
                   dtype=self.dtype, name="stage3")(c3, m.get(8), m.get(16))
        c5 = Stage(blocks[3], 512, stride=2, norm=self.norm,
                   dtype=self.dtype, name="stage4")(c4, m.get(16), m.get(32))
        return c2, c3, c4, c5


class ResNetHead(nn.Module):
    """C4 detection head: stage 5 on pooled 14x14 ROIs -> global avg pool.

    Reference: rcnn/symbol/symbol_resnet.py — ROIPooling 14x14 then the
    stage-5 residual blocks (stride 2 -> 7x7) then global average pooling,
    feeding cls_score/bbox_pred FCs.
    Input (R, 14, 14, 1024) -> output (R, 2048).
    """

    depth: int = 50
    norm: str = "frozen_bn"
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, rois_feat: jnp.ndarray) -> jnp.ndarray:
        blocks = STAGE_BLOCKS[self.depth]
        x = ResNetStage(blocks[3], 512, stride=2, norm=self.norm,
                        dtype=self.dtype,
                        name="stage4")(rois_feat.astype(self.dtype))
        return jnp.mean(x, axis=(1, 2))  # (R, 2048)


class VGGConv(nn.Module):
    """VGG-16 conv1_1..conv5_3, stride-16 512-channel features.

    Reference: rcnn/symbol/symbol_vgg.py get_vgg_conv (13 convs, 4 pools —
    pool5 omitted so the feature stride stays 16; conv1-conv2 frozen via
    fixed_param_prefix, here a stop_gradient cut after block 2).
    """

    freeze_blocks: int = 2
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, masks=None) -> jnp.ndarray:
        """masks: packed-canvas placement masks (see ResNetC4). VGG convs
        carry biases, so gap cells are re-zeroed after EVERY conv — a
        biased conv turns zeros into a bias halo that the next conv
        would read where the bucketed path reads implicit zero pad."""
        plan = ((2, 64), (2, 128), (3, 256), (3, 512), (3, 512))
        m = masks or {}
        x = x.astype(self.dtype)
        stride = 1
        for b, (n_convs, width) in enumerate(plan, start=1):
            for c in range(1, n_convs + 1):
                x = nn.Conv(width, (3, 3), padding=[(1, 1), (1, 1)],
                            dtype=self.dtype, param_dtype=jnp.float32,
                            name=f"conv{b}_{c}")(x)
                x = nn.relu(x)
                if stride in m:
                    x = x * m[stride].astype(x.dtype)
            if b < 5:  # no pool5 — keep stride 16
                x = nn.max_pool(x, (2, 2), strides=(2, 2))
                stride *= 2
            if b == self.freeze_blocks:
                x = jax.lax.stop_gradient(x)
        return x  # (B, H/16, W/16, 512)


class VGGHead(nn.Module):
    """fc6/fc7 head on 7x7 pooled ROIs (reference: symbol_vgg.py fc6, fc7).

    Input (R, 7, 7, 512) -> (R, 4096). Dropout as in the reference (0.5),
    active only when ``deterministic=False``.
    """

    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, rois_feat: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        r = rois_feat.shape[0]
        x = rois_feat.astype(self.dtype).reshape(r, -1)
        x = nn.Dense(4096, dtype=self.dtype, param_dtype=jnp.float32, name="fc6")(x)
        x = nn.relu(x)
        x = nn.Dropout(0.5, deterministic=deterministic)(x)
        x = nn.Dense(4096, dtype=self.dtype, param_dtype=jnp.float32, name="fc7")(x)
        x = nn.relu(x)
        x = nn.Dropout(0.5, deterministic=deterministic)(x)
        return x
