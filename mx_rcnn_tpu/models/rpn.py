"""RPN head.

Reference: the rpn_conv/rpn_cls_score/rpn_bbox_pred trio built inline in
rcnn/symbol/symbol_vgg.py and symbol_resnet.py: 3x3 conv (512) + relu, then
two sibling 1x1 convs producing 2A objectness logits and 4A box deltas.

TPU delta: outputs are NHWC with channels last — (B, H, W, 2A) and
(B, H, W, 4A) — matching ops/proposal.py's expected layout. The per-pixel
channel order is [bg x A, fg x A] for scores (so ``[..., A:]`` is fg) and A
groups of 4 for deltas, consistent with ops/anchors.anchor_grid ordering.
"""

from __future__ import annotations

from typing import Any, ClassVar, Tuple

import jax.numpy as jnp
from flax import linen as nn

from mx_rcnn_tpu.train.precision import island


class RPNHead(nn.Module):
    #: Spatial receptive radius (px on the feature grid) of ONE of the
    #: head's 3x3 convs; the 1x1 siblings add 0. Between two stacked convs
    #: the caller's ``keep`` re-zeroes what is not the map.
    #: models/fpn.py::apply_rpn_head_packed sizes its inter-level canvas
    #: gap from this so activations cannot leak across packed levels; a
    #: deeper head MUST raise it (and gets a loud failure if the
    #: attribute is missing entirely).
    SPATIAL_RADIUS: ClassVar[int] = 1

    num_anchors: int = 9
    channels: int = 512
    # stacked 3x3 convs, each with its ReLU (ViTDet's head has 2)
    convs: int = 1
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, feat: jnp.ndarray,
                 keep=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """``keep``: (1, H, W, 1) where the map is, re-applied between two
        convs (a packed canvas's gap cells read zero, as a lone level's
        SAME padding does); unused with one conv."""
        a = self.num_anchors
        x = feat.astype(self.dtype)
        for i in range(self.convs):
            if i and keep is not None:
                x = x * keep.astype(x.dtype)
            x = nn.relu(nn.Conv(self.channels, (3, 3),
                                padding=[(1, 1), (1, 1)], dtype=self.dtype,
                                param_dtype=jnp.float32,
                                name="rpn_conv" + (str(i + 1) if i else ""))(
                                    x))
        cls_logits = nn.Conv(2 * a, (1, 1), dtype=self.dtype,
                             param_dtype=jnp.float32,
                             kernel_init=nn.initializers.normal(0.01),
                             name="rpn_cls_score")(x)
        bbox_deltas = nn.Conv(4 * a, (1, 1), dtype=self.dtype,
                              param_dtype=jnp.float32,
                              kernel_init=nn.initializers.normal(0.01),
                              name="rpn_bbox_pred")(x)
        return island(cls_logits), island(bbox_deltas)
