"""Loss functions with the reference's exact normalization semantics.

Reference: the loss wiring inside rcnn/symbol/symbol_vgg.py /
symbol_resnet.py get_*_train:

- RPN cls: ``SoftmaxOutput(..., use_ignore=True, ignore_label=-1,
  normalization='valid')`` — cross-entropy summed over non-ignored anchors,
  divided by the non-ignored count (≈ RPN_BATCH_SIZE).
- RPN bbox: ``smooth_l1(scalar=3.0)`` × rpn_bbox_weight, ``MakeLoss``
  grad_scale 1/RPN_BATCH_SIZE — i.e. a *fixed-constant* normalizer, not the
  live fg count (SURVEY.md §4.5 'key numeric gotchas').
- RCNN cls: ``SoftmaxOutput(normalization='batch')`` — mean over sampled
  rois.
- RCNN bbox: ``smooth_l1(scalar=1.0)`` × bbox_weight, grad_scale
  1/BATCH_ROIS.

At >1 image per device the fixed constants are multiplied by the image count
(equivalent to the reference's per-device B=1 recipe replicated).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mx_rcnn_tpu.train.precision import island


def smooth_l1(x: jnp.ndarray, sigma: float) -> jnp.ndarray:
    """Elementwise smooth-L1 with the reference's sigma parameterization.

    f(x) = 0.5 (sigma x)^2        if |x| < 1/sigma^2
           |x| - 0.5/sigma^2      otherwise
    (mx.symbol.smooth_l1 semantics; sigma=3 for RPN, sigma=1 for RCNN.)
    """
    s2 = sigma * sigma
    ax = jnp.abs(x)
    return jnp.where(ax < 1.0 / s2, 0.5 * s2 * x * x, ax - 0.5 / s2)


def softmax_ce_with_ignore(logits: jnp.ndarray, labels: jnp.ndarray) -> tuple:
    """Cross-entropy with ignore-label −1, 'valid' normalization.

    logits: (N, C); labels: (N,) int32, −1 = ignore.
    Returns (loss_scalar, per_example_ce, valid_mask) — the per-example terms
    feed the RPNLogLoss/RCNNLogLoss metrics.
    """
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(island(logits), axis=-1)
    # The label's log-probability by a dense select over the classes, not
    # `take_along_axis`: that is a gather of one element an example, which
    # the chip does an element at a time (28 ms over a pyramid's 2.2 M
    # anchors a step; PERF.md section 6, PR 33). One term of the sum is not
    # zero, so the value is the gathered one to the bit.
    at_label = safe[:, None] == jnp.arange(logits.shape[-1])
    ce = -jnp.sum(jnp.where(at_label, logp, 0.0), axis=-1)
    ce = jnp.where(valid, ce, 0.0)
    count = jnp.maximum(jnp.sum(island(valid)), 1.0)
    return jnp.sum(ce) / count, ce, valid


def rpn_losses(
    rpn_cls_logits: jnp.ndarray,
    rpn_bbox_deltas: jnp.ndarray,
    labels: jnp.ndarray,
    bbox_targets: jnp.ndarray,
    bbox_weights: jnp.ndarray,
    rpn_batch_size: int,
) -> dict:
    """RPN pair of losses.

    Args:
      rpn_cls_logits: (B, N, 2) per-anchor [bg, fg] logits.
      rpn_bbox_deltas: (B, N, 4).
      labels: (B, N) in {−1, 0, 1}; bbox_targets/weights: (B, N, 4).
    """
    b = rpn_cls_logits.shape[0]
    cls_loss, ce, valid = softmax_ce_with_ignore(
        rpn_cls_logits.reshape(-1, 2), labels.reshape(-1)
    )
    diff = island(rpn_bbox_deltas - bbox_targets)
    l1 = smooth_l1(diff, sigma=3.0) * bbox_weights
    bbox_loss = jnp.sum(l1) / (rpn_batch_size * b)
    return {
        "rpn_cls_loss": cls_loss,
        "rpn_bbox_loss": bbox_loss,
        "rpn_ce": ce,
        "rpn_valid": valid,
    }


def rcnn_losses(
    cls_logits: jnp.ndarray,
    bbox_pred: jnp.ndarray,
    labels: jnp.ndarray,
    bbox_targets: jnp.ndarray,
    bbox_weights: jnp.ndarray,
    batch_rois: int,
    batch_images: int,
) -> dict:
    """RCNN pair of losses.

    Args:
      cls_logits: (R, C); bbox_pred: (R, 4C); labels: (R,) int32 (−1 masks a
      degenerate slot); bbox_targets/weights: (R, 4C).
    """
    cls_loss, ce, valid = softmax_ce_with_ignore(cls_logits, labels)
    diff = island(bbox_pred - bbox_targets)
    l1 = smooth_l1(diff, sigma=1.0) * bbox_weights
    bbox_loss = jnp.sum(l1) / (batch_rois * batch_images)
    return {
        "rcnn_cls_loss": cls_loss,
        "rcnn_bbox_loss": bbox_loss,
        "rcnn_ce": ce,
        "rcnn_valid": valid,
    }
