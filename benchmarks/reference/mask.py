"""Plain reference: Mask R-CNN on ResNet-FPN, the end-to-end training step in
straightforward float32 ``jax.numpy``.

Written from the published description (He, Gkioxari, Dollar, Girshick 2017,
"Mask R-CNN", section 3 and Fig. 4 right, the FPN head; the recipe of
Detectron's ``e2e_mask_rcnn_R-101-FPN_1x.yaml``, head
``mask_rcnn_fcn_head_v1up4convs``), independent of ``mx_rcnn_tpu``: it
imports nothing of the program and is given nothing the program made. Weights
come from ``benchmarks/weights.py`` by path, inputs from the traffic
generator. One image at a time; every product at ``highest``.

What a mask detector shares with the pyramid detector is imported from
``benchmarks/reference/fpn.py`` (and, through it, ``c4.py``) and not written
twice: trunk, neck, RPN, proposals, the sampler, the roi -> level rule, the
four-tap pooling, the box head, its four losses, the SGD-momentum trainer and
the input plane. What is the mask branch's own is here: the foreground block
of the sampled rois, their 14x14 pooling from the assigned level, the head
(four 3x3 convolutions, a 2x2 stride-2 transposed convolution, a 1x1
convolution to a map a class), the 28x28 target, the loss, and a trainer
with the fifth normaliser.

Departures, beside those ``fpn.py`` lists:

- **the target follows the publication, not the program**: cell (i, j) of a
  roi's 28x28 grid is 1 where its centre lies inside the matched object's
  mask (Detectron rasterises the object's polygon in the roi's frame). The
  program stores each object's mask once, 56x56 in its box's frame,
  resamples it bilinearly with zero padding onto the roi's grid and
  thresholds at 0.5 (``targets/mask_targets.py``): the two differ at most in
  the ring of cells a box's edge crosses;
- **masks are boxes** for the benchmark's traffic: ``benchmarks/synth.py``
  paints each object as a filled rectangle and a rectangle's polygon is its
  box, so ``drivers/train.py::reference_batch`` hands over no mask and the
  target is made from the matched ground-truth box. Where two painted
  rectangles overlap the later hides the earlier; both sides ignore that;
- widths are counted inclusive (+1), as everywhere in these references;
- the branch runs over the first ``round(fg_fraction * batch_rois)`` sampled
  slots: ``c4.sample_rois`` fills its slots foreground first, and the recipe
  gives the branch no more than that many rois an image;
- a foreground roi is one whose sampled label is above 0 (class 0 is the
  background in every dataset of this family);
- the transposed convolution's stored kernel is indexed as a correlation
  over the zero-stuffed input (flax's ``ConvTranspose``, the layout the
  weights' paths name): output cell (2i + a, 2j + b) reads tap
  (1 - a, 1 - b).

``precision`` is ``c4.py``'s ("f32", or the stand-ins "bf16", "fp8",
"f32/rpn_bf16"), passed through to the head as to the rest, and three
planted faults of the branch that the cell's limits have to refuse
(``benchmarks/readings_mask.py``): "f32/mask_off" (the mask loss left out),
"f32/mask_p2" (every roi pooled from P2, whatever Eq. 1 says) and
"f32/mask_bins7" (7x7 bins, each read by the four cells of the 14x14 grid
it covers).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference import c4, fpn
from benchmarks.reference.c4 import prepare_boxes, prepare_image  # noqa: F401

HEAD = "mask_head"


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def param_shapes(spec: dict) -> dict:
    """``fpn.param_shapes`` and the mask head's six layers, by the program's
    paths."""
    s = fpn.param_shapes(spec)
    cin, w = spec["fpn_channels"], spec["mask_head_width"]
    layers = [(f"mask_conv{i}", (3, 3, cin if i == 0 else w, w))
              for i in range(spec["mask_convs"])]
    layers += [("mask_deconv", (2, 2, w, w)),
               ("mask_logits", (1, 1, w, spec["num_classes"]))]
    for name, shape in layers:
        s[f"{HEAD}/{name}/kernel"] = shape
        s[f"{HEAD}/{name}/bias"] = shape[-1:]
    return s


# --------------------------------------------------------------------------
# the branch
# --------------------------------------------------------------------------

def foreground(rois, roi_ok, row, key, spec):
    """The block of sampled slots the branch runs over: their rois, which
    of them are live foreground rois, the class and the box of the object
    each was matched to. The sampler is ``c4.sample_rois`` on the same key
    as the box head's: the same rois in the same slots."""
    t = spec["train"]
    s_rois, labels, _, _, ok = c4.sample_rois(
        rois, roi_ok, row["gt_boxes"], row["gt_classes"], row["gt_valid"],
        key, t, spec["num_classes"])
    n = int(round(t["fg_fraction"] * t["batch_rois"]))
    s_rois, labels, ok = s_rois[:n], labels[:n], ok[:n]
    iou = jnp.where(row["gt_valid"][None, :],
                    c4.iou_matrix(s_rois, row["gt_boxes"]), -1.0)
    matched = row["gt_boxes"][jnp.argmax(iou, axis=1)]
    return s_rois, ok & (labels > 0), labels, matched


def pool(pyr, rois, spec, precision="f32"):
    """(R, 14, 14, C): ``mask_sampling_ratio`` points a bin axis, four taps
    a point, from the level Eq. 1 assigns each roi."""
    size = spec["mask_pool_size"]
    sized = dict(spec, roi_pool_size=size,
                 roi_sampling_ratio=spec["mask_sampling_ratio"])
    if precision == "f32/mask_p2":     # planted: the level rule ignored
        sized["roi_levels"] = spec["roi_levels"][:1]
    if precision == "f32/mask_bins7":  # planted: half the bins an axis
        coarse = fpn.pool(pyr, rois, dict(sized, roi_pool_size=size // 2))
        return jnp.repeat(jnp.repeat(coarse, 2, axis=1), 2, axis=2)
    return fpn.pool(pyr, rois, sized)


def deconv2x2(x, w, precision):
    """(R, h, w, C) -> (R, 2h, 2w, C'), stride 2: every input cell writes
    its own 2x2 block of the output, no two blocks overlap."""
    r, h, wd, _ = x.shape
    y = jnp.einsum("rhwc,abcd->rhawbd", c4._round_to(x, precision),
                   c4._round_to(w, precision)[::-1, ::-1],
                   precision=lax.Precision.HIGHEST)
    return y.reshape(r, 2 * h, 2 * wd, w.shape[-1])


def mask_head(p, pooled, spec, precision="f32"):
    """(R, 14, 14, C) -> (R, 28, 28, classes) logits, a map a class."""
    x = pooled
    for i in range(spec["mask_convs"]):
        x = jax.nn.relu(
            c4._conv(x, p[f"{HEAD}/mask_conv{i}/kernel"], 1, 1, precision)
            + p[f"{HEAD}/mask_conv{i}/bias"])
    x = jax.nn.relu(deconv2x2(x, p[f"{HEAD}/mask_deconv/kernel"], precision)
                    + p[f"{HEAD}/mask_deconv/bias"])
    return (c4._conv(x, p[f"{HEAD}/mask_logits/kernel"], 1, 0, precision)
            + p[f"{HEAD}/mask_logits/bias"])


def mask_targets(rois, boxes, size):
    """(R, 4) rois, (R, 4) boxes of their objects -> (R, size, size) in
    {0, 1}: 1 where the centre of the roi's cell lies inside the object,
    which for a painted rectangle is its box [x1, x2 + 1) x [y1, y2 + 1)."""
    at = (jnp.arange(size, dtype=jnp.float32) + 0.5) / size
    rw = jnp.maximum(rois[:, 2] - rois[:, 0] + 1.0, 1.0)
    rh = jnp.maximum(rois[:, 3] - rois[:, 1] + 1.0, 1.0)
    cx = rois[:, 0, None] + at[None] * rw[:, None]
    cy = rois[:, 1, None] + at[None] * rh[:, None]
    in_x = (cx >= boxes[:, 0, None]) & (cx < boxes[:, 2, None] + 1.0)
    in_y = (cy >= boxes[:, 1, None]) & (cy < boxes[:, 3, None] + 1.0)
    return (in_y[:, :, None] & in_x[:, None, :]).astype(jnp.float32)


def sigmoid_ce(x, t):
    """-t log s(x) - (1 - t) log(1 - s(x)), in the form that overflows for
    no x."""
    return jnp.maximum(x, 0.0) - x * t + jnp.log1p(jnp.exp(-jnp.abs(x)))


def mask_parts(p, got, row, key, spec, precision="f32"):
    """The branch of one image on ``fpn.image_parts``' pyramid and
    proposals: the SUM over its live foreground rois of the mean per-pixel
    cross-entropy of the roi's class's map (the caller divides by the
    batch's live rois), their count, and the pieces by name."""
    rois, live, labels, matched = foreground(got["rois"], got["roi_ok"], row,
                                             key, spec)
    pooled = pool(got["pyramid"], rois, spec, precision)
    pooled = pooled * live[:, None, None, None].astype(pooled.dtype)
    logits = mask_head(p, pooled, spec, precision)
    targets = mask_targets(rois, matched, spec["mask_resolution"])
    own = jnp.take_along_axis(logits, labels[:, None, None, None],
                              axis=-1)[..., 0]
    per_roi = jnp.mean(sigmoid_ce(own, targets), axis=(1, 2))
    total = jnp.sum(jnp.where(live, per_roi, 0.0))
    if precision == "f32/mask_off":    # planted: the loss term left out
        total = 0.0 * total
    return {"mask_sum": total, "n_live": jnp.sum(live.astype(jnp.float32)),
            "mask_rois": rois, "mask_live": live, "mask_labels": labels,
            "mask_matched": matched, "mask_pooled": pooled,
            "mask_targets": targets, "mask_logits": logits}


def image_parts(p, row, keys, spec, precision="f32"):
    """``fpn.image_parts`` and ``mask_parts`` of one image, by name."""
    got = fpn.image_parts(p, row, keys, spec, precision)
    return {**got, **mask_parts(p, got, row, keys[1], spec, precision)}


# --------------------------------------------------------------------------
# the training step
# --------------------------------------------------------------------------

class Trainer(fpn.Trainer):
    """``fpn.Trainer`` over five losses. The fifth's normaliser is the live
    foreground rois of the whole batch, which only a pass through proposals
    and sampler knows: that pass is made first, image by image (as
    ``c4.Trainer`` counts the labelled anchors first), by the SAME compiled
    program as the gradient, read for its count alone. A forward-only
    program would run faster and be a second program of this size to
    compile in every process (the gradient's is 679 MB of code for a v5e,
    220 s; builder's off-chip compile, PR 34): the compile is what a run
    waits for, not the sixteen passes."""

    def __init__(self, spec: dict, params: dict, precision: str = "f32"):
        super().__init__(spec, params, precision)
        # every leaf of the head is trained (``c4.is_trainable`` reads the
        # stem's "conv0" in "mask_conv0")
        for k in [k for k in self.fixed if k.startswith(HEAD + "/")]:
            self.train[k] = self.fixed.pop(k)

    def _weighted(self, train_p, fixed_p, row, keys, norm):
        got = image_parts({**fixed_p, **train_p}, row, keys, self.spec,
                          self.precision)
        sums = jnp.append(got["sums"], got["mask_sum"]) * norm
        return jnp.sum(sums), (sums, got["n_ok"], got["n_live"])

    def grads(self, batch: dict, key, rows=None):
        """``c4.Trainer.grads`` with the mask loss's normaliser: (loss, five
        loss parts, {path: gradient}) of one step's batch."""
        t = self.spec["train"]
        b = batch["image"].shape[0]
        keys = c4.step_keys(key, b)
        rows = list(range(b)) if rows is None else list(rows)
        nb = len(rows)
        at = lambda i: {k: jnp.asarray(v[i]) for k, v in batch.items()}
        n_rpn = sum(float(self._count(at(i), keys[i, 0])) for i in rows)
        nought = jnp.zeros((5,), jnp.float32)
        n_live = sum(float(self._grad(self.train, self.fixed, at(i), keys[i],
                                      nought)[0][1][2]) for i in rows)
        norm = jnp.asarray([1.0 / max(n_rpn, 1.0),
                            1.0 / (t["rpn_batch_size"] * nb),
                            1.0 / (t["batch_rois"] * nb),
                            1.0 / (t["batch_rois"] * nb),
                            1.0 / max(n_live, 1.0)], jnp.float32)
        total, parts, n_ok = None, None, 0.0
        for i in rows:
            (_, (p_i, ok_i, _)), g_i = self._grad(self.train, self.fixed,
                                                  at(i), keys[i], norm)
            n_ok += float(ok_i)
            total = g_i if total is None else jax.tree.map(jnp.add, total, g_i)
            parts = p_i if parts is None else parts + p_i
        if n_ok != t["batch_rois"] * nb:
            raise RuntimeError(
                f"reference: {n_ok} valid sampled rois, expected "
                f"{t['batch_rois'] * nb}; the class-loss normaliser assumed "
                "every slot valid")
        return float(jnp.sum(parts)), np.asarray(parts), total
