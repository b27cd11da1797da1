"""Tests for assign_anchor / sample_rois vs reference semantics."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mx_rcnn_tpu.ops.anchors import anchor_grid
from mx_rcnn_tpu.targets.rpn_targets import assign_anchor
from mx_rcnn_tpu.targets.rcnn_targets import sample_rois


def pad_gt(boxes, g=8):
    out = np.zeros((g, 4), np.float32)
    valid = np.zeros((g,), bool)
    if len(boxes):
        out[: len(boxes)] = boxes
        valid[: len(boxes)] = True
    return jnp.array(out), jnp.array(valid)


class TestAssignAnchor:
    def setup_method(self):
        self.anchors = jnp.array(anchor_grid(16, 16, stride=16))
        self.im_info = jnp.array([256.0, 256.0, 1.0])
        self.key = jax.random.PRNGKey(0)

    def test_positive_on_exact_match(self):
        # gt equal to one anchor -> that anchor must be labeled 1.
        a = np.asarray(self.anchors)
        inside = (a[:, 0] >= 0) & (a[:, 1] >= 0) & (a[:, 2] < 256) & (a[:, 3] < 256)
        idx = int(np.nonzero(inside)[0][0])
        gt, gtv = pad_gt([a[idx]])
        t = assign_anchor(self.anchors, gt, gtv, self.im_info, self.key)
        assert int(t.labels[idx]) == 1
        # Its regression target is ~0 and weighted.
        assert np.allclose(t.bbox_targets[idx], 0.0, atol=1e-5)
        assert np.allclose(t.bbox_weights[idx], 1.0)

    def test_outside_anchors_ignored(self):
        gt, gtv = pad_gt([[10, 10, 100, 100]])
        t = assign_anchor(self.anchors, gt, gtv, self.im_info, self.key)
        a = np.asarray(self.anchors)
        outside = ~(
            (a[:, 0] >= 0) & (a[:, 1] >= 0) & (a[:, 2] < 256) & (a[:, 3] < 256)
        )
        assert np.all(np.asarray(t.labels)[outside] == -1)
        assert np.all(np.asarray(t.bbox_weights)[outside] == 0)

    def test_batch_size_cap(self):
        gt, gtv = pad_gt([[10, 10, 100, 100], [120, 120, 240, 240]])
        t = assign_anchor(
            self.anchors, gt, gtv, self.im_info, self.key, rpn_batch_size=256
        )
        labels = np.asarray(t.labels)
        assert (labels >= 0).sum() <= 256
        assert (labels == 1).sum() <= 128
        assert (labels == 1).sum() >= 1  # best-per-gt guarantee

    def test_no_gt_all_background(self):
        gt, gtv = pad_gt([])
        t = assign_anchor(self.anchors, gt, gtv, self.im_info, self.key)
        labels = np.asarray(t.labels)
        assert (labels == 1).sum() == 0
        # All inside anchors become negatives, capped at the 256 batch size.
        a = np.asarray(self.anchors)
        inside = (
            (a[:, 0] >= 0) & (a[:, 1] >= 0) & (a[:, 2] < 256) & (a[:, 3] < 256)
        ).sum()
        assert (labels == 0).sum() == min(inside, 256)

    def test_jit_matches_eager(self):
        gt, gtv = pad_gt([[10, 10, 100, 100]])
        f = lambda k: assign_anchor(self.anchors, gt, gtv, self.im_info, k)
        eager = f(self.key)
        jitted = jax.jit(f)(self.key)
        assert np.array_equal(eager.labels, jitted.labels)
        assert np.allclose(eager.bbox_targets, jitted.bbox_targets)


def _oracle_subsample(mask, limit, key):
    n = mask.shape[0]
    keys = jnp.where(mask, jax.random.uniform(key, (n,)), 2.0)
    order = jnp.argsort(keys)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    return mask & (rank < limit)


def _oracle_assign_anchor(anchors, gt_boxes, gt_valid, im_info, key, *,
                          rpn_batch_size=256, rpn_fg_fraction=0.5,
                          positive_overlap=0.7, negative_overlap=0.3,
                          allowed_border=0.0, clobber_positives=False):
    """`assign_anchor` as it stood before PR 33, kept as the oracle: the
    dense (N, G) overlap matrix against every padded slot, a rank of every
    anchor for each subsampling, a matched box gathered for every anchor."""
    from mx_rcnn_tpu.ops.boxes import bbox_overlaps, bbox_transform

    n = anchors.shape[0]
    k_fg, k_bg = jax.random.split(key)
    y0 = im_info[3] if im_info.shape[0] >= 5 else 0.0
    x0 = im_info[4] if im_info.shape[0] >= 5 else 0.0
    inside = (
        (anchors[:, 0] >= x0 - allowed_border)
        & (anchors[:, 1] >= y0 - allowed_border)
        & (anchors[:, 2] < x0 + im_info[1] + allowed_border)
        & (anchors[:, 3] < y0 + im_info[0] + allowed_border)
    )
    iou = bbox_overlaps(anchors, gt_boxes)
    iou = jnp.where(gt_valid[None, :], iou, -1.0)
    any_gt = jnp.any(gt_valid)
    max_iou = jnp.max(iou, axis=1)
    argmax_gt = jnp.argmax(iou, axis=1)
    gt_best = jnp.max(jnp.where(inside[:, None], iou, -1.0), axis=0)
    is_gt_best = jnp.any(
        (jnp.abs(iou - gt_best[None, :]) < 1e-9) & gt_valid[None, :]
        & (gt_best[None, :] > 0), axis=1)
    labels = jnp.full((n,), -1, jnp.int32)
    neg = max_iou < negative_overlap
    pos = (max_iou >= positive_overlap) | is_gt_best
    if clobber_positives:
        labels = jnp.where(inside & pos, 1, labels)
        labels = jnp.where(inside & neg, 0, labels)
    else:
        labels = jnp.where(inside & neg, 0, labels)
        labels = jnp.where(inside & pos, 1, labels)
    labels = jnp.where(any_gt, labels, jnp.where(inside, 0, -1))
    num_fg_cap = int(rpn_batch_size * rpn_fg_fraction)
    fg_mask = _oracle_subsample(labels == 1, num_fg_cap, k_fg)
    labels = jnp.where((labels == 1) & ~fg_mask, -1, labels)
    n_fg = jnp.sum(fg_mask.astype(jnp.int32))
    bg_mask = _oracle_subsample(labels == 0, rpn_batch_size - n_fg, k_bg)
    labels = jnp.where((labels == 0) & ~bg_mask, -1, labels)
    matched_gt = gt_boxes[argmax_gt]
    bbox_targets = bbox_transform(anchors, matched_gt)
    bbox_targets = jnp.where((labels == 1)[:, None], bbox_targets, 0.0)
    bbox_weights = jnp.where((labels == 1)[:, None], 1.0, 0.0)
    return labels, bbox_targets, bbox_weights


def _boxes(rng, count, extent=512.0):
    """`count` random boxes inside the image, sides 16..extent/2."""
    wh = rng.uniform(16, extent / 2, (count, 2))
    xy = rng.uniform(0, extent - 1 - wh)
    return np.concatenate([xy, xy + wh], axis=1).astype(np.float32)


def _slots(boxes, g, at=None):
    """(1, g, 4) padded boxes and (1, g) validity; `at` names the valid
    slots (default: a prefix). Padding slots hold garbage, not zeros."""
    at = np.arange(len(boxes)) if at is None else np.asarray(at)
    out = np.full((g, 4), 7.0, np.float32)
    valid = np.zeros((g,), bool)
    out[at], valid[at] = boxes, True
    return out[None], valid[None]


def _anchor_cases():
    """name -> (gt_boxes (B,G,4), gt_valid (B,G), im_info (B,3|5), kwargs).
    The anchors are the 32x32x9 grid of a 512x512 image: 9216, of which
    2344 lie inside, so the default batch of 256 subsamples."""
    rng = np.random.default_rng(33)
    anchors = anchor_grid(32, 32, stride=16)
    a = anchors[(anchors[:, 0] >= 0) & (anchors[:, 1] >= 0)
                & (anchors[:, 2] < 512) & (anchors[:, 3] < 512)]
    info = np.array([[512.0, 512.0, 1.0]], np.float32)
    cases = {}
    for count in (0, 1, 5, 100):
        cases[f"{count}_valid_boxes"] = (*_slots(_boxes(rng, count), 100),
                                         info, {})
    cases["valid_slots_not_a_prefix"] = (
        *_slots(_boxes(rng, 4), 12, at=[9, 2, 11, 5]), info, {})
    twin = _boxes(rng, 1)
    cases["two_identical_boxes"] = (
        *_slots(np.concatenate([_boxes(rng, 1), twin, twin]), 8, at=[6, 1, 4]),
        info, {})
    cases["a_box_equal_to_an_anchor"] = (
        *_slots(np.stack([a[len(a) // 2], a[3]]), 8), info, {})
    # a batch of 32: more than 16 positives, and the 16 left for the
    # negatives bind too
    cases["more_positives_than_the_cap"] = (
        *_slots(np.array([[160, 160, 290, 290]], np.float32), 8), info,
        dict(positive_overlap=0.3, rpn_batch_size=32))
    cases["fewer_positives_than_the_cap"] = (
        *_slots(_boxes(rng, 2), 8), info, {})
    # thresholds that overlap, so that an anchor is positive AND negative
    # and the order of the two writes decides
    for clobber in (False, True):
        cases[f"clobber_positives_{clobber}"] = (
            *_slots(_boxes(rng, 5), 8), info,
            dict(positive_overlap=0.2, negative_overlap=0.4,
                 clobber_positives=clobber))
    # a packed row [h, w, scale, y0, x0]: a 320x400 rect at (64, 48)
    packed = _boxes(rng, 3, extent=300.0) + np.array([48, 64, 48, 64],
                                                     np.float32)
    cases["packed_im_info_row"] = (
        *_slots(packed, 8),
        np.array([[320.0, 400.0, 1.0, 64.0, 48.0]], np.float32), {})
    # images of one batch that differ in box count: the loop runs to the
    # batch's maximum, the shorter images' late slots masked
    many = [_slots(_boxes(rng, c), 10, at=rng.permutation(10)[:c])
            for c in (3, 0, 7, 1)]
    cases["a_batch_of_differing_counts"] = (
        np.concatenate([m[0] for m in many]),
        np.concatenate([m[1] for m in many]), np.repeat(info, 4, axis=0), {})
    cases["border_allowed"] = (*_slots(_boxes(rng, 3), 8), info,
                               dict(allowed_border=24.0))
    return jnp.asarray(anchors), cases


_ANCHORS, _CASES = _anchor_cases()


@pytest.mark.parametrize("name", sorted(_CASES))
def test_assign_anchors_equals_the_oracle(name):
    """The labelling that walks the valid slots, keeps by top-k and computes
    targets for the kept positives only gives what the dense one gave: the
    same anchors kept (labels and weights equal), targets within 1e-6."""
    from mx_rcnn_tpu.targets.rpn_targets import assign_anchors

    gt_boxes, gt_valid, im_info, kw = _CASES[name]
    b = gt_boxes.shape[0]
    for seed in range(3):
        keys = jax.random.split(jax.random.PRNGKey(seed), b)
        got = jax.jit(lambda k: assign_anchors(
            _ANCHORS, jnp.asarray(gt_boxes), jnp.asarray(gt_valid),
            jnp.asarray(im_info), k, **kw))(keys)
        n_valid = gt_valid.sum(axis=1)
        assert got.counts.tolist()[:2] == [n_valid.max(), gt_valid.shape[1]]
        for i in range(b):
            labels, targets, weights = _oracle_assign_anchor(
                _ANCHORS, jnp.asarray(gt_boxes[i]), jnp.asarray(gt_valid[i]),
                jnp.asarray(im_info[i]), keys[i], **kw)
            np.testing.assert_array_equal(got.labels[i], labels)
            np.testing.assert_array_equal(got.bbox_weights[i], weights)
            np.testing.assert_allclose(got.bbox_targets[i], targets,
                                       atol=1e-6, rtol=0)
        labels = np.asarray(got.labels)
        assert got.counts.tolist()[2:] == [(labels == 1).sum(),
                                           (labels == 0).sum()]
        if name == "more_positives_than_the_cap":
            assert (labels == 1).sum() == 16 and (labels == 0).sum() == 16
        if name == "fewer_positives_than_the_cap":
            assert 0 < (labels == 1).sum() < 128


def test_subsample_orders_tied_keys_as_a_stable_argsort():
    """Equal keys: the kept set is the stable sort's, lower anchor first
    (a float32 uniform draw over 279,279 anchors ties in its thousands)."""
    from mx_rcnn_tpu.targets import rpn_targets

    n, limit = 5000, 40
    mask = jnp.asarray(np.random.default_rng(0).random((2, n)) < 0.5)
    real = jax.random.uniform
    jax.random.uniform = lambda k, shape: jnp.round(real(k, shape) * 20) / 20
    try:
        keys = jax.random.split(jax.random.PRNGKey(5), 2)
        kept, idx, chosen = rpn_targets._random_subsample(
            mask, jnp.asarray([limit, 0]), 64, keys)
        want = [_oracle_subsample(mask[i], lim, keys[i])
                for i, lim in enumerate((limit, 0))]
    finally:
        jax.random.uniform = real
    np.testing.assert_array_equal(kept, np.stack(want))
    assert int(kept[0].sum()) == limit and int(kept[1].sum()) == 0
    assert set(np.asarray(idx[0])[np.asarray(chosen[0])]) == set(
        np.nonzero(np.asarray(kept[0]))[0])


class TestSampleRois:
    NUM_CLASSES = 5

    def _run(self, rois, roi_valid, gts, classes, key=0, **kw):
        g = 8
        gt, gtv = pad_gt(gts, g)
        cls = np.zeros((g,), np.int32)
        cls[: len(classes)] = classes
        return sample_rois(
            jnp.array(rois, jnp.float32),
            jnp.array(roi_valid),
            gt,
            jnp.array(cls),
            gtv,
            jax.random.PRNGKey(key),
            num_classes=self.NUM_CLASSES,
            batch_rois=16,
            **kw,
        )

    def test_gt_appended_as_fg(self):
        # No proposals overlap gt, but the appended gt itself is a perfect fg.
        rois = np.array([[200, 200, 220, 220]] * 4, np.float32)
        valid = np.ones(4, bool)
        s = self._run(rois, valid, [[10, 10, 50, 50]], [3])
        labels = np.asarray(s.labels)
        fg = np.asarray(s.fg_mask)
        assert fg.sum() >= 1
        assert np.all(labels[fg] == 3)
        # fg rois are the gt box itself.
        assert np.allclose(np.asarray(s.rois)[fg][0], [10, 10, 50, 50])

    def test_fg_fraction_cap(self):
        # All 20 proposals identical to gt -> fg candidates abound; cap at 25%.
        rois = np.tile(np.array([[10, 10, 50, 50]], np.float32), (20, 1))
        s = self._run(rois, np.ones(20, bool), [[10, 10, 50, 50]], [2])
        assert np.asarray(s.fg_mask).sum() == 4  # 0.25 * 16
        assert np.asarray(s.labels)[np.asarray(s.fg_mask)].tolist() == [2] * 4

    def test_bg_labels_zero_weights_zero(self):
        rois = np.array([[200, 200, 240, 240]] * 10, np.float32)
        s = self._run(rois, np.ones(10, bool), [[10, 10, 50, 50]], [1])
        labels = np.asarray(s.labels)
        bg = np.asarray(s.valid) & ~np.asarray(s.fg_mask)
        assert np.all(labels[bg] == 0)
        w = np.asarray(s.bbox_weights)
        assert np.all(w[bg] == 0)

    def test_target_normalization_and_expansion(self):
        rois = np.array([[10, 10, 50, 50]], np.float32)  # exact gt match
        s = self._run(
            rois, np.ones(1, bool), [[10, 10, 50, 50]], [2],
            bbox_means=(0.1, 0.1, 0.1, 0.1), bbox_stds=(0.2, 0.2, 0.2, 0.2),
        )
        fg = np.asarray(s.fg_mask)
        t = np.asarray(s.bbox_targets)[fg][0].reshape(self.NUM_CLASSES, 4)
        # Raw delta 0 -> normalized (0 - 0.1)/0.2 = -0.5, only in class-2 block.
        assert np.allclose(t[2], -0.5, atol=1e-5)
        assert np.allclose(t[[0, 1, 3, 4]], 0.0)
        w = np.asarray(s.bbox_weights)[fg][0].reshape(self.NUM_CLASSES, 4)
        assert np.allclose(w[2], 1.0)
        assert np.allclose(w[[0, 1, 3, 4]], 0.0)

    def test_respects_roi_validity(self):
        # Invalid proposals must never be sampled even if they overlap gt.
        rois = np.tile(np.array([[10, 10, 50, 50]], np.float32), (6, 1))
        valid = np.zeros(6, bool)
        s = self._run(rois, valid, [[10, 10, 50, 50]], [1])
        # Only the appended gt can be fg.
        assert np.asarray(s.fg_mask).sum() == 1

    def test_jit_matches_eager(self):
        rois = np.random.RandomState(1).uniform(0, 200, (12, 4)).astype(np.float32)
        rois[:, 2:] += rois[:, :2]
        gt, gtv = pad_gt([[10, 10, 80, 80]], 8)
        cls = jnp.array([1] + [0] * 7, jnp.int32)
        valid = jnp.ones(12, bool)

        def f(k):
            return sample_rois(
                jnp.array(rois), valid, gt, cls, gtv, k,
                num_classes=self.NUM_CLASSES, batch_rois=16,
            )

        key = jax.random.PRNGKey(3)
        eager, jitted = f(key), jax.jit(f)(key)
        assert np.array_equal(eager.labels, jitted.labels)
        assert np.allclose(eager.bbox_targets, jitted.bbox_targets)
