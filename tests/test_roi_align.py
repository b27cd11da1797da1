"""ROIAlign / ROIPool vs numpy references and invariants."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mx_rcnn_tpu.ops.roi_align import roi_align, roi_pool


def test_roi_align_constant_map():
    # Pooling a constant feature map must return the constant.
    feat = jnp.full((1, 16, 16, 3), 2.5)
    rois = jnp.array([[[8.0, 8.0, 120.0, 120.0]]])
    out = roi_align(feat, rois, output_size=7, spatial_scale=1.0 / 16.0)
    assert out.shape == (1, 1, 7, 7, 3)
    assert np.allclose(out, 2.5, atol=1e-5)


def test_roi_align_linear_ramp():
    # f(x,y) = x is reproduced exactly by bilinear sampling + averaging.
    w = 32
    ramp = jnp.tile(jnp.arange(w, dtype=jnp.float32)[None, :, None], (w, 1, 1))
    feat = ramp[None]  # (1, 32, 32, 1)
    # roi covering feature cols [4, 28] at scale 1 (image == feature coords).
    rois = jnp.array([[[4.0, 4.0, 28.0, 28.0]]])
    out = roi_align(feat, rois, output_size=4, spatial_scale=1.0, sampling_ratio=2)
    # bin width = 24/4 = 6; bin k spans x in [4+6k, 4+6k+6); mean sample x
    # = 4 + 6k + 3 = centre of the bin.
    want = np.array([7.0, 13.0, 19.0, 25.0])
    assert np.allclose(np.asarray(out)[0, 0, 2, :, 0], want, atol=1e-4)


def test_roi_align_batch_index():
    # The image a roi is pooled from is its row of the leading axis.
    feat = jnp.stack([jnp.zeros((8, 8, 1)), jnp.ones((8, 8, 1))])  # (2,8,8,1)
    rois = jnp.array([[[0.0, 0.0, 7.0, 7.0]], [[0.0, 0.0, 7.0, 7.0]]])
    out = roi_align(feat, rois, output_size=2, spatial_scale=1.0)
    assert out.shape == (2, 1, 2, 2, 1)
    assert np.allclose(out[0], 0.0)
    assert np.allclose(out[1], 1.0)


def test_roi_pool_max_semantics():
    # Single hot pixel: max pool must find it in the covering bin.
    feat = np.zeros((1, 8, 8, 1), np.float32)
    feat[0, 5, 6, 0] = 9.0
    rois = jnp.array([[0.0, 0.0, 0.0, 7.0, 7.0]])
    out = np.asarray(roi_pool(jnp.array(feat), rois, output_size=2, spatial_scale=1.0))
    # Bin (1,1) covers rows/cols [4,8): contains (5,6).
    assert out[0, 1, 1, 0] == 9.0
    assert out[0, 0, 0, 0] == 0.0


def test_roi_pool_scale_quantization():
    # spatial_scale 1/16: image box (0,0,31,31) -> feature box (0,0,2,2).
    feat = np.arange(16, dtype=np.float32).reshape(1, 4, 4, 1)
    rois = jnp.array([[0.0, 0.0, 0.0, 31.0, 31.0]])
    out = np.asarray(
        roi_pool(jnp.array(feat), rois, output_size=1, spatial_scale=1.0 / 16.0)
    )
    # max over rows/cols 0..2 = feat[2,2] = 10.
    assert out[0, 0, 0, 0] == 10.0


def test_jit_and_grad():
    feat = jnp.ones((1, 8, 8, 2))
    rois = jnp.array([[[2.0, 2.0, 6.0, 6.0]]])

    def f(x):
        return roi_align(x, rois, output_size=2, spatial_scale=1.0).sum()

    g = jax.grad(f)(feat)
    assert g.shape == feat.shape
    # Gradient mass = number of pooled outputs (mean weights sum to 1/bin).
    assert np.isclose(float(g.sum()), 2 * 2 * 2, atol=1e-4)


def _oracle(feat, rois, *args, **kw):
    """``roi_align_gather`` on grouped rois: the free (batch_idx, box) rows
    it takes are built from the grouping, its (B·R, ...) answer regrouped."""
    from mx_rcnn_tpu.ops.roi_align import roi_align_gather

    b, r = rois.shape[:2]
    idx = jnp.repeat(jnp.arange(b, dtype=rois.dtype), r)[:, None]
    flat = jnp.concatenate([idx, rois.reshape(b * r, 4)], axis=1)
    out = roi_align_gather(feat, flat, *args, **kw)
    return out.reshape(b, r, *out.shape[1:])


def test_roi_align_matmul_matches_gather_oracle():
    """The MXU matmul formulation == the per-point bilinear gather oracle."""
    rs = np.random.RandomState(3)
    feat = jnp.asarray(rs.randn(2, 12, 10, 5).astype(np.float32))
    rois = jnp.asarray(
        [
            [[5.0, 3.0, 90.0, 100.0],
             [30.0, 40.0, 32.0, 44.0]],      # tiny box (sub-bin)
            [[0.0, 0.0, 159.0, 191.0],
             [-10.0, -10.0, 200.0, 300.0]],  # out-of-bounds corners
        ],
        jnp.float32,
    )
    for aligned in (False, True):
        for sr in (1, 2):
            a = roi_align(feat, rois, 7, 1.0 / 16.0, sampling_ratio=sr,
                          aligned=aligned)
            b = _oracle(feat, rois, 7, 1.0 / 16.0, sampling_ratio=sr,
                        aligned=aligned)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


def test_roi_align_matmul_grad_matches_gather_oracle():
    rs = np.random.RandomState(4)
    feat = jnp.asarray(rs.randn(1, 8, 8, 3).astype(np.float32))
    rois = jnp.asarray([[[10.0, 6.0, 100.0, 90.0]]], jnp.float32)

    g1 = jax.grad(lambda x: roi_align(x, rois, 4, 1 / 16).sum())(feat)
    g2 = jax.grad(lambda x: _oracle(x, rois, 4, 1 / 16).sum())(feat)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4,
                               atol=1e-5)


def _grouped_case(b, with_windows, seed):
    """Features (b, 12, 10, 4), 5 rois an image at scale 1/16 (some tiny,
    some past the border) and, with windows, one placement rect an image:
    origin on the stride, extent not."""
    rs = np.random.RandomState(seed)
    feat = jnp.asarray(rs.randn(b, 12, 10, 4).astype(np.float32))
    x1 = rs.uniform(-12, 120, (b, 5))
    y1 = rs.uniform(-12, 150, (b, 5))
    bw = rs.uniform(1, 110, (b, 5))
    bh = rs.uniform(1, 130, (b, 5))
    rois = jnp.asarray(np.stack([x1, y1, x1 + bw, y1 + bh], -1), jnp.float32)
    if not with_windows:
        return feat, rois, None
    win = np.stack([rs.randint(0, 3, b) * 16.0, rs.randint(0, 3, b) * 16.0,
                    rs.uniform(70, 150, b), rs.uniform(60, 120, b)], -1)
    return feat, rois, jnp.asarray(win, jnp.float32)


def _own_image_oracle(feat, rois, windows, i):
    """Image i's rois pooled by the oracle from image i's map alone; under
    a window, from the window's own cells with the rois moved to its
    origin — the bucketed map the window stands for."""
    f, r = feat[i:i + 1], rois[i:i + 1]
    if windows is not None:
        y0, x0, wh, ww = (float(v) for v in windows[i])
        cy, cx = int(y0 / 16), int(x0 / 16)
        f = f[:, cy:cy + int(np.ceil(wh / 16)), cx:cx + int(np.ceil(ww / 16))]
        r = r - jnp.asarray([x0, y0, x0, y0], jnp.float32)
    return lambda x: _oracle(x, r, 3, 1 / 16)[0], f


@pytest.mark.parametrize("with_windows", [False, True],
                         ids=["whole_map", "windows"])
@pytest.mark.parametrize("b", [1, 2, 4])
def test_grouped_roi_align_reads_each_rois_own_image(b, with_windows):
    """What the per-image masks used to guarantee, now held by the shape:
    image i's rois equal the oracle on image i alone, forward and gradient,
    and do not move when every OTHER image's map turns to noise."""
    feat, rois, windows = _grouped_case(b, with_windows, seed=10 * b)
    per_roi = (None if windows is None
               else jnp.repeat(windows[:, None], rois.shape[1], axis=1))
    pool = lambda x: roi_align(x, rois, 3, 1 / 16, windows=per_roi)
    out = pool(feat)
    assert out.shape == (b, 5, 3, 3, 4)
    cot = jnp.asarray(np.random.RandomState(1).randn(*out.shape), jnp.float32)
    grad = jax.grad(lambda x: (pool(x) * cot).sum())(feat)
    for i in range(b):
        want, f = _own_image_oracle(feat, rois, windows, i)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(want(f)),
                                   rtol=1e-4, atol=1e-4)
        g = jax.grad(lambda x: (want(x) * cot[i]).sum())(f)[0]
        if windows is None:
            got = grad[i]
        else:  # the window's cells carry the whole gradient
            cy, cx = int(windows[i, 0] / 16), int(windows[i, 1] / 16)
            got = grad[i, cy:cy + g.shape[0], cx:cx + g.shape[1]]
            np.testing.assert_allclose(float(jnp.abs(grad[i]).sum()),
                                       float(jnp.abs(got).sum()), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(g),
                                   rtol=1e-4, atol=1e-4)
        noise = jnp.asarray(
            np.random.RandomState(2).randn(*feat.shape), jnp.float32)
        others = noise.at[i].set(feat[i])
        np.testing.assert_array_equal(np.asarray(pool(others)[i]),
                                      np.asarray(out[i]))


@pytest.mark.parametrize("aligned", [False, True], ids=["classic", "aligned"])
@pytest.mark.parametrize("with_windows", [False, True],
                         ids=["whole_map", "windows"])
def test_weights_and_contraction_are_roi_align_split_in_two(with_windows,
                                                            aligned):
    """``roi_align`` is ``contract_weights`` of ``roi_align_weights``: the
    weights of rois against one map are float32 ``(B, R, P, H)`` and
    ``(B, R, P, W)``, each sample point's hat weights sum to one inside the
    map (or the window), and contracting them gives ``roi_align``'s values
    to the bit (models/fpn.py lays such weights on a canvas of levels)."""
    from mx_rcnn_tpu.ops.roi_align import contract_weights, roi_align_weights

    feat, rois, windows = _grouped_case(2, with_windows, seed=5)
    per_roi = (None if windows is None
               else jnp.repeat(windows[:, None], rois.shape[1], axis=1))
    wy, wx = roi_align_weights(rois, feat.shape[1:3], 3, 1 / 16,
                               aligned=aligned, windows=per_roi)
    assert wy.shape == (2, 5, 3, feat.shape[1]) and wy.dtype == jnp.float32
    assert wx.shape == (2, 5, 3, feat.shape[2]) and wx.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(wy.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(wx.sum(-1)), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(contract_weights(wy, wx, feat)),
        np.asarray(roi_align(feat, rois, 3, 1 / 16, aligned=aligned,
                             windows=per_roi)))


# ---------------------------------------------------------------------------
# The taps form (ops/roi_align_pallas.py): each roi pooled from its taps
# ---------------------------------------------------------------------------

# (name, [x1, y1, x2, y2] px in a 128x160 image, window rect or None): a roi
# Eq. 1 sends to each of P2..P5 (the larger ones reach past the image), one
# far longer than high (along x its bins' samples lie cells apart, so its
# taps are not contiguous), one past its placement window's right and
# bottom edge (its border samples clamp to the window's last cell, several
# onto the same one), and a dead roi (valid False).
_TAP_ROIS = {
    "p2": ([10.0, 20.0, 60.0, 70.0], None),
    "p3": ([10.0, 10.0, 150.0, 130.0], None),
    "p4": ([-40.0, -40.0, 200.0, 200.0], None),
    "p5": ([-160.0, -180.0, 340.0, 300.0], None),
    "elongated": ([2.0, 50.0, 158.0, 58.0], None),
    "clamped_at_window": ([40.0, 50.0, 130.0, 120.0],
                          [0.0, 0.0, 100.0, 80.0]),
    "dead": ([30.0, 30.0, 90.0, 100.0], None),
}
_LEVEL = {"p2": 2, "p3": 3, "p4": 4, "p5": 5, "elongated": 2}
_IMAGE = (128, 160)


def _tap_pyramid(seed, dtype=jnp.bfloat16, planes=2):
    """A 256-channel pyramid of a 128x160 image (P2 32x40 .. P5 4x5): a
    48x40 canvas."""
    from mx_rcnn_tpu.models import fpn as F

    rs = np.random.RandomState(seed)
    return {lv: jnp.asarray(rs.randn(planes, _IMAGE[0] // 2 ** lv,
                                     _IMAGE[1] // 2 ** lv, 256), dtype)
            for lv in F.ROI_LEVELS}


def _tap_case(name, r=1, seed=0):
    """Two planes of ``r`` rois: the named roi jittered; its windows (one an
    image) and validity."""
    rs = np.random.RandomState(seed)
    box, win = _TAP_ROIS[name]
    rois = np.asarray(box, np.float32)[None, None] + rs.uniform(
        -1, 1, (2, r, 4)).astype(np.float32)
    windows = (None if win is None
               else jnp.asarray(np.tile(win, (2, 1)), jnp.float32))
    valid = jnp.full((2, r), name != "dead")
    return jnp.asarray(rois), windows, valid


@pytest.mark.parametrize("name", sorted(_TAP_ROIS))
@pytest.mark.parametrize("bins", [7, 14])
def test_taps_carry_the_dense_weights_entry_for_entry(name, bins):
    """``roi_align_taps`` + ``taps_on_canvas``: every cell a dense weight
    row touches is a tap, with that weight's value to the bit (bfloat16, as
    ``contract_weights`` rounds it); each row once a bin, each column once a
    roi; nothing else carries weight."""
    from mx_rcnn_tpu.models import fpn as F
    from mx_rcnn_tpu.ops.roi_align import (roi_align_taps, roi_align_weights,
                                           taps_on_canvas)

    rois, windows, _ = _tap_case(name)
    level = int(F.roi_levels(rois)[0, 0])
    assert level == _LEVEL.get(name, level)
    assert (np.asarray(F.roi_levels(rois)) == level).all()
    extent = (_IMAGE[0] // 2 ** level, _IMAGE[1] // 2 ** level)
    per_roi = (None if windows is None
               else jnp.repeat(windows[:, None], rois.shape[1], axis=1))
    scale = 1.0 / 2 ** level
    wy, wx = roi_align_weights(rois, extent, bins, scale, windows=per_roi)
    taps = taps_on_canvas(*roi_align_taps(rois, extent, bins, scale,
                                          windows=per_roi), extent)
    bf = lambda a: np.asarray(jnp.asarray(a).astype(jnp.bfloat16), np.float32)
    wy, wx = bf(wy), bf(wx)
    h, w = extent
    s = 2
    rows = np.asarray(taps.rows) // w                   # (B, R, P·S, 2)
    ty = np.asarray(taps.wy)                            # (B, R, P, S, 2)
    cols = np.asarray(taps.cols)[..., None] + [0, 1]    # (B, R, Q·S, 2)
    tx = np.asarray(taps.wx)[:, :, :bins].reshape(
        *rois.shape[:2], bins, bins * s, 8)[..., :2]    # (B, R, Q, Q·S, 2)
    for b in range(rois.shape[0]):
        for r in range(rois.shape[1]):
            for p in range(bins):
                got = np.zeros(h + 1, np.float32)
                own = rows[b, r, p * s:(p + 1) * s].reshape(-1)
                np.add.at(got, np.minimum(own, h), ty[b, r, p].reshape(-1))
                np.testing.assert_array_equal(got[:h], wy[b, r, p])
                assert not got[h:].any()
                got = np.zeros(w + 1, np.float32)
                np.add.at(got, cols[b, r].reshape(-1),
                          tx[b, r, p].reshape(-1))
                np.testing.assert_array_equal(got[:w], wx[b, r, p])
                assert not got[w:].any()
    if name == "elongated":   # a bin's samples cells apart along x
        assert np.diff(np.asarray(taps.cols)[0, 0]).max() > 1
    if name == "clamped_at_window":   # samples clamped onto one cell
        assert (np.asarray(taps.keep)[0, 0] == 0).any()


def _pooled(pyr, rois, valid, bins, windows, impl, aligned=False):
    """Pooled values and the gradient to every level's map of a weighed sum,
    float32."""
    from mx_rcnn_tpu.models import fpn as F

    def weighed(p):
        out = F.pyramid_roi_align(p, rois, valid, bins, windows=windows,
                                  impl=impl, aligned=aligned)
        wave = jnp.cos(jnp.arange(out.size, dtype=jnp.float32))
        return jnp.sum(out.astype(jnp.float32) * wave.reshape(out.shape)), out

    (_, out), grads = jax.jit(
        jax.value_and_grad(weighed, has_aux=True))(pyr)
    return [np.asarray(a, np.float32)
            for a in [out] + [grads[lv] for lv in sorted(grads)]]


def _equal_to_a_sum_order(got, want):
    """bfloat16 values equal up to the order of float32 sums: at most one
    bfloat16 step apart, and nearly all to the bit."""
    np.testing.assert_allclose(got, want, rtol=2.0 ** -7,
                               atol=2.0 ** -7 * np.abs(want).max())
    assert (got == want).mean() > 0.99


@pytest.mark.parametrize("name", sorted(_TAP_ROIS))
@pytest.mark.parametrize("bins", [7, 14])
def test_taps_kernel_pools_as_the_dense_form(name, bins):
    """The kernel pair (the Pallas interpreter) against ``contract_weights``
    on the same canvas, bfloat16: the pooled values and the canvas's VJP
    (the gradient to every level's map) equal up to the order of float32
    sums. A dead roi pools to zeros and sends no gradient, through the same
    work as a live one."""
    rois, windows, valid = _tap_case(name, seed=bins)
    pyr = _tap_pyramid(seed=bins)
    got = _pooled(pyr, rois, valid, bins, windows, "pallas_interpret")
    want = _pooled(pyr, rois, valid, bins, windows, "xla")
    if name == "dead":
        assert not any(a.any() for a in got + want)
        return
    assert np.abs(want[0]).max() > 0.5
    for g, w in zip(got, want):
        _equal_to_a_sum_order(g, w)


def test_taps_kernel_takes_several_grid_steps_a_plane():
    """Every kind of roi in one call, more rois a plane than a grid step
    takes (``ROIS_A_STEP``: 100 rois are 2 steps of 50, as the evaluation
    forward's 1000 proposals are 20; each step's scalar blocks padded to
    whole tiles): the pooled values and the canvas's VJP equal the dense
    form's up to the order of float32 sums, dead rois zeros."""
    rois_a_plane = 100
    from mx_rcnn_tpu.ops import roi_align_pallas as rap

    names = sorted(n for n in _TAP_ROIS if _TAP_ROIS[n][1] is None)
    reps = -(-rois_a_plane // len(names))
    cases = [_tap_case(n, r=reps, seed=i) for i, n in enumerate(names)]
    rois = jnp.concatenate([c[0] for c in cases], 1)[:, :rois_a_plane]
    valid = jnp.concatenate([c[2] for c in cases], 1)[:, :rois_a_plane]
    assert rap._rois_a_step(rois_a_plane) * 2 == rois_a_plane
    pyr = _tap_pyramid(seed=rois_a_plane)
    got = _pooled(pyr, rois, valid, 7, None, "pallas_interpret")
    want = _pooled(pyr, rois, valid, 7, None, "xla")
    dead = ~np.asarray(valid).reshape(-1)
    assert dead.any() and not got[0][dead].any()
    for g, w in zip(got, want):
        _equal_to_a_sum_order(g, w)


@pytest.mark.parametrize("name", ["p2", "p3", "p5", "clamped_at_window"])
def test_aligned_pooling_is_the_same_in_both_forms(name):
    """ROIAlignV2 (``aligned``: the half-pixel shift, ViTDet's) through the
    pyramid: the kernel pair (the Pallas interpreter) against
    ``contract_weights`` on the same canvas, the pooled values and the
    canvas's VJP equal up to the order of float32 sums; and for a roi inside
    its map (the gather zeroes a sample a cell beyond the border where the
    program clamps it), float32, against the per-point gather of the
    assigned level with ``aligned`` (the CUDA kernel's form) to 1e-5 of the
    largest value, the shift moving them: the unshifted pooling is off by
    more than 1e-2."""
    from mx_rcnn_tpu.models import fpn as F

    rois, windows, valid = _tap_case(name, seed=5)
    pyr = _tap_pyramid(seed=6)
    got = _pooled(pyr, rois, valid, 7, windows, "pallas_interpret", True)
    want = _pooled(pyr, rois, valid, 7, windows, "xla", True)
    for g, w in zip(got, want):
        _equal_to_a_sum_order(g, w)
    if name not in ("p2", "p3"):
        return
    f32 = {lv: p.astype(jnp.float32) for lv, p in pyr.items()}
    dense = np.asarray(F.pyramid_roi_align(f32, rois, valid, 7,
                                           impl="xla", aligned=True))
    level = int(np.asarray(F.roi_levels(rois))[0, 0])
    oracle = np.asarray(_oracle(f32[level], rois, 7, 1.0 / 2 ** level,
                                aligned=True)).reshape(dense.shape)
    np.testing.assert_allclose(dense, oracle, rtol=0,
                               atol=1e-5 * np.abs(oracle).max())
    plain = np.asarray(F.pyramid_roi_align(f32, rois, valid, 7, impl="xla"))
    assert np.abs(plain - dense).max() > 1e-2 * np.abs(oracle).max()


@pytest.mark.parametrize("bins", [7, 14])
def test_taps_kernel_matches_the_gather_oracle(bins):
    """Against the CUDA kernel's per-point gather (``roi_align_gather``,
    float32 on the same bfloat16 map): the kernel's bfloat16 values lie
    within the rounding of its two bfloat16 points (weights, H-partial)."""
    from mx_rcnn_tpu.models import fpn as F

    rois, _, valid = _tap_case("p3", seed=3)
    pyr = _tap_pyramid(seed=4)
    got = F.pyramid_roi_align(pyr, rois, valid, bins,
                              impl="pallas_interpret")
    level = 3
    assert (np.asarray(F.roi_levels(rois)) == level).all()
    want = np.asarray(_oracle(pyr[level].astype(jnp.float32), rois, bins,
                              1.0 / 2 ** level), np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               want.reshape(got.shape), rtol=0,
                               atol=3e-2 * np.abs(want).max())


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_no_gradient_reaches_the_rois(impl):
    """The rois are proposals and ground truth: neither form gives them, or
    the weights built from them, a cotangent."""
    from mx_rcnn_tpu.models import fpn as F

    rois, _, valid = _tap_case("p3", r=1)
    pyr = _tap_pyramid(seed=1)
    g = jax.grad(lambda r_: F.pyramid_roi_align(
        pyr, r_, valid, 7, impl=impl).astype(jnp.float32).sum())(rois)
    assert not np.asarray(g).any()


_FORMS = [  # (bins, samples, extent, channels, dtype) -> form
    ((14, 2, (40, 64), 1024, "bfloat16"), "dense"),    # C4's cells
    ((14, 2, (40, 64), 256, "bfloat16"), "dense"),     # C4's map, 256 wide
    ((7, 2, (312, 336), 256, "bfloat16"), "taps"),     # the box pooling
    ((14, 2, (312, 336), 256, "bfloat16"), "dense"),   # the mask pooling
    ((14, 2, (416, 416), 256, "bfloat16"), "dense"),   # ... 56 taps anywhere
    ((7, 2, (200, 200), 256, "bfloat16"), "dense"),    # under the crossover
    ((7, 2, (48, 48), 256, "bfloat16"), "dense"),      # the tiny pyramid
    ((7, 2, (300, 256), 256, "bfloat16"), "taps"),     # the mesh's compile
    ((7, 2, (312, 336), 256, "float32"), "dense"),     # not bfloat16
]


@pytest.mark.parametrize("args,form", _FORMS,
                         ids=[f"{a[0]}x{a[1]}-{a[2][0]}x{a[2][1]}-{a[3]}-"
                              f"{a[4]}" for a, _ in _FORMS])
def test_pooling_form_is_chosen_by_shape(args, form):
    """``pooling_form`` is a pure function of the pooling's shape: taps
    only at the tap count measured to win on the chip (``MAX_TAPS``, 28 an
    axis) and where a roi's (2·P·S)² taps cost less than the dense form's
    P·H·W (``TAP_COST``): the box pooling of the pyramid cells, not C4's
    nor the mask pooling at any map (56 taps an axis); and "auto" runs the
    kernel only on a TPU."""
    from mx_rcnn_tpu.ops.roi_align import pooling_form, pooling_impl

    bins, samples, extent, channels, dtype = args
    assert pooling_form(bins, samples, extent, channels,
                        jnp.dtype(dtype)) == form
    assert pooling_impl("auto", form) == "xla"  # the CPU tests' backend
    assert pooling_impl("pallas_interpret", form) == "pallas_interpret"
