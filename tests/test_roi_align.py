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
