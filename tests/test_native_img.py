"""Native fused normalize+pad (cc/imgproc.c via data/_native_img.py).

The fused kernel matches the numpy transform_image + pad_image chain to
within a couple of f32 ulps (it multiplies by a precomputed reciprocal
where numpy divides — asserted at rtol 1e-6, NOT bit-identity); the flip
variant mirrors exactly. Skips when no C toolchain is available (the
loader then uses the numpy fallback, which the packed/loader tests
already cover).
"""

import numpy as np
import pytest

from mx_rcnn_tpu.data import _native_img
from mx_rcnn_tpu.data.image import pad_image, transform_image

MEANS = (123.68, 116.779, 103.939)
STDS = (58.393, 57.12, 57.375)

pytestmark = pytest.mark.skipif(not _native_img.available(),
                                reason="no C toolchain")


def _ref(img, pad, flip=False):
    if flip:
        img = img[:, ::-1]
    return pad_image(transform_image(img.astype(np.float32), MEANS, STDS),
                     pad)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_fused_matches_numpy_chain(rng, dtype):
    img = (rng.rand(37, 53, 3) * 255).astype(dtype)
    out = _native_img.normalize_pad(img, MEANS, STDS, (40, 64))
    ref = _ref(img, (40, 64))
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-5)
    assert out.dtype == np.float32 and out.shape == (40, 64, 3)


def test_fused_flip_matches_numpy_flip(rng):
    img = (rng.rand(21, 33, 3) * 255).astype(np.uint8)
    out = _native_img.normalize_pad(img, MEANS, STDS, (24, 40), flip=True)
    ref = _ref(img, (24, 40), flip=True)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-5)


def test_fused_exact_fit_no_padding(rng):
    img = (rng.rand(16, 16, 3) * 255).astype(np.uint8)
    out = _native_img.normalize_pad(img, MEANS, STDS, (16, 16))
    np.testing.assert_allclose(out, _ref(img, (16, 16)), rtol=1e-6,
                               atol=1e-5)


def test_fused_rejects_oversize(rng):
    img = (rng.rand(32, 16, 3) * 255).astype(np.uint8)
    with pytest.raises(ValueError, match="exceeds"):
        _native_img.normalize_pad(img, MEANS, STDS, (16, 16))


def test_fused_noncontiguous_mmap_slice(rng, tmp_path):
    """The packed path hands a sliced mmap view — the bridge must copy
    to contiguous before the C call, not crash or corrupt."""
    big = (rng.rand(4, 64, 64, 3) * 255).astype(np.uint8)
    np.save(tmp_path / "shard.npy", big)
    arr = np.load(tmp_path / "shard.npy", mmap_mode="r")
    view = np.asarray(arr[2, :30, :40])
    out = _native_img.normalize_pad(view, MEANS, STDS, (32, 48))
    np.testing.assert_allclose(out, _ref(np.array(view), (32, 48)),
                               rtol=1e-6, atol=1e-5)


# -- the caller's destination (a row of the loader's batch buffer) ---------


def _source(rng, kind):
    img = (rng.rand(37, 53, 3) * 255).astype(
        np.float32 if kind == "float32" else np.uint8)
    return img, kind == "uint8_flipped"


@pytest.mark.parametrize("kind", ["uint8", "uint8_flipped", "float32"])
def test_out_row_equals_fresh_array(rng, kind):
    """normalize_pad(out=row) writes every element of the row, stale ones
    included, to what normalize_pad() returns, and returns the row."""
    img, flip = _source(rng, kind)
    want = _native_img.normalize_pad(img, MEANS, STDS, (40, 64), flip=flip)
    buf = np.full((3, 40, 64, 3), np.nan, np.float32)
    got = _native_img.normalize_pad(img, MEANS, STDS, (40, 64), flip=flip,
                                    out=buf[1])
    assert got.base is buf and np.shares_memory(got, buf[1])
    assert buf[1].tobytes() == want.tobytes()
    assert np.isnan(buf[0]).all() and np.isnan(buf[2]).all()


@pytest.mark.parametrize("bad", [
    pytest.param(lambda: np.empty((40, 63, 3), np.float32), id="shape"),
    pytest.param(lambda: np.empty((40, 64, 3), np.float64), id="dtype"),
    pytest.param(lambda: np.empty((40, 128, 3), np.float32)[:, ::2],
                 id="layout"),
    pytest.param(lambda: np.empty((64, 40, 3), np.float32).transpose(1, 0, 2),
                 id="transposed"),
    pytest.param(lambda: [[0.0]], id="not_an_array"),
])
def test_out_of_the_wrong_kind_is_refused(rng, bad):
    img = (rng.rand(37, 53, 3) * 255).astype(np.uint8)
    with pytest.raises(ValueError, match="out must be"):
        _native_img.normalize_pad(img, MEANS, STDS, (40, 64), out=bad())


@pytest.mark.parametrize("kind", ["uint8", "uint8_flipped", "float32"])
def test_numpy_fallback_honours_out(rng, kind, monkeypatch):
    """Without the native layer the numpy chain writes the same
    destination: transform_image + pad_image's values, bit for bit."""
    img, flip = _source(rng, kind)
    monkeypatch.setattr(_native_img, "get_lib", lambda: None)
    want = _ref(img, (40, 64), flip=flip)
    fresh = _native_img.normalize_pad(img, MEANS, STDS, (40, 64), flip=flip)
    assert fresh.dtype == np.float32 and fresh.tobytes() == want.tobytes()
    buf = np.full((2, 40, 64, 3), np.nan, np.float32)
    got = _native_img.normalize_pad(img, MEANS, STDS, (40, 64), flip=flip,
                                    out=buf[0])
    assert got.base is buf and buf[0].tobytes() == want.tobytes()
    assert np.isnan(buf[1]).all()
    with pytest.raises(ValueError, match="out must be"):
        _native_img.normalize_pad(img, MEANS, STDS, (40, 64),
                                  out=np.empty((40, 64, 3), np.float64))
