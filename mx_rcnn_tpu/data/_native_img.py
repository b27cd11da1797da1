"""Fused normalize+pad: the ctypes bridge to cc/imgproc.c, and its numpy twin.

Same pattern as masks/_native.py: built on first use with the system
compiler into cc/build/libimgproc.so, loaded via ctypes (which releases
the GIL around the call, so loader workers overlap). The kernels write a
destination the CALLER may own: the training loader hands each image its
row of a reused batch buffer (data/loader.py), so a batch's pixels are
written once and no page of it is new to the process. Without a
toolchain ``normalize_pad`` does the same with numpy (transform_image's
arithmetic) into the same destination — the native layer is a pure
accelerator, never a dependency.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import Optional

import numpy as np

from mx_rcnn_tpu.data.image import transform_image
from mx_rcnn_tpu.utils.native_build import build_and_load

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "cc", "imgproc.c")
_SO = os.path.join(_REPO, "cc", "build", "libimgproc.so")

_lib = None
_tried = False
_init_lock = threading.Lock()


def _bind(lib):
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    for name, srcp in (("normalize_pad_u8", u8p),
                       ("normalize_pad_f32", f32p),
                       ("normalize_pad_u8_flip", u8p)):
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = [srcp, ctypes.c_long, ctypes.c_long,
                       f32p, ctypes.c_long, ctypes.c_long, f32p, f32p]


def get_lib():
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _init_lock:
        if _lib is None and not _tried:
            _lib = build_and_load(_SRC, _SO, _bind)
            _tried = True
    return _lib


def available() -> bool:
    return get_lib() is not None


def _destination(out: Optional[np.ndarray], ph: int, pw: int) -> np.ndarray:
    """``out`` checked against what the C kernels assume of the pointer
    they are given, or a fresh array when the caller brought none."""
    if out is None:
        return np.empty((ph, pw, 3), np.float32)
    if (not isinstance(out, np.ndarray) or out.dtype != np.float32
            or out.shape != (ph, pw, 3) or not out.flags.c_contiguous
            or not out.flags.writeable):
        raise ValueError(
            "out must be a writeable C-contiguous float32 array of shape "
            f"{(ph, pw, 3)}, got {type(out).__name__} "
            f"{getattr(out, 'dtype', '')} {getattr(out, 'shape', '')}")
    return out


def normalize_pad(img: np.ndarray, means, stds, pad_shape,
                  flip: bool = False,
                  out: Optional[np.ndarray] = None) -> np.ndarray:
    """Fused (img - mean) / std + zero-pad (+ optional x-mirror) in one
    GIL-free pass. img: (h, w, 3) uint8 or float32. Writes every element
    of ``out`` ((ph, pw, 3) float32, C-contiguous — e.g. a row of a batch
    buffer) and returns it; allocates only when given none. Without the
    native layer the numpy chain writes the same destination."""
    h, w = img.shape[:2]
    ph, pw = pad_shape
    if h > ph or w > pw:
        raise ValueError(f"image {h}x{w} exceeds pad shape {ph}x{pw}")
    dst = _destination(out, ph, pw)
    lib = get_lib()
    if lib is None:
        src = img[:, ::-1] if flip else img
        dst[:h, :w] = transform_image(src.astype(np.float32, copy=False),
                                      means, stds)
        dst[h:] = 0.0
        dst[:h, w:] = 0.0
        return dst
    mean = np.ascontiguousarray(means, np.float32)
    inv_std = np.ascontiguousarray(
        1.0 / np.asarray(stds, np.float32), np.float32)
    if img.dtype == np.uint8:
        src = np.ascontiguousarray(img)
        fn = lib.normalize_pad_u8_flip if flip else lib.normalize_pad_u8
        fn(src, h, w, dst, ph, pw, mean, inv_std)
        return dst
    if flip:  # f32 source flips rarely (jpeg path flips pre-resize)
        img = img[:, ::-1]
    src = np.ascontiguousarray(img, np.float32)
    lib.normalize_pad_f32(src, h, w, dst, ph, pw, mean, inv_std)
    return dst
