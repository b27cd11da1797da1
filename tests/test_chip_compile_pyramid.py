"""Compiles for the chip, without the chip: the tiny pyramid step
(``tests/chip_compile.py``): its five per-level NMS launches, anchor
labelling that moves no row an anchor, and ROIAlign pooling each roi once
from the canvas."""

import re

import jax
import pytest

from chip_compile import (KERNEL, _H_CONTRACTION,  # noqa: F401
                          _assert_kernel_named_in_its_stage, _roi_align_ops,
                          _taps_kernels, _tiny_pyramid_step_hlo,
                          assert_labelling_moves_no_row_an_anchor,
                          one_chip_mesh, topo)
from mx_rcnn_tpu.ops import nms_pallas


def test_pyramid_step_launches_the_kernel_once_a_level(one_chip_mesh,
                                                        monkeypatch):
    """The pyramid step compiles for one described chip, and its five
    per-level NMS are five Mosaic calls named after the kernel, each
    (images, 1, that level's candidates padded to 128): 256 of P2's 4608,
    P3's 1152 and P4's 288 anchors, all 72 of P5's and all 18 of P6's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _tiny_pyramid_step_hlo(one_chip_mesh)
    shapes = re.findall(
        rf'%{nms_pallas.KERNEL_NAME}[\w.]* = f32\[(\d+),1,(\d+)\][^\n]*'
        + KERNEL, hlo)
    assert sorted(int(n) for _, n in shapes) == [128, 128, 256, 256, 256]
    assert {int(b) for b, _ in shapes} == {2}
    _assert_kernel_named_in_its_stage(hlo)
    assert "all-reduce" not in hlo


@pytest.mark.parametrize("family", ["pyramid"])
def test_anchor_labelling_moves_no_row_an_anchor(one_chip_mesh, monkeypatch,
                                                 family):
    """``targets/rpn_targets.py`` as the chip's compiler leaves it, in the
    tiny pyramid step (6138 anchors over P2-P6): the overlaps are walked a
    gt slot at a time by a ``while`` scoped in the stage, and no gather or
    scatter there moves a row for every anchor — the ranking's inverse
    permutation and the matched box of every anchor (13 ms each at 279,279
    anchors x 8 images, PERF.md section 6) are gone. ``rpn_loss``
    picks the label's log-probability by a dense select, no gather either.
    The C4 case is ``test_chip_compile.py``'s."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert_labelling_moves_no_row_an_anchor(
        _tiny_pyramid_step_hlo(one_chip_mesh),
        3 * (32 * 48 + 16 * 24 + 8 * 12 + 4 * 6 + 2 * 3))


def test_pyramid_step_pools_each_roi_once_from_the_canvas(one_chip_mesh,
                                                          monkeypatch):
    """The tiny pyramid step (2 images of 128x192, 32 rois each, 7 bins,
    256 channels): P2's 32x48 over a shelf of P3, P4, P5 is a 48x48
    canvas, whose 2,304 cells cost less than a roi's 784 taps, so the
    form is dense (``ops/roi_align.py::pooling_form``; the taps form's case
    is the mesh's, below): the ``roi_align`` stage holds ONE forward
    contraction over H, canvas-wide, where pooling level by level held four, and no
    launch of the taps kernels; no array in the program is
    ``(B, R, P, W_l, C)`` at P3's, P4's or P5's own width."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _tiny_pyramid_step_hlo(one_chip_mesh)
    ops = _roi_align_ops(hlo)
    forward_h = [n for o, n, path in ops if o == "convolution"
                 and path.endswith(_H_CONTRACTION)]
    assert forward_h == [2 * 32 * 7 * 48 * 256], forward_h
    a_level_wide = {2 * 32 * 7 * w * 256 for w in (24, 12, 6)}
    assert not [(o, n) for o, n, _ in ops if n in a_level_wide]
    assert not re.findall(r"\w+\[2,32,7,(?:24|12|6),256\]", hlo)
    assert re.findall(r"bf16\[2,48,48,256\]", hlo)
    assert not _taps_kernels(hlo)
