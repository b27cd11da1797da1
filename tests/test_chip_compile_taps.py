"""Compiles for the chip, without the chip: the pyramid step of four
800x1024 images on the 4-device data mesh, whose box pooling takes the
taps kernels under a ``shard_map`` (``tests/chip_compile.py``)."""

import re

import jax

from chip_compile import (_roi_align_ops, _taps_kernels,  # noqa: F401
                          _tiny_pyramid_step_hlo, data_mesh, topo)


def test_pyramid_taps_run_under_shard_map_with_no_exchange(data_mesh,
                                                           monkeypatch):
    """Four images of 800x1024 on the 4-device data mesh: P2's 200x256 over
    a shelf of P3, P4, P5 is a 300x256 canvas, wide enough that a roi's
    taps cost less than its area, so the box pooling takes the taps form
    (``pooling_form``): ONE forward and ONE backward
    launch of the kernels, each in a ``shard_map`` over ``data`` (one image
    a device: the launch is one image wide) inside the ``roi_align`` stage;
    no collective moves a map or a roi in the stage, and no array is
    ``(B, R, P, W, C)`` at the canvas's width."""
    from mx_rcnn_tpu.obs.profile import stage_of
    from mx_rcnn_tpu.ops import roi_align_pallas as rap

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _tiny_pyramid_step_hlo(data_mesh, (800, 1024), 4)
    launches = _taps_kernels(hlo)
    assert sorted(name for name, _, _ in launches) == [
        rap.KERNEL_NAME, rap.KERNEL_NAME_GRAD], launches
    assert all(stage_of(path) == "roi_align" and "shard_map" in path
               for _, _, path in launches), launches
    shapes = {name: shape for name, shape, _ in launches}
    assert shapes[rap.KERNEL_NAME].startswith("bf16[1,32,7,7,256]")
    assert shapes[rap.KERNEL_NAME_GRAD].startswith(
        f"bf16[1,{300 * 256},256]")
    ops = _roi_align_ops(hlo)
    moved = [(o, path) for o, _, path in ops if o.startswith(
        ("all-gather", "all-to-all", "collective-permute", "all-reduce"))]
    assert not moved, moved
    assert not re.findall(r"\w+\[\d+,32,7,256,256\]", hlo)
