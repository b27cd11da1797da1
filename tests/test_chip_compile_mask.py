"""Compiles for the chip, without the chip: the mask cell's step at its
sizes (``tests/chip_compile.py``)."""

import re

import jax
import jax.numpy as jnp

from chip_compile import _taps_kernels, one_chip_mesh, topo  # noqa: F401


def test_mask_step_at_the_cells_sizes_fits_one_chip(one_chip_mesh,
                                                    monkeypatch):
    """Mask R-CNN on ResNet-101-FPN at the sizes the cell
    ``mask_r101_train`` runs (benchmarks/configs/mask_r101_fpn_coco.json: 8
    images of 832x1344, 512 rois an image, no remat) lowers from
    ``abstract_step_inputs`` - ``gt_masks`` among them - and compiles for
    one described v5e under the 15.75 GB its compiler allows. The mask
    branch runs over the sampler's foreground block, 128 of the 512 slots:
    no array is (8, 512, 14 bins, ...) - over every slot P2's intermediate
    of the pooling alone is 9.19 GB and the step is refused (PERF.md section
    4) - and the loss picks its class's map without a gather. Both
    poolings contract against the 312x336 canvas of the four levels, once:
    their intermediates are P2's width, 336, and none is 168, 84 or 42
    wide; the box pooling's is not there at all: it is the taps kernels,
    launched once forward and once backward, while the branch's 14x14
    stays dense (its 3,136 taps a roi cost more than the canvas). The one
    full-size compile of this file: about two minutes."""
    from benchmarks import manifest
    from benchmarks.drivers.train import _program_config
    from mx_rcnn_tpu.models.zoo import build_model
    from mx_rcnn_tpu.obs.profile import BRANCH_STAGES, branch_of
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _program_config(manifest.load_json("configs", "mask_r101_fpn_coco"))
    assert cfg.network.use_mask and not cfg.network.remat
    images = cfg.train.batch_images
    model = build_model(cfg, mesh=one_chip_mesh)
    args = abstract_step_inputs(model, cfg, one_chip_mesh, images)
    m = cfg.train.mask_gt_resolution
    assert args[1]["gt_masks"].shape == (images, cfg.train.max_gt_boxes, m, m)
    assert args[1]["gt_masks"].dtype == jnp.uint8
    compiled = make_train_step(model, cfg, mesh=one_chip_mesh).lower(
        *args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15.75e9, total
    hlo = compiled.as_text()
    slots, block = cfg.train.batch_rois, round(
        cfg.train.fg_fraction * cfg.train.batch_rois)
    bins = model.mask_pool_size
    assert (images, slots, block, bins) == (8, 512, 128, 14)
    # (but for the box pooling's taps, (8, 512, 14, 1 or 2) and nothing
    # after: 7 bins x 2 samples of a roi, each one cell or a pair, no map)
    assert not {a for a in re.findall(
        rf"\w+\[{images},{slots},{bins},([\d,]*)\]", hlo)} - {"1", "2"}
    assert not re.findall(rf"\w+\[{images * slots},{bins},{bins},\d+\]", hlo)
    assert re.findall(rf"bf16\[{images},{block},{bins},{bins},256\]", hlo)
    # the box head's pooling by taps (ops/roi_align_pallas.py): one
    # forward and one backward launch, no (B, R, 7, W, C) intermediate;
    # the branch's 14x14 pooling stays dense at this canvas
    # (ops/roi_align.py::pooling_form): its intermediate is P2's width
    from mx_rcnn_tpu.ops import roi_align_pallas as rap

    launches = sorted((name, shape.split("{")[0])
                      for name, shape, _ in _taps_kernels(hlo))
    assert launches == [
        (rap.KERNEL_NAME, f"bf16[{images},{slots},7,7,256]"),
        (rap.KERNEL_NAME_GRAD, f"bf16[{images},{312 * 336},256]")], launches
    pooled_at = rf"\w+\[{images},(?:{block},{bins}|{slots},7),(\d+),256\]"
    assert {int(w) for w in re.findall(pooled_at, hlo)} - {7, bins} == {336}
    assert not re.findall(rf"\w+\[{images},{slots},7,336,256\]", hlo)
    assert re.findall(rf"bf16\[{images},312,336,256\]", hlo)
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert {branch_of(p) for p in paths} - {None} == set(BRANCH_STAGES)
    moved = [ln for ln in hlo.splitlines()
             if re.search(r" (gather|scatter)\(", ln)
             and branch_of(" ".join(re.findall(r'op_name="([^"]*)"', ln)))
             == "mask_loss"]
    assert not moved, moved[:2]
