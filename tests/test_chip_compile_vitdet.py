"""Compiles for the chip, without the chip: the ViTDet cell's step at its
sizes (``tests/chip_compile.py`` describes the v5e), compiled once for the
module and read by each test."""

import re

import jax
import pytest

from chip_compile import _taps_kernels, one_chip_mesh, topo  # noqa: F401


@pytest.fixture(scope="module")
def vitdet_step(one_chip_mesh):
    """(config, compiled step, its HLO text) of ViTDet-B Mask R-CNN at the
    sizes the cell ``vitdet_b_mask_train`` runs
    (benchmarks/configs/vitdet_b_mask_coco.json), lowered from
    ``abstract_step_inputs`` for one described v5e."""
    from benchmarks import manifest
    from benchmarks.drivers.train import _program_config
    from mx_rcnn_tpu.models.zoo import build_model
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    cfg = _program_config(manifest.load_json("configs", "vitdet_b_mask_coco"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        model = build_model(cfg, mesh=one_chip_mesh)
        compiled = make_train_step(model, cfg, mesh=one_chip_mesh).lower(
            *abstract_step_inputs(model, cfg, one_chip_mesh, 1)).compile()
    return cfg, compiled, compiled.as_text()


def test_vitdet_step_at_the_cells_sizes_fits_one_chip(vitdet_step):
    """One 1024x1024 image a chip, window 14, no remat: ONE step program,
    under the 15.75 GB the described v5e's compiler allows."""
    cfg, compiled, hlo = vitdet_step
    assert cfg.network.use_vit and not cfg.network.remat
    assert (cfg.train.batch_images, cfg.network.vit_window) == (1, 14)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15.75e9, total
    assert len(re.findall(r"^HloModule ", hlo, re.M)) == 1


def test_vitdet_step_keeps_the_trunks_attention_in_its_scopes(vitdet_step):
    """The global blocks' scores (12 x 4096 x 4096) lie in ``global_attn``,
    the windowed blocks' (25 windows of 196 tokens) in ``window_attn``,
    the MLPs in ``vit_mlp``, all inside ``backbone``."""
    from mx_rcnn_tpu.obs.profile import VIT_STAGES, stage_of, vit_of

    hlo = vitdet_step[2]
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert {vit_of(p) for p in paths} - {None} == set(VIT_STAGES)
    assert all(stage_of(p) == "backbone" for p in paths if vit_of(p))
    scores = re.findall(
        r'f32\[(?:1,)?12,4096,4096\][^\n]*op_name="([^"]*)"', hlo)
    assert scores and all(vit_of(p) == "global_attn" for p in scores)
    assert re.findall(r'\w+\[25,12,196,196\][^\n]*op_name="[^"]*window_attn',
                      hlo)


def test_vitdet_step_pools_boxes_by_the_taps_kernels(vitdet_step):
    """The box pooling takes the taps kernels, forward and backward, on the
    384x256 canvas of P2-P5; the mask pooling stays dense."""
    from mx_rcnn_tpu.ops import roi_align_pallas as rap

    launches = sorted((name, shape.split("{")[0])
                      for name, shape, _ in _taps_kernels(vitdet_step[2]))
    assert launches == [(rap.KERNEL_NAME, "bf16[1,512,7,7,256]"),
                        (rap.KERNEL_NAME_GRAD, f"bf16[1,{384 * 256},256]")]
