"""Compiles for the chip, without the chip: the pyramid's evaluation
forward at the pyramid cell's test shape (``tests/chip_compile.py``)."""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chip_compile import _taps_kernels, one_chip_mesh, topo  # noqa: F401


@pytest.mark.parametrize("rois", [300, 1000])
def test_pyramid_eval_forward_takes_the_kernel(one_chip_mesh, monkeypatch,
                                               rois):
    """The FPN evaluation forward (``forward_test``; ViTDet's pyramid heads
    pool through the same ``pyramid_roi_align``) at the pyramid cell's test
    shape: one image of 832x1344 and ``rois`` proposals (the config's 300,
    Detectron's 1000: 60 and 50 rois a grid step, whose scalar blocks are
    padded to whole tiles) compiles for one described v5e with ONE launch
    of the taps kernel, forward only, its result the pooled
    ``bf16[1,R,7,7,256]``, inside the ``roi_align`` stage; no array is
    ``(1, R, 7, W, 256)`` at a canvas's width."""
    from benchmarks import manifest
    from benchmarks.drivers.train import _program_config
    from mx_rcnn_tpu.models.fpn import forward_test
    from mx_rcnn_tpu.models.zoo import build_model, init_params
    from mx_rcnn_tpu.obs.profile import stage_of
    from mx_rcnn_tpu.ops import roi_align_pallas as rap

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _program_config(manifest.load_json("configs", "fpn_r101_coco"),
                          {"test.rpn_post_nms_top_n": rois})
    model = build_model(cfg, mesh=one_chip_mesh)
    repl = NamedSharding(one_chip_mesh, P())
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(lambda k: init_params(model, cfg, k),
                       jax.random.PRNGKey(0)))
    h, w = cfg.image.pad_shape
    images = jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32, sharding=repl)
    im_info = jax.ShapeDtypeStruct((1, 3), jnp.float32, sharding=repl)
    hlo = jax.jit(lambda p, i, ii: forward_test(model, p, i, ii, cfg)).lower(
        params, images, im_info).compile().as_text()
    launches = _taps_kernels(hlo)
    assert [(name, shape.split("{")[0]) for name, shape, _ in launches] == [
        (rap.KERNEL_NAME, f"bf16[1,{rois},7,7,256]")], launches
    assert all(stage_of(path) == "roi_align" for _, _, path in launches)
    assert not re.findall(rf"\w+\[1,{rois},7,\d\d+,256\]", hlo)
