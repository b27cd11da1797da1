"""Compiles for the chip, without the chip: the Pallas NMS kernel and the
tiny C4 train steps (``tests/chip_compile.py`` describes the v5e).

- the Pallas NMS kernel (ops/nms_pallas.py) at every N the presets reach —
  6000 / 12000 (C4 test / train), 20000 (alternate training's
  ``test.proposal_pre_nms_top_n``), the FPN per-level 1000 / 2000 and
  all-level 5000 / 10000 — at batch 1 and 2, none stating a VMEM limit;
- the same kernel under a 4-device ``(data, model)`` mesh, where GSPMD
  refuses a bare Mosaic call ("cannot be automatically partitioned") and
  ``nms_dispatch`` has to wrap it in a ``shard_map`` over ``data``;
- a whole data-parallel train step on that mesh, tiny in width, built by
  ``make_train_step`` exactly as ``fit_detector`` builds it; in it the
  ``roi_align`` stage is partitioned over ``data`` with no exchange, each
  device contracting its own images' rois against its own feature maps.

Each asserts ``tpu_custom_call`` in the compiled program: the kernel is in
it, not its jnp stand-in; the train steps also hold it under the name a
trace shows it by (``nms_pallas.KERNEL_NAME``), inside the ``proposal``
stage, on one chip and under the ``shard_map`` alike. Nothing runs — a
compile that passes is not a chip run. The interpret-mode parity tests are
tests/test_nms.py. The pyramid's, the mask cell's, the evaluation
forward's and ViTDet's compiles are in the ``test_chip_compile_*.py``
files beside this one.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chip_compile import (KERNEL, _H_CONTRACTION, _ROI_ALIGN_OUT,  # noqa: F401
                          _assert_kernel_named_in_its_stage, _roi_align_ops,
                          _tiny_step_hlo,
                          assert_labelling_moves_no_row_an_anchor, data_mesh,
                          one_chip, one_chip_mesh, topo)
from mx_rcnn_tpu.ops import nms_pallas
from mx_rcnn_tpu.ops.nms import nms_dispatch


def _nms_args(batch, n, sharding):
    return (jax.ShapeDtypeStruct((batch, n, 4), jnp.float32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((batch, n), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((batch, n), jnp.bool_, sharding=sharding))


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("n", [
    6000, 12000, 20000,        # C4 test / C4 train / alternate proposals
    1000, 2000, 5000, 10000,   # FPN per level (test / train), all levels
])
def test_nms_kernel_compiles_at_every_preset_n(one_chip, n, batch):
    boxes, _, valid = _nms_args(batch, n, one_chip)
    compiled = jax.jit(
        lambda b, v: nms_pallas.nms_keep_sorted(b, v, 0.7)
    ).lower(boxes, valid).compile()
    assert KERNEL in compiled.as_text()


def test_the_alternate_budget_compiles_without_a_stated_vmem_limit(one_chip):
    """The kernel holds no (BLOCK, N) tile, so even the largest preset
    (alternate training's 20000 proposals) fits Mosaic's default scoped
    VMEM: the call states no limit of its own, and the chip's compiler
    takes it."""
    from mx_rcnn_tpu.config import generate_config

    cfg = generate_config("resnet101", "coco")
    assert cfg.test.proposal_pre_nms_top_n == 20000
    assert (cfg.train.rpn_pre_nms_top_n, cfg.train.rpn_post_nms_top_n,
            cfg.test.rpn_pre_nms_top_n, cfg.test.rpn_post_nms_top_n) == (
                12000, 2000, 6000, 300)
    boxes, _, valid = _nms_args(2, cfg.test.proposal_pre_nms_top_n, one_chip)
    lowered = jax.jit(
        lambda b, v: nms_pallas.nms_keep_sorted(b, v, 0.7)
    ).lower(boxes, valid)
    # a stated vmem_limit_bytes is lowered to a scoped-memory entry
    assert "scoped_memory_configs" not in lowered.as_text()
    assert KERNEL in lowered.compile().as_text()


def test_whole_nms_op_compiles_with_auto_dispatch(one_chip, monkeypatch):
    """sort → kernel → top-k as the proposal op calls it, ``impl="auto"``:
    on a TPU backend that is the compiled kernel, never the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(
        lambda b, s, v: nms_dispatch(b, s, v, 0.7, 2000)
    ).lower(*_nms_args(1, 12000, one_chip)).compile()
    assert KERNEL in compiled.as_text()


def test_kernel_partitions_over_a_data_mesh(data_mesh):
    """The smallest function that shards a batch over ``data`` and reaches
    the kernel: GSPMD alone refuses it, the shard_map in nms_dispatch is
    what lets a jit over the mesh hold a Mosaic call."""
    sharded = NamedSharding(data_mesh, P("data"))

    def proposals(boxes, scores, valid):
        with jax.sharding.use_abstract_mesh(data_mesh.abstract_mesh):
            return nms_dispatch(boxes, scores, valid, 0.7, 2000,
                                impl="pallas")

    compiled = jax.jit(proposals, in_shardings=sharded).lower(
        *_nms_args(4, 12000, sharded)).compile()
    assert KERNEL in compiled.as_text()

    bare = jax.jit(
        lambda b, s, v: nms_pallas.batched_nms(b, s, v, 0.7, 2000),
        in_shardings=sharded)
    with pytest.raises(NotImplementedError, match="shard_map"):
        bare.lower(*_nms_args(4, 12000, sharded)).compile()


def test_data_parallel_train_step_compiles_with_the_kernel(data_mesh,
                                                           monkeypatch):
    """make_train_step over the 4-device mesh, as fit_detector builds it
    (tiny widths — the R-101 step takes minutes and is chip_smoke.py's):
    the Pallas NMS is in the partitioned program under its own name, and
    so is the gradient all-reduce."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _tiny_step_hlo(data_mesh, 4)
    assert KERNEL in hlo
    assert "all-reduce" in hlo
    _assert_kernel_named_in_its_stage(hlo)


def test_one_chip_train_step_names_the_kernel(one_chip_mesh, monkeypatch):
    """The same step on a mesh of one described chip (no ``shard_map``
    around the kernel, no collective): the trace will show the kernel as
    ``nms_sweep`` there too."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _tiny_step_hlo(one_chip_mesh, 2)
    assert KERNEL in hlo and "all-reduce" not in hlo
    _assert_kernel_named_in_its_stage(hlo)


@pytest.mark.parametrize("family", ["c4"])
def test_anchor_labelling_moves_no_row_an_anchor(one_chip_mesh, monkeypatch,
                                                 family):
    """``targets/rpn_targets.py`` as the chip's compiler leaves it, in the
    tiny C4 step (8x8 cells x 9 = 576 anchors an image): the overlaps are
    walked a gt slot at a time by a ``while`` scoped in the stage, and no
    gather or scatter there moves a row for every anchor — the ranking's
    inverse permutation and the matched box of every anchor (13 ms each at
    279,279 anchors x 8 images, PERF.md section 6) are gone.
    ``rpn_loss`` picks the label's log-probability by a dense select, no
    gather either. The pyramid case is ``test_chip_compile_pyramid.py``'s."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert_labelling_moves_no_row_an_anchor(_tiny_step_hlo(one_chip_mesh, 2),
                                            8 * 8 * 9)


def test_labelling_over_a_data_mesh_exchanges_one_scalar(data_mesh,
                                                         monkeypatch):
    """Four images on four devices: the labelling's loop runs to the most
    valid boxes of ANY image of the step, so the devices agree on its trip
    count by one all-reduce of a scalar; nothing else of the stage crosses
    devices (each labels its own images' anchors)."""
    from mx_rcnn_tpu.obs.profile import stage_of

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    crossed = [(shape, opcode) for shape, opcode, path in re.findall(
        r'^\s*(?:ROOT )?%[\w.-]+ = (\S+) (all-gather|all-reduce|all-to-all|'
        r'collective-permute)[\w-]*\([^\n]*op_name="([^"]*)"',
        _tiny_step_hlo(data_mesh, 4), re.M) if stage_of(path) == "rpn_targets"]
    assert len(crossed) == 1, crossed
    assert crossed[0][0].startswith("s32[]") and crossed[0][1] == "all-reduce"


def _update_fusions(hlo):
    """(name, shapes put out, holds a convolution) of every fused
    computation with an instruction scoped in the ``update`` stage."""
    from mx_rcnn_tpu.obs.profile import stage_of

    found = []
    for name, body in re.findall(r"^(%[\w.-]+) [^\n]*\{\n(.*?)^\}", hlo,
                                 re.S | re.M):
        if not any(stage_of(p) == "update"
                   for p in re.findall(r'op_name="([^"]*)"', body)):
            continue
        root = re.search(r"^\s*ROOT %[\w.-]+ = (.*?) [\w-]+\(", body, re.M)
        shapes = [[int(d) for d in dims.split(",") if d]
                  for dims in re.findall(r"\w+\[([\d,]*)\]", root.group(1))]
        found.append((name, shapes, "convolution(" in body))
    return found


def test_update_rides_in_the_weight_gradient_convolutions(one_chip_mesh,
                                                          monkeypatch):
    """What the one state layout rests on (PERF.md section 5): the chip's
    compiler fuses each weight leaf's SGD-momentum update into the
    convolution that produces its gradient, so the per-leaf tree costs no
    pass of its own over the parameters. The program holds no stand-alone
    update fusion over a full-size leaf: what updates alone is a bias or
    the like (rank 1: its gradient is a reduction, there is no convolution
    to ride in)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fusions = _update_fusions(_tiny_step_hlo(one_chip_mesh, 2))
    riding = [shapes for _, shapes, conv in fusions if conv]
    alone = [shapes for _, shapes, conv in fusions if not conv]
    # ResNet-50's trainable kernels from stage 2 on, the RPN's, the head's
    assert len(riding) >= 40, len(riding)
    assert all(len(dims) >= 2 for shapes in riding for dims in shapes)
    assert all(len(dims) <= 1 for shapes in alone for dims in shapes), alone
    assert (max(int(np.prod(d)) for shapes in alone for d in shapes)
            < min(int(np.prod(d)) for shapes in riding for d in shapes))


# The tiny step's ROIAlign: 32 rois an image, a 14x14 pool, a 128/16 = 8x8
# map of 1024 channels. Per image, the elements each contraction of
# ops/roi_align.py puts out, forward or backward: (r,p,w,c), (r,p,q,c)
# and the map's own (h,w,c).


def test_roi_align_partitions_over_data_with_no_exchange(data_mesh,
                                                         monkeypatch):
    """Four images on four devices: the image axis is a batch dimension of
    both contractions, so GSPMD leaves each device ITS image's rois against
    ITS map — no collective moves a map or a roi inside the stage, and no
    contraction is four images wide."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops = _roi_align_ops(_tiny_step_hlo(data_mesh, 4))
    moved = [(o, path) for o, _, path in ops if o.startswith(
        ("all-gather", "all-to-all", "collective-permute", "all-reduce"))]
    assert not moved, moved
    sizes = [n for o, n, _ in ops if o == "convolution"]
    assert len(sizes) >= 4, ops   # two einsums, forward and backward
    assert set(sizes) <= _ROI_ALIGN_OUT, sizes


def test_one_chip_roi_align_is_one_batched_contraction(one_chip_mesh,
                                                       monkeypatch):
    """Two images on one chip: ONE forward contraction over H that is two
    images wide, not one per image of all the rois."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ops = _roi_align_ops(_tiny_step_hlo(one_chip_mesh, 2))
    forward_h = [n for o, n, path in ops if o == "convolution"
                 and path.endswith(_H_CONTRACTION)]
    assert forward_h == [2 * 32 * 14 * 8 * 1024], forward_h
    assert {n for o, n, _ in ops if o == "convolution"} <= {
        2 * n for n in _ROI_ALIGN_OUT}
