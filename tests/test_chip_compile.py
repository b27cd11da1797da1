"""Compiles for the chip, without the chip.

The TPU's compiler is installed here and compiles for a v5e that is
DESCRIBED, not attached (on-chip-measurement guide §2.3) — so what only the
chip's compiler can refuse is held by tier-1 at no chip time:

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
tests/test_nms.py.

Ground rules of this file (guide §2): the topology is described inside a
module-scoped fixture that skips when it cannot be, never while a module is
imported; all of these tests stay in this ONE file (only one process at a
time may load the TPU's library — a second file could land on another
worker); no child processes; the compile cache is off around the compiles
(an executable compiled for a described device cannot be read back).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from mx_rcnn_tpu.ops import nms_pallas
from mx_rcnn_tpu.ops.nms import nms_dispatch

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001  # graftlint: disable=broad-except — whatever keeps libtpu from describing the chip becomes a skip that says so
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "model"))


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


@functools.lru_cache(maxsize=None)
def _tiny_step_hlo(mesh, n_images):
    """The compiled step's text; one compile per (mesh, batch) for the
    tests that read it. Call it with ``jax.default_backend`` patched to
    "tpu", as every caller here does."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.faster_rcnn import build_model
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    cfg = generate_config("resnet50", "synthetic", **{
        "train.rpn_pre_nms_top_n": 256, "train.rpn_post_nms_top_n": 64,
        "train.batch_rois": 32, "train.max_gt_boxes": 8,
        "network.anchor_scales": (2, 4, 8), "image.pad_shape": (128, 128)})
    model = build_model(cfg)
    return make_train_step(model, cfg, mesh=mesh).lower(
        *abstract_step_inputs(model, cfg, mesh, n_images)).compile().as_text()


def _assert_kernel_named_in_its_stage(hlo):
    """The program's Mosaic calls are instructions named after the kernel,
    and their scope paths (``op_name``) lie in the ``proposal`` stage."""
    from mx_rcnn_tpu.obs.profile import stage_of

    paths = re.findall(
        rf'%{nms_pallas.KERNEL_NAME}[\w.]* = [^\n]*{KERNEL}[^\n]*'
        r'op_name="([^"]*)"', hlo)
    assert paths, "no Mosaic call named after the kernel"
    assert all(f"/{nms_pallas.KERNEL_NAME}/" in p
               and stage_of(p) == "proposal" for p in paths), paths


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


@pytest.fixture(scope="module")
def one_chip_mesh(topo):
    return Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))


def test_one_chip_train_step_names_the_kernel(one_chip_mesh, monkeypatch):
    """The same step on a mesh of one described chip (no ``shard_map``
    around the kernel, no collective): the trace will show the kernel as
    ``nms_sweep`` there too."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _tiny_step_hlo(one_chip_mesh, 2)
    assert KERNEL in hlo and "all-reduce" not in hlo
    _assert_kernel_named_in_its_stage(hlo)


@functools.lru_cache(maxsize=None)
def _tiny_pyramid_step_hlo(mesh):
    """The FPN step as ``make_train_step`` builds it by default (no
    ``forward_fn``: the family dispatcher), exact top-k, two images on a
    128x192 canvas. Call it with ``jax.default_backend`` patched to "tpu"."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.zoo import build_model
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    cfg = generate_config("resnet50_fpn", "synthetic", **{
        "network.proposal_topk": "exact",
        "train.fpn_rpn_pre_nms_per_level": 256,
        "train.rpn_post_nms_top_n": 64, "train.batch_rois": 32,
        "train.max_gt_boxes": 8, "image.scales": ((128, 192),),
        "image.pad_shape": (128, 192)})
    model = build_model(cfg)
    return make_train_step(model, cfg, mesh=mesh).lower(
        *abstract_step_inputs(model, cfg, mesh, 2)).compile().as_text()


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


def _moved_over_every_anchor(hlo, stage_name, images, anchors):
    """The gathers and scatters scoped in ``stage_name`` that move a row an
    anchor: a gather's result, a scatter's updates, with the per-image or
    the flat anchor count among their dimensions. (A scatter of the 128
    kept positives' targets INTO a zero (anchors, 4) moves 128 rows.) The
    compiler leaves some of them without an ``op_name``: those count for
    the stages their fused computation's other instructions name."""
    from mx_rcnn_tpu.obs.profile import stage_of

    dims_of = {name: [int(d) for d in dims.split(",") if d]
               for name, dims in re.findall(
                   r"^\s*(?:ROOT )?(%[\w.-]+) = \w+\[([\d,]*)\]", hlo, re.M)}
    found = []
    for body in re.findall(r"^%[\w.-]+ [^\n]*\{\n(.*?)^\}", hlo, re.S | re.M):
        around = {stage_of(p) for p in re.findall(r'op_name="([^"]*)"', body)}
        for name, opcode, operands, rest in re.findall(
                r"^\s*(?:ROOT )?(%[\w.-]+) = \S+ (gather|scatter)\(([^)]*)\)"
                r"([^\n]*)", body, re.M):
            own = re.search(r'op_name="([^"]*)"', rest)
            if stage_name not in ({stage_of(own.group(1))} if own else around):
                continue
            moved = (dims_of[name] if opcode == "gather" else
                     dims_of[re.findall(r"%[\w.-]+", operands)[-1]])
            if {anchors, images * anchors} & set(moved):
                found.append((name, opcode, moved))
    return found


@pytest.mark.parametrize("family", ["c4", "pyramid"])
def test_anchor_labelling_moves_no_row_an_anchor(one_chip_mesh, monkeypatch,
                                                 family):
    """``targets/rpn_targets.py`` as the chip's compiler leaves it, in the
    tiny C4 step (8x8 cells x 9 = 576 anchors an image) and the pyramid step
    (6138 over P2-P6): the overlaps are walked a gt slot at a time by a
    ``while`` scoped in the stage, and no gather or scatter there moves a
    row for every anchor — the ranking's inverse permutation and the
    matched box of every anchor (13 ms each at 279,279 anchors x 8 images,
    PERF.md section 6, PR 33) are gone. ``rpn_loss`` picks the label's
    log-probability by a dense select, no gather either."""
    from mx_rcnn_tpu.obs.profile import stage_of

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo, anchors = ((_tiny_step_hlo(one_chip_mesh, 2), 8 * 8 * 9)
                    if family == "c4" else
                    (_tiny_pyramid_step_hlo(one_chip_mesh),
                     3 * (32 * 48 + 16 * 24 + 8 * 12 + 4 * 6 + 2 * 3)))
    for stage_name in ("rpn_targets", "rpn_loss"):
        assert not _moved_over_every_anchor(hlo, stage_name, 2, anchors)
    loops = [path for path in re.findall(
        r'^\s*%[\w.-]+ = [^\n]*? while\([^\n]*op_name="([^"]*)"', hlo, re.M)
        if stage_of(path) == "rpn_targets"]
    assert loops, "no while loop scoped in rpn_targets"


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
_ROI_ALIGN_OUT = {32 * 14 * 8 * 1024, 32 * 14 * 14 * 1024, 8 * 8 * 1024}
_H_CONTRACTION = "/jvp(roi_align)/brph,bhwc->brpwc/dot_general"


def _roi_align_ops(hlo):
    """(opcode, elements put out, op_name) of every instruction, fused or
    not, whose scope path lies in the ``roi_align`` stage."""
    from mx_rcnn_tpu.obs.profile import stage_of

    ops = re.findall(r'^\s*(?:ROOT )?%[\w.-]+ = \w+\[([\d,]*)\]\S* '
                     r'([\w-]+)\([^\n]*op_name="([^"]*)"', hlo, re.M)
    return [(opcode, int(np.prod([int(d) for d in dims.split(",") if d])),
             path) for dims, opcode, path in ops
            if stage_of(path) == "roi_align"]


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


def test_pyramid_step_pools_each_roi_once_from_the_canvas(one_chip_mesh,
                                                          monkeypatch):
    """The tiny pyramid step (2 images of 128x192, 32 rois each, 7 bins,
    256 channels): P2's 32x48 over a shelf of P3, P4, P5 is a 48x48
    canvas, and the ``roi_align`` stage holds ONE forward contraction over
    H, canvas-wide, where pooling level by level held four; no array in
    the program is ``(B, R, P, W_l, C)`` at P3's, P4's or P5's own width."""
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
    6, PR 34) - and the loss picks its class's map without a gather. Both
    poolings contract against the 312x336 canvas of the four levels, once:
    their intermediates are P2's width, 336, and none is 168, 84 or 42
    wide (PR 36). The one full-size compile of this file: about two
    minutes."""
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
    assert not re.findall(rf"\w+\[{images},{slots},{bins},[\d,]*\]", hlo)
    assert not re.findall(rf"\w+\[{images * slots},{bins},{bins},\d+\]", hlo)
    assert re.findall(rf"bf16\[{images},{block},{bins},{bins},256\]", hlo)
    pooled_at = rf"\w+\[{images},(?:{block},{bins}|{slots},7),(\d+),256\]"
    assert {int(w) for w in re.findall(pooled_at, hlo)} - {7, bins} == {336}
    assert re.findall(rf"bf16\[{images},312,336,256\]", hlo)
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert {branch_of(p) for p in paths} - {None} == set(BRANCH_STAGES)
    moved = [ln for ln in hlo.splitlines()
             if re.search(r" (gather|scatter)\(", ln)
             and branch_of(" ".join(re.findall(r'op_name="([^"]*)"', ln)))
             == "mask_loss"]
    assert not moved, moved[:2]
