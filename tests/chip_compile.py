"""What the chip-compile tests share: the described v5e, its meshes, the
tiny C4 and pyramid steps they compile, and the readers of the compiled
text. Not a test file; each ``test_chip_compile*.py`` imports from it.

The TPU's compiler is installed here and compiles for a v5e that is
DESCRIBED, not attached. The topology is described inside a module-scoped
fixture that skips when it cannot be, never while a module is imported;
the compile cache is off around the compiles (an executable compiled for
a described device cannot be read back); no child processes.

The tests are split over files of a few minutes each, so that ``--dist
loadfile`` spreads them over the workers (one file held the whole set at
about ten minutes on one worker). Only one process at a time may load the
TPU's library unless ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` is set, as the tier-1
command sets it: without it, a file that lands on a second worker skips its
tests in its fixture. Run them one file at a time, or under that command.
"""

import functools
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from mx_rcnn_tpu.ops import nms_pallas

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

@pytest.fixture(scope="module")
def one_chip_mesh(topo):
    return Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))

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

@functools.lru_cache(maxsize=None)
def _tiny_pyramid_step_hlo(mesh, image=(128, 192), images=2):
    """The FPN step as ``make_train_step`` builds it by default (no
    ``forward_fn``: the family dispatcher), exact top-k, ``images`` images
    on a canvas of ``image`` pixels. Call it with ``jax.default_backend``
    patched to "tpu"."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.models.zoo import build_model
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    cfg = generate_config("resnet50_fpn", "synthetic", **{
        "network.proposal_topk": "exact",
        "train.fpn_rpn_pre_nms_per_level": 256,
        "train.rpn_post_nms_top_n": 64, "train.batch_rois": 32,
        "train.max_gt_boxes": 8, "image.scales": (image,),
        "image.pad_shape": image})
    model = build_model(cfg)
    return make_train_step(model, cfg, mesh=mesh).lower(
        *abstract_step_inputs(model, cfg, mesh, images)).compile().as_text()

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

def _taps_kernels(hlo):
    """(name, result, op_name) of each launch of the taps kernels
    (ops/roi_align_pallas.py), forward or backward."""
    from mx_rcnn_tpu.ops import roi_align_pallas as rap

    names = f"(?:{rap.KERNEL_NAME_GRAD}|{rap.KERNEL_NAME})"
    return re.findall(rf'%({names})[\w.]* = (\S+) [^\n]*{KERNEL}[^\n]*'
                      r'op_name="([^"]*)"', hlo)


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


def assert_labelling_moves_no_row_an_anchor(hlo, anchors):
    """The labelling (``rpn_targets``) and ``rpn_loss`` of a compiled step
    of 2 images move no row for every one of its ``anchors``, and the
    labelling walks the gt slots in a ``while``."""
    from mx_rcnn_tpu.obs.profile import stage_of

    for stage_name in ("rpn_targets", "rpn_loss"):
        assert not _moved_over_every_anchor(hlo, stage_name, 2, anchors)
    loops = [path for path in re.findall(
        r'^\s*%[\w.-]+ = [^\n]*? while\([^\n]*op_name="([^"]*)"', hlo, re.M)
        if stage_of(path) == "rpn_targets"]
    assert loops, "no while loop scoped in rpn_targets"
