"""The readers this cell brings, on a small trace made by hand and kept
beside the others (benchmarks/fixtures/small_trace_pyramid.json): exact on
its numbers, and silent (None, no exception) where the program has no neck,
no level or no such kernel, as on the parent commit or in a C4 cell."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, trace_reduce, trace_scopes as ts  # noqa: E402

MS = 1_000_000
SPEC = manifest.load_json("configs", "fpn_r101_coco")["spec"]
STAGES = {"backbone_ms": 30.0, "neck_ms": 10.0, "rpn_ms": 0.0,
          "proposal_ms": 3.0 + 1.05 + 0.5, "roi_align_ms": 0.0,
          "box_head_ms": 0.0, "unscoped_share": 0.0}
NEW = ("step.mfu.train.pyramid", "nms_roofline.per_level",
       "proposal.nms_kernel_ms.train") + tuple(
           f"pyramid.{k}.train" for k in STAGES)


def _reader(name):
    return manifest.load_module("layer_metrics", name)


@pytest.fixture(scope="module")
def traced():
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "small_trace_pyramid.json")) as f:
        t = json.load(f)
    dev = {d: [tuple(e) for e in evs] for d, evs in t["devices"].items()}
    host = [tuple(h) for h in t["host"]]
    modules = {d: [tuple(m) for m in ms] for d, ms in t["modules"].items()}
    summary = trace_reduce.reduce_events(
        {d: [e[:3] for e in evs] for d, evs in dev.items()}, host,
        modules=modules)
    return {"trace": summary, "work": "held in memory", "spec": SPEC,
            "device_kind": "TPU v5 lite", "rate": 30.0,
            ts.CACHE_KEY: ts.fold(dev, host, modules)}


def test_the_fixture_is_two_steps_of_95_ms(traced):
    assert traced["trace"]["step_runs"] == 2
    assert traced["trace"]["window_s"] == pytest.approx(0.095)
    assert traced[ts.CACHE_KEY]["step_runs"] == 2


@pytest.mark.parametrize("stage,want", sorted(STAGES.items()))
def test_the_cells_stage_readers(stage, want, traced):
    """A step of the fixture: trunk 20 ms, neck 6 ms forward + 4 ms backward
    (``pyramid.backbone_ms.train`` is trunk + neck, as the accepted
    ``stage.backbone_ms.train`` defines it), the proposal stage's top-k, its
    five launches and half of the odd one; the update and nothing else; each
    reader gives what the accepted one of its stage gives."""
    got = _reader(f"pyramid.{stage}.train").read(traced)
    assert got == pytest.approx(want)
    if stage != "neck_ms":
        assert got == _reader(f"stage.{stage}.train").read(traced)


def test_nms_kernel_ms_counts_every_launch(traced):
    """Five per-level launches a step (1.05 ms) and the one launch of
    another shape in the second step (1 ms): (2 x 1.05 + 1) / 2."""
    got = _reader("proposal.nms_kernel_ms.train").read(traced)
    assert got == pytest.approx(1.55)


def test_per_level_roofline_reads_the_per_level_shapes_only(traced):
    """Least time: 16 x (4 x 2000^2 + 819^2) operations an image over 197
    TFLOP/s, 8 images a step, 2 steps; spent: 2 x 1.05 ms (the 12032-wide
    launch is another configuration's shape)."""
    least = 16 * (4 * 2000 ** 2 + 819 ** 2) / 197e12 * 8 * 2
    got = _reader("nms_roofline.per_level").read(traced)
    assert got == pytest.approx(100 * least / 2.1e-3)
    assert 0 < got < 100
    mod = _reader("nms_roofline.per_level")
    assert mod.kernel_pattern([2000, 819]) == mod.kernel_pattern(
        [819, 2000, 2000])
    from mx_rcnn_tpu.ops import nms_pallas
    assert mod.KERNEL == nms_pallas.KERNEL_NAME == _reader(
        "proposal.nms_kernel_ms.train").KERNEL


def test_pyramid_mfu_is_required_work_times_rate_over_peak(traced):
    from benchmarks import flops_fpn

    need = flops_fpn.fpn_flops(SPEC, "train", 512)
    got = _reader("step.mfu.train.pyramid").read(traced)
    assert got == pytest.approx(100 * need * 30.0 / 197e12)
    assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_silent_where_there_is_nothing_to_read(name, traced):
    """The CPU rehearsal (no trace, no published peak): None. A C4 run
    (no neck, no levels, one 12032-wide launch under a name of no kernel):
    None, but for the copies of the accepted stage readers, which read there
    what those read. A program without scopes: None from every one. Never an
    exception."""
    c4 = manifest.load_json("configs", "c4_r101_coco")["spec"]
    read = _reader(name).read
    assert read({"trace": None, "spec": SPEC, "device_kind": "cpu",
                 "rate": 1.0, "memory_peak_bytes": 0}) is None
    dev = {"/device:TPU:0": [
        ("%fusion.1 = f32[8] x", 0, 20 * MS, "jit(step)/jvp(backbone)/conv"),
        ('%custom-call.7 = f32[8,1,12032]{2,1,0} custom-call(%a), '
         'custom_call_target="tpu_custom_call"', 20 * MS, 5 * MS, "")]}
    host = [("bench.traced", 0, 30 * MS)]
    modules = {"/device:TPU:0": [("jit_step(1)", 0, 30 * MS)]}
    summary = trace_reduce.reduce_events(
        {d: [e[:3] for e in evs] for d, evs in dev.items()}, host,
        modules=modules)
    run = {"trace": summary, "work": "held in memory", "spec": c4,
           "device_kind": "TPU v5 lite", "rate": 80.0,
           ts.CACHE_KEY: ts.fold(dev, host, modules)}
    copy_of = name.replace("pyramid.", "stage.")
    if name.startswith("pyramid.") and name != "pyramid.neck_ms.train":
        assert read(run) == _reader(copy_of).read(run) is not None
    else:
        assert read(run) is None
    bare = {d: [e[:3] + ("",) for e in evs] for d, evs in dev.items()}
    assert read(dict(run, **{ts.CACHE_KEY: ts.fold(bare, host,
                                                   modules)})) is None


def the_pyramid_metrics_hold(bm):
    """Each of the cell's metrics lists it, whatever other cells a list
    holds (the mask cell, since it runs the same pyramid; a cell appended
    later); the metrics of C4's step and of the mesh still leave it out."""
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in NEW:
        assert "fpn_r101_train" in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "train_img_per_s_chip"
    for name in ("step.mfu.train", "nms_roofline",
                 "allreduce.exposed_ms.train"):
        assert "fpn_r101_train" not in by_name[name]["workloads"]
    listed = {m["name"] for m in manifest.metrics_of(bm, "per_layer",
                                                     "fpn_r101_train")}
    assert set(NEW) <= listed and "step.device_ms.train" in listed
    # the cell reads the stages by its own copies, the pyramid.* readers
    assert not {n for n in listed if n.startswith("stage.")}
    assert {m["layer"] for m in bm["per_layer"] if m["name"] in NEW} <= {
        m["layer"] for m in bm["per_layer"] if m["name"] not in NEW}


def test_the_manifest_lists_the_new_metrics_for_this_cell_only():
    the_pyramid_metrics_hold(manifest.load())
