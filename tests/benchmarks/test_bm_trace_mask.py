"""The five readers the mask cell brings, on a small trace made by hand and
kept beside the others (benchmarks/fixtures/small_trace_mask.json): exact on
its numbers, and silent (None, no exception) where the program has no such
scope, as on the parent commit or in a box-only cell; the accepted readers
read the same trace as before the scopes existed."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import (manifest, trace_reduce, trace_scopes as ts,  # noqa: E402
                        trace_scopes_mask as tm)

MS = 1_000_000
CELL = "mask_r101_train"
SPEC = manifest.load_json("configs", "mask_r101_fpn_coco")["spec"]
# a step of the fixture: pooling 4 + 3, head 6 + 8, targets 1 (the second
# step's 4 ms begin 2 ms inside the head's forward: 2) and loss 0.5 + 0.5
WANT = {"mask.align_ms.train": 7.0, "mask.head_ms.train": 14.0,
        "mask.loss_ms.train": (2.0 + 3.0) / 2,
        "mask.branch_share.train": 100 * 47.0 / 109.0}
NEW = tuple(WANT) + ("step.mfu.train.mask",)
APPENDED = ("loop.dispatch_ms.train", "loop.step_gap_ms_p95.train",
            "input.wait_share.train", "step.device_ms.train",
            "device.idle_share.train", "device.peak_hbm_gb.train")


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def _run(dev, host, modules, spec=SPEC, rate=30.0):
    summary = trace_reduce.reduce_events(
        {d: [e[:3] for e in evs] for d, evs in dev.items()}, host,
        modules=modules)
    return {"trace": summary, "work": "held in memory", "spec": spec,
            "device_kind": "TPU v5 lite", "rate": rate,
            ts.CACHE_KEY: ts.fold(dev, host, modules),
            tm.CACHE_KEY: tm.fold(dev, host, modules)}


@pytest.fixture(scope="module")
def parts():
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "small_trace_mask.json")) as f:
        t = json.load(f)
    return ({d: [tuple(e) for e in evs] for d, evs in t["devices"].items()},
            [tuple(h) for h in t["host"]],
            {d: [tuple(m) for m in ms] for d, ms in t["modules"].items()})


@pytest.fixture(scope="module")
def traced(parts):
    return _run(*parts)


def test_the_fixture_is_two_steps_and_109_ms_busy(traced):
    assert traced["trace"]["step_runs"] == 2
    assert traced[tm.CACHE_KEY]["step_runs"] == 2
    assert traced[tm.CACHE_KEY]["busy_ns"] == 109 * MS == traced[
        ts.CACHE_KEY]["busy_ns"]
    assert traced[tm.CACHE_KEY]["branch_ns"] == {
        "mask_align": 14 * MS, "mask_head": 28 * MS, "mask_targets": 3 * MS,
        "mask_loss": 2 * MS}


@pytest.mark.parametrize("name,want", sorted(WANT.items()))
def test_the_branchs_readers(name, want, traced):
    assert _reader(name).read(traced) == pytest.approx(want)


def test_the_accepted_readers_see_the_pooling_as_roi_align_and_the_rest_unscoped(
        traced):
    """``mask_align`` wraps ``pyramid_roi_align``'s own scope, which stays:
    a step's ``roi_align`` is the box head's 5 ms and the branch's 7; head,
    targets and loss (14 + 2.5 ms a step) and the op of no scope (2) are
    unscoped; every stage and the unscoped time add up to the busy time."""
    f = traced[ts.CACHE_KEY]
    assert _reader("pyramid.roi_align_ms.train").read(traced) == 12.0
    assert f["unscoped_ns"] == (28 + 5) * MS
    assert sum(f["stage_ns"].values()) + f["unscoped_ns"] == f["busy_ns"]
    assert tm.branch_of("jit(step)/jvp(box_head)/remask_head_x/dot") is None
    assert tm.branch_of(
        "jit(step)/transpose(jvp(mask_align))/roi_align/dot") == "mask_align"
    assert ts.stage_of(
        "jit(step)/transpose(jvp(mask_align))/roi_align/dot") == "roi_align"
    # the flax module is called mask_head too: its ops are the head's
    assert tm.branch_of("jit(step)/jvp(FPNFasterRCNN.mask_forward)/"
                        "mask_head/mask_conv1/conv") == "mask_head"


def test_mask_mfu_is_required_work_times_rate_over_peak(traced):
    from benchmarks import flops_mask

    need = flops_mask.mask_flops(SPEC, "train", 512)
    got = _reader("step.mfu.train.mask").read(traced)
    assert got == pytest.approx(100 * need * 30.0 / 197e12)
    assert 0 < got < 100


def test_the_copy_is_the_programs_list():
    from mx_rcnn_tpu.obs import profile

    assert tm.BRANCH_STAGES == profile.BRANCH_STAGES
    assert tm._BRANCH_RX.pattern == profile._BRANCH_RX.pattern
    assert not set(tm.BRANCH_STAGES) & set(ts.STAGES)
    assert ts.STAGES == profile.STAGES
    assert {s for g in tm.GROUPS.values() for s in g} == set(tm.BRANCH_STAGES)
    for name in tm.BRANCH_STAGES:
        assert profile.branch_of(f"jit(step)/jvp({name})/x") == name
        assert profile.stage_of(f"jit(step)/jvp({name})/x") is None
        profile.stage(name)  # accepted, as one of STAGES is
    with pytest.raises(ValueError):
        profile.stage("mask_everything")


@pytest.mark.parametrize("name", NEW)
def test_silent_where_there_is_nothing_to_read(name, parts):
    """The CPU rehearsal (no trace, no published peak): None. A box-only
    pyramid run (the same trace with the branch's names taken out of every
    path: the parent commit's program, or ``fpn_r101_train``): None. A
    trace without a device plane: None. Never an exception."""
    read = _reader(name).read
    assert read({"trace": None, "spec": SPEC, "device_kind": "cpu",
                 "rate": 1.0, "memory_peak_bytes": 0}) is None
    dev, host, modules = parts
    bare = {d: [e[:3] + (tm._BRANCH_RX.sub("scope", e[3]),) for e in evs]
            for d, evs in dev.items()}
    fpn = manifest.load_json("configs", "fpn_r101_coco")["spec"]
    assert read(_run(bare, host, modules, spec=fpn)) is None
    assert tm.fold({}, host, modules) is None
    assert tm.fold({"/device:TPU:0": []}, host, modules) is None
    run = {"trace": {"step_runs": 2}, "work": "/nowhere/at/all", "spec": fpn,
           "device_kind": "TPU v5 lite", "rate": 30.0}
    assert read(run) is None    # no xplane file under the run's directory


def _detector_part(at):
    """What a mask step runs besides the fixture's ops, 4.5 ms laid in the
    idle after each of its steps: the neck 0.5 ms, the RPN head 0.5, the
    proposal stage's five per-level NMS launches 0.2 each, and the box
    pooling's two taps kernels, 1 ms forward and 1.5 backward."""
    call = 'custom-call(%a), custom_call_target="tpu_custom_call"'
    nms = "jit(step)/jvp(proposal)/shard_map/nms_sweep/pallas_call"
    half = MS // 2
    return [
        ("%fusion.30 = bf16[2,64,96,256] x", at, half,
         "jit(step)/jvp(FPNFasterRCNN.extract)/neck/neck/lateral2/conv"),
        ("%fusion.31 = bf16[2,64,96,256] x", at + half, half,
         "jit(step)/jvp(rpn_head)/conv"),
        *[(f"%nms_sweep.{i} = f32[8,1,{w}]{{2,1,0}} {call}",
           at + MS + i * MS // 5, MS // 5, nms)
          for i, w in enumerate([2048] * 4 + [896])],
        (f"%roi_align_taps.1 = bf16[8,512,7,7,256]{{4,3,2,1,0}} {call}",
         at + 2 * MS, MS, "jit(step)/jvp(roi_align)/pallas_call"),
        (f"%roi_align_taps_grad.1 = bf16[8,104832,256]{{2,1,0}} {call}",
         at + 3 * MS, 3 * half,
         "jit(step)/transpose(jvp(roi_align))/pallas_call")]


# a step of the fixture with its detector part: each pyramid reader the
# mask cell lists, and what it reads
PYRAMID = {"pyramid.backbone_ms.train": 20.5, "pyramid.neck_ms.train": 0.5,
           "pyramid.rpn_ms.train": 0.5, "pyramid.proposal_ms.train": 1.0,
           "pyramid.roi_align_ms.train": 12.0 + 2.5,
           "pyramid.box_head_ms.train": 2.0,
           "proposal.nms_kernel_ms.train": 1.0,
           "nms_roofline.per_level": 100 * 16 * (4 * 2000 ** 2 + 819 ** 2)
           / 197e12 * 8 * 2 / 2e-3,
           "roi_align_roofline.taps": 100 * 2 * 1_064_304_640 / 819e9 / 5e-3}


@pytest.mark.parametrize("name,want", sorted(PYRAMID.items()))
def test_the_pyramid_readers_read_the_mask_cell(name, want, parts):
    """On the fixture as it is, the readers that have something there read
    it (``pyramid.roi_align_ms.train`` is both poolings: the box head's 5 ms
    and the branch's 7); with the detector part a mask step runs besides,
    each reads its number, and none is None."""
    dev, host, modules = parts
    as_is = _reader(name).read(_run(dev, host, modules))
    if name in ("pyramid.neck_ms.train", "proposal.nms_kernel_ms.train",
                "nms_roofline.per_level", "roi_align_roofline.taps"):
        assert as_is is None        # the fixture has no such op
    else:
        assert as_is == pytest.approx(want - {
            "pyramid.backbone_ms.train": 0.5, "pyramid.rpn_ms.train": 0.5,
            "pyramid.proposal_ms.train": 1.0,
            "pyramid.roi_align_ms.train": 2.5}.get(name, 0.0))
    (d, evs), = dev.items()
    whole = {d: sorted(evs + _detector_part(54 * MS)
                       + _detector_part(115 * MS), key=lambda e: e[1])}
    steps = {d: [("jit_step(1)", 0, 60 * MS), ("jit_step(1)", 60 * MS,
                                               60 * MS)]}
    got = _reader(name).read(_run(whole, host, steps))
    assert got is not None and got == pytest.approx(want)
    assert name in {m["name"] for m in manifest.metrics_of(
        manifest.load(), "per_layer", CELL)}


def test_four_readers_parse_the_trace_once(monkeypatch, parts, tmp_path):
    """The fold is cached on the run under a key of its own, beside
    ``trace_scopes``' and not in its place."""
    dev, host, modules = parts
    calls = []
    os.makedirs(tmp_path / "trace" / "plugins")
    (tmp_path / "trace" / "plugins" / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(ts, "read_xplane", lambda path, chips: (
        calls.append(path), (dev, host, modules))[1])
    run = {"trace": {"step_runs": 2}, "work": str(tmp_path), "chips": 1}
    vals = [_reader(n).read(run) for n in WANT]
    assert len(calls) == 1 and None not in vals
    assert tm.CACHE_KEY in run and tm.CACHE_KEY != ts.CACHE_KEY


def test_the_manifest_lists_the_cells_metrics():
    """Only what belongs to this cell: a later PR may append cells,
    configurations and workloads to any list without an edit here."""
    the_mask_metrics_hold(manifest.load())


def the_mask_metrics_hold(bm):
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "train_img_per_s_chip"
    assert [by_name[n]["source"] for n in NEW] == ["device_trace"] * 4 + [
        "host_clock"]
    listed = {m["name"] for m in manifest.metrics_of(bm, "per_layer", CELL)}
    assert set(NEW) | set(APPENDED) | set(PYRAMID) <= listed
    # the unscoped share would count the branch's head and loss as
    # unscoped, and the pyramid's MFU leaves the branch's work out
    assert not {"pyramid.unscoped_share.train",
                "step.mfu.train.pyramid"} & listed
    assert {m["layer"] for m in bm["per_layer"] if m["name"] in NEW} <= {
        m["layer"] for m in bm["per_layer"] if m["name"] not in NEW}
    e2e = {e["name"]: e for e in bm["end_to_end"]}
    assert CELL in e2e["train_img_per_s_chip"]["workloads"]
    cell = manifest.cell(bm, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_packed_landscape"
    assert cell["config"] == "mask_r101_fpn_coco"
