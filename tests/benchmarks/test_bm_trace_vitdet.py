"""The five readers the ViTDet cell brings, on a small trace made by hand and
kept beside the others (benchmarks/fixtures/small_trace_vitdet.json): exact
on its numbers, silent (None, no exception) where the program has no such
scope, as on the parent commit; the accepted pyramid readers count the whole
trunk as ``backbone``. And the FLOP count held to the table of the cell's
published shapes."""

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import (flops_vitdet, manifest, trace_reduce,  # noqa: E402
                        trace_scopes as ts, trace_scopes_vit as tv)

MS = 1_000_000
CELL = "vitdet_b_mask_train"
SPEC = manifest.load_json("configs", "vitdet_b_mask_coco")["spec"]
# a step of the fixture: block 0's windowed attention 3 + 6, block 2's
# global 5 + 9, the two MLPs 4 + 4 + 8; the trunk 2 + 9 + 14 + 16 = 41 and
# the neck 2, of 47 ms busy
WANT = {"vit.window_attn_ms.train": 9.0, "vit.global_attn_ms.train": 14.0,
        "vit.mlp_ms.train": 16.0}
NEW = tuple(WANT) + ("step.mfu.train.vitdet", "attention_roofline.global")
SHARED = ("loop.dispatch_ms.train", "loop.step_gap_ms_p95.train",
          "input.wait_share.train", "step.device_ms.train",
          "device.idle_share.train", "device.peak_hbm_gb.train",
          "loop.launch_lag_ms.train", "loop.host_idle_ms.train",
          "loop.unlaid_idle_share.train", "loop.host_ms.train",
          "pyramid.backbone_ms.train", "pyramid.neck_ms.train",
          "pyramid.rpn_ms.train", "pyramid.proposal_ms.train",
          "pyramid.roi_align_ms.train", "pyramid.box_head_ms.train",
          "proposal.nms_kernel_ms.train", "nms_roofline.per_level",
          "roi_align_roofline.taps", "mask.align_ms.train",
          "mask.head_ms.train", "mask.loss_ms.train",
          "mask.branch_share.train")


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def _run(dev, host, modules, spec=SPEC):
    summary = trace_reduce.reduce_events(
        {d: [e[:3] for e in evs] for d, evs in dev.items()}, host,
        modules=modules)
    return {"trace": summary, "work": "held in memory", "spec": spec,
            "device_kind": "TPU v5 lite", "rate": 3.0,
            ts.CACHE_KEY: ts.fold(dev, host, modules),
            tv.CACHE_KEY: tv.fold(dev, host, modules)}


@pytest.fixture(scope="module")
def parts():
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "small_trace_vitdet.json")) as f:
        t = json.load(f)
    return ({d: [tuple(e) for e in evs] for d, evs in t["devices"].items()},
            [tuple(h) for h in t["host"]],
            {d: [tuple(m) for m in ms] for d, ms in t["modules"].items()})


@pytest.fixture(scope="module")
def traced(parts):
    return _run(*parts)


def test_the_fixture_is_two_steps_and_47_ms_busy_a_step(traced):
    assert traced["trace"]["step_runs"] == 2
    assert traced[tv.CACHE_KEY]["busy_ns"] == 94 * MS
    assert traced[tv.CACHE_KEY]["scope_ns"] == {
        "window_attn": 18 * MS, "global_attn": 28 * MS, "vit_mlp": 32 * MS}


@pytest.mark.parametrize("name,want", sorted(WANT.items()))
def test_the_trunks_readers(name, want, traced):
    assert _reader(name).read(traced) == pytest.approx(want)


def test_the_pyramid_readers_count_the_whole_trunk_as_backbone(traced):
    assert _reader("pyramid.backbone_ms.train").read(traced) == 43.0
    assert _reader("pyramid.neck_ms.train").read(traced) == 2.0
    assert ts.stage_of("jit(step)/jvp(backbone)/ViTDet.extract/features/"
                       "block2/global_attn/attn/dot_general") == "backbone"


def test_the_step_mfu_is_required_work_over_the_steps_device_time(traced):
    need = flops_vitdet.vitdet_flops(SPEC, "train") * SPEC["batch_images"]
    got = _reader("step.mfu.train.vitdet").read(traced)
    assert got == pytest.approx(100 * need / 0.047 / 197e12)
    assert 0 < got < 100


def test_the_global_attention_roofline_stays_under_100(traced):
    """The least time of the work counted from the shapes (compute-bound:
    628 GFLOP a training image over 197 TFLOP/s) over the scope's 14 ms."""
    work = flops_vitdet.global_attention_work(SPEC)
    assert work["flops"] / 197e12 > work["bytes"] / 819e9
    got = _reader("attention_roofline.global").read(traced)
    assert got == pytest.approx(100 * work["flops"] / 197e12 / 0.014)
    assert 0 < got <= 100


def test_silent_where_the_program_has_no_scope(parts):
    """The parent commit's trunk carries none of the three scopes: every
    new reader returns None and raises nothing; so do the FLOP readers on
    another family's configuration."""
    dev, host, modules = parts
    bare = {d: [(n, s, t, p.replace("window_attn/", "")
                 .replace("global_attn/", "").replace("vit_mlp/", ""))
                for n, s, t, p in evs] for d, evs in dev.items()}
    run = _run(bare, host, modules)
    assert run[tv.CACHE_KEY] is None
    for name in NEW:
        if name != "step.mfu.train.vitdet":
            assert _reader(name).read(run) is None, name
    mask = manifest.load_json("configs", "mask_r101_fpn_coco")["spec"]
    other = _run(*parts, spec=mask)
    assert _reader("step.mfu.train.vitdet").read(other) is None
    assert _reader("attention_roofline.global").read(other) is None
    assert tv.of_run({"trace": None}) is None


def test_the_copy_is_the_programs_list():
    from mx_rcnn_tpu.obs import profile

    assert tv.VIT_STAGES == profile.VIT_STAGES
    assert tv._VIT_RX.pattern == profile._VIT_RX.pattern
    assert not set(tv.VIT_STAGES) & set(ts.STAGES)
    for name in tv.VIT_STAGES:
        assert profile.vit_of(f"jit(step)/jvp(backbone)/{name}/x") == name
        assert profile.stage_of(f"jit(step)/jvp(backbone)/{name}/x") == (
            "backbone")
        profile.stage(name)  # accepted, as one of STAGES is


def test_the_flop_count_is_the_table_of_the_published_shapes():
    """Forward GFLOP of one 1024x1024 image by part, counted by hand from
    the published shapes: the trunk's linears (the windowed blocks' qkv and
    proj on the 4900 tokens of the padded grid), the two attentions with
    the bias's products, the pyramid, the two-conv RPN, the 4conv1fc head
    over 512 rois, the mask head over 128 slots; the whole within 1 % of
    1.57 TFLOP forward and 4.7 training."""
    got = {k: v / 1e9 for k, v in flops_vitdet.parts(SPEC).items()}
    lin = 768 * 768 * 2 * (4 * 4096 * (4 + 8) + 8 * (4900 * 4 + 4096 * 8))
    want = {
        "trunk_linears": lin / 1e9,
        "global_attention": 4 * (4 * 12 * 4096 ** 2 * 64
                                 + 2 * 12 * 4096 * 64 * 128) / 1e9,
        "window_attention": 8 * (4 * 25 * 12 * 196 ** 2 * 64
                                 + 2 * 25 * 12 * 196 * 64 * 28) / 1e9,
        "pyramid": 2 * (2 * 128 ** 2 * 768 * 384 + 256 ** 2 * 384 * 192
                        + 256 ** 2 * 192 * 256 + 128 ** 2 * 384 * 256
                        + 64 ** 2 * 768 * 256 + 32 ** 2 * 768 * 256
                        + 9 * 256 * 256 * (256 ** 2 + 128 ** 2 + 64 ** 2
                                           + 32 ** 2)) / 1e9,
        "rpn": 2 * 87296 * 256 * (2 * 9 * 256 + 18) / 1e9,
        "box_head": 2 * 512 * (4 * 49 * 9 * 256 * 256 + 12544 * 1024
                               + 1024 * 405) / 1e9,
        "mask_head": 2 * 128 * (4 * 196 * 9 * 256 * 256
                                + 784 * 256 * (256 + 81)) / 1e9}
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=1e-9), k
    assert got["patch_embed"] == pytest.approx(2 * 4096 * 768 * 768 / 1e9)
    total = flops_vitdet.vitdet_flops(SPEC, "fwd") / 1e12
    assert total == pytest.approx(1.57, rel=0.01)
    assert flops_vitdet.vitdet_flops(SPEC, "train") / 1e12 == pytest.approx(
        4.7, rel=0.01)


def the_vitdet_metrics_hold(bm):
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == "train_img_per_s_chip"
        assert by_name[name]["source"] == "device_trace"
    listed = {m["name"] for m in manifest.metrics_of(bm, "per_layer", CELL)}
    assert set(NEW) | set(SHARED) <= listed
    # the C4 copies, the pyramid's unscoped share and the other families'
    # MFU readers are not this cell's
    assert not {"stage.backbone_ms.train", "pyramid.unscoped_share.train",
                "step.mfu.train.pyramid", "step.mfu.train.mask"} & listed
    assert {m["layer"] for m in bm["per_layer"] if m["name"] in NEW} <= {
        m["layer"] for m in bm["per_layer"] if m["name"] not in NEW}
    e2e = {e["name"]: e for e in bm["end_to_end"]}
    assert CELL in e2e["train_img_per_s_chip"]["workloads"]
    cell = manifest.cell(bm, CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_packed_landscape"
    assert cell["config"] == "vitdet_b_mask_coco"


def test_the_vitdet_metrics_hold():
    the_vitdet_metrics_hold(manifest.load())


LIMITS = manifest.load_json("limits", CELL)


@pytest.mark.parametrize("number", sorted(
    k for k in LIMITS["limits"] if k != "input"))
def test_each_limit_lies_between_its_readings(number):
    """The cell's limits file as its own rule sets it: each limit above the
    largest lower reading (the program's seeds) and, where the control or a
    planted fault read 3x that or more, under the smallest such upper
    reading."""
    lower, upper = LIMITS["readings"][number][:2]
    limit = LIMITS["limits"][number]
    assert lower < limit
    if upper >= 3 * lower:
        assert limit < upper


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/jvp(ViTDet.extract)/backbone/features/block0/window_attn/"
     "attn/qkv/dot_general", "window_attn"),
    ("jit(step)/transpose(jvp(ViTDet.extract))/backbone/features/block11/"
     "global_attn/attn/bqhd,bkhd->bhqk/dot_general", "global_attn"),
    ("jit(step)/jvp(ViTDet.extract)/backbone/features/block3/vit_mlp/mlp1/"
     "dot_general", "vit_mlp"),
    ("jit(step)/jvp(ViTDet.extract)/backbone/features/patch_embed/conv", None),
    ("jit(step)/jvp(ViTDet.extract)/neck/out2_3/conv", None)])
def test_the_scope_of_an_op_path(path, scope):
    """Paths as the chip's compiler names them (the cell's compiled step):
    the innermost of the trunk's three scopes, or none outside them."""
    assert tv.vit_of(path) == scope


def test_the_reference_holds_the_programs_leaves_at_the_cells_sizes():
    """At the published sizes, abstractly (no memory): the reference's
    ``param_shapes`` is the program's tree leaf for leaf, 113.9M
    parameters, and the harness's weights have a rule for every leaf."""
    import jax

    from benchmarks import weights
    from benchmarks.drivers.train import _program_config
    from mx_rcnn_tpu.models.zoo import build_model, init_params

    conf = manifest.load_json("configs", "vitdet_b_mask_coco")
    cfg = _program_config(conf)
    model = build_model(cfg)
    abstract = jax.eval_shape(lambda k: init_params(model, cfg, k),
                              jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    prog = {weights.path_of(p): tuple(v.shape) for p, v in flat}
    ref = manifest.load_module("reference", conf["reference"])
    assert prog == {k: tuple(v) for k, v in
                    ref.param_shapes(conf["spec"]).items()}
    n = sum(int(np.prod(s)) for s in prog.values())
    assert 113e6 < n < 114e6
    assert prog["features/block2/attn/rel_pos_h"] == (127, 64)
    assert prog["features/block0/attn/rel_pos_h"] == (27, 64)
    jax.eval_shape(lambda k: {p: weights._leaf(k, p, s)
                              for p, s in prog.items()},
                   jax.random.PRNGKey(0))


def test_the_configuration_is_the_programs():
    """The file's sizes agree with the program's preset and overrides
    (``drivers/train.py::check_spec``: batch 1, the 1024x1024 canvas,
    AdamW, no clip, layer decay and the no-decay set), and nothing is cut
    in width or depth."""
    from benchmarks.drivers.train import _program_config, check_spec

    conf = manifest.load_json("configs", "vitdet_b_mask_coco")
    cfg = _program_config(conf)
    check_spec(cfg, conf["spec"])
    assert conf["reduced"] == []
    n = cfg.network
    assert (n.vit_dim, n.vit_depth, n.vit_heads, n.vit_window) == (
        768, 12, 12, 14)
    assert cfg.train.clip_gradient == float("inf")
    assert n.vit_drop_path == 0.0 and cfg.train.batch_images == 1


def test_the_parts_add_up_to_the_forward():
    """``vitdet_flops``: the forward is the sum of its parts; training is
    three times each part but the patch embedding and ROIAlign (no data
    gradient for the image, the pooling's backward scatters its taps once
    more), twice."""
    parts = flops_vitdet.parts(SPEC)
    assert flops_vitdet.vitdet_flops(SPEC, "fwd") == pytest.approx(
        sum(parts.values()))
    want = 3 * sum(parts.values()) - parts["patch_embed"] - parts["roi_align"]
    assert flops_vitdet.vitdet_flops(SPEC, "train") == pytest.approx(want)
