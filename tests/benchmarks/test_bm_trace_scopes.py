"""The reduction by the program's own names (benchmarks/trace_scopes.py):
exact on hand-made tuples, steady on the small trace recorded on the chip
that is kept beside it, silent where there is nothing to read, and held to
the program's lists of stages and spans."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, trace_reduce, trace_scopes as ts  # noqa: E402

MS = 1_000_000
BM = manifest.load()
TEN = ("stage.backbone_ms.train", "stage.rpn_ms.train",
       "stage.proposal_ms.train", "stage.roi_align_ms.train",
       "stage.box_head_ms.train", "stage.update_ms.train",
       "stage.unscoped_share.train", "loop.place_ms.train",
       "loop.enqueue_ms.train", "loop.host_bound_share.train")
C4_CELLS = ("c4_r101_train", "c4_r101_train_dp4")


def the_ten_hold(bm):
    """The ten are listed, in this order among ``per_layer``, each for both
    C4 cells and whatever cells a later PR appends to its list."""
    got = [m for m in bm["per_layer"] if m["name"] in TEN]
    assert [m["name"] for m in got] == list(TEN)
    for m in got:
        assert set(C4_CELLS) <= set(m["workloads"]), m["name"]
        assert m["moves"] == "train_img_per_s_chip"
        assert m["source"] == ("device_trace" if m["name"].startswith(
            "stage.") else "program_span")


NEW = [m for m in BM["per_layer"] if m["name"] in TEN]


@pytest.mark.parametrize("path,stage", [
    ("jit(step)/jvp(roi_align)/dot_general", "roi_align"),
    ("jit(step)/jit(main)/transpose(jvp(roi_align))/dot_general", "roi_align"),
    ("jit(step)/transpose(jvp(backbone))/FasterRCNN.extract/features/conv",
     "backbone"),
    ("jit(step)/jvp(FPNFasterRCNN.extract)/neck/neck/lateral2/conv", "neck"),
    ("jit(step)/jvp(proposal)/shard_map/nms_sweep/pallas_call", "proposal"),
    ("jit(step)/update/add", "update"),
    ("jit(step)/jvp(FasterRCNN.box_head)/dot_general", None),   # a method
    ("jit(step)/jvp()/dynamic_update_slice", None),           # a primitive
    ("jit(step)/reduce_sum", None),
    ("", None),
    (None, None),
])
def test_scope_matching(path, stage):
    assert ts.stage_of(path) == stage


def test_the_copies_are_the_programs_lists():
    from mx_rcnn_tpu.obs import profile, timing

    assert ts.STAGES == profile.STAGES
    assert ts._STAGE_RX.pattern == profile._STAGE_RX.pattern
    assert sorted(s for g in ts.GROUPS.values() for s in g) == sorted(ts.STAGES)
    assert ts.LOOP_SPAN.match(timing.STEP_SPAN)
    assert all(ts.LOOP_SPAN.match(n) for n in timing.LOOP_SPANS)
    assert not ts.LOOP_SPAN.match("training") and not ts.LOOP_SPAN.match(
        trace_reduce.WINDOW_SPAN)
    from mx_rcnn_tpu.ops import nms_pallas
    assert nms_pallas.KERNEL_NAME == "nms_sweep"


def test_scope_paths_come_out_of_the_programs_the_trace_carries(tmp_path):
    """A trace made on the spot: the wire reader finds each compiled
    program's instructions with their scope paths in the metadata plane,
    as the program's own copy of it does (obs/profile.py), and an op takes
    the program whose execution contains it."""
    import glob

    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.obs import profile

    def two_stages(x):
        with jax.named_scope("roi_align"):
            y = x @ x
        with jax.named_scope("box_head"):
            return jnp.tanh(y) @ y

    f = jax.jit(jax.grad(lambda x: two_stages(x).sum()))
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    with open(path, "rb") as fh:
        raw = fh.read()
    paths = ts.program_paths(raw)
    assert paths == profile.program_paths(raw)
    program, = [p for p in paths if p.startswith("jit__lambda(")]
    found = {ts.stage_of(v) for v in paths[program].values()}
    assert found == {"roi_align", "box_head", None}
    assert any("transpose(jvp(roi_align))" in v
               for v in paths[program].values())
    # an op is looked up in the program that was running when it started
    ins, want = next((k, v) for k, v in paths[program].items() if v)
    text = f"%{ins} = f32[32,32]{{1,0}} fusion(f32[32,32] %p), kind=kLoop"
    assert ts.instruction_of(text) == ins
    ops = [(text, 15, 5), (text, 40, 5), ("%nowhere.1 = f32[] x", 16, 1)]
    runs = [("jit_other(9)", 0, 10), (program, 10, 20)]
    assert [o[3] for o in ts.with_paths(ops, runs, paths)] == [want, "", ""]
    # an executable from the compile cache may run under another id
    again = [(program.split("(")[0] + "(77)", 10, 20)]
    assert ts.with_paths(ops[:1], again, paths)[0][3] == want
    assert ts.table_of({"jit_f(1)": {"a": "x"}, "jit_f(2)": {}}, "jit_f(3)") == {}
    assert ts.read_xplane(path, chips=1)[0] == {}   # no device plane here


def _by_hand():
    """One device, 100 ms: two steps of a program named jit_step. Ops
    overlap once (an async copy under a fusion), idle 40-50 (the loop sits
    in train.place), 70-80 (the loop waits in the loader) and 95-100 (the
    harness drains)."""
    fwd, bwd = "jit(step)/jvp(%s)/conv", "jit(step)/transpose(jvp(%s))/conv"
    dev = {"/device:TPU:0": [
        ("%fusion.1 = f32[8] x", 0, 20 * MS, fwd % "backbone"),
        ("%copy-start.1 = f32[8] y", 10 * MS, 15 * MS, ""),       # 20-25 counts
        ("%fusion.2 = f32[8] x", 25 * MS, 15 * MS, bwd % "roi_align"),
        ("%nms_sweep.1 = f32[8,1,128] custom-call(), "
         'custom_call_target="tpu_custom_call"', 50 * MS, 10 * MS,
         "jit(step)/jvp(proposal)/shard_map/nms_sweep/pallas_call"),
        ("%all-reduce.1 = f32[4] z", 60 * MS, 10 * MS, "jit(step)/psum"),
        ("%fusion.3 = f32[8] x", 80 * MS, 15 * MS, "jit(step)/update/add"),
    ]}
    host = [("bench.traced", 0, 100 * MS),
            ("train.next_batch", 0, 2 * MS),
            ("train", 2 * MS, 60 * MS),
            ("train.place", 38 * MS, 14 * MS),
            ("train.enqueue", 52 * MS, 4 * MS),
            ("train.next_batch", 62 * MS, 33 * MS),
            ("bench.loader_next", 63 * MS, 20 * MS),
            ("bench.drain", 90 * MS, 10 * MS)]
    modules = {"/device:TPU:0": [("jit_step(1)", 0, 45 * MS),
                                 ("jit_step(1)", 50 * MS, 50 * MS)]}
    return dev, host, modules


def test_fold_by_hand():
    f = ts.fold(*_by_hand())
    assert f["window_ns"] == 100 * MS and f["step_runs"] == 2
    assert f["stage_ns"] == {"backbone": 20 * MS, "proposal": 10 * MS,
                             "roi_align": 15 * MS, "update": 15 * MS}
    assert list(f["stage_ns"]) == ["backbone", "proposal", "roi_align",
                                   "update"]          # program order
    # the copy's 10-20 ms ran under the fusion: only 20-25 is its own
    assert f["unscoped_ns"] == (5 + 10) * MS
    assert f["unscoped_ops"][0] == ["%all-reduce.1 f32[4]", 10 * MS]
    assert f["busy_ns"] == 75 * MS == sum(f["stage_ns"].values()) + f[
        "unscoped_ns"]
    assert f["idle_ns"] == 25 * MS
    # 40-50 under train.place, 70-80 in the loader: the program's; 95-100
    # under bench.drain: the harness's, though train.next_batch covers it
    assert f["host_bound_ns"] == 20 * MS
    assert f["idle_by_span"] == {"train.place": 10 * MS,
                                 "train.next_batch": 10 * MS,
                                 "the harness's own": 5 * MS}
    assert f["span_ns"]["train.place"] == [1, 14 * MS]
    assert f["span_ns"]["train.next_batch"] == [2, 17.5 * MS]
    # the same interval and busy time as the accepted reduction
    dev, host, modules = _by_hand()
    r = trace_reduce.reduce_events(
        {d: [e[:3] for e in evs] for d, evs in dev.items()},
        [h for h in host if h[0].startswith("bench.")], modules=modules)
    assert r["window_s"] * 1e9 == pytest.approx(f["window_ns"])
    assert r["busy_s"] * 1e9 == pytest.approx(f["busy_ns"])
    assert r["step_runs"] == f["step_runs"]


def test_the_interval_is_the_step_programs_runs_inside_the_mark():
    dev, host, modules = _by_hand()
    modules["/device:TPU:0"] = [("jit_bump(2)", 0, 1 * MS),
                                ("jit_step(1)", 25 * MS, 15 * MS),
                                ("jit_step(1)", 50 * MS, 20 * MS)]
    f = ts.fold(dev, host, modules)
    assert f["window_ns"] == 45 * MS and f["step_runs"] == 2
    assert f["stage_ns"] == {"proposal": 10 * MS, "roi_align": 15 * MS}
    assert f["unscoped_ns"] == 10 * MS and f["idle_ns"] == 10 * MS
    assert f["span_ns"] == {"train.place": [1, 14 * MS],
                            "train.enqueue": [1, 4 * MS]}


def test_a_program_without_scopes_or_spans_reads_as_nothing():
    """The parent commit: every op unscoped, no ``train.*`` span. The fold
    says so and the readers then report nothing, without raising."""
    dev, host, modules = _by_hand()
    dev = {d: [e[:3] + ("",) for e in evs] for d, evs in dev.items()}
    host = [h for h in host if h[0].startswith("bench.")]
    f = ts.fold(dev, host, modules)
    assert f["stage_ns"] == {} and f["span_ns"] == {}
    assert f["unscoped_ns"] == f["busy_ns"] == 75 * MS
    assert f["host_bound_ns"] == 0
    run = {"trace": {"busy_s": 1}, "work": "/nowhere", ts.CACHE_KEY: f}
    for m in NEW:
        reader = manifest.load_module("layer_metrics", m["name"])
        assert reader.read(run) is None, m["name"]
    with pytest.raises(ValueError):
        ts.fold({}, host)


@pytest.mark.parametrize("m", NEW, ids=lambda m: m["name"])
def test_new_reader(m):
    """Each new metric's reader: silent without a device trace (the CPU
    rehearsal), a number per step where the program names its stages."""
    reader = manifest.load_module("layer_metrics", m["name"])
    assert reader.read({"trace": None, "work": "/nowhere"}) is None
    assert reader.read({"events": []}) is None
    run = {"trace": {"busy_s": 1}, "work": "/nowhere",
           ts.CACHE_KEY: ts.fold(*_by_hand())}
    want = {"stage.backbone_ms.train": 10.0, "stage.rpn_ms.train": 0.0,
            "stage.proposal_ms.train": 5.0, "stage.roi_align_ms.train": 7.5,
            "stage.box_head_ms.train": 0.0, "stage.update_ms.train": 7.5,
            "stage.unscoped_share.train": 20.0, "loop.place_ms.train": 14.0,
            "loop.enqueue_ms.train": 4.0,
            "loop.host_bound_share.train": 20.0}[m["name"]]
    assert reader.read(run) == pytest.approx(want)
    assert m["moves"] == "train_img_per_s_chip"
    assert set(C4_CELLS) <= set(m["workloads"])
    assert m["source"] == ("device_trace" if m["name"].startswith("stage.")
                           else "program_span")


def test_ten_readers_parse_the_trace_once(monkeypatch, tmp_path):
    trace = tmp_path / "trace" / "plugins"
    trace.mkdir(parents=True)
    (trace / "vm.xplane.pb").write_bytes(b"")
    calls = []

    def read_xplane(path, chips):
        calls.append((path, chips))
        return _by_hand()

    monkeypatch.setattr(ts, "read_xplane", read_xplane)
    run = {"trace": {"busy_s": 1}, "work": str(tmp_path), "chips": 4}
    vals = [manifest.load_module("layer_metrics", m["name"]).read(run)
            for m in NEW]
    assert len(NEW) == 10 and None not in vals
    the_ten_hold(BM)
    assert calls == [(str(trace / "vm.xplane.pb"), 4)]
    # the six stages and the unscoped time add up to the device's step
    f = run[ts.CACHE_KEY]
    stages = sum(v for m, v in zip(NEW, vals)
                 if m["name"].startswith("stage.") and m["unit"] == "ms")
    assert stages + f["unscoped_ns"] / 1e6 / f["step_runs"] == pytest.approx(
        f["busy_ns"] / 1e6 / f["step_runs"])


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "small_trace_scopes.json")) as f:
        return json.load(f)


def test_recorded_trace_folds_to_what_it_did_on_the_chip(recorded):
    devices = {k: [(n, s, d, recorded["paths"][p]) for n, s, d, p in v]
               for k, v in recorded["devices"].items()}
    host = [tuple(h) for h in recorded["host"]]
    want = recorded["expected"]
    f = ts.fold(devices, host, window=tuple(recorded["window"]))
    # stages + unscoped = busy, to the nanosecond
    assert sum(f["stage_ns"].values()) + f["unscoped_ns"] == f["busy_ns"]
    assert f["busy_ns"] == want["busy_ns"]
    assert f["stage_ns"] == want["stage_ns"]
    assert f["unscoped_ns"] == want["unscoped_ns"]
    # a whole step: every stage of the C4 family is in it
    assert set(f["stage_ns"]) == set(ts.STAGES) - {"neck"}
    assert f["busy_ns"] + f["idle_ns"] == f["window_ns"]
    assert f["host_bound_ns"] == want["host_bound_ns"]
    assert f["span_ns"] == {k: list(v) for k, v in want["span_ns"].items()}
    # the accepted reduction sees the same busy time in the same window,
    # and still finds the kernel by its target and shape under its new name
    r = trace_reduce.reduce_events(
        {d: [e[:3] for e in evs] for d, evs in devices.items()},
        [h for h in host if h[0].startswith("bench.")],
        window=tuple(recorded["window"]))
    assert round(r["busy_s"] * 1e9) == f["busy_ns"]
    nms = manifest.load_module("layer_metrics", "nms_roofline")
    spent = trace_reduce.kernel_seconds(r, nms.kernel_pattern(12000))
    assert spent == pytest.approx(want["nms_s"], rel=1e-9)
    assert any(n.startswith("%nms_sweep") for n in r["by_name"])
