"""Every idle stretch of the traced interval laid to a cause
(benchmarks/trace_idle.py): launch lag, host idle by the loop's phase, or
unlaid; exact on hand-made tuples, adding up to ``device.idle_share.train``,
silent where there is nothing to read, and held to the program's names."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, trace_idle, trace_reduce, trace_scopes as ts  # noqa: E402

MS = 1_000_000
BM = manifest.load()
NAMES = ("loop.launch_lag_ms.train", "loop.host_idle_ms.train",
         "loop.unlaid_idle_share.train", "loop.host_ms.train")
TRACE_READERS = NAMES[:3]


def _reader(name):
    return manifest.load_module("layer_metrics", name)


def _by_hand(late_last=False, devices=1):
    """A capture of 200 ms: a lead-in step (enqueued 0-5, run 6-30), then
    inside ``bench.traced`` three steps. B is handed over at 55 and starts
    at 100 while the loop blocks in ``train.key``: 80-100 is launch lag. The
    chip then idles 130-140 under ``train.snapshot`` and 141-155 under a
    collection nested in ``train.observe``: host idle. C's program has an
    idle stretch of its own, 170-175, while the loop sits in
    ``bench.drain``: unlaid. ``late_last``: C starts 10 ms late instead,
    handed over at 155 with the loop already in the drain at 160."""
    shift = 10 * MS if late_last else 0
    ops = [("%fusion.0 = f32[8] x", 6 * MS, 24 * MS, ""),
           ("%fusion.1 = f32[8] x", 45 * MS, 35 * MS, ""),
           ("%fusion.1 = f32[8] x", 100 * MS, 30 * MS, ""),
           ("%fold.1 = u32[2] k", 140 * MS, 1 * MS, ""),
           ("%fusion.1 = f32[8] x", 155 * MS + shift, 15 * MS, ""),
           ("%fusion.2 = f32[8] x", 175 * MS + shift, 10 * MS, "")]
    runs = [("jit_step(1)", 6 * MS, 24 * MS), ("jit_step(1)", 45 * MS, 35 * MS),
            ("jit_step(1)", 100 * MS, 30 * MS),
            ("jit_fold_in(2)", 140 * MS, 1 * MS),
            ("jit_step(1)", 155 * MS + shift, 30 * MS)]
    host = [("train", 0, 8 * MS), ("train.enqueue", 0, 5 * MS),
            ("bench.traced", 40 * MS, 160 * MS),
            ("train", 40 * MS, 10 * MS), ("train.enqueue", 42 * MS, 3 * MS),
            ("train", 50 * MS, 52 * MS), ("train.enqueue", 52 * MS, 3 * MS),
            ("train.key", 56 * MS, 44 * MS),
            ("bench.step_dispatch", 102 * MS, 58 * MS),
            ("train", 102 * MS, 58 * MS),
            ("train.snapshot", 120 * MS, 20 * MS),
            ("train.observe", 141 * MS, 12 * MS),
            ("train.gc", 142 * MS, 10 * MS),
            ("train.enqueue", 153 * MS, 2 * MS),
            ("bench.drain", 160 * MS, 40 * MS)]
    names = [f"/device:TPU:{d}" for d in range(devices)]
    return ({d: list(ops) for d in names}, host, {d: list(runs) for d in names})


def test_split_by_hand():
    s = trace_idle.split(*_by_hand())
    assert s["window_ns"] == 140 * MS and s["step_runs"] == 3
    assert s["idle_ns"] == 49 * MS
    # handed over at 55, started at 100: the chip's 80-100 is lag, though
    # the loop sat in train.key, a host span, all that time
    assert s["lag_ns"] == 20 * MS
    assert s["host_ns"] == 24 * MS and s["unlaid_ns"] == 5 * MS
    assert s["by_span"] == {"train.snapshot": 10 * MS, "train.gc": 14 * MS,
                            trace_idle.HARNESS_OWN: 5 * MS}
    assert s["lag_ns"] + s["host_ns"] + s["unlaid_ns"] == s["idle_ns"]
    # the accepted reading lays the lag to the host
    assert ts.fold(*_by_hand())["host_bound_ns"] == 44 * MS


def test_a_late_start_under_the_drain_is_lag_not_the_harness():
    s = trace_idle.split(*_by_hand(late_last=True))
    assert s["window_ns"] == 150 * MS
    # C handed over at 155 starts at 165: the gap 141-165 is cut in two
    assert s["lag_ns"] == (20 + 10) * MS and s["lag_longest_ns"] == 20 * MS
    assert s["by_span"] == {"train.snapshot": 10 * MS, "train.gc": 14 * MS,
                            trace_idle.HARNESS_OWN: 5 * MS}


def test_enqueues_and_executions_that_do_not_pair_give_none():
    dev, host, modules = _by_hand()
    for d in modules:
        modules[d] = modules[d][1:]   # the lead-in's execution is missing
    s = trace_idle.split(dev, host, modules)
    assert s["lag_ns"] is None
    run = {"trace": {"busy_s": 1}, "work": "/nowhere",
           trace_idle.CACHE_KEY: s}
    for name in TRACE_READERS:
        assert _reader(name).read(run) is None, name


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("late_last", [False, True])
def test_the_three_add_up_to_the_idle_share(devices, late_last):
    """(lag + host idle) a step x steps / interval + the unlaid share is
    ``device.idle_share.train``, read as the harness reads it."""
    dev, host, modules = _by_hand(late_last, devices)
    run = {"trace": trace_reduce.reduce_events(
        {d: [e[:3] for e in evs] for d, evs in dev.items()},
        [h for h in host if h[0].startswith("bench.")], modules=modules),
        "work": "/nowhere", trace_idle.CACHE_KEY: trace_idle.split(
            dev, host, modules)}
    lag, host_idle, unlaid = (_reader(n).read(run) for n in TRACE_READERS)
    steps, window_ms = run["trace"]["step_runs"], run["trace"]["window_s"] * 1e3
    idle = _reader("device.idle_share.train").read(run)
    assert lag == pytest.approx((30 if late_last else 20) / 3)
    assert host_idle == pytest.approx(8.0)
    assert 100 * (lag + host_idle) * steps / window_ms + unlaid == (
        pytest.approx(idle))


def test_a_program_without_loop_spans_reads_as_nothing():
    """A checkout whose program emits no ``train.*`` span, and the CPU
    rehearsal (no device trace): every reader is silent and none raises."""
    dev, host, modules = _by_hand()
    bare = [h for h in host if h[0].startswith("bench.")]
    assert trace_idle.split(dev, bare, modules) is None
    assert trace_idle.split({}, host, modules) is None
    run = {"trace": {"busy_s": 1}, "work": "/nowhere",
           trace_idle.CACHE_KEY: None, "events": []}
    for name in NAMES:
        assert _reader(name).read(run) is None, name
        assert _reader(name).read({"trace": None, "work": "/nowhere"}) is None
        assert _reader(name).read({"events": [{"step_ms": 1.0}]}) is None


def test_host_ms_reads_the_step_timers_events_only():
    """The window's events mix StepTimer's (``step_ms`` and the phases) and
    Speedometer's (``samples_per_sec``); the first of them is the iteration
    whose batch request waited for the profiler to stop and the window to
    open (left out); a parent's carry no ``key_ms``."""
    class Loader:
        t_open_mono = 1000.0

    timer = [{"type": "step", "t_mono": 1000.05, "step_ms": 107000.0,
              "data_wait_ms": 106910.0, "key_ms": 80.0, "enqueue_ms": 6.0},
             {"type": "step", "t_mono": 1000.15, "step_ms": 90.0,
              "data_wait_ms": 0.1, "key_ms": 80.0, "place_ms": 3.0,
              "enqueue_ms": 6.0},
             {"type": "step", "t_mono": 1000.25, "step_ms": 100.0,
              "data_wait_ms": 0.1, "key_ms": 84.0, "place_ms": 3.0,
              "enqueue_ms": 6.0, "snapshot_ms": 4.0, "gc_ms": 1.5}]
    speedometer = [{"type": "step", "t_mono": 1000.2, "samples_per_sec": 88.0,
                    "window": 20}]
    read = _reader("loop.host_ms.train").read
    run = {"trace": {"busy_s": 1}, "events": speedometer + timer,
           "loader": Loader()}
    assert read(run) == pytest.approx((4.0 + 10.0) / 2)
    parent = [{k: v for k, v in e.items() if k != "key_ms"} for e in timer]
    assert read(dict(run, events=speedometer + parent)) is None
    assert read(dict(run, trace=None)) is None   # the rehearsal


def test_three_readers_parse_the_trace_once(monkeypatch, tmp_path):
    trace = tmp_path / "trace" / "plugins"
    trace.mkdir(parents=True)
    (trace / "vm.xplane.pb").write_bytes(b"")
    calls = []

    def read_xplane(path, chips):
        calls.append((path, chips))
        return _by_hand(devices=chips)

    monkeypatch.setattr(ts, "read_xplane", read_xplane)
    run = {"trace": {"busy_s": 1}, "work": str(tmp_path), "chips": 4}
    vals = [_reader(n).read(run) for n in TRACE_READERS]
    assert vals == pytest.approx([20 / 3, 8.0, 100 * 5 / 140])
    assert calls == [(str(trace / "vm.xplane.pb"), 4)]


def test_the_copies_are_the_programs_names():
    """The reader's names against the program's (``test_bm_trace_scopes``'
    check of ``LOOP_SPANS`` cannot be extended: a benchmark file this PR
    may not edit)."""
    from mx_rcnn_tpu.obs import timing

    assert trace_idle.ENQUEUE_SPAN in timing.LOOP_SPANS
    assert {"train.observe", "train.snapshot"} <= set(timing.LOOP_SPANS)
    assert all(ts.LOOP_SPAN.match(n)
               for n in timing.LOOP_SPANS + (timing.GC_SPAN,))
    assert not ts.HARNESS_SPAN.match(timing.GC_SPAN)


def the_four_hold(bm):
    """The four in their order among ``per_layer``, wherever they stand in
    it, each listing every cell (``test_bm_manifest_room.py`` holds a
    manifest with a cell and a metric appended to it)."""
    cells = [w["name"] for w in bm["workloads"]]
    got = {m["name"]: m for m in bm["per_layer"] if m["name"] in NAMES}
    assert list(got) == list(NAMES)
    for m in got.values():
        assert m["workloads"] == cells and m["layer"] == "train loop"
        assert m["moves"] == "train_img_per_s_chip"
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert not m["name"].startswith(("stage.", "loop.place",
                                         "loop.enqueue", "loop.host_bound"))


def test_the_manifest_lists_the_four_for_every_cell():
    the_four_hold(BM)
