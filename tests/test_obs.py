"""graftscope (mx_rcnn_tpu/obs) gates.

Unit layer: JSONL schema round-trip, StepTimer phase splits on a fake
loader, watchdog stall detection, report aggregation over a synthetic
event log, and the disabled sink's zero-event / zero-drain contract.

Integration layer (tier-1, compile_heavy): a short synthetic
``fit_detector`` run with obs enabled must produce a foldable event
stream — run_meta, per-step timing, epoch, checkpoint — and
``python -m mx_rcnn_tpu.obs.report`` must fold it into throughput +
compile-count fields; with obs disabled no file is written and the
MetricBag lazy-drain discipline is untouched.
"""

import gc
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.obs import (
    EVENT_TYPES,
    EventLog,
    NullEventLog,
    StallWatchdog,
    StepTimer,
    compile_track,
    event_log_path,
    obs_from_config,
    open_event_log,
    run_meta_fields,
)
from mx_rcnn_tpu.obs import report
from mx_rcnn_tpu.train.callback import Speedometer
from mx_rcnn_tpu.train.metrics import MetricBag

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# EventLog
# ---------------------------------------------------------------------------

def test_event_log_schema_roundtrip(tmp_path):
    """One record of every type survives the JSONL round trip with the
    common stamps (wall/monotonic time, process, step) and its payload —
    including numpy scalars/arrays, which must land as plain JSON."""
    log = open_event_log(str(tmp_path), process_index=0)
    for i, t in enumerate(EVENT_TYPES):
        log.set_step(i)
        log.emit(t, payload=i, np_scalar=np.float32(1.5),  # graftlint: disable=obs-event-schema — iterating the schema itself
                 np_arr=np.arange(3))
    log.close()
    events = report.load_events(str(tmp_path))
    assert [e["type"] for e in events] == list(EVENT_TYPES)
    for i, e in enumerate(events):
        assert e["step"] == i and e["process"] == 0
        assert e["t_wall"] > 0 and e["t_mono"] > 0
        assert e["payload"] == i
        assert e["np_scalar"] == 1.5
        assert e["np_arr"] == [0, 1, 2]


def test_event_log_rejects_unknown_type(tmp_path):
    sink = EventLog(str(tmp_path / "e.jsonl"))
    with pytest.raises(ValueError, match="unknown event type"):
        sink.emit("not_a_type")
    sink.close()


def test_event_log_buffers_steps_flushes_critical(tmp_path):
    """step records buffer up to flush_every; stall/crash-class records
    hit disk immediately (they must survive the hang they diagnose)."""
    path = str(tmp_path / "e.jsonl")
    log = EventLog(path, flush_every=64)

    def lines():
        with open(path) as fh:
            return sum(1 for _ in fh)

    log.emit("step", step_ms=1.0)
    log.emit("step", step_ms=1.0)
    assert lines() == 0  # still buffered
    log.emit("stall", waited_s=9.0)
    assert lines() == 3  # critical record flushed the buffer with it
    log.close()
    assert lines() == 3


def test_event_log_path_per_process(tmp_path):
    # grafttower naming: every host (process 0 included) is a peer
    # stream of the fleet merge.
    assert event_log_path(str(tmp_path)).endswith("events_p0.jsonl")
    assert event_log_path(str(tmp_path), 3).endswith("events_p3.jsonl")


def test_run_meta_fields_digest_and_versions():
    cfg = generate_config("resnet50", "synthetic")
    fields = run_meta_fields(cfg, tool="test")
    assert len(fields["config_digest"]) == 16
    assert fields["network"] == "resnet50" and fields["tool"] == "test"
    assert "jax_version" in fields
    # digest tracks the config
    cfg2 = generate_config("resnet50", "synthetic",
                           **{"train.lr": 0.5})
    assert run_meta_fields(cfg2)["config_digest"] != fields["config_digest"]


def test_null_sink_is_inert(tmp_path):
    """The disabled sink touches nothing: no files, no state, and
    obs_from_config returns it without reading obs.dir."""
    n = NullEventLog()
    n.emit("step", step_ms=1.0)
    n.set_step(5)
    n.flush()
    n.close()
    assert n.step == 0 and n.path is None
    cfg = generate_config("resnet50", "synthetic",
                          **{"obs.dir": str(tmp_path / "never")})
    sink = obs_from_config(cfg)
    assert isinstance(sink, NullEventLog)
    assert not (tmp_path / "never").exists()
    assert os.listdir(tmp_path) == []


# ---------------------------------------------------------------------------
# StepTimer
# ---------------------------------------------------------------------------

def _slow_loader(n, wait_s):
    for i in range(n):
        time.sleep(wait_s)
        yield {"image": np.zeros((1, 4, 4, 3), np.float32), "i": i}


def test_step_timer_phase_split(tmp_path):
    """Each iteration over a fake loader emits a step event whose
    data_wait_ms reflects the loader's sleep, with dispatch_ms marked at
    the dispatched() call and step_ms covering the whole iteration."""
    log = open_event_log(str(tmp_path))
    timer = StepTimer(log)
    seen = []
    for i, batch in timer.iterate(0, _slow_loader(3, wait_s=0.02)):
        seen.append((i, batch["i"]))
        time.sleep(0.01)
        timer.dispatched()
    assert not timer._hooks  # the epoch's collector hook went with it
    log.close()
    assert seen == [(0, 0), (1, 1), (2, 2)]
    steps = [e for e in report.load_events(str(tmp_path))
             if e["type"] == "step"]
    assert len(steps) == 3
    for n, e in enumerate(steps):
        assert e["step"] == n + 1  # global counter advanced per iteration
        assert e["epoch"] == 0 and e["batch"] == n
        assert e["data_wait_ms"] >= 15.0  # the 20 ms loader sleep
        assert e["dispatch_ms"] >= 8.0  # the 10 ms "dispatch"
        assert e["step_ms"] >= e["data_wait_ms"] + e["dispatch_ms"] - 1.0
    assert timer.total_steps == 3


def test_step_timer_disabled_is_passthrough_and_lazy():
    """With the null sink, iterate degrades to enumerate (same objects,
    zero events) and never drains a MetricBag — the lazy-drain
    discipline (train/metrics.py) is untouched, i.e. no per-step host
    sync is added by instrumentation."""
    timer = StepTimer(NullEventLog())
    batches = [{"x": 1}, {"x": 2}]
    bag = MetricBag()
    out = []
    for i, batch in timer.iterate(0, batches):
        bag.update({"TotalLoss": 1.0})
        timer.dispatched()
        out.append((i, batch))
    assert out == [(0, batches[0]), (1, batches[1])]
    assert out[0][1] is batches[0]  # identity: no copies, no wrapping
    assert len(bag._pending) == 2  # nothing forced a drain
    assert timer.total_steps == 0


def _host_events(trace_dir):
    """[(thread, name, start_ns, end_ns, stats)] of the host plane."""
    import glob

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return [(line.name, e.name, e.start_ns, e.start_ns + e.duration_ns,
             dict(e.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def test_step_timer_spans_land_on_the_profilers_clock(tmp_path):
    """Under a profiler trace the host plane holds the loop's phases:
    ``train.next_batch`` between the step annotations, ``train.key`` /
    ``train.place`` / ``train.observe`` / ``train.enqueue`` /
    ``train.metrics`` / ``train.snapshot`` inside them, on one thread,
    a ``train.gc`` collection nested in the phase it interrupted,
    with ``step_num`` rising as the step events' ``step`` does; the same
    clock reads put every phase's ``<phase>_ms`` into the event, and the
    phases tile the iteration."""
    import jax

    from mx_rcnn_tpu.obs.timing import GC_SPAN, LOOP_SPANS, STEP_SPAN

    log = open_event_log(str(tmp_path / "obs"))
    timer = StepTimer(log)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for epoch in range(2):  # the counter runs on across epochs
            for _ in timer.iterate(epoch, _slow_loader(2, wait_s=0.005)):
                with timer.span("train.key"):
                    pass
                with timer.span("train.place"):
                    time.sleep(0.02)
                with timer.span("train.observe"):
                    pass
                with timer.span("train.enqueue"):
                    time.sleep(0.01)
                    timer.dispatched()
                with timer.span("train.metrics"):
                    time.sleep(0.002)
                with timer.span("train.snapshot"):
                    gc.collect(1)
                with timer.span("train.observe"):
                    pass
            with timer.span("train.checkpoint"):
                pass
    finally:
        jax.profiler.stop_trace()
        timer.close()
    log.close()

    ours = [e for e in _host_events(str(tmp_path / "trace"))
            if e[1] == STEP_SPAN or e[1] in LOOP_SPANS]
    assert len({e[0] for e in ours}) == 1  # the loop thread
    steps = sorted((e for e in ours if e[1] == STEP_SPAN),
                   key=lambda e: e[2])
    assert [e[4]["step_num"] for e in steps] == [1, 2, 3, 4]
    inside = {name: [e for e in ours if e[1] == name] for name in LOOP_SPANS}
    # 2 x (2 batches + the exhausted next()), 4 bodies (two observe spans
    # each), 2 epoch ends
    assert [len(inside[n]) for n in LOOP_SPANS] == [6, 4, 4, 4, 4, 2, 8, 4]
    for name in LOOP_SPANS[1:5] + LOOP_SPANS[6:]:
        for e, st in zip(sorted(inside[name], key=lambda e: e[2])[::len(
                inside[name]) // 4], steps):
            assert st[2] <= e[2] and e[3] <= st[3]  # nested in its step
    for e in inside["train.next_batch"] + inside["train.checkpoint"]:
        assert not any(st[2] < e[3] and e[2] < st[3] for st in steps)
    gcs = [e for e in _host_events(str(tmp_path / "trace"))
           if e[1] == GC_SPAN]
    assert sum(any(s[2] <= g[2] and g[3] <= s[3]
                   for s in inside["train.snapshot"]) for g in gcs) >= 4

    events = [e for e in report.load_events(str(tmp_path / "obs"))
              if e["type"] == "step"]
    assert [e["step"] for e in events] == [1, 2, 3, 4]
    phases = ("key_ms", "place_ms", "observe_ms", "enqueue_ms",
              "metrics_ms", "snapshot_ms")
    for e in events:
        assert e["place_ms"] >= 18.0 and e["enqueue_ms"] >= 8.0
        assert e["place_ms"] + e["enqueue_ms"] <= e["dispatch_ms"] + 0.5
        assert e["gc_ms"] > 0 and "checkpoint_ms" not in e
        assert e["gc_ms"] <= e["step_ms"]
        tiled = e["data_wait_ms"] + sum(e[k] for k in phases)
        assert e["step_ms"] - 2.0 <= tiled <= e["step_ms"] + 0.01
    with pytest.raises(ValueError, match="LOOP_SPANS"):
        timer.span("train.lunch")


def test_step_timer_disabled_makes_no_annotation(monkeypatch):
    """With the null sink the loop creates no annotation object at all:
    ``span()`` hands out ONE shared null context whatever the name, and
    ``iterate`` never reaches the profiler."""
    import jax.profiler

    def boom(*a, **k):
        raise AssertionError("an annotation was created with obs off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", boom)
    timer = StepTimer(NullEventLog())
    null = timer.span("train.place")
    assert timer.span("train.enqueue") is null
    assert timer.span("not even a span's name") is null
    for i, batch in timer.iterate(0, [{"x": 1}, {"x": 2}], start=3):
        with timer.span("train.place"), timer.span("train.enqueue"):
            pass
        timer.dispatched()
    assert i == 4 and timer.total_steps == 0


def test_step_timer_disabled_registers_no_collector_hook(monkeypatch):
    """With the null sink no ``gc.callbacks`` hook is registered, even for
    the new phases' names and a collection inside the loop: zero
    annotations, and ``close()`` has nothing to take away."""
    import jax.profiler

    def boom(*a, **k):
        raise AssertionError("an annotation was created with obs off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    before = list(gc.callbacks)
    timer = StepTimer(NullEventLog())
    for _ in timer.iterate(0, [{"x": 1}, {"x": 2}]):
        with timer.span("train.observe"), timer.span("train.snapshot"):
            gc.collect(1)
        assert gc.callbacks == before
    timer.close()
    assert gc.callbacks == before


def test_step_timer_collector_hook_lives_with_the_loop(tmp_path):
    """An enabled timer registers ONE hook while an epoch's ``iterate``
    runs (set-up and the epoch's end pay nothing); ``close()`` removes a
    hook that an error left behind. A young collection counts into
    ``gc_ms`` without an annotation, an older one with one."""
    from mx_rcnn_tpu.obs.timing import GC_SPAN

    log = open_event_log(str(tmp_path))
    timer = StepTimer(log)
    before = list(gc.callbacks)
    for epoch in range(2):
        for _ in timer.iterate(epoch, [{"x": 1}]):
            assert len(gc.callbacks) == len(before) + 1
            with timer.span("train.snapshot"):
                gc.collect(0)
        assert gc.callbacks == before
    left = timer.iterate(2, [{"x": 1}, {"x": 2}])
    next(left)                      # an error leaves the loop here
    healed = timer.iterate(2, [{"x": 3}])
    next(healed)                    # the next session's loop begins
    assert len(gc.callbacks) == len(before) + 2
    timer.close()
    assert gc.callbacks == before
    left.close(), healed.close()    # collected later: nothing to remove
    timer.close()
    assert gc.callbacks == before
    log.close()
    steps = [e for e in report.load_events(str(tmp_path))
             if e["type"] == "step"]
    assert len(steps) >= 2 and all(e["gc_ms"] > 0 for e in steps[:2])
    spent = {}
    from mx_rcnn_tpu.obs.timing import _Collector

    hook = _Collector(spent)
    hook("start", {"generation": 0})
    assert hook.span is None
    hook("stop", {"generation": 0})
    hook("start", {"generation": 2})
    assert hook.span is not None and hook.span.name == GC_SPAN
    hook("stop", {"generation": 2})
    assert hook.span is None and spent[GC_SPAN] > 0


def test_trace_controller_unarmed_takes_no_lock(tmp_path):
    """Nothing armed and no window open: ``before_step`` and
    ``step_completed`` are one compare each and never touch the lock the
    watchdog's thread shares."""
    from mx_rcnn_tpu.obs.profile import TraceController

    class NoLock:
        def __enter__(self):
            raise AssertionError("the lock was taken")

        def __exit__(self, *exc):
            return False

    log = open_event_log(str(tmp_path))
    tc = TraceController(log, str(tmp_path / "trace"), trace_at_step=5,
                         trace_steps=1)
    real, tc._lock = tc._lock, NoLock()
    try:
        for step in range(1, 5):
            tc.before_step(step)      # armed for step 5: not yet
            tc.step_completed(step)   # no window open
        tc._lock = real
        tc.before_step(5)             # opens the armed window, locked
        assert tc._active_dir is not None
        tc.step_completed(5)
        assert tc._active_dir is None and tc._arm_at == 0
        tc._lock = NoLock()
        tc.before_step(6)
        tc.step_completed(6)
    finally:
        tc._lock = real
        tc.close()
        log.close()
    traces = [e for e in report.load_events(str(tmp_path))
              if e["type"] == "trace"]
    assert len(traces) == 1 and traces[0]["reason"] == "step 5"


# ---------------------------------------------------------------------------
# Speedometer emission
# ---------------------------------------------------------------------------

def test_speedometer_logs_and_emits(tmp_path):
    log = open_event_log(str(tmp_path))
    meter = Speedometer(batch_size=2, frequent=2, event_log=log)
    bag = MetricBag()
    bag.update({"TotalLoss": 1.0})
    assert meter(0, 0, bag) is None
    speed = meter(0, 1, bag)
    assert speed is not None and speed > 0
    log.close()
    windows = [e for e in report.load_events(str(tmp_path))
               if e["type"] == "step" and "samples_per_sec" in e]
    assert len(windows) == 1
    assert windows[0]["window"] == 2
    assert windows[0]["samples_per_sec"] == pytest.approx(speed, rel=1e-3)


# ---------------------------------------------------------------------------
# StallWatchdog
# ---------------------------------------------------------------------------

def test_watchdog_fires_on_stall_with_stacks(tmp_path):
    """An artificially stalled step trips the watchdog exactly once per
    episode, and the stall event carries this (main) thread's stack."""
    log = open_event_log(str(tmp_path))
    wd = StallWatchdog(log, stall_factor=2.0, min_stall_s=0.05, poll_s=10)
    for _ in range(5):
        wd.beat(0.01)
    assert wd.threshold_s() == pytest.approx(0.05)  # min_stall_s floor
    now = time.monotonic()
    assert not wd.check(now)  # fresh heartbeat: no stall
    assert wd.check(now + 1.0)  # stalled
    assert not wd.check(now + 2.0)  # one event per episode
    wd.beat(0.01)  # heartbeat re-arms the tripwire
    assert wd.check(time.monotonic() + 1.0)
    log.close()
    stalls = [e for e in report.load_events(str(tmp_path))
              if e["type"] == "stall"]
    assert len(stalls) == 2
    assert stalls[0]["waited_s"] >= 0.9
    assert stalls[0]["median_step_s"] == pytest.approx(0.01)
    assert any("test_obs" in stack or "MainThread" in name
               for name, stack in stalls[0]["stacks"].items())


def test_watchdog_threshold_scales_with_median():
    wd = StallWatchdog(NullEventLog(), stall_factor=10.0, min_stall_s=1.0)
    # pre-first-step: cold-start grace (compiles are slow, not stalls)
    assert wd.threshold_s() == pytest.approx(
        StallWatchdog.COLD_GRACE * 1.0)
    for d in (0.2, 0.3, 0.4):
        wd.beat(d)
    assert wd.threshold_s() == pytest.approx(3.0)  # 10 x median(0.3)


def test_watchdog_thread_emits(tmp_path):
    """The real daemon thread path: a stalled 'run' produces a stall
    event on disk without any synchronous check() calls."""
    log = open_event_log(str(tmp_path))
    wd = StallWatchdog(log, stall_factor=2.0, min_stall_s=0.05,
                       poll_s=0.02)
    wd.beat(0.01)  # one completed step arms the steady-state threshold
    wd.start()
    try:
        time.sleep(0.3)  # no further beats: stalled from here on
    finally:
        wd.stop()
    log.close()
    assert any(e["type"] == "stall"
               for e in report.load_events(str(tmp_path)))


# ---------------------------------------------------------------------------
# graftprof: cost accounting (obs/costs.py)
# ---------------------------------------------------------------------------

def test_executable_costs_vs_hand_count(tmp_path):
    """FLOP/HBM extraction on a tiny jitted matmul vs hand-counted
    values: a 64x64 @ 64x64 product is 2·64³ FLOPs (+ the sum's
    epsilon), one 16 KiB input, one f32 scalar output."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.obs import costs

    compiled = jax.jit(lambda x: (x @ x).sum()).lower(
        jnp.ones((64, 64), jnp.float32)).compile()
    c = costs.executable_costs(compiled)
    hand = 2 * 64 ** 3
    assert abs(c["flops"] - hand) / hand < 0.05
    assert c["hbm_args"] == 64 * 64 * 4
    assert c["hbm_output"] == 4
    assert c["hbm_bytes"] >= c["hbm_args"] + c["hbm_output"]
    assert c["bytes_accessed"] > 0

    # mfu_from: analytic flops x measured rate / peak (and the guards)
    assert costs.mfu_from(hand, 100.0, peak_flops=float(hand) * 1000
                          ) == pytest.approx(0.1)
    assert costs.mfu_from(None, 100.0, peak_flops=1e12) is None
    assert costs.mfu_from(hand, 0.0, peak_flops=1e12) is None
    assert costs.mfu_from(hand, 100.0, peak_flops=None) is None


def test_peaks_table_raises_on_unknown_device_or_dtype():
    """One table keyed by device_kind and dtype, published figures only:
    a device that is not in it, or a dtype with no published peak (f32
    on the v5e), is an error — never the bf16 default."""
    from mx_rcnn_tpu.obs import costs

    assert costs.peak_flops_for("TPU v5 lite", "bf16") == 197e12
    assert costs.peak_flops_for("TPU v5 lite", "bfloat16") == 197e12
    assert costs.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(costs.UnknownPeakError, match="cpu"):
        costs.peak_flops_for("cpu", "bf16")
    with pytest.raises(costs.UnknownPeakError, match="float32"):
        costs.peak_flops_for("TPU v5 lite", "f32")


def test_batch_pad_waste_fraction():
    """real pixels ÷ canvas pixels from im_info — plain batches and
    batches with more leading axes (packed planes); malformed batches
    degrade to {}."""
    from mx_rcnn_tpu.obs import costs

    batch = {"image": np.zeros((2, 64, 64, 3), np.float32),
             "im_info": np.asarray([[32, 64, 1.0], [64, 64, 1.0]],
                                   np.float32)}
    pw = costs.batch_pad_waste(batch)
    assert pw["canvas"] == [64, 64]
    assert pw["pad_waste"] == pytest.approx(
        1 - (32 * 64 + 64 * 64) / (2 * 64 * 64))
    # more leading axes (P, I, ...) flatten
    stacked = {"image": np.zeros((2, 2, 64, 64, 3), np.float32),
               "im_info": np.tile(batch["im_info"], (2, 1, 1))}
    assert costs.batch_pad_waste(stacked)["pad_waste"] == pw["pad_waste"]
    assert costs.step_fields(batch) == {"canvas": [64, 64],
                                        "pad_waste": pw["pad_waste"]}
    assert costs.batch_pad_waste({"no": "contract"}) == {}
    assert costs.step_fields({"no": "contract"}) == {}


def test_loader_pad_waste_counters():
    """AnchorLoader accumulates real/canvas pixel counters per batch
    (from worker threads — the graftprof canvas-packing baseline):
    128px synthetic images on a 256-pad canvas waste exactly 75%."""
    from mx_rcnn_tpu.config import generate_config
    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset
    from mx_rcnn_tpu.data.loader import AnchorLoader

    cfg = generate_config("resnet50", "synthetic", **{
        "image.pad_shape": (256, 256), "image.scales": ((128, 256),),
        "train.batch_images": 2, "train.flip": False,
        "train.max_gt_boxes": 4})
    ds = SyntheticDataset("train", num_images=4, image_size=128,
                          max_objects=2, min_size_frac=4, max_size_frac=2)
    loader = AnchorLoader(ds.gt_roidb(), cfg, num_shards=1)
    assert loader.pad_waste_stats() is None  # nothing assembled yet
    with loader:
        n = sum(1 for _ in loader)
    stats = loader.pad_waste_stats()
    assert n == 2 and stats["batches"] == 2
    assert stats["real_px"] == 4 * 128 * 128
    assert stats["canvas_px"] == 4 * 256 * 256
    assert stats["pad_waste"] == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# graftprof: trace windows (obs/profile.py)
# ---------------------------------------------------------------------------

def test_trace_controller_step_window(tmp_path):
    """obs.trace_at_step semantics: the window opens before step K,
    closes trace_steps completed steps later, and the closed window
    emits a `trace` event with the per-stage summary: the scope paths
    come out of the compiled program the capture carries, forward and
    backward ops alike."""
    import jax
    import jax.numpy as jnp

    from mx_rcnn_tpu.obs.profile import (STAGES, TraceController, stage,
                                         summarize_trace)

    log = open_event_log(str(tmp_path))
    tc = TraceController(log, str(tmp_path / "trace"),
                         trace_at_step=2, trace_steps=1)
    def two_stages(x):
        with stage("backbone"):
            y = x @ x
        with stage("update"):
            return jnp.tanh(y) @ y

    f = jax.jit(jax.grad(lambda x: two_stages(x).sum()))
    x = jnp.ones((64, 64))
    for step in range(1, 5):
        tc.before_step(step)  # window opens BEFORE step K, so K=1 works
        f(x).block_until_ready()
        tc.step_completed(step)
    tc.close()
    log.close()
    traces = [e for e in report.load_events(str(tmp_path))
              if e["type"] == "trace"]
    assert len(traces) == 1  # one window per arming
    assert traces[0]["reason"] == "step 2"
    summary = traces[0]["summary"]
    assert summary is not None and summary["events"] > 0
    assert set(summary) == {"file", "events", "total_ms", "stages",
                            "unscoped_ms", "top_ops"}
    assert set(summary["stages"]) == {"backbone", "update"} <= set(STAGES)
    assert min(summary["stages"].values()) > 0
    assert summary["total_ms"] == pytest.approx(
        sum(summary["stages"].values()) + summary["unscoped_ms"], abs=0.01)
    assert summary["top_ops"] and summary["top_ops"][0]["ms"] > 0
    # the summarizer is reusable on the saved dir, and honest about
    # a dir with no capture
    assert summarize_trace(traces[0]["dir"]) is not None
    assert summarize_trace(str(tmp_path / "nowhere")) is None


def test_stage_of_reads_the_innermost_stage_of_a_scope_path():
    from mx_rcnn_tpu.obs.profile import STAGES, stage, stage_of

    assert stage_of("jit(step)/jvp(roi_align)/dot_general") == "roi_align"
    assert stage_of(
        "jit(step)/transpose(jvp(roi_align))/dot_general") == "roi_align"
    assert stage_of("jit(step)/transpose(jvp(FPNFasterRCNN.extract))/"
                    "neck/neck/select_and_scatter_add") == "neck"
    assert stage_of("jit(step)/jvp(proposal)/shard_map/nms_sweep") == \
        "proposal"
    assert stage_of("jit(step)/jvp(backbone)/neck/conv") == "neck"
    # a stage is a whole segment: neither a flax method's name nor a
    # primitive's is one
    assert stage_of("jit(step)/jvp(FasterRCNN.box_head)/dot_general") is None
    assert stage_of("jit(step)/jvp()/dynamic_update_slice") is None
    assert stage_of("jit(step)/dynamic-update-slice.3") is None
    assert stage_of("") is None
    assert len(set(STAGES)) == len(STAGES) == 11
    with pytest.raises(ValueError, match="STAGES"):
        stage("backward")


def test_branch_of_reads_the_mask_branchs_scopes_beside_the_stages():
    """``BRANCH_STAGES`` is a second closed list: ``stage`` opens its
    scopes, ``branch_of`` reads them, and ``stage_of`` sees a path that
    holds one exactly as it did before the branch had names - the pooling
    as ``roi_align`` (``pyramid_roi_align``'s scope inside ``mask_align``),
    the rest as no stage."""
    from mx_rcnn_tpu.obs.profile import (BRANCH_STAGES, STAGES, branch_of,
                                         stage, stage_of)

    assert BRANCH_STAGES == ("mask_align", "mask_head", "mask_targets",
                             "mask_loss")
    assert not set(BRANCH_STAGES) & set(STAGES)
    pooled = "jit(step)/transpose(jvp(mask_align))/roi_align/dot_general"
    assert (branch_of(pooled), stage_of(pooled)) == ("mask_align",
                                                     "roi_align")
    head = ("jit(step)/jvp(mask_head)/FPNFasterRCNN.mask_forward/mask_head/"
            "mask_conv0/conv_general_dilated")
    assert (branch_of(head), stage_of(head)) == ("mask_head", None)
    assert branch_of("jit(step)/jvp(mask_loss)/log1p") == "mask_loss"
    assert branch_of("jit(step)/mask_targets/vmap(dot_general)") == \
        "mask_targets"
    # a whole segment, as a stage is
    assert branch_of("jit(step)/jvp(box_head)/unmask_head2/dot") is None
    assert branch_of("jit(step)/jvp(roi_align)/dot_general") is None
    for name in BRANCH_STAGES:
        stage(name)
    with pytest.raises(ValueError, match="BRANCH_STAGES"):
        stage("mask")


@pytest.mark.compile_heavy
@pytest.mark.parametrize("network", ["resnet50", "resnet50_fpn",
                                     "resnet50_fpn_mask"])
def test_lowered_train_step_carries_every_stage(network):
    """The tiny C4 and FPN train steps, lowered as fit_detector builds
    them, name every stage the family uses in their ops' metadata:
    forward (``jvp(stage)``), backward (``transpose(jvp(stage))``) where
    gradients flow, and the optimizer's ``update``. A refactor that drops
    a scope fails here, not on the chip."""
    import re

    from mx_rcnn_tpu.models.zoo import build_model, forward_train
    from mx_rcnn_tpu.obs.profile import (BRANCH_STAGES, STAGES, branch_of,
                                         stage_of)
    from mx_rcnn_tpu.parallel.mesh import create_mesh
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    cfg = generate_config(network, "synthetic", **{
        "train.rpn_pre_nms_top_n": 256, "train.rpn_post_nms_top_n": 64,
        "train.batch_rois": 32, "train.max_gt_boxes": 8,
        "image.pad_shape": (128, 128)})
    model, mesh = build_model(cfg), create_mesh("1")
    text = make_train_step(
        model, cfg, mesh=mesh, forward_fn=forward_train).lower(
            *abstract_step_inputs(model, cfg, mesh, 1)).as_text(
                debug_info=True)
    paths = set(re.findall(r'loc\("(jit\(step\)[^"]*)"', text))
    forward = {stage_of(p) for p in paths if "transpose(" not in p}
    backward = {stage_of(p) for p in paths if "transpose(jvp(" in p}
    used = set(STAGES) - ({"neck"} if network == "resnet50" else set())
    assert forward - {None} == used
    # no gradient flows through the targets, the proposals, the sampling
    # or the update itself
    assert backward - {None} == used - {
        "rpn_targets", "proposal", "roi_sample", "update"}
    # the update is no part of the differentiated function
    assert any(p.startswith("jit(step)/update/") for p in paths)
    # the mask branch's four scopes, where there is a mask branch: the
    # targets carry no gradient; its pooling is still a `roi_align` to
    # every reader of STAGES
    branch = set(BRANCH_STAGES) if network.endswith("_mask") else set()
    assert {branch_of(p) for p in paths
            if "transpose(" not in p} - {None} == branch
    assert {branch_of(p) for p in paths
            if "transpose(jvp(" in p} - {None} == branch - {"mask_targets"}
    assert {stage_of(p) for p in paths
            if branch_of(p) == "mask_align"} <= {"roi_align"}
    assert {stage_of(p) for p in paths if branch_of(p) in (
        "mask_head", "mask_targets", "mask_loss")} <= {None}


def test_watchdog_stall_arms_trace_window(tmp_path):
    """The stall tripwire opens ONE trace window before dumping stacks;
    the next completed step closes it into a `trace` event."""
    from mx_rcnn_tpu.obs.profile import TraceController

    log = open_event_log(str(tmp_path))
    tc = TraceController(log, str(tmp_path / "trace"))
    wd = StallWatchdog(log, stall_factor=2.0, min_stall_s=0.01,
                       poll_s=10, tracer=tc)
    wd.beat(0.005)
    assert wd.check(time.monotonic() + 1.0)  # stall → window opens
    wd.beat(0.005)
    assert wd.check(time.monotonic() + 1.0)  # second stall: window spent
    tc.step_completed(1)  # heartbeat after recovery closes the window
    tc.close()
    log.close()
    events = report.load_events(str(tmp_path))
    traces = [e for e in events if e["type"] == "trace"]
    assert len(traces) == 1 and traces[0]["reason"] == "stall"
    # ordering: the window opened before the stall record was written
    types = [e["type"] for e in events]
    assert types.index("stall") < types.index("trace")


# ---------------------------------------------------------------------------
# Compile tracking
# ---------------------------------------------------------------------------

def test_compile_tracker_emits_with_shape_signature(tmp_path):
    import jax

    log = open_event_log(str(tmp_path))
    compile_track.activate(log)
    try:
        compile_track.note_batch(
            {"image": np.zeros((1, 6, 11, 3), np.float32)})
        jax.jit(lambda x: x * 2.5 + 1.25)(np.ones((2, 3), np.float32))
    finally:
        compile_track.deactivate()
    log.close()
    compiles = [e for e in report.load_events(str(tmp_path))
                if e["type"] == "compile"]
    backend = [e for e in compiles if e["phase"] == "backend_compile"]
    assert backend, compiles  # tiny kernels are below the persistent-
    # cache threshold, so the jit above really XLA-compiles every run
    assert backend[0]["duration_ms"] > 0
    assert backend[0]["shapes"] == {"image": [1, 6, 11, 3]}
    # jax 0.9 names the program it compiled: the report can tell a
    # train step ("jit(step)") from the helpers around it
    assert backend[0]["fun"] == "jit(<lambda>)"


def test_compile_counter_tallies_backend_compiles():
    """graftprof's per-bench-row compile accounting: the counter sees
    the real XLA compiles in its window (no EventLog needed) and stops
    counting once the window closes."""
    import jax

    with compile_track.count() as cc:
        # tiny unique kernel — below the persistent-cache threshold, so
        # it backend-compiles every run
        jax.jit(lambda x: x * 1.618 + 0.577)(np.ones((3, 5), np.float32))
    assert cc.n >= 1 and cc.seconds > 0
    n_before = cc.n
    jax.jit(lambda x: x * 2.718 - 1.414)(np.ones((3, 5), np.float32))
    assert cc.n == n_before  # closed window: no further tallies


# ---------------------------------------------------------------------------
# report folding
# ---------------------------------------------------------------------------

def _synthetic_events():
    mk = lambda t, **kw: dict(  # noqa: E731 — local record factory
        {"type": t, "t_wall": 0.0, "t_mono": 0.0, "process": 0, "step": 0},
        **kw)
    return [
        mk("run_meta", config_digest="abc", network="resnet50",
           batch_size=2, steps_per_epoch=4),
        mk("compile", phase="backend_compile", duration_ms=500.0,
           shapes=None),
        # graftprof: per-bucket XLA cost accounting — flops chosen so the
        # p50-20ms bucket lands at MFU 0.5 against the stamped peak
        mk("cost", label="train_step", shapes={"image": [2, 8, 8, 3]},
           peak_flops=1e12, flops=1e10, bytes_accessed=5e9,
           hbm_bytes=2e9, hbm_args=1.5e9, hbm_temps=4e8, hbm_output=1e8,
           hbm_alias=0.0),
        mk("step", step=1, epoch=0, batch=0, data_wait_ms=5.0,
           step_ms=20.0, canvas=[8, 8], pad_waste=0.25),
        mk("step", step=2, epoch=0, batch=1, data_wait_ms=1.0,
           step_ms=10.0, canvas=[8, 8], pad_waste=0.15),
        mk("step", step=2, epoch=0, batch=1, samples_per_sec=150.0,
           window=2),
        mk("compile", phase="backend_compile", duration_ms=300.0, step=2,
           shapes={"image": [1, 8, 8, 3]}),
        mk("compile", phase="jaxpr_trace", duration_ms=10.0, step=2),
        mk("step", step=3, epoch=0, batch=2, data_wait_ms=2.0,
           step_ms=10.0, canvas=[8, 8], pad_waste=0.25),
        mk("step", step=4, epoch=0, batch=3, data_wait_ms=2.0,
           step_ms=40.0, canvas=[8, 8], pad_waste=0.35),
        mk("trace", dir="obs/trace/step2", reason="step 2",
           summary={"stages": {"backbone": 9.0}, "unscoped_ms": 1.0,
                    "total_ms": 10.0, "events": 4, "top_ops": []}),
        mk("epoch", epoch=0, metrics={"TotalLoss": 1.0}, pad_waste=0.25),
        mk("checkpoint", epoch=1, prefix="p"),
        mk("eval", images=8, results={"mAP": 0.5}),
        mk("stall", waited_s=9.0),
        mk("crash", step=4, error="RuntimeError('boom')"),
    ]


def test_report_aggregates_synthetic_log():
    s = report.summarize(_synthetic_events())
    assert s["steps"] == 4 and s["epochs"] == 1 and s["checkpoints"] == 1
    # measured Speedometer window preferred over derived throughput
    assert s["throughput"]["img_s"] == 150.0
    assert s["throughput"]["step_ms_p50"] == 20.0
    assert s["throughput"]["step_ms_max"] == 40.0
    assert s["data_wait"]["fraction"] == pytest.approx(10.0 / 80.0)
    # only backend_compile counts as a compile; the one at step>=1 is a
    # steady-state recompile and surfaces its shape signature
    assert s["compile"]["count"] == 2
    assert s["compile"]["total_ms"] == 800.0
    assert s["compile"]["steady_state_count"] == 1
    assert s["compile"]["steady_state_shapes"] == [{"image": [1, 8, 8, 3]}]
    assert s["evals"] == [{"mAP": 0.5}]
    assert s["stalls"] == 1
    assert s["crash"]["step"] == 4
    # graftprof folds: the cost bucket joins the canvas-matched steps
    # (p50 20 ms at 1e10 flops against the stamped 1e12 peak → MFU 0.5)
    assert len(s["cost"]["buckets"]) == 1
    bucket = s["cost"]["buckets"][0]
    assert bucket["canvas"] == [8, 8] and bucket["steps"] == 4
    assert bucket["mfu"] == pytest.approx(0.5)
    assert s["cost"]["mfu"] == pytest.approx(0.5)
    assert s["cost"]["hbm_bytes"] == 2e9
    assert s["pad_waste"] == pytest.approx(0.25)  # p50 of the step events
    assert s["traces"][0]["reason"] == "step 2"
    assert s["traces"][0]["summary"]["stages"]["backbone"] == 9.0
    assert "stages(ms)={'backbone': 9.0} unscoped(ms)=1.0" in \
        report.render(s)
    blob = report.bench_blob(s)
    assert blob["value"] == 150.0 and blob["compile_count"] == 2
    assert blob["stall_count"] == 1
    assert blob["data_wait_fraction"] == pytest.approx(0.125)
    assert blob["mfu"] == pytest.approx(0.5)
    assert blob["hbm_bytes"] == 2e9
    assert blob["pad_waste"] == pytest.approx(0.25)
    assert "mfu 0.5" in report.render(s)
    # derived-throughput fallback when no Speedometer window exists
    s2 = report.summarize([e for e in _synthetic_events()
                           if "samples_per_sec" not in e])
    assert s2["throughput"]["img_s"] == pytest.approx(2 * 1000.0 / 20.0)


def test_report_folds_the_roi_levels_event():
    """A pyramid run's one ``roi_levels`` event reaches the summary and
    the rendered report; a run without it (C4) says nothing."""
    s = report.summarize(_synthetic_events())
    assert s["roi_level_share"] is None and "roi levels" not in report.render(s)
    share = [0.885, 0.0984, 0.0129, 0.0037]
    s = report.summarize(_synthetic_events() + [
        {"type": "roi_levels", "epoch": 0, "dispatch": 1, "share": share}])
    assert s["roi_level_share"] == share
    assert "roi levels: P2 88.5%, P3 9.8%, P4 1.3%, P5 0.4%" in report.render(s)
    # the pooling's form rides on the same event (a log from before PR 36
    # has none, and says nothing of it)
    assert s["roi_pooling"] is None and "roi pooling" not in report.render(s)
    s = report.summarize(_synthetic_events() + [
        {"type": "roi_levels", "epoch": 0, "dispatch": 1, "share": share,
         "canvas": [312, 336], "poolings": 1}])
    assert s["roi_pooling"] == {"canvas": [312, 336], "poolings": 1}
    assert ("roi pooling: 1 a call of each roi, from a canvas of 312x336 "
            "cells") in report.render(s)


def test_report_folds_the_rpn_targets_event():
    """The run's one ``rpn_targets`` event reaches the summary and the
    rendered report; a log without it says nothing."""
    s = report.summarize(_synthetic_events())
    assert s["rpn_targets"] is None and "rpn targets" not in report.render(s)
    s = report.summarize(_synthetic_events() + [
        {"type": "rpn_targets", "epoch": 0, "dispatch": 1, "slots_walked": 5,
         "slots_padded": 100, "kept_pos": 212, "kept_neg": 1836}])
    assert s["rpn_targets"] == {"slots_walked": 5, "slots_padded": 100,
                                "kept_pos": 212, "kept_neg": 1836}
    assert ("rpn targets: walked 5 of 100 gt slots, kept 212 positives and "
            "1836 negatives") in report.render(s)


def test_report_folds_the_mask_rois_event():
    """A mask run's one ``mask_rois`` event reaches the summary and the
    rendered report; a run without the branch says nothing."""
    s = report.summarize(_synthetic_events())
    assert s["mask_rois"] is None and "mask rois" not in report.render(s)
    ev = {"slots": 128, "per_image_min": 9, "per_image_mean": 31.25,
          "per_image_max": 64, "share": [0.9, 0.08, 0.02, 0.0]}
    s = report.summarize(_synthetic_events() + [
        dict(ev, type="mask_rois", epoch=0, dispatch=1)])
    assert s["mask_rois"] == ev
    assert ("mask rois:  9 / 31.25 / 64 (min / mean / max an image) of 128 "
            "branch slots live at the first dispatch; P2 90.0%, P3 8.0%, "
            "P4 2.0%, P5 0.0%") in report.render(s)


def test_report_cli_roundtrip(tmp_path):
    log = open_event_log(str(tmp_path / "run"))
    log.emit("run_meta", batch_size=1)
    log.emit("step", step_ms=10.0, data_wait_ms=1.0)
    log.close()
    out = tmp_path / "blob.json"
    assert report.main([str(tmp_path / "run"), "--json", str(out)]) == 0
    blob = json.loads(out.read_text())
    assert blob["steps"] == 1 and "compile_count" in blob
    # truncated tail line (killed run) is skipped, not fatal
    with open(log.path, "a") as fh:
        fh.write('{"type": "st')
    assert len(report.load_events(str(tmp_path / "run"))) == 2


# ---------------------------------------------------------------------------
# fit_detector integration (tier-1 acceptance gate)
# ---------------------------------------------------------------------------

OBS_TINY = {
    "image.pad_shape": (128, 128),
    "image.scales": ((128, 128),),
    "network.norm": "group",
    "network.freeze_at": 0,
    "network.anchor_scales": (2, 4, 8),
    "train.rpn_pre_nms_top_n": 256,
    "train.rpn_post_nms_top_n": 64,
    "train.batch_rois": 32,
    "train.max_gt_boxes": 8,
    "train.batch_images": 1,
    "train.flip": False,
}


def _tiny_fit(tmp_path, prefix_name, **obs_overrides):
    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset
    from mx_rcnn_tpu.tools.train import fit_detector

    cfg = generate_config("resnet50", "synthetic",
                          **{**OBS_TINY, **obs_overrides})
    ds = SyntheticDataset("train", num_images=4, image_size=128,
                          max_objects=2, min_size_frac=4, max_size_frac=2)
    return fit_detector(cfg, ds.gt_roidb(),
                        prefix=str(tmp_path / prefix_name),
                        end_epoch=1, frequent=2)


@pytest.mark.compile_heavy
def test_fit_detector_obs_enabled_and_report(tmp_path):
    """The acceptance gate: a short synthetic fit with obs enabled writes
    a run_meta + per-step + epoch event stream — including graftprof's
    cost/trace/pad-waste layer — and the report CLI folds it into
    throughput, compile-count, MFU and HBM fields."""
    obs_dir = tmp_path / "obsrun"
    params = _tiny_fit(tmp_path, "ckpt",
                       **{"obs.enabled": True, "obs.dir": str(obs_dir),
                          "obs.trace_at_step": 2, "obs.trace_steps": 1,
                          "obs.health_every": 2})
    assert params is not None
    events = report.load_events(str(obs_dir))
    types = {e["type"] for e in events}
    assert {"run_meta", "step", "epoch", "checkpoint", "cost",
            "trace", "health"} <= types

    # graftpulse rides the same fit: a health reading every 2nd dispatch
    # (4 dispatches -> 2), clean — all-zero nonfinite counts, finite
    # norms, no anomaly
    health = [e for e in events if e["type"] == "health"]
    assert [e["dispatch"] for e in health] == [2, 4]
    for e in health:
        assert all(v == 0 for v in e["nonfinite"].values())
        assert e["grad_norm"] > 0
    assert not [e for e in events if e["type"] == "anomaly"]

    # graftprof: one cost event for the single shape bucket, with real
    # XLA numbers behind the computed MFU
    cost = next(e for e in events if e["type"] == "cost")
    assert cost["flops"] > 0 and cost["hbm_bytes"] > 0
    # a CPU run has no published peak: no MFU denominator is stamped
    assert "peak_flops" not in cost
    assert cost["shapes"]["image"] == [1, 128, 128, 3]
    # the armed window closed and folded (128px images on a 128 canvas:
    # pad_waste is an exact 0)
    trace = next(e for e in events if e["type"] == "trace")
    assert trace["reason"] == "step 2"
    assert trace["summary"] is None or trace["summary"]["events"] > 0

    # ONE rpn_targets event a run, read at the first dispatch: the loop
    # over gt slots walked the image's boxes (1-2 here), not the 8 padded
    labelled = [e for e in events if e["type"] == "rpn_targets"]
    assert len(labelled) == 1 and labelled[0]["dispatch"] == 1
    assert 1 <= labelled[0]["slots_walked"] <= 2
    assert labelled[0]["slots_padded"] == 8
    assert 1 <= labelled[0]["kept_pos"] <= 128
    assert labelled[0]["kept_pos"] + labelled[0]["kept_neg"] <= 256

    meta = next(e for e in events if e["type"] == "run_meta")
    assert meta["batch_size"] == 1 and meta["steps_per_epoch"] == 4
    assert meta["mesh"] == {"data": 1, "model": 1}
    assert len(meta["config_digest"]) == 16

    timed = [e for e in events if e["type"] == "step" and "step_ms" in e]
    assert len(timed) == 4
    for e in timed:
        assert e["data_wait_ms"] >= 0 and e["step_ms"] > 0
        assert "dispatch_ms" in e
        # every phase of the loop body, each iteration (obs/timing.py)
        assert {"key_ms", "place_ms", "observe_ms", "enqueue_ms",
                "metrics_ms", "snapshot_ms"} <= set(e)
        assert e["canvas"] == [128, 128]
        assert e["pad_waste"] == 0.0  # 128px content on a 128 canvas
    # a collection met somewhere in the first dispatch's tracing
    assert "gc_ms" in timed[0]
    epochs = [e for e in events if e["type"] == "epoch"]
    assert epochs[0]["epoch"] == 0
    assert "TotalLoss" in epochs[0]["metrics"]
    assert epochs[0]["pad_waste"] == 0.0  # the loader's counters
    assert epochs[0]["pad_canvas_px"] == 4 * 128 * 128

    # the report CLI (the artifact future BENCH/regression gates consume)
    out = tmp_path / "report.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "mx_rcnn_tpu.obs.report", str(obs_dir),
         "--json", str(out)],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "throughput" in proc.stdout
    blob = json.loads(out.read_text())
    assert blob["steps"] == 4
    assert blob["value"] > 0  # throughput (img/s) from the run
    assert isinstance(blob["compile_count"], int)
    assert blob["detail"]["epochs"] == 1
    assert blob["detail"]["checkpoints"] == 1
    assert blob["stall_count"] == 0
    # graftpulse + env-fingerprint fields ride the bench blob into the
    # perf ledger (anomaly accounting, environment-drift attribution)
    assert blob["anomaly_count"] == 0 and blob["health_checks"] == 2
    assert blob["detail"]["health"]["last"]["grad_norm"] > 0
    assert blob["jax_version"] and blob["jaxlib_version"]
    assert isinstance(blob["git_dirty"], bool)
    # graftprof: the folded blob carries the computed-cost fields the
    # perf ledger gates. MFU is a device metric: a CPU run has no
    # published peak to divide by, so it is None here — never a number
    # from a CPU under that name (tests/test_obs.py's report-fold test
    # covers the arithmetic with a stamped peak).
    assert blob["mfu"] is None
    assert meta["backend"] == "cpu" and meta["device_kind"]
    assert blob["hbm_bytes"] > 0
    assert blob["pad_waste"] == 0.0
    assert blob["detail"]["cost"]["buckets"][0]["canvas"] == [128, 128]


@pytest.mark.compile_heavy
def test_fit_detector_obs_disabled_writes_nothing(tmp_path):
    """Default config: no obs directory, no JSONL — the telemetry layer
    must be invisible when off."""
    params = _tiny_fit(tmp_path, "ckpt2")
    assert params is not None
    assert not (tmp_path / "ckpt2.obs").exists()
    assert not any(p.name.endswith(".jsonl") for p in tmp_path.rglob("*"))
