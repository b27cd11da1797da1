"""grafttower (obs/fleet.py) gates — fleet-scope observability.

Two layers, same split as test_quorum.py:

- **fold units** (tier-1): hand-built two-host streams with deliberate
  wall-clock skew pin the merge/alignment contract; heartbeat cadence +
  stale (hung) detection; barrier-event emission and wait attribution;
  the ``--fleet`` CLI fold; torn-line byte-offset warnings.
- **ONE trainer gate** (``slow``): a real 2-sim-host run where chaos
  ``slow_step_at`` drags one host's every dispatch — after an injected
  +300 s wall skew on that host's stream, the fleet report must still
  merge the timelines, rank the injected host straggler, attribute the
  barrier wait to it, and flag it hung once ``host_die_at_step``
  SIGKILLs it (stale heartbeat trail, no final beat).
"""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from mx_rcnn_tpu.obs import open_event_log, report
from mx_rcnn_tpu.obs.fleet import fleet_summary, merge_streams, render_fleet
from mx_rcnn_tpu.obs.watchdog import StallWatchdog
from mx_rcnn_tpu.resilience import FileKVStore, Quorum

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVER = os.path.join(REPO_ROOT, "tests", "_resilience_driver.py")

#: injected wall-clock offset for the skew fixtures/gate (seconds) —
#: deliberately huge so a fold that trusts t_wall cannot pass by luck.
SKEW = 300.0


# ---------------------------------------------------------------------------
# stream builders (hand-built two-host fixtures)
# ---------------------------------------------------------------------------

def _rec(type_, host, t_true, *, wall_skew=0.0, mono_origin=0.0, **fields):
    """One event as host ``host``'s EventLog would stamp it at true time
    ``t_true``: its wall clock reads true + its skew; its monotonic
    clock has an arbitrary per-process origin."""
    rec = {"type": type_, "t_wall": t_true + wall_skew,
           "t_mono": (t_true - 1000.0) + mono_origin,
           "process": host, "step": fields.pop("step", 0)}
    rec.update(fields)
    return rec


def _h0(type_, t_true, **fields):
    return _rec(type_, 0, t_true, wall_skew=0.0, mono_origin=50.0,
                **fields)


def _h1(type_, t_true, **fields):
    # host 1's wall clock runs SKEW seconds ahead (an NTP step the fleet
    # never noticed); its monotonic origin is unrelated to host 0's.
    return _rec(type_, 1, t_true, wall_skew=SKEW, mono_origin=7000.0,
                **fields)


def _two_host_streams(hung=False):
    """Two synthetic host streams over ~25 s of true time: host 1 runs a
    +0.25 s per-dispatch tail (the straggler) and, when ``hung``, is
    killed at true t=1013 — its heartbeat trail just stops, no final
    beat, while host 0 lives on. The epoch/1 barrier (host 0 waited
    1.0 s for host 1; released within one poll of the same true instant)
    is always present: it is the residual-skew correction signal, and in
    the real run it fires before any kill too."""
    h0 = [_h0("run_meta", 1000.2, batch_size=1)]
    h1 = [_h1("run_meta", 1000.7)]
    for i in range(5):
        t = 1002.0 + 2.0 * i
        h0.append(_h0("step", t, step_ms=400.0, data_wait_ms=5.0,
                      epoch=0, batch=i + 1, step=i + 1))
        h1.append(_h1("step", t + 0.25, step_ms=650.0,
                      data_wait_ms=5.0, epoch=0, batch=i + 1,
                      step=i + 1))
    h0.append(_h0("barrier", 1012.0, name="epoch/1", wait_s=1.0,
                  arrived=[0, 1], absent=[], order=[0, 1], last=1,
                  timed_out=False))
    h1.append(_h1("barrier", 1012.02, name="epoch/1", wait_s=0.02,
                  arrived=[0, 1], absent=[], order=[0, 1], last=1,
                  timed_out=False))
    for t in (1001.0, 1006.0, 1011.0):
        h0.append(_h0("heartbeat", t, every_s=5.0, beat_age_s=0.2,
                      stalls=0, final=False))
    for t in (1001.5, 1006.5, 1011.5):
        h1.append(_h1("heartbeat", t, every_s=5.0, beat_age_s=0.2,
                      stalls=0, final=False))
    if not hung:
        h0.append(_h0("heartbeat", 1013.0, every_s=5.0, beat_age_s=0.2,
                      stalls=0, final=True))
        h1.append(_h1("heartbeat", 1013.1, every_s=5.0, beat_age_s=0.2,
                      stalls=0, final=True))
    else:
        # host 1 died at 1013 (trail above is its last word); host 0
        # lived on alone waiting at the next barrier — the fleet clock
        # keeps ticking past host 1's death, then host 0 shuts down
        # cleanly with its final beat.
        for t in (1016.0, 1021.0, 1026.0):
            h0.append(_h0("heartbeat", t, every_s=5.0, beat_age_s=3.0,
                          stalls=0, final=False))
        h0.append(_h0("heartbeat", 1027.0, every_s=5.0, beat_age_s=3.0,
                      stalls=0, final=True))
    return {0: h0, 1: h1}


# ---------------------------------------------------------------------------
# merge / skew alignment
# ---------------------------------------------------------------------------

def test_merge_aligns_injected_wall_skew():
    """The +300 s wall skew must cancel: barrier releases land within a
    poll interval on the merged timeline, and per-dispatch interleaving
    follows TRUE time (host 1's completion right after host 0's), not
    the skewed wall stamps."""
    merged = merge_streams(_two_host_streams())
    assert [e["t_fleet"] for e in merged] == sorted(
        e["t_fleet"] for e in merged)
    bars = {e["process"]: e["t_fleet"] for e in merged
            if e["type"] == "barrier"}
    assert abs(bars[0] - bars[1]) < 0.5, bars  # raw skew was 300 s
    # recovered per-host clock offsets ride on the reference run_meta
    meta = next(e for e in merged if "fleet_offsets" in e)
    assert 299.0 < float(meta["fleet_offsets"]["1"]) < 301.0
    # dispatch k: h0 completes, then h1 0.25 s later, BEFORE h0's k+1
    steps = [(e["process"], e["batch"]) for e in merged
             if e["type"] == "step"]
    for i in range(1, 6):
        assert steps.index((1, i)) == steps.index((0, i)) + 1


def test_merge_without_barriers_stands_on_anchors():
    """No shared barriers → no residual correction, but the anchor
    projection alone must already order unskewed streams correctly."""
    streams = _two_host_streams()
    for s in streams.values():
        s[:] = [e for e in s if e["type"] != "barrier"]
        for e in s:
            if e["process"] == 1:
                e["t_wall"] -= SKEW  # honest clocks this time
    merged = merge_streams(streams)
    steps = [(e["process"], e["batch"]) for e in merged
             if e["type"] == "step"]
    for i in range(1, 6):
        assert steps.index((1, i)) == steps.index((0, i)) + 1


# ---------------------------------------------------------------------------
# the fold: straggler ranking, barrier attribution, hung detection
# ---------------------------------------------------------------------------

def test_fleet_summary_ranks_straggler_and_attributes_barrier_wait():
    fs = fleet_summary(_two_host_streams())
    assert fs["straggler"] == 1
    assert fs["straggler_ranking"][0] == 1
    assert (fs["per_host"][1]["lateness_s"]
            > fs["per_host"][0]["lateness_s"])
    # every shared dispatch was 0.25 s apart
    assert 0.2 < fs["skew"]["p50_s"] < 0.3
    # host 0's 1.0 s of barrier wait is OWED by host 1 (it arrived last)
    assert fs["barriers"]["rounds"] == 1
    assert fs["barriers"]["owed_s"][1] == pytest.approx(1.0)
    assert fs["per_host"][1]["barrier_wait_owed_s"] == pytest.approx(1.0)
    assert fs["per_host"][0]["barrier_wait_owed_s"] == 0.0
    assert fs["hung"] == []
    assert fs["per_host"][0]["heartbeat"]["status"] == "clean"
    out = render_fleet(fs)
    assert "straggler table" in out and "straggler:  host 1" in out


def test_fleet_summary_flags_killed_host_as_hung():
    """A SIGKILLed host's trail: fresh-until-death heartbeats, no final
    beat, stream ends while the fleet clock keeps running — that is
    ``hung``, and distinct from host 0's clean final beat."""
    fs = fleet_summary(_two_host_streams(hung=True))
    assert fs["hung"] == [1]
    hb1 = fs["per_host"][1]["heartbeat"]
    assert hb1["status"] == "hung" and not hb1["final"]
    assert hb1["age_s"] > 2.0 * hb1["every_s"]
    assert fs["per_host"][0]["heartbeat"]["status"] == "clean"
    assert "HUNG" in render_fleet(fs)


def test_fleet_summary_without_heartbeats_says_so():
    streams = _two_host_streams()
    for s in streams.values():
        s[:] = [e for e in s if e["type"] != "heartbeat"]
    fs = fleet_summary(streams)
    assert fs["per_host"][0]["heartbeat"]["status"] == "no-heartbeats"
    assert fs["hung"] == []


# ---------------------------------------------------------------------------
# heartbeat emission (obs/watchdog.py)
# ---------------------------------------------------------------------------

def _events(path):
    with open(path, encoding="utf-8") as fh:
        return [json.loads(line) for line in fh if line.strip()]


def test_heartbeat_cadence_and_final_beat(tmp_path):
    """Synchronously driven cadence: first call beats, within-interval
    calls don't, the next interval does; stop() appends exactly one
    final beat (the clean-shutdown marker a SIGKILL can never leave)."""
    log = open_event_log(str(tmp_path), process_index=0)
    wd = StallWatchdog(log, poll_s=60.0, heartbeat_every_s=5.0)
    assert wd.maybe_heartbeat(now=100.0)
    assert not wd.maybe_heartbeat(now=102.0)   # inside the interval
    assert wd.maybe_heartbeat(now=105.5)
    wd.stop()  # thread never started; still emits the final beat
    log.close()
    beats = [e for e in _events(log.path) if e["type"] == "heartbeat"]
    assert len(beats) == 3
    assert [b["final"] for b in beats] == [False, False, True]
    assert all(b["every_s"] == 5.0 and "beat_age_s" in b for b in beats)


def test_heartbeat_rides_watchdog_thread(tmp_path):
    """Thread mode: the beacon shares the watchdog daemon thread and
    beats at its own (shorter) cadence."""
    log = open_event_log(str(tmp_path), process_index=0)
    wd = StallWatchdog(log, poll_s=60.0, heartbeat_every_s=0.02)
    wd.start()
    time.sleep(0.2)
    wd.stop()
    log.close()
    beats = [e for e in _events(log.path) if e["type"] == "heartbeat"]
    assert len(beats) >= 3  # ~10 intervals elapsed; be scheduler-lenient
    assert sum(b["final"] for b in beats) == 1
    assert beats[-1]["final"]


def test_heartbeat_disabled_by_default_knob(tmp_path):
    log = open_event_log(str(tmp_path), process_index=0)
    wd = StallWatchdog(log, poll_s=60.0)  # heartbeat_every_s=0
    assert not wd.maybe_heartbeat(now=100.0)
    wd.stop()
    log.close()
    assert [e for e in _events(log.path)
            if e["type"] == "heartbeat"] == []


# ---------------------------------------------------------------------------
# barrier events (resilience/quorum.py)
# ---------------------------------------------------------------------------

def test_barrier_emits_typed_event_with_order_and_last(tmp_path):
    store = FileKVStore(str(tmp_path / "kv"))
    log0 = open_event_log(str(tmp_path / "obs"), process_index=0)
    log1 = open_event_log(str(tmp_path / "obs"), process_index=1)
    q0 = Quorum(store, 0, 2, timeout_s=5.0, poll_s=0.005, elog=log0)
    q1 = Quorum(store, 1, 2, timeout_s=5.0, poll_s=0.005, elog=log1)
    t = threading.Thread(target=q0.barrier, args=("epoch/1",))
    t.start()
    time.sleep(0.08)  # host 0 sits in the barrier; host 1 arrives last
    q1.barrier("epoch/1")
    t.join(timeout=5.0)
    log0.close()
    log1.close()
    (b0,) = [e for e in _events(log0.path) if e["type"] == "barrier"]
    (b1,) = [e for e in _events(log1.path) if e["type"] == "barrier"]
    for b in (b0, b1):
        assert b["name"] == "epoch/1"
        assert b["arrived"] == [0, 1] and b["absent"] == []
        assert b["order"] == [0, 1] and b["last"] == 1
        assert not b["timed_out"]
    assert b0["wait_s"] > 0.05       # host 0 paid host 1's lateness
    assert b1["wait_s"] < b0["wait_s"]


def test_barrier_timeout_event_marks_absentee(tmp_path):
    store = FileKVStore(str(tmp_path / "kv"))
    log0 = open_event_log(str(tmp_path / "obs"), process_index=0)
    q0 = Quorum(store, 0, 2, timeout_s=0.1, poll_s=0.005, elog=log0)
    arrived = q0.barrier("save/1")
    assert arrived == {0}
    log0.close()
    (b,) = [e for e in _events(log0.path) if e["type"] == "barrier"]
    assert b["timed_out"] and b["absent"] == [1] and b["last"] == 0


def test_barrier_tolerates_legacy_stampless_arrivals(tmp_path):
    """A pre-grafttower writer published "1", not a wall stamp: the
    event still emits — that host just drops out of the order."""
    store = FileKVStore(str(tmp_path / "kv"))
    store.set("epoch/1/arrive/0", "1")  # legacy arrival value
    log1 = open_event_log(str(tmp_path / "obs"), process_index=1)
    q1 = Quorum(store, 1, 2, timeout_s=5.0, poll_s=0.005, elog=log1)
    q1.barrier("epoch/1")
    log1.close()
    (b,) = [e for e in _events(log1.path) if e["type"] == "barrier"]
    assert b["arrived"] == [0, 1]
    assert b["order"] == [1] and b["last"] == 1


# ---------------------------------------------------------------------------
# stream discovery + torn-line warnings (obs/report.py satellites)
# ---------------------------------------------------------------------------

def test_load_events_folds_all_per_host_streams(tmp_path):
    d = str(tmp_path / "obs")
    for idx in (0, 1, 2):
        log = open_event_log(d, process_index=idx)
        log.emit("heal", downtime_s=float(idx))
        log.close()
    events = report.load_events(d)
    assert {e["process"] for e in events} == {0, 1, 2}
    assert report.summarize(events)["heals"]["count"] == 3


def test_load_events_still_reads_legacy_stream_names(tmp_path):
    d = tmp_path / "obs"
    d.mkdir()
    for name, host in (("events.jsonl", 0), ("events.1.jsonl", 1)):
        (d / name).write_text(json.dumps(
            {"type": "heal", "t_wall": 1.0, "t_mono": 1.0,
             "process": host, "step": 0}) + "\n")
    events = report.load_events(str(d))
    assert {e["process"] for e in events} == {0, 1}


def test_torn_line_warning_names_file_and_byte_offset(tmp_path, capsys):
    path = tmp_path / "events_p1.jsonl"
    good = json.dumps({"type": "heal", "t_wall": 1.0, "t_mono": 1.0,
                       "process": 1, "step": 0}) + "\n"
    path.write_text(good + '{"type": "step", "t_wall": 2.')  # torn tail
    records = report.load_jsonl_tolerant(str(path))
    assert len(records) == 1
    err = capsys.readouterr().err
    assert str(path) in err
    assert f"byte {len(good.encode())}" in err


# ---------------------------------------------------------------------------
# the --fleet CLI fold
# ---------------------------------------------------------------------------

def _write_streams(d, streams):
    os.makedirs(d, exist_ok=True)
    for idx, recs in streams.items():
        with open(os.path.join(d, f"events_p{idx}.jsonl"), "w",
                  encoding="utf-8") as fh:
            for r in recs:
                fh.write(json.dumps(r) + "\n")


def test_report_fleet_cli_prints_straggler_table(tmp_path, capsys):
    d = str(tmp_path / "obs")
    _write_streams(d, _two_host_streams())
    blob_path = str(tmp_path / "fleet.json")
    rc = report.main(["--fleet", d, "--json", blob_path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "grafttower fleet report" in out
    assert "straggler table" in out and "straggler:  host 1" in out
    with open(blob_path, encoding="utf-8") as fh:
        blob = json.load(fh)
    assert blob["fleet_straggler"] == 1
    assert blob["fleet_barrier_wait_s"] == pytest.approx(1.02)
    assert 0.2 < blob["fleet_skew_p50_s"] < 0.3
    assert blob["detail"]["fleet"]["barriers"]["rounds"] == 1


def test_report_fleet_cli_rejects_non_directory(tmp_path, capsys):
    path = tmp_path / "events_p0.jsonl"
    path.write_text("")
    assert report.main(["--fleet", str(path)]) == 2


# ---------------------------------------------------------------------------
# the 2-sim-host trainer gate
# ---------------------------------------------------------------------------

def _spawn_fleet_host(idx, n_hosts, prefix, kv_dir, obs_dir, chaos_env,
                      timeout_s):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MX_RCNN_CHAOS=chaos_env)
    for k in ("MXRCNN_SIM_PROCESS_ID", "MXRCNN_SIM_NUM_PROCESSES"):
        env.pop(k, None)
    cmd = [sys.executable, DRIVER, "--fit", prefix,
           "--sim-host", str(idx), "--sim-hosts", str(n_hosts),
           "--quorum-dir", kv_dir, "--quorum-timeout", str(timeout_s),
           "--obs-dir", obs_dir,
           "--set", "obs.heartbeat_every_s=0.2"]
    return subprocess.Popen(cmd, env=env, cwd=REPO_ROOT,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _skew_stream(path, offset_s):
    """Simulate the NTP skew a real fleet would have: shift every wall
    stamp of one host's (possibly torn — it was SIGKILLed) stream."""
    records = report.load_jsonl_tolerant(path)
    with open(path, "w", encoding="utf-8") as fh:
        for r in records:
            r["t_wall"] = float(r.get("t_wall", 0.0)) + offset_s
            fh.write(json.dumps(r) + "\n")


@pytest.mark.chaos
@pytest.mark.slow
@pytest.mark.compile_heavy
def test_fleet_report_attributes_chaos_slowed_then_killed_host(tmp_path):
    """The ISSUE acceptance run: host 1 drags a chaos-injected 200 ms
    tail on every dispatch of epoch 1 (straggler), then host_die_at_step
    SIGKILLs it at the first dispatch of epoch 2 (hung). Host 0 rides
    the epoch/2 barrier to its deadline and completes alone. After a
    +300 s wall-skew injection on host 1's stream, the fleet fold must
    still (a) merge the timelines, (b) rank host 1 straggler and hand it
    the barrier wait, (c) flag host 1 hung via its stale heartbeat
    trail."""
    prefix = str(tmp_path / "run")
    kv = str(tmp_path / "kv")
    obs = str(tmp_path / "obs")
    chaos_env = "slow_step_at=1:1:200 host_die_at_step=1:4"
    procs = [_spawn_fleet_host(i, 2, prefix, kv, obs, chaos_env,
                               timeout_s=15)
             for i in range(2)]
    outs = [p.communicate(timeout=570)[0] for p in procs]
    assert procs[0].returncode == 0, outs[0][-2000:]
    assert procs[1].returncode == -9, outs[1][-2000:]  # SIGKILLed

    _skew_stream(os.path.join(obs, "events_p1.jsonl"), SKEW)
    hosts = {idx: report.load_jsonl_tolerant(path)
             for idx, path in report.event_streams(obs).items()}
    assert set(hosts) == {0, 1}
    fs = fleet_summary(hosts)

    # (a) merged despite the injected skew: the recovered offset is the
    # injection (to within barrier-release jitter)
    assert 298.0 < float(fs["offsets_s"]["1"]) < 302.0
    # (b) straggler + barrier-wait attribution
    assert fs["straggler"] == 1
    assert (fs["per_host"][1]["lateness_s"]
            > fs["per_host"][0]["lateness_s"])
    assert (fs["barriers"]["owed_s"][1]
            > fs["barriers"]["owed_s"].get(0, 0.0))
    # (c) hung, not slow-and-alive: beats stopped, no final beat, while
    # host 0 closed its stream with one
    assert fs["hung"] == [1]
    assert fs["per_host"][0]["heartbeat"]["status"] == "clean"

    # the CLI smoke the runbook (and script/smoke_resilience.sh) uses
    proc = subprocess.run(
        [sys.executable, "-m", "mx_rcnn_tpu.obs.report", "--fleet", obs],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "straggler table" in proc.stdout
    assert "HUNG" in proc.stdout
