"""graftfeed gates — input-plane fault tolerance (data/feedguard.py).

Three layers, cheapest first:

- FeedGuard unit gates with injectable sleep/clock: classification,
  retry-under-deadline, deterministic quarantine + persistence/reapply,
  the cap abort;
- loader-level chaos gates on a real AnchorLoader (no jax, no fit):
  transient-IO retry leaves the stream bit-identical, a chaos-killed
  prefetch worker is resurrected at its queue position, a hang raises
  DataStallError within data.wait_deadline_s, close() stays idempotent;
- fit-level chaos gates riding tests/_resilience_driver.py: a corrupt
  record quarantines and the run COMPLETES; SIGTERM mid-quarantine +
  ``--resume auto`` is BIT-exact vs an uninterrupted chaos run;
  a hang crashes with a flight dump whose stall event names
  data-wait; the default quarantine cap aborts loudly.
"""

from __future__ import annotations

import dataclasses
import errno
import json
import os
import time

import numpy as np
import pytest

from mx_rcnn_tpu.config import DataConfig
from mx_rcnn_tpu.data.feedguard import (
    DataStallError,
    DataWorkerError,
    FeedGuard,
    QuarantineExceededError,
    classify_record_error,
)
from mx_rcnn_tpu.data.loader import AnchorLoader
from mx_rcnn_tpu.obs import report
from mx_rcnn_tpu.obs.events import EventLog
from mx_rcnn_tpu.resilience import PreemptionExit, chaos

import _resilience_driver as driver

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _fresh_chaos(monkeypatch):
    """No injection leaks between tests (or in from the outer env)."""
    monkeypatch.delenv(chaos.ENV_VAR, raising=False)
    chaos.reset()
    yield
    chaos.reset()


def _dcfg(**kw):
    return dataclasses.replace(DataConfig(), **kw)


def _roidb(n=6):
    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset

    ds = SyntheticDataset("train", num_images=n, image_size=64,
                          max_objects=1, min_size_frac=3, max_size_frac=2)
    return ds.gt_roidb()


def _batches(loader):
    loader.set_epoch(0)
    try:
        return list(iter(loader))
    finally:
        loader.close()


def _assert_streams_equal(a, b):
    assert len(a) == len(b)
    for ba, bb in zip(a, b):
        assert set(ba) == set(bb)
        for k in ba:
            np.testing.assert_array_equal(ba[k], bb[k], err_msg=k)


# ---------------------------------------------------------------------------
# classification + retry (FeedGuard units, injectable clock)
# ---------------------------------------------------------------------------

def test_classify_record_error_errno_and_markers():
    assert classify_record_error(OSError(errno.EIO, "x")) == "transient"
    assert classify_record_error(
        OSError(errno.ETIMEDOUT, "x")) == "transient"
    assert classify_record_error(OSError(errno.ESTALE, "x")) == "transient"
    # wrapped decoder/mmap flake signatures
    assert classify_record_error(
        ValueError("truncated read at offset 4096")) == "transient"
    assert classify_record_error(
        RuntimeError("mount: Stale file handle")) == "transient"
    # corruption is permanent — so is a generic OSError (ENOENT is a
    # missing file, not a flake)
    assert classify_record_error(
        ValueError("corrupt JPEG data: bad Huffman code")) == "permanent"
    assert classify_record_error(OSError(errno.ENOENT, "x")) == "permanent"


def test_retry_rides_transient_flakes_then_succeeds(tmp_path):
    """Two EIO flakes back off (jittered, bounded) and the record loads;
    nothing is quarantined; each retry leaves a typed ``data`` event."""
    elog = EventLog(str(tmp_path / "events_p0.jsonl"))
    sleeps = []
    fails = {"left": 2}

    def load(i):
        if fails["left"]:
            fails["left"] -= 1
            raise OSError(errno.EIO, "Input/output error")
        return ("record", i)

    g = FeedGuard(_dcfg(record_backoff_base_s=0.05,
                        record_backoff_max_s=0.2),
                  n_records=10, elog=elog, sleep=sleeps.append,
                  clock=lambda: 0.0)
    assert g.load(load, 3) == (("record", 3), 3)
    assert g.quarantined_count == 0 and g.retry_count == 2
    assert len(sleeps) == 2
    assert 0.05 <= sleeps[0] <= 0.05 * 1.25   # base, +25% jitter max
    assert 0.1 <= sleeps[1] <= 0.1 * 1.25     # doubled
    elog.close()
    retries = [e for e in report.load_events(str(tmp_path))
               if e["type"] == "data" and e["kind"] == "retry"]
    assert len(retries) == 2
    assert retries[0]["record"] == 3 and retries[0]["attempt"] == 1
    assert "Input/output error" in retries[0]["error"]


def test_retry_deadline_reclassifies_as_permanent():
    """A record still transiently failing past data.record_deadline_s is
    quarantined (the give-up OSError chains the original flake)."""
    t = {"now": 0.0}

    def clock():
        t["now"] += 40.0   # two attempts cross the 60s deadline
        return t["now"]

    def load(i):
        if i == 2:
            raise OSError(errno.ETIMEDOUT, "read timed out")
        return ("record", i)

    g = FeedGuard(_dcfg(record_deadline_s=60.0,
                        quarantine_max_fraction=0.5),
                  n_records=10, seed=0, sleep=lambda s: None, clock=clock)
    result, actual = g.load(load, 2)
    assert actual != 2 and result == ("record", actual)
    assert g.quarantined_count == 1


def test_retry_disabled_propagates_raw_transient():
    """data.record_deadline_s=0 restores pre-graftfeed behavior for
    transient IO: the raw OSError stays loud, nothing is quarantined."""
    g = FeedGuard(_dcfg(record_deadline_s=0.0), n_records=10,
                  sleep=lambda s: None)
    with pytest.raises(OSError) as ei:
        g.load(lambda i: (_ for _ in ()).throw(
            OSError(errno.EIO, "Input/output error")), 1)
    assert ei.value.errno == errno.EIO
    assert g.quarantined_count == 0


# ---------------------------------------------------------------------------
# quarantine: determinism, persistence, reapply, the cap
# ---------------------------------------------------------------------------

def test_quarantine_replacement_is_pure_and_avoids_set(tmp_path):
    """The substitute is f(seed, epoch, record): two independent guards
    draw the SAME replacement, and a replacement never lands on a
    quarantined record (chained corruption re-quarantines)."""
    def corrupt(bad):
        def load(i):
            if i in bad:
                raise ValueError(f"corrupt JPEG data: record {i}")
            return i
        return load

    def fresh():
        g = FeedGuard(_dcfg(quarantine_max_fraction=0.9), n_records=20,
                      seed=7, sleep=lambda s: None)
        g.set_epoch(3)
        return g

    g1, g2 = fresh(), fresh()
    r1 = g1.load(corrupt({4}), 4)
    assert r1 == g2.load(corrupt({4}), 4)  # pure draw, no shared rng
    # chained: the replacement for 4 is ALSO corrupt -> both quarantined,
    # final substitute avoids both
    g3 = fresh()
    result, actual = g3.load(corrupt({4, r1[1]}), 4)
    assert actual not in (4, r1[1]) and result == actual
    assert g3.quarantined_count == 2
    # a later load of a known-quarantined record pre-resolves without
    # re-attempting (the load_fn would raise if called on 4 again)
    assert g3.resolve(4) not in (4, r1[1])


def test_quarantine_persists_and_reapplies_on_resume(tmp_path):
    """quarantine.jsonl round-trip: the interrupted run's file re-arms a
    resume=True guard (quarantine_applied event), so substitutions
    replay without re-discovery; a fresh (non-resume) guard ignores
    the stale file."""
    path = str(tmp_path / "quarantine.jsonl")
    elog = EventLog(str(tmp_path / "events_p0.jsonl"))
    g = FeedGuard(_dcfg(quarantine_max_fraction=0.9), n_records=20,
                  seed=1, elog=elog, quarantine_path=path,
                  sleep=lambda s: None)
    _, actual = g.load(lambda i: i if i != 5 else (_ for _ in ()).throw(
        ValueError("corrupt JPEG data")), 5)
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 1
    assert lines[0]["record"] == 5
    assert lines[0]["replacement"] == actual
    assert "corrupt JPEG" in lines[0]["reason"]

    g_resumed = FeedGuard(_dcfg(), n_records=20, seed=1, elog=elog,
                          quarantine_path=path, resume=True,
                          sleep=lambda s: None)
    assert g_resumed.quarantined_count == 1
    assert g_resumed.resolve(5) == actual  # same pure draw, no load
    g_fresh = FeedGuard(_dcfg(), n_records=20, seed=1,
                        quarantine_path=path, sleep=lambda s: None)
    assert g_fresh.quarantined_count == 0
    elog.close()
    applied = [e for e in report.load_events(str(tmp_path))
               if e["type"] == "data"
               and e["kind"] == "quarantine_applied"]
    assert len(applied) == 1 and applied[0]["count"] == 1


def test_quarantine_cap_aborts_loudly(tmp_path):
    """Crossing data.quarantine_max_fraction raises (not substitutes) —
    with the evidence persisted FIRST and a quarantine_cap event."""
    path = str(tmp_path / "quarantine.jsonl")
    elog = EventLog(str(tmp_path / "events_p0.jsonl"))
    g = FeedGuard(_dcfg(quarantine_max_fraction=0.25), n_records=4,
                  elog=elog, quarantine_path=path, sleep=lambda s: None)
    g.load(lambda i: i if i != 0 else (_ for _ in ()).throw(
        ValueError("corrupt JPEG data")), 0)   # 1/4 == cap: allowed
    with pytest.raises(QuarantineExceededError) as ei:
        g.load(lambda i: i if i != 1 else (_ for _ in ()).throw(
            ValueError("corrupt JPEG data")), 1)   # 2/4 > cap
    assert "quarantine_max_fraction" in str(ei.value)
    assert len(open(path).readlines()) == 2  # persisted before the abort
    elog.close()
    kinds = [e["kind"] for e in report.load_events(str(tmp_path))
             if e["type"] == "data"]
    assert kinds.count("quarantine") == 2
    assert kinds.count("quarantine_cap") == 1


# ---------------------------------------------------------------------------
# chaos keys (resilience/chaos.py data sites)
# ---------------------------------------------------------------------------

def test_chaos_parse_data_keys_and_validation():
    spec = chaos.parse("data_corrupt_at=1:3 data_io_error_at=0:2:2 "
                       "data_hang_at=1:0 data_worker_die_at=1")
    assert spec.data_corrupt_at == "1:3"
    assert spec.data_io_error_at == "0:2:2"
    assert spec.data_hang_at == "1:0" and spec.data_worker_die_at == 1
    assert spec.active
    with pytest.raises(ValueError, match="data_corrupt_at"):
        chaos.parse("data_corrupt_at=1:2:3")   # E:I, not E:I:N
    with pytest.raises(ValueError, match="data_io_error_at"):
        chaos.parse("data_io_error_at=0:2")    # E:I:N, not E:I


def test_chaos_data_hooks_fire_at_their_keys():
    chaos.reset()
    spec = chaos.parse("data_corrupt_at=1:3 data_io_error_at=0:2:2 "
                       "data_worker_die_at=1")
    spec.maybe_data_corrupt(0, 3)  # wrong epoch: inert
    spec.maybe_data_corrupt(1, 2)  # wrong record: inert
    with pytest.raises(ValueError, match="corrupt JPEG"):
        spec.maybe_data_corrupt(1, 3)
    with pytest.raises(ValueError, match="corrupt JPEG"):
        spec.maybe_data_corrupt(1, 3)  # corruption is NOT transient
    for _ in range(2):              # N=2 flakes, then the read heals
        with pytest.raises(OSError) as ei:
            spec.maybe_data_io_error(0, 2)
        assert ei.value.errno == errno.EIO
    spec.maybe_data_io_error(0, 2)  # third attempt: clean
    assert spec.maybe_worker_die(0) is False
    assert spec.maybe_worker_die(1) is True
    assert spec.maybe_worker_die(1) is False  # dies ONCE


# ---------------------------------------------------------------------------
# loader-level: retry / worker resurrection / hang / close
# ---------------------------------------------------------------------------

def _loader(roidb, guard=None):
    return AnchorLoader(roidb, driver.tiny_config(), num_shards=1,
                        shuffle=False, seed=0, guard=guard)


def test_loader_transient_retry_stream_bitexact(tmp_path):
    """Two injected EIO flakes on one record: the guarded loader backs
    off, retries, and yields the EXACT stream of an unguarded run."""
    roidb = _roidb()
    baseline = _batches(_loader(roidb))
    guard = FeedGuard(_dcfg(record_backoff_base_s=0.001,
                            record_backoff_max_s=0.002),
                      n_records=len(roidb),
                      chaos_spec=chaos.parse("data_io_error_at=0:2:2"))
    chaosed = _batches(_loader(roidb, guard=guard))
    _assert_streams_equal(baseline, chaosed)
    assert guard.retry_count == 2 and guard.quarantined_count == 0


def test_loader_worker_death_resurrected_stream_intact(tmp_path):
    """A chaos-killed prefetch worker (abrupt return, claim + slot kept)
    is detected by consumer-side supervision, its position requeued, a
    replacement spawned — every batch still arrives, in order."""
    roidb = _roidb()
    baseline = _batches(_loader(roidb))
    elog = EventLog(str(tmp_path / "events_p0.jsonl"))
    guard = FeedGuard(_dcfg(), n_records=len(roidb), elog=elog,
                      chaos_spec=chaos.parse("data_worker_die_at=0"))
    chaosed = _batches(_loader(roidb, guard=guard))
    _assert_streams_equal(baseline, chaosed)
    elog.close()
    deaths = [e for e in report.load_events(str(tmp_path))
              if e["type"] == "data_worker"]
    assert len(deaths) == 1
    assert deaths[0]["resurrected"] is True
    assert deaths[0]["deaths"] == 1 and deaths[0]["restart_max"] == 3


def test_loader_worker_death_budget_exhausted_raises():
    """data.worker_restart_max=0: the first death is over budget —
    DataWorkerError (NOT RuntimeError: graftheal must not retry a
    broken input plane)."""
    roidb = _roidb()
    guard = FeedGuard(_dcfg(worker_restart_max=0), n_records=len(roidb),
                      chaos_spec=chaos.parse("data_worker_die_at=0"))
    loader = _loader(roidb, guard=guard)
    with pytest.raises(DataWorkerError) as ei:
        _batches(loader)
    assert not isinstance(ei.value, RuntimeError)
    loader.close()  # already closed by the raise path: must not hang


def test_loader_hang_raises_datastall_within_deadline():
    """A wedged record read (chaos hang >> deadline) turns into
    DataStallError once the blocking next() outlasts
    data.wait_deadline_s — and close() returns promptly because the
    cancel predicate releases the hung worker."""
    roidb = _roidb()
    guard = FeedGuard(_dcfg(wait_deadline_s=1.0), n_records=len(roidb),
                      chaos_spec=chaos.parse("data_hang_at=0:0 hang_s=60"))
    loader = _loader(roidb, guard=guard)
    t0 = time.monotonic()
    with pytest.raises(DataStallError, match="wait_deadline_s"):
        _batches(loader)
    elapsed = time.monotonic() - t0
    assert elapsed < 10.0, elapsed  # deadline + join slack, nowhere near 60
    loader.close()


def test_loader_close_idempotent_and_dead_worker_safe():
    """close() twice is a no-op; closing mid-iteration with a chaos-dead
    worker in the pool (its thread object still in the join list)
    neither hangs nor raises."""
    roidb = _roidb()
    loader = _loader(roidb)
    it = iter(loader)
    next(it)
    loader.close()
    loader.close()
    guard = FeedGuard(_dcfg(), n_records=len(roidb),
                      chaos_spec=chaos.parse("data_worker_die_at=0"))
    loader = _loader(roidb, guard=guard)
    it = iter(loader)
    next(it)        # worker 0 may already be dead; batches still flow
    loader.close()  # join skips dead threads
    loader.close()


# ---------------------------------------------------------------------------
# fit-level: quarantine-complete + kill->resume parity, hang, cap
# (riding tests/_resilience_driver.py — same tiny 64^2 fit as the
# graftguard/graftheal gates)
# ---------------------------------------------------------------------------

RESUMABLE_RC = 75
#: 1 of 3 synthetic records quarantined = 33% — the tiny-fit gates must
#: lift the (production-sized) 1% default to let the run proceed.
_CAP_OVER = {"data.quarantine_max_fraction": 0.5}


def _quarantine_parity(tmp_path, monkeypatch):
    """The tentpole gate: chaos-corrupt record 1 in epoch 0 ->
    quarantined (event + jsonl), run COMPLETES on the deterministic
    substitute; SIGTERM mid-epoch-1 + --resume auto re-applies the
    quarantine file and finishes BIT-exact vs the uninterrupted chaos
    run."""
    monkeypatch.setenv(chaos.ENV_VAR, "data_corrupt_at=0:1")
    chaos.reset()
    obs_u = str(tmp_path / "obs_uninterrupted")
    params_u = driver.run_fit(str(tmp_path / "uninterrupted"),
                              obs_dir=obs_u, over_extra=_CAP_OVER)
    quars = [e for e in report.load_events(obs_u)
             if e["type"] == "data" and e["kind"] == "quarantine"]
    assert len(quars) == 1
    assert quars[0]["record"] == 1 and quars[0]["epoch"] == 0
    assert "corrupt JPEG" in quars[0]["reason"]
    qfile = [json.loads(l)
             for l in open(os.path.join(obs_u, "quarantine.jsonl"))]
    assert len(qfile) == 1 and qfile[0]["record"] == 1
    assert qfile[0]["replacement"] == quars[0]["replacement"]
    # the report folds it (the smoke script greps this line)
    summary = report.summarize(report.load_events(obs_u))
    assert summary["data"]["quarantined"][0]["record"] == 1
    assert "1 record(s) quarantined" in report.render(summary)
    assert report.bench_blob(summary)["data_quarantined"] == 1

    monkeypatch.setenv(chaos.ENV_VAR,
                       "data_corrupt_at=0:1 sigterm_at_step=4")
    chaos.reset()
    obs_k = str(tmp_path / "obs_killed")
    with pytest.raises(PreemptionExit) as ei:
        driver.run_fit(str(tmp_path / "killed"), obs_dir=obs_k,
                       over_extra=_CAP_OVER)
    assert ei.value.code == RESUMABLE_RC

    monkeypatch.setenv(chaos.ENV_VAR, "data_corrupt_at=0:1")
    chaos.reset()
    # SAME obs dir: --resume auto re-applies obs_k/quarantine.jsonl, so
    # the resumed epoch-1 stream substitutes record 1 exactly like the
    # uninterrupted run (which quarantined it back in epoch 0).
    params_r = driver.run_fit(str(tmp_path / "killed"),
                              resume="auto", obs_dir=obs_k,
                              over_extra=_CAP_OVER)
    applied = [e for e in report.load_events(obs_k)
               if e["type"] == "data"
               and e["kind"] == "quarantine_applied"]
    assert len(applied) == 1 and applied[0]["count"] == 1
    import jax

    la = jax.tree_util.tree_leaves_with_path(params_u)
    lb = {jax.tree_util.keystr(p): v
          for p, v in jax.tree_util.tree_leaves_with_path(params_r)}
    assert len(la) == len(lb)
    for path, va in la:
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(lb[jax.tree_util.keystr(path)]),
            err_msg=jax.tree_util.keystr(path))


# The four fit gates below are slow-marked like the graftquorum
# subprocess gates: ~200s of tiny fits that would bust the tier-1 wall
# clock. `script/smoke_resilience.sh` (`pytest -m chaos`) runs them.
@pytest.mark.slow
@pytest.mark.compile_heavy
def test_quarantine_kill_resume_parity_tree(tmp_path, monkeypatch):
    _quarantine_parity(tmp_path, monkeypatch)


@pytest.mark.slow
@pytest.mark.compile_heavy
def test_hang_crashes_with_data_wait_attribution(tmp_path, monkeypatch):
    """Dead storage mid-run: the blocking next() raises DataStallError
    at data.wait_deadline_s (escaping graftheal — not a RuntimeError),
    and the crash flight dump carries a stall event whose phase says
    data_wait, not dispatch."""
    monkeypatch.setenv(chaos.ENV_VAR, "data_hang_at=0:2 hang_s=600")
    chaos.reset()
    obs_dir = str(tmp_path / "obs")
    t0 = time.monotonic()
    with pytest.raises(DataStallError):
        driver.run_fit(str(tmp_path / "hung"), end_epoch=1,
                       obs_dir=obs_dir,
                       over_extra={"data.wait_deadline_s": 4.0,
                                   "obs.stall_min_s": 0.3,
                                   "obs.stall_factor": 0.01,
                                   "obs.watchdog_poll_s": 0.1})
    assert time.monotonic() - t0 < 120.0  # deadline + teardown, not 600
    events = report.load_events(obs_dir)
    stalls = [e for e in events if e["type"] == "stall"]
    assert any(e.get("phase") == "data_wait" for e in stalls), stalls
    crashes = [e for e in events if e["type"] == "crash"]
    assert len(crashes) == 1
    assert "DataStallError" in crashes[0]["error"]
    flight = os.path.join(obs_dir, "flight_crash.json")
    assert os.path.exists(flight)
    ring = json.load(open(flight))["events"]
    assert any(e["type"] == "stall" and e.get("phase") == "data_wait"
               for e in ring)


@pytest.mark.slow
@pytest.mark.compile_heavy
def test_quarantine_cap_aborts_fit(tmp_path, monkeypatch):
    """Under the PRODUCTION default cap (1%), one corrupt record in a
    3-record dataset is a broken dataset: the fit aborts with
    QuarantineExceededError, the evidence persisted and the cap event
    emitted — no silent training on substitutes."""
    monkeypatch.setenv(chaos.ENV_VAR, "data_corrupt_at=0:1")
    chaos.reset()
    obs_dir = str(tmp_path / "obs")
    with pytest.raises(QuarantineExceededError, match="broken"):
        driver.run_fit(str(tmp_path / "capped"), end_epoch=1,
                       obs_dir=obs_dir)
    events = report.load_events(obs_dir)
    kinds = [e["kind"] for e in events if e["type"] == "data"]
    assert "quarantine" in kinds and "quarantine_cap" in kinds
    assert os.path.exists(os.path.join(obs_dir, "quarantine.jsonl"))
    assert os.path.exists(os.path.join(obs_dir, "flight_crash.json"))
    assert report.summarize(events)["data"]["cap_trips"] == 1
