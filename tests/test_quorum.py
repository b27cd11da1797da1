"""graftquorum (resilience/quorum.py) gates — multi-host coordinated
resilience, exercised for real on CPU.

Two layers:

- **protocol units** (tier-1, no device work): FileKVStore atomicity,
  deadline-bounded barriers, generation-numbered heal rounds with
  exclusion and min-fraction, the two-phase coordinated stop under
  drift, the chaos multi-host keys, and the simulated-host identity
  wrappers;
- **N-process trainer gates** (``slow`` — each spawns full training
  subprocesses): the ISSUE acceptance scenarios. Each "host" is a
  separate CPU process running the FULL replicated computation
  (deterministic, bit-identical trajectories — no cross-process
  collectives) whose coordination identity comes from
  ``MXRCNN_SIM_PROCESS_ID``, coordinating through a shared FileKVStore
  exactly as a pod fleet would through jax.distributed's KV service:

  * coordinated preemption: SIGTERM one of two hosts -> BOTH drain to
    the agreed boundary, exactly ONE published save (complete host set
    in graft_meta.json), both exit rc 75, and a dual ``--resume auto``
    reaches params BIT-exact vs an uninterrupted run;
  * multi-host heal with exclusion: both hosts lose devices, one is
    chaos-armed to miss the heal rendezvous -> survivors seal a quorum
    without it, the run continues, the excluded host exits rc 75;
  * elastic grow / rescale (in-process, single host): a heal that
    re-acquires MORE devices grows past the nominal footprint; one too
    deep for the global batch rescales it (rows-per-device constant,
    schedule rebased).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.obs import open_event_log, report
from mx_rcnn_tpu.parallel.partition import elastic_mesh_spec
from mx_rcnn_tpu.resilience import (
    RESUMABLE_RC,
    CoordinatedStop,
    FileKVStore,
    PreemptionExit,
    Quorum,
    QuorumError,
    QuorumExcludedError,
    chaos,
)
from mx_rcnn_tpu.train.checkpoint import (
    checkpoint_meta,
    latest_checkpoint,
    save_checkpoint,
)

import _resilience_driver as driver

pytestmark = pytest.mark.chaos

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVER = os.path.join(REPO_ROOT, "tests", "_resilience_driver.py")


def _subprocess_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for k in ("MX_RCNN_CHAOS", "MXRCNN_SIM_PROCESS_ID",
              "MXRCNN_SIM_NUM_PROCESSES"):
        env.pop(k, None)
    env.update(extra)
    return env


@pytest.fixture(autouse=True)
def _fresh_chaos(monkeypatch):
    monkeypatch.delenv(chaos.ENV_VAR, raising=False)
    monkeypatch.delenv("MXRCNN_SIM_PROCESS_ID", raising=False)
    monkeypatch.delenv("MXRCNN_SIM_NUM_PROCESSES", raising=False)
    chaos.reset()
    yield
    chaos.reset()


def _quorum(store, index, count, **kw):
    kw.setdefault("timeout_s", 2.0)
    kw.setdefault("poll_s", 0.005)
    return Quorum(store, index, count, **kw)


# ---------------------------------------------------------------------------
# KV store
# ---------------------------------------------------------------------------

def test_file_kv_store_set_get_propose(tmp_path):
    store = FileKVStore(str(tmp_path / "kv"))
    assert store.get("a/b") is None
    store.set("a/b", "1")
    assert store.get("a/b") == "1"
    store.set("a/b", "2")  # set = last-writer-wins
    assert store.get("a/b") == "2"
    # propose = FIRST-writer-wins: the loser gets the winning value back
    assert store.propose("stop/req/value", "5") == "5"
    assert store.propose("stop/req/value", "9") == "5"
    assert store.get("stop/req/value") == "5"


def test_file_kv_store_rejects_escaping_keys(tmp_path):
    store = FileKVStore(str(tmp_path / "kv"))
    with pytest.raises(ValueError, match="escapes store root"):
        store.set("../outside", "x")


# ---------------------------------------------------------------------------
# barriers
# ---------------------------------------------------------------------------

def test_barrier_all_arrive(tmp_path):
    store = FileKVStore(str(tmp_path / "kv"))
    qs = [_quorum(store, i, 3) for i in range(3)]
    results = {}

    def arrive(q):
        results[q.index] = q.barrier("save/1")

    threads = [threading.Thread(target=arrive, args=(q,)) for q in qs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r == {0, 1, 2} for r in results.values()), results


def test_barrier_partial_set_on_deadline(tmp_path):
    """A host that never arrives does NOT hang the others forever — the
    deadline returns the partial set and the caller decides."""
    store = FileKVStore(str(tmp_path / "kv"))
    q0 = _quorum(store, 0, 2, timeout_s=0.3)
    arrived = q0.barrier("save/1")
    assert arrived == {0}


def test_barrier_waits_only_for_active_hosts(tmp_path):
    """After an exclusion shrinks ``active``, later barriers must not
    deadline on the dead host (else every epoch save eats the timeout)."""
    store = FileKVStore(str(tmp_path / "kv"))
    q0 = _quorum(store, 0, 2, timeout_s=5.0)
    q0.active = {0}
    t0 = time.monotonic()
    assert q0.barrier("epoch/3") == {0}
    assert time.monotonic() - t0 < 1.0


# ---------------------------------------------------------------------------
# heal rounds
# ---------------------------------------------------------------------------

def test_heal_round_agrees_min_devices_topology(tmp_path):
    """Both hosts arrive with different re-acquired capacity: the leader
    seals the spec derived from the MINIMUM, and both adopt it."""
    store = FileKVStore(str(tmp_path / "kv"))
    qs = [_quorum(store, i, 2) for i in range(2)]
    outcomes = {}

    def heal(q, n_dev):
        outcomes[q.index] = q.heal_round(
            0, n_dev, lambda d, n: f"{d}x1")

    threads = [threading.Thread(target=heal, args=(qs[0], 8)),
               threading.Thread(target=heal, args=(qs[1], 6))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert outcomes[0].spec == outcomes[1].spec == "6x1"
    assert outcomes[0].devices == 6
    assert outcomes[0].arrived == [0, 1] and outcomes[0].excluded == []


def test_heal_round_seals_without_straggler_then_excludes_it(tmp_path):
    """Host 1 misses the deadline: host 0 seals a one-host quorum and
    continues; host 1, arriving late at the SAME generation, discovers
    the seal without its index and raises QuorumExcludedError (-> the
    trainer turns that into a resumable rc-75 exit)."""
    store = FileKVStore(str(tmp_path / "kv"))
    q0 = _quorum(store, 0, 2, timeout_s=0.3)
    outcome = q0.heal_round(0, 4, lambda d, n: f"{d}x1")
    assert outcome.arrived == [0] and outcome.excluded == [1]
    assert q0.active == {0}

    q1 = _quorum(store, 1, 2, timeout_s=0.3)
    with pytest.raises(QuorumExcludedError, match="missed heal generation"):
        q1.heal_round(0, 4, lambda d, n: f"{d}x1")


def test_heal_round_below_min_fraction_aborts(tmp_path):
    store = FileKVStore(str(tmp_path / "kv"))
    q0 = _quorum(store, 0, 3, timeout_s=0.3, min_fraction=0.9)
    with pytest.raises(QuorumError, match="min fraction"):
        q0.heal_round(0, 4, lambda d, n: f"{d}x1")


# ---------------------------------------------------------------------------
# coordinated stop
# ---------------------------------------------------------------------------

def test_coordinated_stop_agrees_max_under_drift(tmp_path):
    """Host 0 is signaled at boundary 5 while host 1 already drifted to
    boundary 7: the agreed stop is 7 on BOTH hosts — no host is asked to
    stop at a boundary it already passed."""
    store = FileKVStore(str(tmp_path / "kv"))
    s0 = CoordinatedStop(_quorum(store, 0, 2))
    s1 = CoordinatedStop(_quorum(store, 1, 2))
    s0.request(5)
    agreed = {}

    def check(s, boundary):
        agreed[s.quorum.index] = s.check(boundary)

    threads = [threading.Thread(target=check, args=(s0, 5)),
               threading.Thread(target=check, args=(s1, 7))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert agreed == {0: 7, 1: 7}
    # cached thereafter: later boundaries return the same agreement
    assert s0.check(6) == 7


def test_coordinated_stop_check_is_none_without_request(tmp_path):
    store = FileKVStore(str(tmp_path / "kv"))
    s0 = CoordinatedStop(_quorum(store, 0, 2))
    assert s0.check(3) is None  # the un-signaled steady state: one get


# ---------------------------------------------------------------------------
# chaos multi-host keys + simulated-host identity
# ---------------------------------------------------------------------------

def test_chaos_parse_multihost_keys():
    spec = chaos.parse("host_die_at_step=1:4 barrier_timeout_at=quorum_barrier")
    assert spec.host_die_at_step == "1:4"
    assert spec.barrier_timeout_at == "quorum_barrier" and spec.active
    with pytest.raises(ValueError, match="H:K"):
        chaos.parse("host_die_at_step=four")
    with pytest.raises(ValueError, match="registered"):
        chaos.parse("barrier_timeout_at=not_a_site")


def test_chaos_barrier_timeout_blocks_arrival(tmp_path, monkeypatch):
    """Armed ``barrier_timeout_at=quorum_barrier``: this process does
    not arrive (a host hung past the deadline), so peers see a partial
    set — the exclusion path, injected deterministically."""
    monkeypatch.setenv(chaos.ENV_VAR, "barrier_timeout_at=quorum_barrier")
    chaos.reset()
    store = FileKVStore(str(tmp_path / "kv"))
    q0 = _quorum(store, 0, 1, timeout_s=0.2)
    assert q0.barrier("save/1") == set()


def test_chaos_barrier_timeout_host_scoping(tmp_path, monkeypatch):
    """``H:site`` scoping: armed for host 1, host 0 arrives normally."""
    monkeypatch.setenv(chaos.ENV_VAR, "barrier_timeout_at=1:quorum_barrier")
    monkeypatch.setenv("MXRCNN_SIM_PROCESS_ID", "0")
    chaos.reset()
    store = FileKVStore(str(tmp_path / "kv"))
    q0 = _quorum(store, 0, 1)
    assert q0.barrier("save/1") == {0}


def test_sim_process_identity_wrappers(monkeypatch):
    from mx_rcnn_tpu.parallel.distributed import (
        is_primary, process_count, process_index)

    monkeypatch.setenv("MXRCNN_SIM_PROCESS_ID", "3")
    monkeypatch.setenv("MXRCNN_SIM_NUM_PROCESSES", "4")
    assert process_index() == 3 and process_count() == 4
    assert not is_primary()
    monkeypatch.setenv("MXRCNN_SIM_PROCESS_ID", "0")
    assert is_primary()


# ---------------------------------------------------------------------------
# torn-save detection (satellite a)
# ---------------------------------------------------------------------------

def test_latest_checkpoint_skips_torn_multihost_emergency(tmp_path, caplog):
    """An emergency save whose meta records FEWER hosts than expected
    (a host died before the publication barrier) is skipped with a
    warning; resume falls back to the newest COMPLETE state."""
    prefix = str(tmp_path / "run")
    w = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    save_checkpoint(prefix, 1, w,
                    meta={"epoch": 1, "dispatch": None,
                          "hosts": [0, 1], "host_count": 2})
    save_checkpoint(prefix, 1, w, dispatch=2,
                    meta={"epoch": 1, "dispatch": 2,
                          "hosts": [0], "host_count": 2})  # torn
    found = latest_checkpoint(prefix)
    assert found == (1, None), found
    assert any("torn" in r.message for r in caplog.records)

    # the same emergency save with a COMPLETE host set is trusted
    save_checkpoint(prefix, 1, w, dispatch=3,
                    meta={"epoch": 1, "dispatch": 3,
                          "hosts": [0, 1], "host_count": 2})
    assert latest_checkpoint(prefix) == (1, 3)


# ---------------------------------------------------------------------------
# per-host event streams + report folding
# ---------------------------------------------------------------------------

def test_report_folds_per_host_streams_and_quorum_summary(tmp_path):
    d = str(tmp_path / "obs")
    log0 = open_event_log(d, process_index=0)
    log1 = open_event_log(d, process_index=1)
    assert os.path.basename(log1.path) == "events_p1.jsonl"
    log0.emit("quorum", kind="heal", generation=0, hosts=[0],
              excluded=[1], devices=4, spec="4x1")
    log1.emit("quorum", kind="excluded", error="missed heal generation 0")
    log0.close()
    log1.close()

    events = report.load_events(d)
    assert [e["process"] for e in events if e["type"] == "quorum"] \
        in ([0, 1], [1, 0])
    summary = report.summarize(events)
    assert summary["quorum"]["rounds"] == 2
    assert summary["quorum"]["hosts"] == 2
    assert summary["quorum"]["excluded"] == [1]
    assert "quorum" in report.render(summary)


# ---------------------------------------------------------------------------
# elastic phase 2 spec derivation (parallel/partition.py)
# ---------------------------------------------------------------------------

def test_elastic_mesh_spec_grow_and_rescale_modes():
    # shrink (default) never grows past the nominal footprint
    assert elastic_mesh_spec(2, 1, 8, 4) == "2x1"
    # grow: onto the largest micro-batch divisor the devices allow
    assert elastic_mesh_spec(2, 1, 8, 4, mode="grow") == "4x1"
    assert elastic_mesh_spec(2, 1, 3, 4, mode="grow") == "2x1"
    # rescale: a non-divisor count is taken as-is (the trainer rebuilds
    # the loader and rebases the schedule)
    assert elastic_mesh_spec(4, 1, 3, 4, mode="rescale") == "3x1"
    assert elastic_mesh_spec(4, 1, 8, 4, mode="rescale") == "4x1"
    with pytest.raises(ValueError, match="elastic mode"):
        elastic_mesh_spec(4, 1, 3, 4, mode="stretch")


# ---------------------------------------------------------------------------
# multi-host trainer gates (the ISSUE acceptance scenarios)
# ---------------------------------------------------------------------------

def _spawn_host(idx, n_hosts, prefix, kv_dir, *, resume=None,
                obs_dir="", chaos_env=None, end_epoch=2, timeout_s=120):
    cmd = [sys.executable, DRIVER, "--fit", prefix,
           "--end-epoch", str(end_epoch),
           "--sim-host", str(idx), "--sim-hosts", str(n_hosts),
           "--quorum-dir", kv_dir, "--quorum-timeout", str(timeout_s)]
    if resume:
        cmd += ["--resume", resume] if resume != True else ["--resume"]
    if obs_dir:
        cmd += ["--obs-dir", obs_dir]
    env = _subprocess_env(**({"MX_RCNN_CHAOS": chaos_env}
                             if chaos_env else {}))
    return subprocess.Popen(cmd, env=env, cwd=REPO_ROOT,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _run_host0_inprocess(prefix, kv_dir, monkeypatch, *, resume=False,
                         obs_dir=""):
    """Host 0 runs IN-PROCESS (so its returned params are directly
    comparable to the conftest baselines) while host 1 is a true
    subprocess."""
    monkeypatch.setenv("MXRCNN_SIM_PROCESS_ID", "0")
    monkeypatch.setenv("MXRCNN_SIM_NUM_PROCESSES", "2")
    return driver.run_fit(
        prefix, resume=resume, obs_dir=obs_dir,
        over_extra={"resilience.quorum_store_dir": kv_dir,
                    "resilience.quorum_timeout_s": 120.0})


def _coordinated_preemption(tmp_path, monkeypatch, baseline):
    prefix = str(tmp_path / "run")
    obs0 = str(tmp_path / "obs")

    # leg A: host 1 (subprocess) is chaos-SIGTERM'd mid-epoch-1; host 0
    # (in-process) is never signaled but must drain and stop too.
    kv_a = str(tmp_path / "kv_a")
    proc1 = _spawn_host(1, 2, prefix, kv_a,
                        chaos_env="sigterm_at_step=4")
    with pytest.raises(PreemptionExit) as ei:
        _run_host0_inprocess(prefix, kv_a, monkeypatch, obs_dir=obs0)
    assert ei.value.code == RESUMABLE_RC
    out1, _ = proc1.communicate(timeout=570)
    assert proc1.returncode == RESUMABLE_RC, (proc1.returncode, out1[-2000:])

    # exactly ONE consistent published state: latest_checkpoint agrees,
    # and its meta records the COMPLETE participating host set.
    found = latest_checkpoint(prefix)
    assert found is not None, os.listdir(prefix)
    meta = checkpoint_meta(prefix, *found)
    assert meta["host_count"] == 2 and meta["hosts"] == [0, 1], meta
    quorum_events = [e for e in report.load_events(obs0)
                     if e["type"] == "quorum"]
    assert any(e["kind"] == "preempt" and e["hosts"] == [0, 1]
               for e in quorum_events), quorum_events

    # leg B: dual --resume auto (fresh KV namespace — one dir per launch
    # attempt, the documented supervisor contract) -> bit-exact.
    kv_b = str(tmp_path / "kv_b")
    proc1 = _spawn_host(1, 2, prefix, kv_b, resume="auto")
    params_r = _run_host0_inprocess(prefix, kv_b, monkeypatch,
                                    resume="auto")
    out1, _ = proc1.communicate(timeout=570)
    assert proc1.returncode == 0, (proc1.returncode, out1[-2000:])
    _assert_trees_bitexact(baseline, params_r)


def _assert_trees_bitexact(a, b):
    import jax

    la = jax.tree_util.tree_leaves_with_path(a)
    lb = {jax.tree_util.keystr(p): v
          for p, v in jax.tree_util.tree_leaves_with_path(b)}
    assert len(la) == len(lb)
    for path, va in la:
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(lb[jax.tree_util.keystr(path)]),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.slow
@pytest.mark.compile_heavy
def test_coordinated_preemption_two_hosts_tree(tmp_path, monkeypatch,
                                               tree_f32_baseline):
    _coordinated_preemption(tmp_path, monkeypatch,
                            baseline=tree_f32_baseline)


@pytest.mark.slow
@pytest.mark.compile_heavy
def test_multihost_heal_excludes_straggler(tmp_path):
    """Both hosts lose their device at step 4 and heal; host 1 is
    chaos-armed to miss the heal rendezvous (H:site scoping). Host 0
    seals a one-host quorum (min_fraction 0.5 holds), finishes the run
    alone (rc 0) — its heal event carries the quorum outcome; host 1
    discovers the seal moved on without it and exits rc 75."""
    prefix = str(tmp_path / "run")
    kv = str(tmp_path / "kv")
    obs = str(tmp_path / "obs")
    chaos_env = ("device_lost_at_step=4 "
                 "barrier_timeout_at=1:quorum_barrier")
    procs = [_spawn_host(i, 2, prefix, kv, obs_dir=obs,
                         chaos_env=chaos_env, timeout_s=20)
             for i in range(2)]
    outs = [p.communicate(timeout=570)[0] for p in procs]
    assert procs[0].returncode == 0, outs[0][-2000:]
    assert procs[1].returncode == RESUMABLE_RC, outs[1][-2000:]

    events = report.load_events(obs)  # folds both events_p<k>.jsonl
    (heal0,) = [e for e in events
                if e["type"] == "heal" and e["process"] == 0]
    assert heal0["quorum_hosts"] == [0]
    assert heal0["quorum_excluded"] == [1]
    assert heal0["quorum_spec"]  # survivors agreed a topology
    assert any(e["type"] == "quorum" and e["kind"] == "excluded"
               and e["process"] == 1 for e in events)
    summary = report.summarize(events)
    assert summary["quorum"]["excluded"] == [1]


@pytest.mark.slow
@pytest.mark.compile_heavy
def test_elastic_grow_beyond_nominal_footprint(tmp_path, monkeypatch):
    """elastic_mode=grow: the run starts on a 2-wide mesh, loses a
    device, and the backend comes back with all 8 CPU devices — the
    healed session grows the data axis to 4 (the largest micro-batch
    divisor), beyond the nominal footprint."""
    monkeypatch.setenv(chaos.ENV_VAR, "device_lost_at_step=2")
    chaos.reset()
    prefix = str(tmp_path / "grown")
    obs = str(tmp_path / "obs")
    metrics = []
    driver.run_fit(prefix, mesh="2", num_images=8,
                   obs_dir=obs, epoch_metrics=metrics,
                   over_extra={"train.batch_images": 2,
                               "resilience.elastic_mode": "grow"})
    (ev,) = [e for e in report.load_events(obs) if e["type"] == "heal"]
    assert ev["devices_before"] == 2
    assert [e for e, _ in metrics] == [0, 1]  # completed both epochs
    # the rebuilt session's topology lands in the epoch save's sidecar
    meta = checkpoint_meta(prefix, 2, None)
    assert meta["mesh"] == {"data": 4, "model": 1}, meta


@pytest.mark.slow
@pytest.mark.compile_heavy
def test_elastic_rescale_too_deep_shrink(tmp_path, monkeypatch):
    """elastic_mode=rescale: 4-wide mesh shrinks to 3 devices — no
    divisor of the global batch, so the trainer keeps rows-per-device
    constant instead: loader rebuilt for 3 shards, images/dispatch drops
    4 -> 3 (visible in the epoch save's meta sidecar), LR schedule
    rebased, and the run completes without intervention."""
    monkeypatch.setenv(chaos.ENV_VAR,
                       "device_lost_at_step=2 shrink_on_reacquire=3")
    chaos.reset()
    prefix = str(tmp_path / "rescaled")
    obs = str(tmp_path / "obs")
    metrics = []
    driver.run_fit(prefix, mesh="4", num_images=8, obs_dir=obs,
                   epoch_metrics=metrics,
                   over_extra={"resilience.elastic_mode": "rescale"})
    (ev,) = [e for e in report.load_events(obs) if e["type"] == "heal"]
    assert ev["devices_before"] == 4 and ev["devices_after"] == 3
    assert [e for e, _ in metrics] == [0, 1]
    meta = checkpoint_meta(prefix, 2, None)
    assert meta["images_per_dispatch"] == 3, meta


# ---------------------------------------------------------------------------
# multi-host loud sync fallback + heal gate without a store (satellite c)
# ---------------------------------------------------------------------------

@pytest.mark.compile_heavy
def test_multihost_async_fallback_is_loud(tmp_path, monkeypatch, caplog):
    """Multi-host identity with NO reachable KV store: the async writer
    falls back to sync LOUDLY — one ``checkpoint`` event with
    fallback="sync" — and heal disables itself with a warning instead of
    wedging the fleet (coordination needs a store)."""
    monkeypatch.setenv("MXRCNN_SIM_PROCESS_ID", "0")
    monkeypatch.setenv("MXRCNN_SIM_NUM_PROCESSES", "2")
    obs = str(tmp_path / "obs")
    driver.run_fit(str(tmp_path / "run"), end_epoch=1, obs_dir=obs)
    falls = [e for e in report.load_events(obs)
             if e["type"] == "checkpoint" and e.get("fallback") == "sync"]
    assert len(falls) == 1 and "multi-host" in falls[0]["reason"]
    assert any("no KV store reachable" in r.message
               for r in caplog.records)
    assert any("heal disabled" in r.message for r in caplog.records)
