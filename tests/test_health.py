"""graftpulse gates (mx_rcnn_tpu/obs/health.py + train/health.py).

Unit layer: the in-graph reductions (finite counts + masked norms, the
whole-tree fold), chaos nan-injection math, HealthMonitor
cadence/tripwires/known-good capture (including the zero-added-host-sync
contract — off-cadence observes convert NOTHING), FlightRecorder ring +
EventLog integration, torn-JSONL tolerance, the env fingerprint, and the
report/ledger folds of health/anomaly events.

Integration layer (tier-1, compile_heavy + chaos): enabling
``obs.health_every`` on the tiny fit adds ZERO extra compiled
executables vs the same fit with health off (the reductions fuse into
the one train-step program), and the full nan_at_step matrix — chaos
poisons one step's gradients in-graph, the tripwire catches it, arms the
anomaly actions (event, flight dump, emergency checkpoint of the last
known-good state) and ``--resume auto`` continues BIT-exact vs an
uninterrupted run, f32 AND bf16 compute.
"""

import json
import math
import os

import numpy as np
import pytest

import _resilience_driver as driver
from mx_rcnn_tpu.obs import env_fingerprint, open_event_log, report, run_meta_fields
from mx_rcnn_tpu.obs import ledger as perf_ledger
from mx_rcnn_tpu.obs.health import FlightRecorder, HealthMonitor, NumericsAnomaly
from mx_rcnn_tpu.resilience import chaos

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _fresh_chaos(monkeypatch):
    """No injection leaks between tests (or in from the outer env)."""
    monkeypatch.delenv(chaos.ENV_VAR, raising=False)
    chaos.reset()
    yield
    chaos.reset()


# ---------------------------------------------------------------------------
# train/health.py — the in-graph reductions
# ---------------------------------------------------------------------------

def test_finite_stats_counts_and_masked_norm():
    """One fused pass: nonfinite COUNT plus the finite-MASKED squared sum
    — the norm stays informative while a few elements overflow."""
    import jax
    import jax.numpy as jnp
    from mx_rcnn_tpu.train import health as health_mod

    x = np.array([1.0, -2.0, np.nan, np.inf, 3.0, -np.inf], np.float32)
    nf, sq = jax.jit(health_mod.finite_stats)(jnp.asarray(x))
    assert int(nf) == 3
    np.testing.assert_allclose(float(sq), 1.0 + 4.0 + 9.0, rtol=1e-6)

    # bf16 buffer: the squared sum accumulates in f32 (a bf16 square
    # saturates where the f32 accumulator does not even notice)
    big = jnp.full((8,), 256.0, jnp.bfloat16)
    nf_b, sq_b = jax.jit(health_mod.finite_stats)(big)
    assert int(nf_b) == 0
    np.testing.assert_allclose(float(sq_b), 8 * 256.0 * 256.0, rtol=1e-2)


def test_probe_tree_fold():
    """Every float leaf folds into ONE count + ONE squared sum (int
    leaves skipped)."""
    import jax
    import jax.numpy as jnp
    from mx_rcnn_tpu.train import health as health_mod

    tree = {"a": jnp.asarray([np.nan, 1.0], jnp.float32),
            "b": {"c": jnp.asarray([2.0, np.inf], jnp.float32),
                  "n": jnp.arange(2, dtype=jnp.int32)}}
    folded = jax.jit(lambda t: health_mod.probe_tree("param", t))(tree)
    assert set(folded) == {"param/tree/nf", "param/tree/sq"}
    assert int(folded["param/tree/nf"]) == 2
    np.testing.assert_allclose(float(folded["param/tree/sq"]), 1.0 + 4.0)


def test_chaos_poison_grads_fires_only_at_armed_step():
    """nan_at_step's in-graph injection: NaN exactly when the optimizer
    step being produced equals the armed step; numerically identity
    otherwise; int leaves pass through untouched."""
    import jax
    import jax.numpy as jnp

    g = {"w": jnp.asarray([1.0, 2.0], jnp.float32),
         "i": jnp.arange(3, dtype=jnp.int32)}
    fn = jax.jit(lambda gr, s: chaos.poison_grads(gr, s, 3))
    hit = fn(g, jnp.asarray(2, jnp.int32))    # producing step 3: poisoned
    assert np.isnan(np.asarray(hit["w"])).all()
    np.testing.assert_array_equal(np.asarray(hit["i"]), np.arange(3))
    clean = fn(g, jnp.asarray(3, jnp.int32))  # producing step 4: identity
    np.testing.assert_array_equal(np.asarray(clean["w"]), [1.0, 2.0])


# ---------------------------------------------------------------------------
# HealthMonitor — cadence, folding, tripwires
# ---------------------------------------------------------------------------

class _Scalar:
    """Stands in for a device scalar: converting it to float IS the
    device→host pull the cadence contract meters."""

    def __init__(self, value, pulls):
        self.value = value
        self.pulls = pulls

    def __float__(self):
        self.pulls[0] += 1
        return float(self.value)


def _reading(pulls, loss=1.0, grad_sq=1.0, grad_nf=0, param_nf=0):
    return {"loss": _Scalar(loss, pulls),
            "grad/float32/nf": _Scalar(grad_nf, pulls),
            "grad/float32/sq": _Scalar(grad_sq, pulls),
            "param/float32/nf": _Scalar(param_nf, pulls),
            "param/float32/sq": _Scalar(4.0, pulls)}


def test_monitor_cadence_pulls_nothing_off_cadence(tmp_path):
    """The zero-added-host-syncs contract: observe() stores a REFERENCE;
    only every Nth dispatch converts anything to float."""
    log = open_event_log(str(tmp_path))
    mon = HealthMonitor(log, every=3)
    pulls = [0]
    assert mon.observe(_reading(pulls), epoch=0, dispatch=1) is None
    assert mon.observe(_reading(pulls), epoch=0, dispatch=2) is None
    assert pulls[0] == 0  # two dispatches, zero device pulls
    mon.observe(_reading(pulls), epoch=0, dispatch=3)
    assert pulls[0] == 5  # the one cadenced read converts the 5 scalars
    log.close()
    events = report.load_events(str(tmp_path))
    health = [e for e in events if e["type"] == "health"]
    assert len(health) == 1 and health[0]["dispatch"] == 3
    assert health[0]["nonfinite"] == {"grad/float32": 0, "param/float32": 0}
    assert abs(health[0]["norm"]["grad/float32"] - 1.0) < 1e-9
    assert health[0]["grad_norm"] == 1.0


def test_monitor_nonfinite_trips_with_actions(tmp_path):
    """A nonfinite count becomes ACTION: anomaly event, trace window,
    emergency save of the last known-good carry, flight dump, then
    NumericsAnomaly under action=abort."""

    class _Tracer:
        armed = 0

        def anomaly_window(self):
            self.armed += 1

    class _Good:
        epoch, dispatch = 0, 2

    log = open_event_log(str(tmp_path))
    tracer = _Tracer()
    recorder = FlightRecorder(str(tmp_path))
    log.attach_ring(recorder)
    saves = []
    mon = HealthMonitor(
        log, every=1, tracer=tracer, recorder=recorder,
        capture=lambda: _Good(),
        save=lambda good: saves.append(good) or "/ckpt/0000d00002")
    pulls = [0]
    mon.observe(_reading(pulls), epoch=0, dispatch=1)  # clean: good refreshed
    assert mon.good is not None
    with pytest.raises(NumericsAnomaly) as ei:
        mon.observe(_reading(pulls, grad_nf=7), epoch=0, dispatch=2)
    assert "--resume auto" in str(ei.value)
    assert tracer.armed == 1 and len(saves) == 1
    log.close()

    events = report.load_events(str(tmp_path))
    anomaly = next(e for e in events if e["type"] == "anomaly")
    assert anomaly["reasons"] == ["nonfinite:grad/float32=7"]
    assert anomaly["saved"] == "/ckpt/0000d00002"
    assert anomaly["good_dispatch"] == 2
    flight = json.load(open(os.path.join(str(tmp_path),
                                         "flight_anomaly.json")))
    assert flight["reason"] == "anomaly"
    # the dump follows the emit: the ring's tail is the anomaly itself
    assert flight["events"][-1]["type"] == "anomaly"


def test_monitor_warn_mode_and_unpolluted_windows(tmp_path):
    """action=warn reports reasons without raising — and an anomalous
    reading must NOT be folded into the trailing windows (a poisoned
    median would mask the next fault)."""
    log = open_event_log(str(tmp_path))
    mon = HealthMonitor(log, every=1, grad_factor=10.0, action="warn")
    pulls = [0]
    for i in range(HealthMonitor.MIN_GRAD_HISTORY):
        assert mon.observe(_reading(pulls, grad_sq=1.0 + 0.01 * i),
                           epoch=0, dispatch=i + 1) is None
    spike = _reading(pulls, grad_sq=1e8)  # norm 1e4 >> 10x median ~1
    reasons = mon.observe(spike, epoch=0, dispatch=99)
    assert reasons and reasons[0].startswith("grad_explode")
    # same spike again: the median did NOT absorb the anomaly
    reasons2 = mon.observe(_reading(pulls, grad_sq=1e8),
                           epoch=0, dispatch=100)
    assert reasons2 and reasons2[0].startswith("grad_explode")
    log.close()


def test_monitor_loss_zscore_and_norm_overflow(tmp_path):
    """The loss z-score wire arms after MIN_LOSS_HISTORY clean readings;
    an f32 squared-sum overflow with every element finite surfaces as
    grad_norm_overflow (the count alone cannot see it)."""
    log = open_event_log(str(tmp_path))
    mon = HealthMonitor(log, every=1, loss_z=5.0, action="warn")
    pulls = [0]
    for i in range(HealthMonitor.MIN_LOSS_HISTORY):
        assert mon.observe(_reading(pulls, loss=1.0 + 0.01 * (i % 3)),
                           epoch=0, dispatch=i + 1) is None
    reasons = mon.observe(_reading(pulls, loss=50.0), epoch=0, dispatch=20)
    assert reasons and reasons[0].startswith("loss_z")

    mon2 = HealthMonitor(log, every=1, action="warn")
    reasons = mon2.observe(_reading(pulls, grad_sq=float("inf")),
                           epoch=0, dispatch=1)
    assert reasons == ["grad_norm_overflow"]
    log.close()


def test_monitor_rejects_unknown_action(tmp_path):
    with pytest.raises(ValueError):
        HealthMonitor(open_event_log(str(tmp_path)), action="explode")


# ---------------------------------------------------------------------------
# FlightRecorder — the crash-time ring
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_and_buffered_events(tmp_path):
    """The ring sees every emit AT EMIT TIME — including buffered kinds
    the JSONL flush cadence has not written yet — and keeps only the
    last K. The dump is the rc!=0 artifact."""
    log = open_event_log(str(tmp_path), flush_every=10_000)
    ring = FlightRecorder(str(tmp_path / "dumps"), capacity=4)
    log.attach_ring(ring)
    for i in range(6):
        log.set_step(i)
        log.emit("step", batch=i)
    # nothing on disk yet (buffered), but the ring holds the last 4
    assert report.load_events(str(tmp_path)) == []
    snap = ring.snapshot()
    assert [e["batch"] for e in snap] == [2, 3, 4, 5]
    path = ring.dump("stall")
    payload = json.load(open(path))
    assert payload["reason"] == "stall" and payload["last_step"] == 5
    assert [e["batch"] for e in payload["events"]] == [2, 3, 4, 5]
    # repeat dumps for one reason overwrite (the log keeps full history)
    log.emit("stall", stalled_s=1.0)
    assert ring.dump("stall") == path
    assert json.load(open(path))["events"][-1]["type"] == "stall"
    log.close()


def test_flight_recorder_dump_is_best_effort(tmp_path):
    """Every dump caller sits on a failure path (watchdog thread, heal,
    the crash handler's re-raise): an unwritable obs dir must log and
    return None, never raise over the error being diagnosed."""
    target = tmp_path / "blocked"
    target.write_text("a FILE where the dump dir should go")
    ring = FlightRecorder(str(target))  # makedirs/open will fail
    ring.append({"type": "step", "step": 1})
    assert ring.dump("crash") is None


# ---------------------------------------------------------------------------
# torn JSONL tails + env fingerprint
# ---------------------------------------------------------------------------

def test_report_skips_torn_tail_with_warning(tmp_path, capsys):
    """SIGKILL mid-append leaves a partial final line: fold the intact
    prefix, warn about the tear, never raise."""
    log = open_event_log(str(tmp_path))
    log.emit("epoch", epoch=0, metrics={})
    log.close()
    with open(log.path, "a", encoding="utf-8") as fh:
        fh.write('{"type": "step", "t_wall": 17')  # torn mid-append
    events = report.load_events(str(tmp_path))
    assert len(events) == 1 and events[0]["type"] == "epoch"
    assert "torn tail" in capsys.readouterr().err


def test_ledger_skips_torn_tail_with_warning(tmp_path, capsys):
    path = str(tmp_path / "ledger.jsonl")
    perf_ledger.append_rows(path, [perf_ledger.normalize_row(
        "c4", {"img_s_per_chip": 1.0}, round_=1, sha="abc", source="test")])
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"config": "c4_r101", "img_s')
    rows = perf_ledger.load_rows(path)
    assert len(rows) == 1 and rows[0]["config"] == "c4"
    assert "torn tail" in capsys.readouterr().err


def test_env_fingerprint_in_run_meta_and_ledger_rows():
    """jax/jaxlib versions + git_dirty ride run_meta and propagate from
    a bench blob down onto every ledger row — the environment-drift
    attribution fields."""
    env = env_fingerprint()
    assert env["jax_version"] and env["jaxlib_version"]
    assert isinstance(env["git_dirty"], bool)
    meta = run_meta_fields()
    for k in ("jax_version", "jaxlib_version", "git_dirty"):
        assert meta[k] == env[k]

    blob = {"value": 2.0, "metric": "img/s/chip", "mfu": 0.3,
            "jax_version": "9.9.9", "jaxlib_version": "9.9.8",
            "git_dirty": False,
            "detail": {"c4_r101_b2": {"img_s_per_chip": 2.0}}}
    rows = perf_ledger.rows_from_artifact(blob, round_=1, sha="abc")
    assert len(rows) == 2
    for row in rows:
        assert row["jax_version"] == "9.9.9"
        assert row["jaxlib_version"] == "9.9.8"
        assert row["git_dirty"] is False


# ---------------------------------------------------------------------------
# report fold of health/anomaly events
# ---------------------------------------------------------------------------

def test_report_folds_health_and_anomaly():
    events = [
        {"type": "run_meta", "jax_version": "0.4.0", "jaxlib_version":
         "0.4.1", "git_dirty": True, "config_digest": "d" * 16},
        {"type": "health", "step": 2, "epoch": 0, "dispatch": 2,
         "loss": 1.5, "loss_z": None, "grad_norm": 3.0,
         "nonfinite": {"grad/float32": 0}},
        {"type": "health", "step": 4, "epoch": 0, "dispatch": 4,
         "loss": 1.4, "loss_z": 0.3, "grad_norm": 2.5,
         "nonfinite": {"grad/float32": 12}},
        {"type": "anomaly", "step": 4, "epoch": 0, "dispatch": 4,
         "reasons": ["nonfinite:grad/float32=12"], "loss": 1.4,
         "saved": "/ckpt/0000d00003", "flight": "/obs/flight_anomaly.json"},
    ]
    summary = report.summarize(events)
    assert summary["health"]["checks"] == 2
    assert summary["health"]["nonfinite_checks"] == 1
    assert summary["health"]["last"]["grad_norm"] == 2.5
    assert summary["anomalies"][0]["reasons"] == [
        "nonfinite:grad/float32=12"]
    assert summary["run"]["git_dirty"] is True

    text = report.render(summary)
    assert "health:     2 reading(s), 1 with nonfinites" in text
    assert "ANOMALY" in text and "0000d00003" in text

    blob = report.bench_blob(summary)
    assert blob["anomaly_count"] == 1 and blob["health_checks"] == 2
    assert blob["jax_version"] == "0.4.0" and blob["git_dirty"] is True


# ---------------------------------------------------------------------------
# integration: the tiny fit — zero extra executables, nan matrix
# ---------------------------------------------------------------------------

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


@pytest.mark.compile_heavy
def test_health_adds_zero_executables_and_zero_syncs():
    """The HLO/transfer acceptance gate, on the step itself: with
    health=True the train step is still ONE compiled executable — one
    jit cache entry, same count as health=False (the reductions fuse
    into the same program; no separate health program) — and reading
    the pulse to host compiles NOTHING further. The pure-observer claim
    (health outputs never perturb the update) is gated end to end by
    the nan matrix below: each health-ON resumed run must reach params
    BIT-exact vs a health-OFF uninterrupted baseline."""
    import jax

    from mx_rcnn_tpu.models.faster_rcnn import build_model, init_params
    from mx_rcnn_tpu.obs import compile_track
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step
    from mx_rcnn_tpu.train.optimizer import build_optimizer

    cfg = driver.tiny_config()
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=10)
    batch = _tiny_batch()
    rng = jax.random.PRNGKey(11)

    step_on = make_train_step(model, cfg, donate=False, health=True)
    s_on, m_on, pulse = step_on(create_train_state(params, tx), batch, rng)
    assert step_on._cache_size() == 1

    # the cadenced device→host read of the pulse piggybacks on outputs
    # the step already produced: no compile, finite clean numbers
    with compile_track.count() as cc:
        vals = {k: float(v) for k, v in pulse.items()}
    assert cc.n == 0 and step_on._cache_size() == 1
    assert all(v == 0 for k, v in vals.items() if k.endswith("/nf"))
    assert math.isfinite(vals["loss"])
    assert vals["grad/tree/sq"] > 0 and vals["update/tree/sq"] > 0
    assert math.isfinite(float(m_on["TotalLoss"]))


def _tiny_batch():
    """One 64^2 synthetic train batch."""
    import jax.numpy as jnp

    rs = np.random.RandomState(3)
    gt = np.zeros((1, 4, 4), np.float32)
    gt[:, 0] = [8, 8, 40, 40]
    valid = np.zeros((1, 4), bool)
    valid[:, 0] = True
    classes = np.zeros((1, 4), np.int32)
    classes[:, 0] = 1
    return {
        "image": jnp.asarray(rs.randn(1, 64, 64, 3).astype(np.float32)),
        "im_info": jnp.asarray([[64, 64, 1.0]], np.float32),
        "gt_boxes": jnp.asarray(gt),
        "gt_classes": jnp.asarray(classes),
        "gt_valid": jnp.asarray(valid),
    }


def _nan_gate(tmp_path, monkeypatch, compute, params_u):
    """The graftpulse acceptance matrix body: chaos nan_at_step=5 (2x3
    dispatch grid: dispatch 2 of epoch 1) poisons the final gradients
    in-graph; health_every=1 must catch it AT that dispatch, leave an
    anomaly event + flight dump + an emergency checkpoint of the last
    known-good state (after step 4 = epoch 1 dispatch 1), and
    ``--resume auto`` — chaos disarmed — must reach final params
    BIT-exact vs an uninterrupted run."""
    monkeypatch.setenv(chaos.ENV_VAR, "nan_at_step=5")
    chaos.reset()
    obs_dir = str(tmp_path / "obs_nan")
    prefix = str(tmp_path / "run")
    with pytest.raises(NumericsAnomaly) as ei:
        driver.run_fit(prefix, compute=compute,
                       obs_dir=obs_dir, health_every=1)
    assert "--resume auto" in str(ei.value)

    events = report.load_events(obs_dir)
    anomalies = [e for e in events if e["type"] == "anomaly"]
    assert len(anomalies) == 1
    a = anomalies[0]
    assert any(r.startswith("nonfinite:") for r in a["reasons"])
    assert a["good_epoch"] == 1 and a["good_dispatch"] == 1
    assert a["saved"] and a["saved"].endswith("0001d00001")
    # the four clean checks before the poisoned one folded cleanly
    health = [e for e in events if e["type"] == "health"]
    assert len(health) == 5
    assert all(v == 0 for e in health[:4]
               for v in e["nonfinite"].values())
    assert any(v > 0 for v in health[-1]["nonfinite"].values())

    # flight dumps: the anomaly ring (tail = the anomaly record) and the
    # crash dump of the aborting run
    flight = json.load(open(os.path.join(obs_dir, "flight_anomaly.json")))
    assert flight["events"][-1]["type"] == "anomaly"
    assert any(e["type"] == "health" for e in flight["events"])
    assert os.path.isfile(os.path.join(obs_dir, "flight_crash.json"))

    # the report names the anomaly (the runbook's first read)
    summary = report.summarize(events)
    assert summary["anomalies"][0]["saved"] == a["saved"]

    # resume bit-exact from the known-good step
    monkeypatch.delenv(chaos.ENV_VAR)
    chaos.reset()
    params_r = driver.run_fit(prefix, compute=compute,
                              resume="auto",
                              obs_dir=str(tmp_path / "obs_resumed"),
                              health_every=1)
    _assert_trees_bitexact(params_u, params_r)


@pytest.mark.compile_heavy
def test_nan_tripwire_resume_tree_f32(tmp_path, monkeypatch,
                                      tree_f32_baseline):
    _nan_gate(tmp_path, monkeypatch, compute="f32",
              params_u=tree_f32_baseline)


@pytest.mark.compile_heavy
def test_nan_tripwire_resume_tree_bf16(tmp_path, monkeypatch, bf16_baseline):
    _nan_gate(tmp_path, monkeypatch, compute="bf16", params_u=bf16_baseline)
