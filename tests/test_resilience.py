"""graftguard (mx_rcnn_tpu/resilience) gates — the round-5 postmortem as tests.

Every failure mode TPU_OUTAGE_r5.log / BENCH_r05 / VERDICT.md recorded is
injected here deterministically (resilience/chaos.py) and must be survived:

- classified backend acquisition: injected UNAVAILABLE xN -> the run
  proceeds after backoff with ``backend_retry`` events; a permanent error
  fails fast; the deadline bounds an endless outage.
- deadline-isolated benching: a hung config forfeits ONE row (a structured
  timeout row in partial.json), never the sweep (the rc=124 lesson).
- preemption-safe training: SIGTERM mid-epoch -> emergency checkpoint +
  resumable rc 75, and ``--resume auto`` reaches BIT-exact final params vs
  an uninterrupted run, under f32 and bf16 compute.
- atomic checkpoints: SIGKILL inside the save's crash window leaves only a
  ``*.tmp-*`` dir no resume path ever considers.

All tests carry the ``chaos`` marker (script/smoke_resilience.sh runs just
this subset); they are tier-1 (NOT slow) — waiting for a real outage to
exercise recovery code is how round 5 happened.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mx_rcnn_tpu.config import ResilienceConfig
from mx_rcnn_tpu.obs import open_event_log, report
from mx_rcnn_tpu.resilience import (
    RESUMABLE_RC,
    BackendUnavailableError,
    PreemptionExit,
    PreemptionGuard,
    acquire_backend,
    chaos,
    classify_backend_error,
)
from mx_rcnn_tpu.resilience.isolate import run_with_deadline
from mx_rcnn_tpu.train.checkpoint import (
    checkpoint_name,
    latest_checkpoint,
    latest_epoch,
    load_checkpoint,
)

import _resilience_driver as driver

pytestmark = pytest.mark.chaos

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DRIVER = os.path.join(REPO_ROOT, "tests", "_resilience_driver.py")

UNAVAILABLE_MSG = "UNAVAILABLE: TPU backend setup/compile error (Unavailable)."


def _subprocess_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MX_RCNN_CHAOS", None)
    env.update(extra)
    return env


@pytest.fixture(autouse=True)
def _fresh_chaos(monkeypatch):
    """No injection leaks between tests (or in from the outer env)."""
    monkeypatch.delenv(chaos.ENV_VAR, raising=False)
    chaos.reset()
    yield
    chaos.reset()


# ---------------------------------------------------------------------------
# chaos spec parsing
# ---------------------------------------------------------------------------

def test_chaos_parse_roundtrip():
    spec = chaos.parse("backend_unavailable=3, sigterm_at_step=5 "
                       "hang_bench=c4_r101 hang_s=2.5 "
                       "die_at=checkpoint_finalize backend_permanent=true")
    assert spec.backend_unavailable == 3 and spec.sigterm_at_step == 5
    assert spec.hang_bench == "c4_r101" and spec.hang_s == 2.5
    assert spec.die_at == "checkpoint_finalize" and spec.backend_permanent
    assert spec.active


def test_chaos_unset_is_inert():
    spec = chaos.from_env(environ={})
    assert not spec.active
    # every hook is a no-op (site names must still be REGISTERED ones —
    # the chaos-site-name lint rule holds for tests too)
    spec.maybe_fail_backend()
    spec.maybe_sigterm(10_000)
    spec.maybe_hang("anything")
    spec.maybe_die("checkpoint_finalize")
    spec.maybe_device_loss(10_000)
    assert spec.maybe_shrink(["d0", "d1"]) == ["d0", "d1"]
    spec.fire("train_dispatch", step=10_000)
    assert chaos.site("backend_reacquire",
                      devices=["d0", "d1"]) == ["d0", "d1"]
    fire = spec.fire  # aliased: exercising the RUNTIME check, not lint
    with pytest.raises(ValueError, match="unregistered chaos site"):
        fire("not_a_site")


def test_chaos_rejects_unknown_key_and_bad_value():
    """A typo'd injection silently doing nothing would un-test the gate
    it was written for — parse must be loud."""
    with pytest.raises(ValueError, match="known keys"):
        chaos.parse("backend_unavailible=3")
    with pytest.raises(ValueError):
        chaos.parse("backend_unavailable=lots")
    with pytest.raises(ValueError, match="boolean"):
        chaos.parse("backend_permanent=treu")  # must not coerce to False
    assert not chaos.parse("backend_permanent=false").backend_permanent


# ---------------------------------------------------------------------------
# classified backend acquisition (acceptance gate a)
# ---------------------------------------------------------------------------

def test_classify_backend_error():
    assert classify_backend_error(RuntimeError(UNAVAILABLE_MSG)) == "transient"
    assert classify_backend_error(
        RuntimeError("DEADLINE_EXCEEDED: backend slow")) == "transient"
    assert classify_backend_error(
        RuntimeError("ABORTED: backend restarting")) == "transient"
    assert classify_backend_error(
        RuntimeError("INVALID_ARGUMENT: bad topology")) == "permanent"
    assert classify_backend_error(ValueError("nonsense")) == "permanent"


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_acquire_retries_transient_with_exponential_backoff(tmp_path):
    """UNAVAILABLE x3 -> backs off 2,4,8 (base 2, jitter 0), emits one
    backend_retry per failure + backend_up, and returns the devices —
    exactly what the round-5 watcher did by hand for 11 hours, minus the
    hand and the fixed cadence."""
    rcfg = ResilienceConfig(backend_deadline_s=1000.0,
                            backend_backoff_base_s=2.0,
                            backend_backoff_max_s=300.0,
                            backend_backoff_jitter=0.0)
    clock = _FakeClock()
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock.t += s

    calls = {"n": 0}

    def probe():
        calls["n"] += 1
        if calls["n"] <= 3:
            raise RuntimeError(UNAVAILABLE_MSG)
        return ["dev0", "dev1"]

    elog = open_event_log(str(tmp_path))
    devices = acquire_backend(rcfg, elog=elog, probe=probe, sleep=sleep,
                              clock=clock)
    elog.close()
    assert devices == ["dev0", "dev1"] and calls["n"] == 4
    assert sleeps == [2.0, 4.0, 8.0]

    events = report.load_events(str(tmp_path))
    retries = [e for e in events if e["type"] == "backend_retry"]
    ups = [e for e in events if e["type"] == "backend_up"]
    assert len(retries) == 3 and len(ups) == 1
    assert [r["attempt"] for r in retries] == [1, 2, 3]
    assert "UNAVAILABLE" in retries[-1]["error"]
    assert ups[0]["attempts"] == 4 and ups[0]["device_count"] == 2
    # the obs.report fold OUTAGES.md tells operators to read
    summary = report.summarize(events)
    assert summary["backend"]["retries"] == 3
    assert summary["backend"]["retry_wait_s"] == pytest.approx(14.0)
    assert "UNAVAILABLE" in summary["backend"]["last_error"]
    assert report.bench_blob(summary)["backend_retries"] == 3


def test_acquire_backoff_caps_and_respects_deadline():
    """An outage that outlasts backend_deadline_s raises
    BackendUnavailableError (chained to the last transient error), and no
    single sleep overshoots the deadline."""
    rcfg = ResilienceConfig(backend_deadline_s=10.0,
                            backend_backoff_base_s=4.0,
                            backend_backoff_max_s=8.0,
                            backend_backoff_jitter=0.0)
    clock = _FakeClock()
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock.t += s

    def probe():
        raise RuntimeError(UNAVAILABLE_MSG)

    with pytest.raises(BackendUnavailableError, match="3 attempts") as ei:
        acquire_backend(rcfg, probe=probe, sleep=sleep, clock=clock)
    assert isinstance(ei.value.__cause__, RuntimeError)
    # 4, then min(cap 8, remaining 6): never sleeps past the deadline
    assert sleeps == [4.0, 6.0]


class _Dev:
    def __init__(self, platform):
        self.platform = platform


def test_acquire_detects_silent_platform_fallback(monkeypatch):
    """jax can come up on the CPU when the accelerator is not there — the probe
    then 'succeeds' instantly with the wrong device list. With
    resilience.backend_platform set, that fallback classifies as a
    transient outage (backend cache cleared so later probes can see the
    recovered accelerator) and retries until the expected platform appears."""
    from mx_rcnn_tpu.resilience import backend as backend_mod

    clears = []
    monkeypatch.setattr(backend_mod, "_clear_backend_cache",
                        lambda: clears.append(1))
    rcfg = ResilienceConfig(backend_platform="tpu",
                            backend_deadline_s=100.0,
                            backend_backoff_base_s=1.0,
                            backend_backoff_jitter=0.0)
    clock = _FakeClock()
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        clock.t += s

    calls = {"n": 0}

    def probe():
        calls["n"] += 1
        if calls["n"] <= 2:
            return [_Dev("cpu")]  # the silent-fallback device list
        return [_Dev("tpu")]

    devices = acquire_backend(rcfg, probe=probe, sleep=sleep, clock=clock)
    assert [d.platform for d in devices] == ["tpu"] and calls["n"] == 3
    assert sleeps == [1.0, 2.0] and len(clears) == 2

    # an all-fallback outage still hits the deadline like any other
    rcfg = ResilienceConfig(backend_platform="tpu", backend_deadline_s=3.0,
                            backend_backoff_base_s=2.0,
                            backend_backoff_jitter=0.0)
    with pytest.raises(BackendUnavailableError) as ei:
        acquire_backend(rcfg, probe=lambda: [_Dev("cpu")], sleep=sleep,
                        clock=clock)
    assert "without a 'tpu' device" in str(ei.value.__cause__)
    # and unset (the default: CPU tests/dev boxes) accepts whatever came up
    devices = acquire_backend(ResilienceConfig(), probe=lambda: [_Dev("cpu")],
                              sleep=sleep, clock=clock)
    assert [d.platform for d in devices] == ["cpu"]


def test_acquire_permanent_fails_fast():
    """Retrying an INVALID_ARGUMENT for hours is how a
    misconfigured run burns a deadline — the original error propagates
    on attempt 1 with zero sleeps."""
    rcfg = ResilienceConfig()
    sleeps = []

    def probe():
        raise RuntimeError("INVALID_ARGUMENT: bad topology")

    with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
        acquire_backend(rcfg, probe=probe, sleep=sleeps.append)
    assert sleeps == []


def test_acquire_through_chaos_env(monkeypatch, tmp_path):
    """The acceptance-gate wiring end to end: MX_RCNN_CHAOS arms the
    DEFAULT probe (the one train/eval/bench use), the injected outage
    rides through classified retry, and the run proceeds."""
    monkeypatch.setenv(chaos.ENV_VAR, "backend_unavailable=2")
    chaos.reset()
    rcfg = ResilienceConfig(backend_deadline_s=60.0,
                            backend_backoff_base_s=0.01,
                            backend_backoff_max_s=0.02)
    elog = open_event_log(str(tmp_path))
    devices = acquire_backend(rcfg, elog=elog, sleep=lambda s: None)
    elog.close()
    assert len(devices) >= 1  # the real (cpu) backend, post-outage
    events = report.load_events(str(tmp_path))
    assert sum(e["type"] == "backend_retry" for e in events) == 2
    assert sum(e["type"] == "backend_up" for e in events) == 1


def test_acquire_through_chaos_env_permanent(monkeypatch):
    monkeypatch.setenv(chaos.ENV_VAR, "backend_permanent=1")
    chaos.reset()
    with pytest.raises(RuntimeError, match="INVALID_ARGUMENT"):
        acquire_backend(ResilienceConfig(), sleep=lambda s: None)


# ---------------------------------------------------------------------------
# PreemptionGuard
# ---------------------------------------------------------------------------

def test_preemption_exit_carries_resumable_rc():
    assert RESUMABLE_RC == 75  # BSD EX_TEMPFAIL — the supervisor contract
    exc = PreemptionExit(signal.SIGTERM)
    assert isinstance(exc, SystemExit) and exc.code == RESUMABLE_RC
    assert exc.signum == signal.SIGTERM


def test_guard_records_real_sigterm_and_restores_handlers():
    prev_term = signal.getsignal(signal.SIGTERM)
    prev_int = signal.getsignal(signal.SIGINT)
    guard = PreemptionGuard()
    with guard:
        assert not guard.requested
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(200):  # delivery is near-immediate in-thread
            if guard.requested:
                break
            time.sleep(0.005)
        assert guard.requested and guard.signum == signal.SIGTERM
    assert signal.getsignal(signal.SIGTERM) is prev_term
    assert signal.getsignal(signal.SIGINT) is prev_int


def test_guard_second_sigint_is_immediate():
    """The first Ctrl-C asks for an orderly save; the second means NOW."""
    guard = PreemptionGuard()
    guard._handle(signal.SIGINT, None)
    assert guard.requested and guard.signum == signal.SIGINT
    with pytest.raises(KeyboardInterrupt):
        guard._handle(signal.SIGINT, None)


def test_guard_inert_off_main_thread():
    results = []
    t = threading.Thread(target=lambda: results.append(
        PreemptionGuard().install()))
    t.start()
    t.join()
    assert results == [False]


# ---------------------------------------------------------------------------
# checkpoint name grammar / resume-point discovery
# ---------------------------------------------------------------------------

def test_checkpoint_name_grammar_and_ordering(tmp_path):
    assert checkpoint_name(7) == "0007"
    assert checkpoint_name(3, 12) == "0003d00012"
    for d in ("0001", "0001d00003", "0000d00005", "0002.tmp-123",
              "checkpoint_junk"):
        (tmp_path / d).mkdir()
    # emergency (1,3) outranks boundary (1,-) == (1,0); tmp/junk invisible
    assert latest_checkpoint(str(tmp_path)) == (1, 3)
    # the pre-graftguard contract ignores emergency saves entirely
    assert latest_epoch(str(tmp_path)) == 1
    (tmp_path / "0002").mkdir()
    assert latest_checkpoint(str(tmp_path)) == (2, None)
    assert latest_epoch(str(tmp_path)) == 2


def test_latest_checkpoint_empty(tmp_path):
    assert latest_checkpoint(str(tmp_path)) is None
    assert latest_checkpoint(str(tmp_path / "never_made")) is None


# ---------------------------------------------------------------------------
# deadline isolation (acceptance gate b)
# ---------------------------------------------------------------------------

def test_run_with_deadline_returns_child_row():
    row = run_with_deadline(driver.sweep_runner, "cfg_a", timeout_s=60.0,
                            label="cfg_a")
    assert row == {"img_s_per_chip": 1.0, "which": "cfg_a"}


def test_run_with_deadline_kills_hung_child():
    t0 = time.monotonic()
    row = run_with_deadline(driver.sleepy_runner, "hung", timeout_s=3.0,
                            label="hung")
    assert row["timeout_s"] == 3.0 and "deadline" in row["error"]
    assert time.monotonic() - t0 < 30.0  # killed, not waited out


def test_run_with_deadline_reports_child_error():
    row = run_with_deadline(driver.error_runner, "boom", timeout_s=60.0,
                            label="boom")
    assert row == {"error": "RuntimeError: cell dropped mid-measure (boom)"}


def test_sweep_survives_injected_hang(monkeypatch, tmp_path):
    """THE BENCH_r05 gate: chaos hangs config "b" past its deadline; the
    sweep records a structured timeout row for it and still completes
    "a" and "c", all three durable in partial.json."""
    import bench

    monkeypatch.setenv(chaos.ENV_VAR, "hang_bench=b hang_s=120")
    flush = str(tmp_path / "partial.json")
    detail = bench.run_sweep({"a": "a", "b": "b", "c": "c"},
                             driver.sweep_runner, flush_path=flush,
                             timeout_s=8.0)
    assert detail["a"] == {"img_s_per_chip": 1.0, "which": "a"}
    assert detail["c"] == {"img_s_per_chip": 1.0, "which": "c"}
    assert detail["b"]["timeout_s"] == 8.0 and "error" in detail["b"]
    with open(flush, encoding="utf-8") as fh:
        on_disk = json.load(fh)
    assert set(on_disk) == {"a", "b", "c"}
    assert on_disk["b"]["timeout_s"] == 8.0


# ---------------------------------------------------------------------------
# atomic checkpoint publication (satellite: crash-window test)
# ---------------------------------------------------------------------------

def test_checkpoint_crash_window_leaves_nothing_resumable(tmp_path):
    """SIGKILL between the full orbax write and the publishing rename
    (chaos site ``checkpoint_finalize``): the prefix holds only a
    ``*.tmp-*`` dir, which NO resume path considers — then a clean save
    of the same tree publishes and loads."""
    prefix = str(tmp_path / "ck")
    proc = subprocess.run(
        [sys.executable, DRIVER, "--crash-save", prefix],
        env=_subprocess_env(MX_RCNN_CHAOS="die_at=checkpoint_finalize"),
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=240)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode, proc.stderr)
    leftovers = os.listdir(prefix)
    assert leftovers and all(".tmp-" in d for d in leftovers), leftovers
    assert latest_epoch(prefix) is None
    assert latest_checkpoint(prefix) is None

    proc = subprocess.run(
        [sys.executable, DRIVER, "--crash-save", prefix],
        env=_subprocess_env(), cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr
    assert latest_epoch(prefix) == 1
    # the clean save also swept the dead process's abandoned tmp dir
    assert not any(".tmp-" in d for d in os.listdir(prefix))
    expect = np.arange(6, dtype=np.float32).reshape(2, 3)
    loaded, _ = load_checkpoint(prefix, 1,
                                template={"w": np.zeros_like(expect)})
    np.testing.assert_array_equal(loaded["w"], expect)


def test_checkpoint_resave_crash_preserves_previous_good(tmp_path):
    """A re-save of an EXISTING checkpoint dir must never destroy the
    previous good copy before the new one is published: SIGKILL at the
    ``checkpoint_swap`` site (old set aside, new not yet renamed in)
    leaves the old data recoverable at ``<name>.old`` — never a window
    where an rmtree'd checkpoint is simply gone — and the next clean
    save publishes and cleans up every leftover."""
    prefix = str(tmp_path / "ck")
    proc = subprocess.run(
        [sys.executable, DRIVER, "--crash-save", prefix],
        env=_subprocess_env(), cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr

    proc = subprocess.run(
        [sys.executable, DRIVER, "--crash-save", prefix, "--scale", "3"],
        env=_subprocess_env(MX_RCNN_CHAOS="die_at=checkpoint_swap"),
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=240)
    assert proc.returncode == -signal.SIGKILL, (proc.returncode, proc.stderr)
    names = os.listdir(prefix)
    expect = np.arange(6, dtype=np.float32).reshape(2, 3)
    # the old data survived the crash (outside the resume name grammar)
    assert "0001.old" in names and "0001" not in names, names
    assert latest_checkpoint(prefix) is None

    proc = subprocess.run(
        [sys.executable, DRIVER, "--crash-save", prefix, "--scale", "3"],
        env=_subprocess_env(), cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr
    assert latest_epoch(prefix) == 1
    assert sorted(os.listdir(prefix)) == ["0001"]  # aside + tmps cleaned
    loaded, _ = load_checkpoint(prefix, 1,
                                template={"w": np.zeros_like(expect)})
    np.testing.assert_array_equal(loaded["w"], 3 * expect)


# ---------------------------------------------------------------------------
# preemption-safe training (acceptance gate c): kill -> resume parity
# ---------------------------------------------------------------------------

def _assert_trees_bitexact(a, b):
    import jax

    la = jax.tree_util.tree_leaves_with_path(a)
    lb = {jax.tree_util.keystr(p): v
          for p, v in jax.tree_util.tree_leaves_with_path(b)}
    assert len(la) == len(lb)
    for path, va in la:
        np.testing.assert_array_equal(np.asarray(va),
                                      np.asarray(lb[jax.tree_util.keystr(path)]),
                                      err_msg=jax.tree_util.keystr(path))


def _parity(tmp_path, monkeypatch, compute="f32", params_u=None):
    """SIGTERM at global step 4 (mid-epoch 1 of 2x3) -> PreemptionExit
    rc 75 with a dispatch-tagged emergency save and a `preempt` event;
    --resume auto then reaches params BIT-exact vs uninterrupted
    (``params_u`` supplies a precomputed uninterrupted baseline — the
    session-scope bf16 one is shared with test_heal.py)."""
    if params_u is None:
        params_u = driver.run_fit(str(tmp_path / "uninterrupted"),
                                  compute=compute)

    monkeypatch.setenv(chaos.ENV_VAR, "sigterm_at_step=4")
    chaos.reset()
    obs_dir = str(tmp_path / "obs_interrupted")
    with pytest.raises(PreemptionExit) as ei:
        driver.run_fit(str(tmp_path / "killed"), obs_dir=obs_dir,
                       compute=compute)
    assert ei.value.code == RESUMABLE_RC
    assert latest_checkpoint(str(tmp_path / "killed")) == (1, 1)
    assert os.path.isdir(tmp_path / "killed" / "0001d00001")
    preempts = [e for e in report.load_events(obs_dir)
                if e["type"] == "preempt"]
    assert len(preempts) == 1 and preempts[0]["step"] == 4
    assert preempts[0]["saved"].endswith("0001d00001")

    monkeypatch.delenv(chaos.ENV_VAR)
    chaos.reset()
    obs_resumed = str(tmp_path / "obs_resumed")
    params_r = driver.run_fit(str(tmp_path / "killed"),
                              resume="auto", obs_dir=obs_resumed,
                              compute=compute)
    _assert_trees_bitexact(params_u, params_r)
    # telemetry indices CONTINUE at the skip point (dispatch 1 of the
    # interrupted epoch) — no double-use of batch numbers the
    # pre-preemption run already logged/emitted.
    resumed_e1 = sorted(e["batch"] for e in report.load_events(obs_resumed)
                        if e["type"] == "step" and e["epoch"] == 1)
    assert resumed_e1 == [1, 2], resumed_e1


@pytest.mark.compile_heavy
def test_kill_resume_parity_tree(tmp_path, monkeypatch, tree_f32_baseline):
    _parity(tmp_path, monkeypatch, params_u=tree_f32_baseline)


@pytest.mark.compile_heavy
def test_kill_resume_parity_bf16(tmp_path, monkeypatch, bf16_baseline):
    """graftcast under interruption: compute_dtype=bf16 — the emergency
    save is the f32 state, and the resumed run is still bit-exact vs an
    uninterrupted bf16 run (bf16 rounding is deterministic on a fixed
    backend)."""
    _parity(tmp_path, monkeypatch, compute="bf16", params_u=bf16_baseline)


@pytest.mark.compile_heavy
def test_preemption_rc_subprocess(tmp_path):
    """The process-level contract a supervisor sees: chaos SIGTERM at
    step 2 -> the driver exits rc 75 (not a crash, not rc 0), leaving a
    resumable emergency checkpoint behind."""
    prefix = str(tmp_path / "run")
    proc = subprocess.run(
        [sys.executable, DRIVER, "--fit", prefix, "--end-epoch", "2"],
        env=_subprocess_env(MX_RCNN_CHAOS="sigterm_at_step=2"),
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=570)
    assert proc.returncode == RESUMABLE_RC, (proc.returncode, proc.stderr)
    found = latest_checkpoint(prefix)
    assert found is not None and found[1] is not None, found
