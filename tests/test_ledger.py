"""graftprof perf ledger (mx_rcnn_tpu/obs/ledger.py) gates.

Unit layer: artifact normalization (partial.json / printed line / driver
wrapper), append/load round-trip, show rendering, and the check gate's
best-prior regression math with an injected regression.

Acceptance layer (tier-1): the COMMITTED seed history — the rounds 1–5
rows kept as tests/fixtures/bench_history_seed.jsonl (older installation,
not re-measured) — must exist, contain the known trajectory
(c4_r101_b2 peaking at 46.019 img/s / MFU 0.2811 in round 4, the round-5
rc=124 error row), and `python -m mx_rcnn_tpu.obs.ledger
check` must flag an injected >10% throughput regression against it with
a non-zero exit code. stdlib-only — no jax in any of these tests.
"""

import json
import os
import subprocess
import sys

import pytest

from mx_rcnn_tpu.obs import ledger

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# normalization + round-trip
# ---------------------------------------------------------------------------

def test_rows_from_partial_json_shape(tmp_path):
    detail = {"c4": {"img_s_per_chip": 40.0, "mfu": 0.25, "step_ms": 25.0,
                     "hbm_bytes": 1.2e9, "pad_waste": 0.08,
                     "compile_s": 3.5, "n_executables": 1,
                     "reps_img_s": [40.0]},
              "bad": {"error": "RuntimeError: cell failed"}}
    rows = ledger.rows_from_artifact(detail, round_=7, sha="cafe1234",
                                     source="partial.json")
    by_cfg = {r["config"]: r for r in rows}
    assert by_cfg["c4"]["img_s_per_chip"] == 40.0
    assert by_cfg["c4"]["mfu"] == 0.25
    assert by_cfg["c4"]["hbm_bytes"] == 1.2e9
    assert by_cfg["c4"]["compile_s"] == 3.5
    assert "reps_img_s" not in by_cfg["c4"]  # only ledger fields carry over
    assert by_cfg["c4"]["round"] == 7 and by_cfg["c4"]["git_sha"] == "cafe1234"
    assert by_cfg["bad"]["error"].startswith("RuntimeError")


def test_rows_from_driver_wrapper_and_failed_round():
    ok = {"n": 4, "rc": 0, "parsed": {
        "metric": "m", "value": 46.0, "mfu": 0.28,
        "headline_config": "c4_b2",
        "detail": {"c4_b2": {"img_s_per_chip": 46.0, "mfu": 0.28}}}}
    rows = ledger.rows_from_artifact(ok)
    assert rows[0]["config"] == "headline"
    assert rows[0]["img_s_per_chip"] == 46.0
    assert rows[0]["headline_config"] == "c4_b2"
    assert rows[1]["config"] == "c4_b2" and rows[1]["round"] == 4
    # rc=124 with no parsed output (the BENCH_r05 shape) stays visible
    dead = ledger.rows_from_artifact({"n": 5, "rc": 124, "parsed": None})
    assert dead[0]["config"] == "headline" and "rc=124" in dead[0]["error"]


def test_append_load_show_roundtrip(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    assert ledger.load_rows(path) == []
    n = ledger.append_rows(path, [
        ledger.normalize_row("c4", {"img_s_per_chip": 40.0, "mfu": 0.25},
                             round_=3),
        ledger.normalize_row("c4", {"img_s_per_chip": 44.0, "mfu": 0.27},
                             round_=4),
    ])
    assert n == 2
    rows = ledger.load_rows(path)
    assert [r["round"] for r in rows] == [3, 4]
    # torn tail write (killed appender) is skipped, not fatal
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"config": "torn')
    assert len(ledger.load_rows(path)) == 2
    out = ledger.render_show(rows)
    assert "c4" in out and "40.000" in out and "0.2700" in out
    assert ledger.render_show(rows, config="nope").startswith(
        "perf ledger: no rows")


def test_check_flags_injected_regression():
    history = [
        ledger.normalize_row("c4", {"img_s_per_chip": 40.0, "mfu": 0.25},
                             round_=3),
        ledger.normalize_row("c4", {"img_s_per_chip": 44.0, "mfu": 0.27},
                             round_=4),
        ledger.normalize_row("c4", {"error": "rc=124"}, round_=5),
    ]
    # within 10% of the best prior (44.0 / 0.27): clean
    ok = [ledger.normalize_row("c4", {"img_s_per_chip": 42.0, "mfu": 0.26},
                               round_=6)]
    assert ledger.check_rows(history, ok, threshold=0.10) == []
    # >10% below best throughput: flagged, naming the best prior round
    bad = [ledger.normalize_row("c4", {"img_s_per_chip": 35.0, "mfu": 0.26},
                                round_=6)]
    problems = ledger.check_rows(history, bad, threshold=0.10)
    assert len(problems) == 1
    assert "img_s_per_chip" in problems[0] and "round 4" in problems[0]
    # an MFU-only regression is flagged independently of throughput
    bad_mfu = [ledger.normalize_row(
        "c4", {"img_s_per_chip": 44.0, "mfu": 0.20}, round_=6)]
    assert any("mfu" in p for p in
               ledger.check_rows(history, bad_mfu, threshold=0.10))
    # no prior history → first measurement IS the baseline
    fresh = [ledger.normalize_row("new_cfg", {"img_s_per_chip": 1.0},
                                  round_=6)]
    assert ledger.check_rows(history, fresh) == []
    # error candidates (failed rows) are skipped, not graded
    err = [ledger.normalize_row("c4", {"error": "boom"}, round_=6)]
    assert ledger.check_rows(history, err) == []


def test_check_never_compares_across_compute_dtypes():
    """graftcast: rows are graded only against prior rows of the SAME
    compute dtype. Pre-graftcast rows (no field) count as bf16 — the
    only dtype the repo ran before round 8."""
    history = [
        # pre-graftcast row: implicitly bf16
        ledger.normalize_row("c4", {"img_s_per_chip": 44.0, "mfu": 0.28},
                             round_=4),
        ledger.normalize_row("c4", {"img_s_per_chip": 25.0, "mfu": 0.30,
                                    "compute_dtype": "f32"}, round_=6),
    ]
    assert ledger.row_dtype(history[0]) == "bf16"
    # an f32 candidate at half the bf16 throughput is NOT a regression —
    # its bar is the f32 row, not the bf16 one
    f32_cand = [ledger.normalize_row(
        "c4", {"img_s_per_chip": 24.0, "mfu": 0.29,
               "compute_dtype": "f32"}, round_=7)]
    assert ledger.check_rows(history, f32_cand, threshold=0.10) == []
    # a bf16 candidate is graded against the bf16 best (44.0), and the
    # faster f32-relative number cannot hide the drop
    bf16_cand = [ledger.normalize_row(
        "c4", {"img_s_per_chip": 30.0, "mfu": 0.27,
               "compute_dtype": "bf16"}, round_=7)]
    problems = ledger.check_rows(history, bf16_cand, threshold=0.10)
    assert problems and "round 4" in problems[0]
    # best_prior with an explicit dtype never crosses over
    best = ledger.best_prior(history, "c4", dtype="f32")
    assert best["img_s_per_chip"][0] == 25.0


def test_check_default_splits_latest_round():
    rows = [
        ledger.normalize_row("c4", {"img_s_per_chip": 44.0}, round_=4),
        ledger.normalize_row("c4", {"img_s_per_chip": 30.0}, round_=6),
    ]
    history, candidates = ledger._latest_round_split(rows)
    assert [r["round"] for r in history] == [4]
    assert [r["round"] for r in candidates] == [6]
    assert ledger.check_rows(history, candidates)
    # unkeyed (round=None) rows are the NEWEST measurements — they must
    # be the candidate set, never silently skipped behind stale rounds
    rows.append(ledger.normalize_row("c4", {"img_s_per_chip": 28.0}))
    history, candidates = ledger._latest_round_split(rows)
    assert [r["round"] for r in candidates] == [None]
    assert len(history) == 2
    assert ledger.check_rows(history, candidates)


# ---------------------------------------------------------------------------
# the committed seed history + CLI contract (tier-1 acceptance)
# ---------------------------------------------------------------------------

def _cli(*args, ledger_path=None):
    cmd = [sys.executable, "-m", "mx_rcnn_tpu.obs.ledger"]
    if ledger_path:
        cmd += ["--ledger", ledger_path]
    return subprocess.run(cmd + list(args), cwd=REPO_ROOT,
                          capture_output=True, text=True, timeout=60)


SEED = os.path.join(REPO_ROOT, "tests", "fixtures",
                    "bench_history_seed.jsonl")


def _seed_rows():
    """The immutable rounds 1–5 history, a fixture of its own: bench.py
    appends live rows to bench_obs/history.jsonl, never here."""
    return ledger.load_rows(SEED)


def test_committed_seed_history_backfilled():
    """The seed fixture carries the rounds 1–5 history: the known
    trajectory points and the round-5 error row."""
    rows = _seed_rows()
    assert rows, f"{SEED} missing or empty"
    best = ledger.best_prior(rows, "c4_r101_b2")
    assert best["img_s_per_chip"][0] == pytest.approx(46.019)
    assert best["img_s_per_chip"][1]["round"] == 4
    assert best["mfu"][0] == pytest.approx(0.2811)
    rounds = {r.get("round") for r in rows}
    assert {1, 2, 3, 4, 5} <= rounds
    assert any(r.get("round") == 5 and r.get("error") for r in rows)


def test_ledger_check_cli_flags_regression_against_seed(tmp_path):
    """The acceptance gate: an injected >10% throughput regression vs
    the seed history exits non-zero through the real CLI; a row within
    tolerance exits 0. Runs against a copy of the seed fixture."""
    seed = tmp_path / "seed_ledger.jsonl"
    ledger.append_rows(str(seed), _seed_rows())

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps(
        {"c4_r101_b2": {"img_s_per_chip": 36.0, "mfu": 0.28}}))
    proc = _cli("check", "--candidate", str(bad), ledger_path=str(seed))
    assert proc.returncode == 1, (proc.stdout, proc.stderr)
    assert "REGRESSION" in proc.stdout and "c4_r101_b2" in proc.stdout

    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps(
        {"c4_r101_b2": {"img_s_per_chip": 47.1, "mfu": 0.285}}))
    proc = _cli("check", "--candidate", str(ok), ledger_path=str(seed))
    assert proc.returncode == 0, (proc.stdout, proc.stderr)

    # show renders the trajectory (the PERF.md reading aid): the r3/r4
    # points of the seed
    proc = _cli("show", "--config", "c4_r101_b2", ledger_path=str(seed))
    assert proc.returncode == 0
    assert "46.019" in proc.stdout and "0.2811" in proc.stdout

    # default mode on the seed slice: the latest round (5) is the rc=124
    # outage — an all-error candidate set must NOT read as a green gate
    # (rc 2, not 0)
    proc = _cli("check", ledger_path=str(seed))
    assert proc.returncode == 2, (proc.stdout, proc.stderr)
    assert "no gradable" in proc.stderr


def test_ledger_add_cli_roundtrip(tmp_path):
    led = str(tmp_path / "led.jsonl")
    src = tmp_path / "partial.json"
    src.write_text(json.dumps(
        {"cfg_a": {"img_s_per_chip": 10.0, "mfu": 0.1}}))
    proc = _cli("add", str(src), "--round", "9", ledger_path=led)
    assert proc.returncode == 0, proc.stderr
    rows = ledger.load_rows(led)
    assert rows[0]["config"] == "cfg_a" and rows[0]["round"] == 9
    assert rows[0]["git_sha"]  # stamped from .git by the CLI
