"""graftprof perf ledger — append-only cross-run performance history.

    python -m mx_rcnn_tpu.obs.ledger add FILE [--round N]
    python -m mx_rcnn_tpu.obs.ledger backfill WRAPPER.json [WRAPPER.json ...]
    python -m mx_rcnn_tpu.obs.ledger show [--config NAME]
    python -m mx_rcnn_tpu.obs.ledger check [--candidate FILE] [--threshold 0.1]

A loose per-round bench artifact is useful per round, invisible as a
trajectory, and nothing ever FAILS when a number regresses.
The ledger is the tracked, diffable record: one JSONL row per measured
config per round, keyed by (config, git sha, round), appended by
``bench.py`` as each row completes (``bench_obs/history.jsonl`` under
the checkout; ``MX_RCNN_PERF_LEDGER`` overrides). The root
``PERF_LEDGER.jsonl`` is NOT this file: that name is the driver's record,
which no code path of the repo writes.

- ``add`` appends rows from any bench artifact: a ``partial.json``
  detail dict, the printed bench JSON line, or a driver
  ``BENCH_r0N.json`` wrapper — all three shapes are normalized.
- ``backfill`` seeds history from driver wrappers (rounds and rc are
  taken from the wrapper; an rc=124 with no parsed output lands as an
  error row so it stays visible in the trajectory). The rounds 1–5 rows
  made this way live on as tests/fixtures/bench_history_seed.jsonl.
- ``show`` renders the per-config trajectory (round, img/s, MFU,
  step ms, HBM, pad waste, compile cost).
- ``check`` diffs candidate rows against the BEST prior row per config
  and exits non-zero on a throughput or MFU regression past the
  threshold (default 10%).

stdlib-only, like ``obs.report`` — a ledger can be appended/folded on
any machine the JSON can be copied to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

#: row fields copied verbatim from bench rows when present (everything
#: else a recipe emits stays in the source artifact, not the ledger).
_METRIC_FIELDS = (
    "img_s_per_chip", "mfu", "step_ms", "hbm_bytes", "pad_waste",
    "compile_s", "n_executables", "tree_ms",
    "ms_per_img", "error", "timeout_s", "compute_dtype",
    # environment-drift attribution (graftpulse satellite): a cross-run
    # regression should be pinnable to an env change — jaxlib upgrade,
    # uncommitted local patch — not just the git sha. bench.py stamps
    # the versions into every live row (own_device); blob-level
    # values propagate to rows in rows_from_artifact.
    "jax_version", "jaxlib_version", "git_dirty",
    # the device a row was measured on (bench.py::own_device): a number
    # is never read without it
    "platform", "device_kind", "device_count",
)
#: blob-level env fields copied down onto every row they wrap
_ENV_FIELDS = ("jax_version", "jaxlib_version", "git_dirty")
#: the two regression-gated metrics (higher is better for both)
_GATED = ("img_s_per_chip", "mfu")


def row_dtype(row: Dict[str, Any]) -> str:
    """A row's compute dtype for comparison purposes. Rows predating
    graftcast carry no field — they all ran the bf16 default (the only
    compute dtype the repo had), so missing means "bf16"."""
    return str(row.get("compute_dtype") or "bf16")


def default_path() -> str:
    """MX_RCNN_PERF_LEDGER, else bench_obs/history.jsonl under the repo
    root (resolved from this file — cwd-independent, like the lint
    settings). Never the root PERF_LEDGER.jsonl: that is the driver's."""
    env = os.environ.get("MX_RCNN_PERF_LEDGER")
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "bench_obs", "history.jsonl")


def load_rows(path: str) -> List[Dict[str, Any]]:
    """Parse the ledger JSONL; a torn tail line — SIGKILL mid-append —
    is skipped WITH a warning, never fatal (the shared
    obs.report.load_jsonl_tolerant contract)."""
    if not os.path.exists(path):
        return []
    from mx_rcnn_tpu.obs.report import load_jsonl_tolerant

    return load_jsonl_tolerant(path, hint="append")


def append_rows(path: str, rows: Iterable[Dict[str, Any]]) -> int:
    """Append rows as JSONL lines. Append-only by design: history is
    never rewritten, corrections are new rows."""
    rows = [r for r in rows if r]
    if not rows:
        return 0
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "a", encoding="utf-8") as fh:
        for r in rows:
            fh.write(json.dumps(r, sort_keys=True) + "\n")
    return len(rows)


def _git_sha() -> Optional[str]:
    from mx_rcnn_tpu.obs.events import _git_sha as sha_of

    return sha_of(os.path.dirname(os.path.abspath(__file__)))


def normalize_row(config: str, row: Dict[str, Any],
                  round_: Optional[int] = None, sha: Optional[str] = None,
                  source: Optional[str] = None) -> Dict[str, Any]:
    out: Dict[str, Any] = {"config": config, "round": round_,
                           "git_sha": sha, "t_wall": round(time.time(), 3)}
    if source:
        out["source"] = source
    for k in _METRIC_FIELDS:
        if k in row and row[k] is not None:
            out[k] = row[k]
    return out


def rows_from_artifact(blob: Any, round_: Optional[int] = None,
                       sha: Optional[str] = None,
                       source: Optional[str] = None) -> List[Dict[str, Any]]:
    """Normalize any bench artifact shape into ledger rows.

    Accepted: a driver wrapper ({n, rc, parsed}), the printed bench line
    ({metric, value, detail}), or a bare detail dict ({config: row}).
    A wrapper with no parsed payload (rc!=0 — the BENCH_r05 shape) lands
    as one error row so failed rounds stay on the trajectory."""
    if not isinstance(blob, dict):
        raise ValueError("bench artifact must be a JSON object")
    if "parsed" in blob or "rc" in blob:  # driver wrapper
        round_ = blob.get("n", round_)
        parsed = blob.get("parsed")
        if not parsed:
            return [dict(normalize_row("headline", {}, round_, sha, source),
                         error=f"rc={blob.get('rc')} (no parsed output)")]
        blob = parsed
    rows: List[Dict[str, Any]] = []
    env: Dict[str, Any] = {}
    if "value" in blob and "metric" in blob:  # printed bench line
        # blob-level env fingerprint (report.bench_blob): applies to
        # every row the blob wraps — copied down after normalization.
        env = {k: blob[k] for k in _ENV_FIELDS if k in blob}
        rows.append(normalize_row(
            "headline",
            {"img_s_per_chip": blob.get("value"), "mfu": blob.get("mfu")},
            round_, sha, source))
        if blob.get("headline_config"):
            rows[-1]["headline_config"] = blob["headline_config"]
        blob = blob.get("detail") or {}
    for config, row in blob.items():
        if isinstance(row, dict):
            rows.append(normalize_row(config, row, round_, sha, source))
    for r in rows:
        for k, v in env.items():
            r.setdefault(k, v)
    return rows


# ---------------------------------------------------------------------------
# show / check
# ---------------------------------------------------------------------------

def _fmt(v, width=9, prec=3):
    if v is None:
        return " " * (width - 1) + "-"
    if isinstance(v, float):
        return f"{v:{width}.{prec}f}"
    return f"{v!s:>{width}}"


def render_show(rows: List[Dict[str, Any]],
                config: Optional[str] = None) -> str:
    """The trajectory, grouped by config, rounds in order — read it
    top-to-bottom per config; the gated metrics are the first two
    numeric columns (see PERF.md's graftprof section)."""
    if config:
        rows = [r for r in rows if r.get("config") == config]
    if not rows:
        return "perf ledger: no rows" + (f" for config {config!r}"
                                         if config else "")
    by_cfg: Dict[str, List[Dict[str, Any]]] = {}
    for r in rows:
        by_cfg.setdefault(r.get("config", "?"), []).append(r)
    lines = [f"perf ledger — {len(rows)} row(s), "
             f"{len(by_cfg)} config(s)",
             f"{'config':22s} {'round':>5} {'dtype':>5} "
             f"{'img/s/chip':>10} {'mfu':>7} "
             f"{'step_ms':>8} {'hbm_GB':>7} {'pad_waste':>9} "
             f"{'compile_s':>9} {'sha':>8}"]
    for cfg in sorted(by_cfg):
        hist = sorted(by_cfg[cfg],
                      key=lambda r: (r.get("round") is None,
                                     r.get("round") or 0,
                                     r.get("t_wall") or 0))
        for r in hist:
            hbm = r.get("hbm_bytes")
            lines.append(
                f"{cfg:22s} {_fmt(r.get('round'), 5)} "
                f"{row_dtype(r):>5} "
                f"{_fmt(r.get('img_s_per_chip'), 10)} "
                f"{_fmt(r.get('mfu'), 7, 4)} {_fmt(r.get('step_ms'), 8, 2)} "
                f"{_fmt(hbm / 1e9 if hbm else None, 7, 2)} "
                f"{_fmt(r.get('pad_waste'), 9, 4)} "
                f"{_fmt(r.get('compile_s'), 9, 1)} "
                f"{(r.get('git_sha') or '-')[:8]:>8}"
                + (f"  ! {r['error']}" if r.get("error") else ""))
    return "\n".join(lines)


def best_prior(history: List[Dict[str, Any]], config: str,
               before_round: Optional[int] = None,
               dtype: Optional[str] = None
               ) -> Dict[str, Optional[Tuple[float, Dict[str, Any]]]]:
    """Best prior value per gated metric for ``config`` (optionally only
    rounds strictly before ``before_round``). 'Best' is per-metric: the
    throughput best and the MFU best may be different rows (b1 vs b2
    recipes trade them off). ``dtype`` restricts to rows of that compute
    dtype (graftcast): a bf16 row's ~2x throughput must not become the
    bar an f32 row is graded against, and an f32 row must not hide a
    bf16 regression — cross-dtype rows are simply not comparable."""
    out: Dict[str, Optional[Tuple[float, Dict[str, Any]]]] = {
        m: None for m in _GATED}
    for r in history:
        if r.get("config") != config or r.get("error"):
            continue
        if dtype is not None and row_dtype(r) != dtype:
            continue
        if (before_round is not None and r.get("round") is not None
                and r["round"] >= before_round):
            continue
        for m in _GATED:
            v = r.get(m)
            if isinstance(v, (int, float)) and (
                    out[m] is None or v > out[m][0]):
                out[m] = (float(v), r)
    return out


def check_rows(history: List[Dict[str, Any]],
               candidates: List[Dict[str, Any]],
               threshold: float = 0.10) -> List[str]:
    """Regression messages for every candidate metric more than
    ``threshold`` below the best prior row of the same config AND the
    same compute dtype (graftcast: a bf16 win must not mask an f32
    regression, and vice versa). Configs with no same-dtype prior
    history pass (first measurement IS the baseline)."""
    problems = []
    for cand in candidates:
        cfg = cand.get("config")
        if not cfg or cand.get("error"):
            continue
        prior = best_prior(history, cfg, before_round=cand.get("round"),
                           dtype=row_dtype(cand))
        for metric in _GATED:
            v = cand.get(metric)
            best = prior.get(metric)
            if best is None or not isinstance(v, (int, float)):
                continue
            best_v, best_row = best
            if best_v > 0 and v < (1.0 - threshold) * best_v:
                problems.append(
                    f"{cfg}: {metric} {v:g} is "
                    f"{100.0 * (1 - v / best_v):.1f}% below best prior "
                    f"{best_v:g} (round {best_row.get('round')}, "
                    f"sha {(best_row.get('git_sha') or '?')[:8]})")
    return problems


def latest_round(rows: List[Dict[str, Any]]) -> Optional[int]:
    rounds = [r["round"] for r in rows
              if isinstance(r.get("round"), int)]
    return max(rounds) if rounds else None


def _latest_round_split(rows: List[Dict[str, Any]]
                        ) -> Tuple[List[Dict[str, Any]],
                                   List[Dict[str, Any]]]:
    """History vs candidates for the no-`--candidate` check mode.
    Rows with ``round: null`` are UNKEYED appends (a bench run outside
    the driver) — they are the newest measurements and must be graded,
    not silently skipped; when present they are the candidate set and
    every keyed row is history. Otherwise the latest integer round is
    the candidate set (bench.py auto-derives the next round when
    MX_RCNN_BENCH_ROUND is unset, so this is the normal path)."""
    unkeyed = [r for r in rows if r.get("round") is None]
    if unkeyed:
        return [r for r in rows if r.get("round") is not None], unkeyed
    latest = latest_round(rows)
    if latest is None:
        return rows, []
    return ([r for r in rows if r.get("round") != latest],
            [r for r in rows if r.get("round") == latest])


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _load_artifact(path: str):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mx_rcnn_tpu.obs.ledger",
        description=__doc__.splitlines()[0])
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default: MX_RCNN_PERF_LEDGER or "
                         "bench_obs/history.jsonl under the repo root)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_add = sub.add_parser("add", help="append rows from a bench artifact")
    p_add.add_argument("source", help="partial.json / printed bench line / "
                                      "driver BENCH_r0N.json wrapper")
    p_add.add_argument("--round", type=int, default=None)
    p_back = sub.add_parser("backfill",
                            help="seed history from driver wrappers")
    p_back.add_argument("sources", nargs="+")
    p_show = sub.add_parser("show", help="render the trajectory")
    p_show.add_argument("--config", default=None)
    p_check = sub.add_parser("check", help="regression-gate candidate rows")
    p_check.add_argument("--candidate", default=None,
                         help="bench artifact to gate; default: the "
                              "ledger's latest round vs everything before")
    p_check.add_argument("--threshold", type=float, default=0.10,
                         help="allowed fractional drop (default 0.10)")
    args = ap.parse_args(argv)
    path = args.ledger or default_path()

    if args.cmd == "add":
        rows = rows_from_artifact(_load_artifact(args.source),
                                  round_=args.round, sha=_git_sha(),
                                  source=os.path.basename(args.source))
        n = append_rows(path, rows)
        print(f"appended {n} row(s) to {path}")
        return 0
    if args.cmd == "backfill":
        total = 0
        for src in args.sources:
            rows = rows_from_artifact(_load_artifact(src),
                                      source=os.path.basename(src))
            total += append_rows(path, rows)
        print(f"backfilled {total} row(s) from {len(args.sources)} "
              f"artifact(s) into {path}")
        return 0
    if args.cmd == "show":
        print(render_show(load_rows(path), config=args.config))
        return 0
    # check
    history = load_rows(path)
    if args.candidate:
        candidates = rows_from_artifact(_load_artifact(args.candidate),
                                        sha=_git_sha(),
                                        source=os.path.basename(
                                            args.candidate))
    else:
        history, candidates = _latest_round_split(history)
    gradable = [c for c in candidates if not c.get("error")
                and any(isinstance(c.get(m), (int, float)) for m in _GATED)]
    if not gradable:
        # An all-error/empty candidate set must not read as a green gate
        # (the r05 rc=124 shape: error rows are skipped by check_rows).
        print("perf ledger check: no gradable candidate rows "
              f"({len(candidates)} candidate(s), all error/metric-free)",
              file=sys.stderr)
        return 2
    problems = check_rows(history, candidates, threshold=args.threshold)
    if problems:
        print(f"perf ledger check: {len(problems)} regression(s) past "
              f"{args.threshold:.0%}:")
        for p in problems:
            print(f"  REGRESSION {p}")
        return 1
    print(f"perf ledger check: OK ({len(candidates)} candidate row(s) "
          f"within {args.threshold:.0%} of best prior)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
