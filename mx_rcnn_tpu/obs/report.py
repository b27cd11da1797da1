"""Fold a graftscope JSONL event stream into a run report.

    python -m mx_rcnn_tpu.obs.report RUN_DIR_OR_JSONL [--json OUT.json]

Prints a human summary (phase timing, throughput percentiles, compile
accounting, data-wait fraction, stalls/crashes) and optionally writes a
BENCH-compatible JSON blob (top-level metric/value/unit plus the full
summary as detail) that BENCH_*.json tooling and regression gates can
consume. stdlib-only — runs anywhere the JSONL can be copied to.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional


def load_jsonl_tolerant(path: str, hint: str = "run") -> List[Dict[str, Any]]:
    """Parse a JSONL file whose appends can race a kill: an unparseable
    line — the normal signature of SIGKILL mid-append — is skipped WITH
    a stderr warning naming the file and the byte offset of each torn
    line (a silently half-read stream would fold a killed run into a
    clean-looking artifact, and "somewhere in some stream" is useless
    when a fleet dir holds one JSONL per host), never fatal. Shared by
    this module's event streams and obs/ledger.py's perf rows (``hint``
    names what was being appended, for the warning). Binary read: byte
    offsets must be file offsets usable with ``tail -c``, not decoded
    character counts."""
    records = []
    torn_at: List[int] = []
    offset = 0
    with open(path, "rb") as fh:
        for raw in fh:
            line = raw.strip()
            if line:
                try:
                    records.append(json.loads(line.decode("utf-8")))
                except (json.JSONDecodeError, UnicodeDecodeError):
                    torn_at.append(offset)  # torn tail of a killed write
            offset += len(raw)
    if torn_at:
        where = ", ".join(f"byte {o}" for o in torn_at[:4])
        if len(torn_at) > 4:
            where += f", … ({len(torn_at)} total)"
        print(f"warning: {path}: skipped {len(torn_at)} unparseable "
              f"JSONL line(s) at {where} — torn tail of a killed "
              f"{hint}?", file=sys.stderr)
    return records


def event_streams(path: str) -> Dict[int, str]:
    """The per-host streams in a run dir: host index → file path.
    Discovers the grafttower names (``events_p<k>.jsonl``) and the
    pre-grafttower ones (``events.jsonl`` = host 0, ``events.<i>.jsonl``)
    so old run dirs keep folding."""
    streams: Dict[int, str] = {}
    for name in sorted(os.listdir(path)):
        idx = None
        if name.startswith("events_p") and name.endswith(".jsonl"):
            mid = name[len("events_p"):-len(".jsonl")]
            idx = int(mid) if mid.isdigit() else None
        elif name == "events.jsonl":
            idx = 0
        elif name.startswith("events.") and name.endswith(".jsonl"):
            mid = name[len("events."):-len(".jsonl")]
            idx = int(mid) if mid.isdigit() else None
        if idx is not None and idx not in streams:
            streams[idx] = os.path.join(path, name)
    return streams


def load_events(path: str) -> List[Dict[str, Any]]:
    """Parse one JSONL event file, or a run dir — folding EVERY per-host
    stream it holds (``events_p<k>.jsonl``, plus the legacy
    ``events.jsonl``/``events.<i>.jsonl`` names; see event_streams) into
    one list ordered by wall time, so a multi-host run's quorum/heal/
    preempt records interleave the way the fleet experienced them. Each
    record already carries its ``process`` stamp. Tolerates a torn tail
    line per stream (load_jsonl_tolerant). For the skew-corrected fleet
    timeline use ``--fleet`` / obs/fleet.py — wall order is only as
    honest as the hosts' clocks."""
    if not os.path.isdir(path):
        return load_jsonl_tolerant(path, hint="run")
    records: List[Dict[str, Any]] = []
    for _, stream_path in sorted(event_streams(path).items()):
        records.extend(load_jsonl_tolerant(stream_path, hint="run"))
    records.sort(key=lambda e: e.get("t_wall", 0.0))
    return records


def _percentile(sorted_vals: List[float], pct: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, round(pct / 100.0 * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _fold_costs(cost_events, timed,
                all_step_ms: List[float]) -> Dict[str, Any]:
    """Join per-bucket XLA cost accounting (graftprof `cost` events) with
    the measured step times of that bucket's canvas → per-bucket and
    aggregate MFU."""
    buckets = []
    agg_flops = agg_time_s = 0.0
    for c in cost_events:
        shapes = c.get("shapes") or {}
        img = shapes.get("image") or ()
        canvas = list(img[-3:-1]) if len(img) >= 3 else None
        in_bucket = sorted(
            e["step_ms"] for e in timed
            if canvas is None or e.get("canvas") == canvas) or all_step_ms
        p50 = _percentile(in_bucket, 50)
        flops = c.get("flops")
        peak = c.get("peak_flops") or 0.0
        step_s = p50 / 1e3
        mfu = (flops / step_s / peak
               if flops and step_s > 0 and peak > 0 else None)
        if flops and in_bucket and p50 > 0:
            agg_flops += flops * len(in_bucket)
            agg_time_s += (p50 / 1e3) * len(in_bucket)
        buckets.append({
            "canvas": canvas,
            # graftcast: the dtype this bucket's peak was chosen for —
            # MFUs from different compute dtypes must not be compared
            "compute_dtype": c.get("compute_dtype"),
            "flops": flops,
            "bytes_accessed": c.get("bytes_accessed"),
            "hbm_bytes": c.get("hbm_bytes"),
            "steps": len(in_bucket),
            "step_ms_p50": round(p50, 3),
            "mfu": round(mfu, 4) if mfu is not None else None,
        })
    peak = next((c.get("peak_flops") for c in cost_events
                 if c.get("peak_flops")), None)
    overall = (round(agg_flops / agg_time_s / peak, 4)
               if peak and agg_time_s > 0 and agg_flops > 0 else None)
    hbm = [b["hbm_bytes"] for b in buckets if b.get("hbm_bytes")]
    return {"buckets": buckets, "mfu": overall,
            "hbm_bytes": max(hbm) if hbm else None}


def summarize(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Fold an event list into the run summary dict (the --json payload's
    ``detail``). Keys are stable — BENCH tooling reads them."""
    by_type: Dict[str, List[Dict[str, Any]]] = {}
    for e in events:
        by_type.setdefault(e.get("type", "?"), []).append(e)

    run_meta = (by_type.get("run_meta") or [{}])[0]
    timed = [e for e in by_type.get("step", ()) if "step_ms" in e]
    speed = [e["samples_per_sec"] for e in by_type.get("step", ())
             if "samples_per_sec" in e]

    step_ms = sorted(e["step_ms"] for e in timed)
    total_step_ms = sum(step_ms)
    data_wait_ms = sorted(e.get("data_wait_ms", 0.0) for e in timed)
    total_wait_ms = sum(data_wait_ms)

    batch_size = run_meta.get("batch_size")
    # Throughput: prefer the Speedometer's measured windows (they bracket
    # the MetricBag drain, i.e. real end-to-end time); else derive from
    # the per-step median and the run_meta batch size.
    p50 = _percentile(step_ms, 50)
    if speed:
        img_s = _percentile(sorted(speed), 50)
    elif batch_size and p50 > 0:
        img_s = batch_size * 1000.0 / p50
    else:
        img_s = None

    compiles = [e for e in by_type.get("compile", ())
                if e.get("phase") == "backend_compile"]
    # A compile after the first completed step is a steady-state
    # recompile — the silent throughput killer the tracker exists for.
    recompiles = [e for e in compiles if e.get("step", 0) >= 1]

    # graftprof: per-bucket cost accounting joined with measured step
    # time → computed MFU (obs/costs.py emits one `cost` event per
    # compiled shape bucket; step events carry the batch canvas).
    cost = _fold_costs(by_type.get("cost", ()), timed, step_ms)
    pad_vals = sorted(e["pad_waste"] for e in timed if "pad_waste" in e)
    pad_waste = (round(_percentile(pad_vals, 50), 4) if pad_vals else None)

    crash = (by_type.get("crash") or [None])[-1]
    # graftpulse: cadenced numerics readings + tripped anomalies
    health_evs = by_type.get("health", ())
    last_health = health_evs[-1] if health_evs else None
    snapshots = by_type.get("snapshot", ())
    roi_levels = (by_type.get("roi_levels") or [{}])[-1]
    rpn_targets = (by_type.get("rpn_targets") or [None])[-1]
    mask_rois = (by_type.get("mask_rois") or [None])[-1]
    summary: Dict[str, Any] = {
        "run": {k: run_meta.get(k) for k in
                ("config_digest", "network", "dataset", "mesh",
                 "jax_version", "jaxlib_version", "git_dirty", "backend",
                 "device_count", "git_sha", "batch_size",
                 "steps_per_epoch", "prefix", "tool", "compute_dtype")
                if k in run_meta},
        "events": len(events),
        "steps": len(timed),
        "epochs": len(by_type.get("epoch", ())),
        "throughput": {
            "img_s": round(img_s, 3) if img_s is not None else None,
            "step_ms_p50": round(p50, 3),
            "step_ms_p90": round(_percentile(step_ms, 90), 3),
            "step_ms_max": round(step_ms[-1], 3) if step_ms else 0.0,
        },
        "data_wait": {
            "ms_p50": round(_percentile(data_wait_ms, 50), 3),
            "fraction": (round(total_wait_ms / total_step_ms, 4)
                         if total_step_ms else 0.0),
        },
        "compile": {
            "count": len(compiles),
            "total_ms": round(sum(e.get("duration_ms", 0.0)
                                  for e in compiles), 3),
            "steady_state_count": len(recompiles),
            "steady_state_shapes": [e.get("shapes") for e in recompiles],
        },
        "cost": cost,
        "pad_waste": pad_waste,
        "traces": [{"dir": e.get("dir"), "reason": e.get("reason"),
                    "summary": e.get("summary")}
                   for e in by_type.get("trace", ())],
        "checkpoints": len(by_type.get("checkpoint", ())),
        "evals": [e.get("results") for e in by_type.get("eval", ())],
        "bench": {e.get("config", f"cfg{i}"):
                  {k: v for k, v in e.items()
                   if k not in ("type", "t_wall", "t_mono", "process",
                                "step", "config")}
                  for i, e in enumerate(by_type.get("bench", ()))},
        "stalls": len(by_type.get("stall", ())),
        # graftpulse: how many health readings the run folded, how many
        # saw a nonfinite count, and the last reading's numbers (the
        # first thing the "run went nonfinite" runbook reads).
        "health": {
            "checks": len(health_evs),
            "nonfinite_checks": sum(
                1 for e in health_evs
                if any((e.get("nonfinite") or {}).values())),
            "last": ({k: last_health.get(k) for k in
                      ("loss", "loss_z", "grad_norm", "nonfinite")}
                     if last_health else None),
        },
        "anomalies": [{"step": e.get("step"), "epoch": e.get("epoch"),
                       "dispatch": e.get("dispatch"),
                       "reasons": e.get("reasons"),
                       "saved": e.get("saved"), "flight": e.get("flight")}
                      for e in by_type.get("anomaly", ())],
        # graftguard: how hard the backend fought acquisition, and whether
        # the run was preempted (OUTAGES.md reads these three lines first).
        "backend": {
            "retries": len(by_type.get("backend_retry", ())),
            "retry_wait_s": round(sum(
                e.get("sleep_s", 0.0)
                for e in by_type.get("backend_retry", ())), 3),
            "last_error": (by_type["backend_retry"][-1].get("error")
                           if by_type.get("backend_retry") else None),
        },
        "preempts": [{"signal": e.get("signal"), "step": e.get("step"),
                      "saved": e.get("saved")}
                     for e in by_type.get("preempt", ())],
        # graftheal: in-run recoveries — how often the backend was lost
        # mid-run, how long the run was down for it, and any elastic
        # shrink transitions (device count before -> after).
        "heals": {
            "count": len(by_type.get("heal", ())),
            "downtime_s": round(sum(e.get("downtime_s", 0.0)
                                    for e in by_type.get("heal", ())), 3),
            "shrinks": [f"{e.get('devices_before')}->"
                        f"{e.get('devices_after')}"
                        for e in by_type.get("heal", ())
                        if e.get("devices_before") is not None
                        and e.get("devices_after") is not None
                        and e["devices_before"] != e["devices_after"]],
            "last_error": (by_type["heal"][-1].get("error")
                           if by_type.get("heal") else None),
            # the periodic host snapshots a loss would roll back to: how
            # many were installed, the longest one was in flight
            # (dispatches: what it adds to the replay bound) and the most
            # of the loop's time one took
            "snapshots": len(snapshots),
            "snapshot_in_flight_max": max(
                (e.get("in_flight", 0) for e in snapshots), default=None),
            "snapshot_loop_ms_max": max(
                (e.get("loop_ms", 0.0) for e in snapshots), default=None),
        },
        # pyramid families: the first dispatch's sampled rois by the level
        # FPN Eq. 1 pools each from (P2..P5), None for the others
        "roi_level_share": roi_levels.get("share"),
        # ... and the form of the pooling: the canvas (rows, columns) the
        # levels are stacked into, the poolings a call makes of each roi
        "roi_pooling": ({k: roi_levels[k] for k in ("canvas", "poolings")}
                        if "canvas" in roi_levels else None),
        "rpn_targets": rpn_targets and {k: rpn_targets.get(k) for k in (
            "slots_walked", "slots_padded", "kept_pos", "kept_neg")},
        # the mask branch: the first dispatch's live rois of its slots
        "mask_rois": mask_rois and {k: mask_rois.get(k) for k in (
            "slots", "per_image_min", "per_image_mean", "per_image_max",
            "share")},
        # graftquorum: multi-host coordination rounds — per-host records
        # interleaved by load_events, so `hosts` is how many distinct
        # process stamps the fold saw and `excluded` collects every host
        # any round sealed out (the "who got dropped" runbook line).
        "quorum": {
            "rounds": len(by_type.get("quorum", ())),
            "hosts": len({e.get("process", 0) for e in events}),
            "excluded": sorted({h for e in by_type.get("quorum", ())
                                for h in (e.get("excluded") or ())}),
            "last": ({k: by_type["quorum"][-1].get(k) for k in
                      ("kind", "hosts", "excluded", "agreed", "spec")}
                     if by_type.get("quorum") else None),
        },
        # graftfeed: input-plane fault accounting — which records were
        # quarantined (and what replaced them), how often transient IO
        # was retried, and whether any prefetch workers died mid-run.
        # OUTAGES.md's "the data plane broke" runbook reads this fold.
        "data": {
            "quarantined": [
                {"record": e.get("record"), "epoch": e.get("epoch"),
                 "replacement": e.get("replacement"),
                 "reason": e.get("reason")}
                for e in by_type.get("data", ())
                if e.get("kind") == "quarantine"],
            "retries": sum(1 for e in by_type.get("data", ())
                           if e.get("kind") == "retry"),
            "retry_wait_s": round(sum(
                e.get("sleep_s", 0.0) for e in by_type.get("data", ())
                if e.get("kind") == "retry"), 3),
            "reapplied": sum(e.get("count", 0)
                             for e in by_type.get("data", ())
                             if e.get("kind") == "quarantine_applied"),
            "cap_trips": sum(1 for e in by_type.get("data", ())
                             if e.get("kind") == "quarantine_cap"),
            "worker_deaths": len(by_type.get("data_worker", ())),
            "worker_resurrections": sum(
                1 for e in by_type.get("data_worker", ())
                if e.get("resurrected")),
        },
        "crash": ({"error": crash.get("error"), "step": crash.get("step")}
                  if crash else None),
    }
    return summary


def bench_blob(summary: Dict[str, Any]) -> Dict[str, Any]:
    """BENCH-compatible wrapper: one headline metric line + full detail."""
    img_s = summary["throughput"]["img_s"]
    return {
        "metric": "graftscope_train_img_per_sec",
        "value": img_s if img_s is not None else 0.0,
        "unit": "img/s",
        "steps": summary["steps"],
        "compile_count": summary["compile"]["count"],
        "compile_total_ms": summary["compile"]["total_ms"],
        "data_wait_fraction": summary["data_wait"]["fraction"],
        "stall_count": summary["stalls"],
        "backend_retries": summary["backend"]["retries"],
        "heal_count": summary["heals"]["count"],
        # graftfeed: quarantine pressure and worker churn belong on the
        # same ledger row — a throughput regression with nonzero
        # data_retries is a storage problem, not a model problem.
        "data_quarantined": len(summary["data"]["quarantined"]),
        "data_retries": summary["data"]["retries"],
        "data_worker_deaths": summary["data"]["worker_deaths"],
        # graftprof: the computed-MFU / HBM / padding numbers regression
        # gates (obs/ledger.py) track alongside throughput.
        "mfu": summary["cost"]["mfu"],
        "hbm_bytes": summary["cost"]["hbm_bytes"],
        "pad_waste": summary["pad_waste"],
        # graftpulse: anomaly accounting + the environment-drift fields
        # (jax/jaxlib/git_dirty ride into ledger rows via this blob, so
        # a cross-run regression is attributable to env change too).
        "anomaly_count": len(summary["anomalies"]),
        "health_checks": summary["health"]["checks"],
        # grafttower (--fleet folds only): the skew/wait aggregate, so
        # multi-host ledger rows carry "how lockstep was the fleet"
        # next to throughput (obs/fleet.py).
        **({"fleet_skew_p50_s": summary["fleet"]["skew"]["p50_s"],
            "fleet_skew_p90_s": summary["fleet"]["skew"]["p90_s"],
            "fleet_barrier_wait_s":
                summary["fleet"]["barriers"]["total_wait_s"],
            "fleet_straggler": summary["fleet"]["straggler"],
            "fleet_hung_hosts": summary["fleet"]["hung"]}
           if "fleet" in summary else {}),
        **{k: summary["run"][k]
           for k in ("jax_version", "jaxlib_version", "git_dirty")
           if k in summary["run"]},
        "detail": summary,
    }


def render(summary: Dict[str, Any]) -> str:
    run = summary["run"]
    tp = summary["throughput"]
    dw = summary["data_wait"]
    co = summary["compile"]
    lines = [
        "graftscope run report",
        "  run:        " + ", ".join(
            f"{k}={v}" for k, v in run.items()) if run else "  run:        -",
        f"  events:     {summary['events']} "
        f"({summary['steps']} steps, {summary['epochs']} epochs, "
        f"{summary['checkpoints']} checkpoints, "
        f"{len(summary['evals'])} evals)",
        f"  throughput: {tp['img_s']} img/s | step p50 {tp['step_ms_p50']} "
        f"ms, p90 {tp['step_ms_p90']} ms, max {tp['step_ms_max']} ms",
        f"  data wait:  p50 {dw['ms_p50']} ms ({dw['fraction']:.1%} of "
        "step time)",
        f"  compiles:   {co['count']} ({co['total_ms']:.0f} ms total), "
        f"{co['steady_state_count']} in steady state",
        f"  stalls:     {summary['stalls']}",
    ]
    cost = summary.get("cost") or {}
    if cost.get("buckets"):
        hbm = cost.get("hbm_bytes")
        lines.append(
            f"  cost:       mfu {cost.get('mfu')} | hbm "
            f"{hbm / 1e9:.2f} GB | {len(cost['buckets'])} bucket(s): "
            + ", ".join(
                f"{b.get('canvas')} mfu={b.get('mfu')}"
                for b in cost["buckets"])
            if hbm else
            f"  cost:       mfu {cost.get('mfu')} | "
            f"{len(cost['buckets'])} bucket(s)")
    if summary.get("pad_waste") is not None:
        # Canvas utilization (real/canvas px) rides next to MFU above:
        # graftcanvas packed-vs-bucketed runs grade both in one report.
        lines.append(f"  pad waste:  {summary['pad_waste']:.1%} of canvas "
                     f"pixels (p50) | canvas util "
                     f"{1.0 - summary['pad_waste']:.1%}")
    for t in summary.get("traces", ()):
        folded = t.get("summary") or {}
        lines.append(f"  trace:      [{t.get('reason')}] {t.get('dir')}"
                     + (f" stages(ms)={folded['stages']} "
                        f"unscoped(ms)={folded.get('unscoped_ms')}"
                        if folded.get("stages") else ""))
    be = summary.get("backend", {})
    if be.get("retries"):
        lines.append(
            f"  backend:    {be['retries']} transient failure(s), "
            f"{be['retry_wait_s']:.0f}s backing off | last: "
            f"{be['last_error']}")
    for p in summary.get("preempts", ()):
        lines.append(f"  preempt:    signal {p['signal']} at step "
                     f"{p['step']} (emergency save: {p['saved']})")
    hl = summary.get("health", {})
    if hl.get("checks"):
        last = hl.get("last") or {}
        z = last.get("loss_z")
        lines.append(
            f"  health:     {hl['checks']} reading(s), "
            f"{hl['nonfinite_checks']} with nonfinites | last: loss "
            f"{last.get('loss')}"
            + (f" (z {z})" if z is not None else "")
            + f", grad norm {last.get('grad_norm')}")
    for a in summary.get("anomalies", ()):
        lines.append(
            f"  ANOMALY:    epoch {a.get('epoch')} dispatch "
            f"{a.get('dispatch')}: {'; '.join(a.get('reasons') or ())} | "
            f"checkpoint {a.get('saved')} | flight {a.get('flight')}")
    he = summary.get("heals", {})
    if he.get("count"):
        shrink = (", shrink " + ", ".join(he["shrinks"])
                  if he.get("shrinks") else "")
        lines.append(
            f"  heal:       {he['count']} in-run recover(ies), "
            f"{he['downtime_s']:.0f}s down{shrink} | last: "
            f"{he['last_error']}")
    if he.get("snapshots"):
        lines.append(
            f"  snapshots:  {he['snapshots']} installed, in flight <= "
            f"{he['snapshot_in_flight_max']} dispatch(es), <= "
            f"{he['snapshot_loop_ms_max']:.1f} ms of the loop each")
    if summary.get("rpn_targets"):
        rt = summary["rpn_targets"]
        lines.append(
            f"  rpn targets: walked {rt['slots_walked']} of "
            f"{rt['slots_padded']} gt slots, kept {rt['kept_pos']} "
            f"positives and {rt['kept_neg']} negatives at the first "
            f"dispatch")
    if summary.get("roi_level_share"):
        lines.append("  roi levels: " + ", ".join(
            f"P{lv} {100 * s:.1f}%" for lv, s in
            enumerate(summary["roi_level_share"], start=2))
            + " of the first dispatch's sampled rois")
    if summary.get("roi_pooling"):
        rp = summary["roi_pooling"]
        lines.append(
            f"  roi pooling: {rp['poolings']} a call of each roi, from a "
            f"canvas of {rp['canvas'][0]}x{rp['canvas'][1]} cells")
    if summary.get("mask_rois"):
        mr = summary["mask_rois"]
        lines.append(
            f"  mask rois:  {mr['per_image_min']} / {mr['per_image_mean']} / "
            f"{mr['per_image_max']} (min / mean / max an image) of "
            f"{mr['slots']} branch slots live at the first dispatch; "
            + ", ".join(f"P{lv} {100 * s:.1f}%" for lv, s in
                        enumerate(mr["share"], start=2)))
    da = summary.get("data", {})
    if (da.get("quarantined") or da.get("retries")
            or da.get("worker_deaths") or da.get("cap_trips")):
        recs = ", ".join(str(q["record"]) for q in da["quarantined"][:8])
        more = (f" (+{len(da['quarantined']) - 8} more)"
                if len(da["quarantined"]) > 8 else "")
        lines.append(
            f"  data:       {len(da['quarantined'])} record(s) "
            f"quarantined{': ' + recs + more if recs else ''} | "
            f"{da['retries']} IO retr(ies), {da['retry_wait_s']:.0f}s "
            f"backing off | {da['worker_deaths']} worker death(s), "
            f"{da['worker_resurrections']} resurrected"
            + (f" | CAP TRIPPED x{da['cap_trips']}"
               if da.get("cap_trips") else ""))
    qu = summary.get("quorum", {})
    if qu.get("rounds"):
        last = qu.get("last") or {}
        excl = (f", excluded hosts {qu['excluded']}" if qu.get("excluded")
                else "")
        lines.append(
            f"  quorum:     {qu['rounds']} coordination round(s) across "
            f"{qu['hosts']} host stream(s){excl} | last: "
            f"kind={last.get('kind')} hosts={last.get('hosts')}")
    for name, row in summary["bench"].items():
        lines.append(f"  bench:      {name}: {row}")
    if summary["crash"]:
        lines.append(f"  CRASH:      step {summary['crash']['step']}: "
                     f"{summary['crash']['error']}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m mx_rcnn_tpu.obs.report",
        description=__doc__.splitlines()[0])
    ap.add_argument("path", help="run directory (holding per-host "
                                 "events_p<k>.jsonl streams) or a JSONL "
                                 "file")
    ap.add_argument("--fleet", action="store_true",
                    help="grafttower fold: merge every host stream onto "
                         "one skew-corrected fleet timeline and append "
                         "the straggler/barrier/heartbeat report "
                         "(obs/fleet.py; path must be a run dir)")
    ap.add_argument("--json", dest="json_out", default=None,
                    metavar="OUT.json",
                    help="also write the BENCH-compatible JSON blob here")
    args = ap.parse_args(argv)
    if args.fleet:
        from mx_rcnn_tpu.obs import fleet

        if not os.path.isdir(args.path):
            print(f"error: --fleet needs a run directory of per-host "
                  f"streams, got {args.path}", file=sys.stderr)
            return 2
        try:
            hosts = {idx: load_jsonl_tolerant(p, hint="run")
                     for idx, p in event_streams(args.path).items()}
        except OSError as exc:
            print(f"error: cannot read {args.path}: {exc}",
                  file=sys.stderr)
            return 2
        if not hosts:
            print(f"error: no event streams in {args.path}",
                  file=sys.stderr)
            return 2
        events = fleet.merge_streams(hosts)
        summary = summarize(events)
        summary["fleet"] = fleet.fleet_summary(hosts)
        print(render(summary))
        print(fleet.render_fleet(summary["fleet"]))
    else:
        try:
            events = load_events(args.path)
        except OSError as exc:
            print(f"error: cannot read {args.path}: {exc}",
                  file=sys.stderr)
            return 2
        summary = summarize(events)
        print(render(summary))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as fh:
            json.dump(bench_blob(summary), fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json_out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
