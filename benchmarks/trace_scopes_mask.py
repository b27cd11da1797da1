"""The mask branch's device time, from the traced run's profile, by the
branch's OWN scope names.

The program (from PR 34 on) names the four parts of its mask branch with
``jax.named_scope``s of a second closed list,
``mx_rcnn_tpu/obs/profile.py::BRANCH_STAGES``: ``mask_align`` (around
``pyramid_roi_align``, whose own ``roi_align`` scope stays inside it),
``mask_head``, ``mask_targets``, ``mask_loss``. They are not among
``trace_scopes.STAGES`` (pinned, with its readers' ``workloads``, by
``tests/benchmarks/test_bm_trace_scopes.py``), so ``trace_scopes.fold`` sees
the branch's pooling as ``roi_align`` and the rest as unscoped; this module
reads the same trace again by the branch's names.

It reuses ``trace_scopes.read_xplane`` (hence ``with_paths``: instruction ->
``op_name`` from the trace's ``Hlo Proto``) and ``interval_of`` unchanged,
and the same rule of attribution: every nanosecond of a device's busy time
goes to at most one op, an op getting the part of its interval that no
earlier-started op covers; of a path that names several of the scopes the
innermost wins. It caches its fold on the run under a key of its own.

Silent, never raising: ``of_run`` is None for the CPU rehearsal (no device
trace), for a trace without a device plane, and for a program without the
scopes (the parent commit, a box-only cell).
"""

from __future__ import annotations

import glob
import os
import re

from benchmarks import trace_scopes

# the program's names (a copy: the benchmark must read a checkout whose
# program has none; tests hold it to obs/profile.py::BRANCH_STAGES)
BRANCH_STAGES = ("mask_align", "mask_head", "mask_targets", "mask_loss")
# which scopes each mask.<group>_ms.train metric adds up
GROUPS = {
    "align": ("mask_align",),
    "head": ("mask_head",),
    "loss": ("mask_targets", "mask_loss"),
}
_BRANCH_RX = re.compile(
    r"(?<![\w.\-])(" + "|".join(BRANCH_STAGES) + r")(?![\w.\-])")
CACHE_KEY = "_trace_scopes_mask"


def branch_of(path: str):
    """The innermost of the branch's scopes named in an op's path, or
    None."""
    hits = _BRANCH_RX.findall(path or "")
    return hits[-1] if hits else None


def fold(devices: dict, host_spans: list, modules=None):
    """``trace_scopes.fold``'s arguments -> {"step_runs", "busy_ns",
    "branch_ns": {scope: ns}}, the mean over the devices; None where there
    is no device op or no op under any of the scopes."""
    ops = [ev for evs in devices.values() for ev in evs]
    if not ops:
        return None
    (lo, hi), step_runs = trace_scopes.interval_of(ops, host_spans, modules)
    busy, branch_ns = 0, {}
    for evs in devices.values():
        cursor = lo
        for _, s, d, path in sorted(evs, key=lambda e: (e[1], e[2])):
            s, e = max(s, cursor), min(s + d, hi)
            if e <= s:
                continue
            cursor = e
            busy += e - s
            scope = branch_of(path)
            if scope is not None:
                branch_ns[scope] = branch_ns.get(scope, 0) + e - s
    if not branch_ns:
        return None
    n = len(devices)
    return {"step_runs": step_runs, "busy_ns": busy / n,
            "branch_ns": {k: branch_ns[k] / n for k in BRANCH_STAGES
                          if k in branch_ns}}


def of_run(run: dict):
    """The traced run's fold, parsed once and kept on the run; None where
    there is nothing to read."""
    if not run.get("trace") or not run.get("work"):
        return None
    if CACHE_KEY not in run:
        paths = glob.glob(os.path.join(run["work"], "trace", "**",
                                       "*.xplane.pb"), recursive=True)
        run[CACHE_KEY] = fold(*trace_scopes.read_xplane(
            max(paths, key=os.path.getmtime),
            run.get("chips", 1))) if paths else None
    return run[CACHE_KEY]


# -- what the readers under layer_metrics/ return ---------------------------

def branch_ms(run, group: str):
    """Device milliseconds a step of the group's scopes, forward and
    backward."""
    f = of_run(run)
    if not f or not f["step_runs"]:
        return None
    return sum(f["branch_ns"].get(s, 0) for s in GROUPS[group]) / 1e6 / f[
        "step_runs"]


def branch_share(run):
    """The four scopes' device time over the device's busy time, in %."""
    f = of_run(run)
    if not f or not f["busy_ns"]:
        return None
    return 100.0 * sum(f["branch_ns"].values()) / f["busy_ns"]
