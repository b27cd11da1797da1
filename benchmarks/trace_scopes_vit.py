"""The attention trunk's device time, from the traced run's profile, by the
trunk's OWN scope names.

The program names the parts of each ViT block with ``jax.named_scope``s of
a third closed list, ``mx_rcnn_tpu/obs/profile.py::VIT_STAGES``:
``window_attn`` (norm1 through the window unpartition of a windowed block),
``global_attn`` (norm1 through proj of a global block) and ``vit_mlp``
(norm2 through mlp2), all inside the ``backbone`` stage. They are not among
``trace_scopes.STAGES``, so ``trace_scopes.fold`` still counts the whole
trunk as ``backbone``; this module reads the same trace again by the
trunk's names, by ``trace_scopes_mask.py``'s rule: every nanosecond of a
device's busy time goes to at most one op, an op getting the part of its
interval that no earlier-started op covers; of a path that names several of
the scopes the innermost wins. It caches its fold on the run under a key of
its own.

Silent, never raising: ``of_run`` is None for the CPU rehearsal (no device
trace), for a trace without a device plane, and for a program without the
scopes (the parent commit, a convolutional trunk).
"""

from __future__ import annotations

import glob
import os
import re

from benchmarks import trace_scopes

# the program's names (a copy: the benchmark must read a checkout whose
# program has none; tests hold it to obs/profile.py::VIT_STAGES)
VIT_STAGES = ("window_attn", "global_attn", "vit_mlp")
_VIT_RX = re.compile(
    r"(?<![\w.\-])(" + "|".join(VIT_STAGES) + r")(?![\w.\-])")
CACHE_KEY = "_trace_scopes_vit"


def vit_of(path: str):
    """The innermost of the trunk's scopes named in an op's path, or
    None."""
    hits = _VIT_RX.findall(path or "")
    return hits[-1] if hits else None


def fold(devices: dict, host_spans: list, modules=None):
    """``trace_scopes.fold``'s arguments -> {"step_runs", "busy_ns",
    "scope_ns": {scope: ns}}, the mean over the devices; None where there
    is no device op or no op under any of the scopes."""
    ops = [ev for evs in devices.values() for ev in evs]
    if not ops:
        return None
    (lo, hi), step_runs = trace_scopes.interval_of(ops, host_spans, modules)
    busy, scope_ns = 0, {}
    for evs in devices.values():
        cursor = lo
        for _, s, d, path in sorted(evs, key=lambda e: (e[1], e[2])):
            s, e = max(s, cursor), min(s + d, hi)
            if e <= s:
                continue
            cursor = e
            busy += e - s
            scope = vit_of(path)
            if scope is not None:
                scope_ns[scope] = scope_ns.get(scope, 0) + e - s
    if not scope_ns:
        return None
    n = len(devices)
    return {"step_runs": step_runs, "busy_ns": busy / n,
            "scope_ns": {k: scope_ns[k] / n for k in VIT_STAGES
                         if k in scope_ns}}


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


def scope_ms(run, scope: str):
    """Device milliseconds a step of one scope, forward and backward; None
    where the trace has none of the trunk's scopes."""
    f = of_run(run)
    if not f or not f["step_runs"] or scope not in f["scope_ns"]:
        return None
    return f["scope_ns"][scope] / 1e6 / f["step_runs"]
