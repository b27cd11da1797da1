"""Every idle stretch of the traced interval laid to a cause: the step
already handed to the runtime and not yet started (launch lag), the loop's
own code holding the chip (host idle, by the ``train.*`` phase it was in),
or neither (unlaid).

- The interval, its step programs and the device's idle stretches are
  ``trace_scopes.fold``'s (so ``trace_reduce``'s): inside the harness's
  ``bench.traced`` span, from the start of the step program's first
  execution to the end of its last.
- *Launch lag.* Each execution of the step program on a device is paired
  with the ``train.enqueue`` span that launched it, in order from the
  capture's start: the harness starts the profiler on a drained device and
  drains it again before it stops, so the n-th enqueue is the n-th
  execution (a count that differs pairs nothing). An idle instant is lag
  when some step had been handed over (its enqueue had returned) and had
  not yet begun (its first op had not started): the chip waited with the
  work in the runtime's hands. Lag comes first, whatever host span the
  instant lies in.
- *Host idle.* Of the rest, a stretch whose midpoint lies inside a
  ``train.*`` span (the loop's phases, the step annotation, a
  ``train.gc`` collection) and outside the harness's own spans: the
  program's host code held the chip. Laid to the innermost span.
- *Unlaid.* The rest: under the harness's own spans (any ``bench.*`` but
  the two that wrap program code, ``trace_scopes.WRAPS_PROGRAM``) or in
  no program span. What the trace cannot explain yet.

So lag + host + unlaid is the device's idle time, and (lag + host idle) a
step x ``step_runs`` / interval + the unlaid share is
``device.idle_share.train``. ``split`` works on ``fold``'s tuples and is
checked by hand (``tests/benchmarks/test_bm_trace_idle.py``); ``of_run``
reads the traced run's xplane through ``trace_scopes.read_xplane`` once and
keeps the result on the run for the three readers that share it, and
writes the split by span to standard error, one line, for the builder.
``host_ms`` reads the ``step`` events of the obs-on, profiler-off window.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import sys
import time

from benchmarks import trace_scopes as ts
from benchmarks.trace_reduce import WINDOW_SPAN, gaps_of, main_module_runs

# the program's name of the step's launch (a copy: the benchmark must read a
# checkout whose program has other spans; tests hold it to obs/timing.py)
ENQUEUE_SPAN = "train.enqueue"
CACHE_KEY = "_trace_idle"
HARNESS_OWN = "the harness's own"
NO_SPAN = "in no program span"


def _pieces(a: int, b: int, waits: list, wait_starts: list):
    """[a, b) cut by the sorted, disjoint ``waits``: (start, end, waiting)."""
    i = max(bisect.bisect_right(wait_starts, a) - 1, 0)
    cur = a
    while i < len(waits) and waits[i][0] < b:
        s, e = waits[i]
        if e > cur:
            if s > cur:
                yield cur, s, False
            yield max(s, cur), min(e, b), True
            cur = min(e, b)
        i += 1
    if cur < b:
        yield cur, b, False


def _innermost(loop: list, loop_starts: list, t: int) -> str:
    """The latest-started ``train.*`` span that holds t (a step annotation
    holds its phases and any collection nested in them)."""
    i = bisect.bisect_right(loop_starts, t)
    return next((name for s, e, name in reversed(loop[max(0, i - 64):i])
                 if s <= t < e), "train")


def split(devices: dict, host_spans: list, modules: dict):
    """devices, host_spans, modules as ``trace_scopes.fold`` takes them.
    -> nanoseconds, the mean over the devices: ``lag_ns`` (None where the
    enqueues and the executions do not pair), ``host_ns``, ``unlaid_ns``,
    ``idle_ns``, ``window_ns``, ``step_runs``, and ``by_span`` (host idle
    by the innermost span, unlaid by why). None where the program emits no
    ``train.*`` span or the trace holds no device op."""
    ops = [ev for evs in devices.values() for ev in evs]
    loop = sorted((s, s + d, name) for name, s, d in host_spans
                  if ts.LOOP_SPAN.match(name))
    if not ops or not loop:
        return None
    (lo, hi), step_runs = ts.interval_of(ops, host_spans, modules)
    # every execution of the step program in the capture, lead-in included
    executions = main_module_runs(modules or {}, float("-inf"), float("inf"))
    enqueued = sorted(s + d for name, s, d in host_spans
                      if name == ENQUEUE_SPAN)
    loop_starts = [s for s, _, _ in loop]
    in_loop = ts._merged((s, e) for s, e, _ in loop)
    harness_own = ts._merged(
        (s, s + d) for name, s, d in host_spans
        if ts.HARNESS_SPAN.match(name) and name != WINDOW_SPAN
        and name not in ts.WRAPS_PROGRAM)
    loop_at, own_at = [m[0] for m in in_loop], [m[0] for m in harness_own]
    n = len(devices)
    paired = bool(executions)
    lag = host = unlaid = idle = 0
    lag_longest = 0
    by_span = {}
    for dev, evs in sorted(devices.items()):
        busy = sorted((s, s + d) for _, s, d, *_ in evs)
        op_starts = [s for s, _ in busy]
        runs = executions.get(dev, [])
        paired = paired and len(runs) == len(enqueued)
        waits = []
        for handed, (began, _) in zip(enqueued, runs):
            i = bisect.bisect_left(op_starts, began)
            first_op = op_starts[i] if i < len(op_starts) else began
            if first_op > handed:
                waits.append((handed, first_op))
        waits = ts._merged(waits) if paired else []
        wait_starts = [w[0] for w in waits]
        for a, b in gaps_of(busy, lo, hi):
            idle += b - a
            for p, q, waiting in _pieces(a, b, waits, wait_starts):
                if waiting:
                    lag += q - p
                    lag_longest = max(lag_longest, q - p)
                    continue
                mid = (p + q) // 2
                if ts._inside(harness_own, own_at, mid):
                    label = HARNESS_OWN
                    unlaid += q - p
                elif ts._inside(in_loop, loop_at, mid):
                    label = _innermost(loop, loop_starts, mid)
                    host += q - p
                else:
                    label = NO_SPAN
                    unlaid += q - p
                by_span[label] = by_span.get(label, 0) + (q - p) / n
    return {
        "window_ns": hi - lo,
        "step_runs": step_runs,
        "idle_ns": idle / n,
        "lag_ns": lag / n if paired else None,
        "lag_longest_ns": lag_longest,
        "host_ns": host / n,
        "unlaid_ns": unlaid / n,
        "by_span": by_span,
        "enqueues": len(enqueued),
    }


def of_run(run: dict):
    """The traced run's split, parsed once and kept on the run; None where
    the run has no device trace (the CPU rehearsal) or nothing to split."""
    if not run.get("trace") or not run.get("work"):
        return None
    if CACHE_KEY not in run:
        paths = glob.glob(os.path.join(run["work"], "trace", "**",
                                       "*.xplane.pb"), recursive=True)
        t0 = time.monotonic()
        got = split(*ts.read_xplane(max(paths, key=os.path.getmtime),
                                    run.get("chips", 1))) if paths else None
        run[CACHE_KEY] = got
        if got:
            ms = {k: (None if got[k] is None else round(got[k] / 1e6, 3))
                  for k in ("window_ns", "idle_ns", "lag_ns", "host_ns",
                            "unlaid_ns", "lag_longest_ns")}
            sys.stderr.write("trace_idle: " + json.dumps(dict(
                ms, step_runs=got["step_runs"], enqueues=got["enqueues"],
                by_span_ms={k: round(v / 1e6, 3) for k, v in sorted(
                    got["by_span"].items(), key=lambda kv: -kv[1])},
                read_s=round(time.monotonic() - t0, 1))) + "\n")
    return run[CACHE_KEY]


# -- what the readers under layer_metrics/ return ---------------------------

def per_step_ms(run, key: str):
    """``key``'s device-idle milliseconds a step of the interval; None
    where the split cannot be made (or the enqueues do not pair)."""
    f = of_run(run)
    if not f or not f["step_runs"] or f["lag_ns"] is None:
        return None
    return f[key] / 1e6 / f["step_runs"]


def unlaid_share(run):
    f = of_run(run)
    if not f or f["lag_ns"] is None:
        return None
    return 100.0 * f["unlaid_ns"] / f["window_ns"]


def host_ms(run):
    """Mean ``step_ms - key_ms - enqueue_ms`` of the StepTimer ``step``
    events of the iterations whole in the window (a Speedometer ``step``
    event has no ``step_ms``; the window's first event is the iteration
    whose batch request waited, inside the loader, for the profiler to
    stop and the window to open): the loop thread's own work outside the
    two calls where back-pressure lands. None without a device trace, or
    where the program's events carry no ``key_ms`` (a parent)."""
    if not run.get("trace"):
        return None
    opened = getattr(run.get("loader"), "t_open_mono", float("-inf"))
    timed = [e for e in run.get("events") or ()
             if "step_ms" in e and e["t_mono"] - e["step_ms"] / 1e3 >= opened]
    rows = [e["step_ms"] - e["key_ms"] - e["enqueue_ms"] for e in timed
            if "key_ms" in e and "enqueue_ms" in e]
    if not rows:
        return None
    fields = sorted({k for e in timed for k in e if k.endswith("_ms")})
    sys.stderr.write("loop_phases: " + json.dumps({
        k: [round(sum(v) / len(v), 3), round(max(v), 3), len(v)]
        for k in fields for v in [[e[k] for e in timed if k in e]]}) + "\n")
    return sum(rows) / len(rows)
