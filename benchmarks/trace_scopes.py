"""From a profiler trace to the program's OWN names: device time by the
stage scope (``jax.named_scope``) of each op, the loop thread's ``train.*``
spans, and the device-idle time that falls inside them.

``trace_reduce`` names ops by their HLO text, which the next refactor of a
kernel renumbers. The program (from PR 25 on) names the stages of its step
(``mx_rcnn_tpu/obs/profile.py::STAGES``) and the phases of its loop
(``mx_rcnn_tpu/obs/timing.py::LOOP_SPANS``); the compiler carries a stage
into the ``op_name`` of every instruction traced under it, forward and
backward (``transpose(jvp(roi_align))/dot_general``). The profiler does NOT
hand that path back with a device op (read on the chip, PR 25: an "XLA Ops"
event carries its instruction's HLO text as its name, and three timing
stats); it keeps the compiled module of every program that ran as an
``Hlo Proto`` in the ``/host:metadata`` plane, instruction metadata
included. This module reads the paths from there: instruction name ->
``op_name``, per program, and each op event takes the program whose
execution on the "XLA Modules" line contains it.

- The interval is ``trace_reduce.reduce_events``' own: inside the harness's
  ``bench.traced`` span, from the start of the step program's first
  execution to the end of its last.
- Every nanosecond of a device's busy time (the union of its op intervals)
  goes to exactly one stage or to ``unscoped``: an op gets the part of its
  interval that no earlier-started op covers. So the stages and the
  unscoped time add up to ``busy_s``, and, per step, to
  ``step.device_ms.train``.
- A stage is one whole segment of the path, bare or wrapped by the
  transforms; the innermost wins. A fusion has the path the compiler gave
  the fusion instruction (its root's). A program without scopes (a parent
  commit) reads as all unscoped, and the readers then report nothing.
- ``host_bound``: idle stretches of a device whose midpoint lies inside a
  ``train.*`` span and outside the harness's own stalls (any ``bench.*``
  span but the two that wrap program code) - idle the program's host code
  answers for.

``fold`` works on plain tuples and is checked by hand and on
``fixtures/small_trace_scopes.json`` (``tests/benchmarks/
test_bm_trace_scopes.py``); ``of_run`` parses the traced run's xplane once
and keeps the result on the run for the ten readers that share it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from benchmarks.trace_reduce import (MODULE_LINE, OP_LINES, WINDOW_SPAN,
                                     gaps_of, main_module_runs, short_name)

# the program's stage names (a copy: the benchmark must read a checkout
# whose program has none; tests hold it to obs/profile.py::STAGES)
STAGES = ("backbone", "neck", "rpn_head", "rpn_targets", "rpn_loss",
          "proposal", "roi_sample", "roi_align", "box_head", "rcnn_loss",
          "update")
# which stages each stage.<group>_ms.train metric adds up
GROUPS = {
    "backbone": ("backbone", "neck"),
    "rpn": ("rpn_head", "rpn_targets", "rpn_loss"),
    "proposal": ("proposal",),
    "roi_align": ("roi_align",),
    "box_head": ("roi_sample", "box_head", "rcnn_loss"),
    "update": ("update",),
}
_STAGE_RX = re.compile(r"(?<![\w.\-])(" + "|".join(STAGES) + r")(?![\w.\-])")
METADATA_PLANE = "/host:metadata"  # one event metadata per compiled program
LOOP_SPAN = re.compile(r"^train(\.|$)")   # the step annotation and phases
HARNESS_SPAN = re.compile(r"^bench\.")
# harness spans that wrap the PROGRAM's code (its loader, its loop body);
# idle under any other harness span is the harness's own
WRAPS_PROGRAM = ("bench.loader_next", "bench.step_dispatch")
CACHE_KEY = "_trace_scopes"


def stage_of(path: str):
    """The innermost stage named in an op's scope path, or None."""
    hits = _STAGE_RX.findall(path or "")
    return hits[-1] if hits else None


def interval_of(ops, host_spans, modules):
    """((lo, hi), step_runs): ``reduce_events``' interval and the step
    program's executions in it."""
    marked = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
    src = [h for h in host_spans if HARNESS_SPAN.match(h[0])] or ops
    window = marked[0] if marked else (
        min(e[1] for e in src), max(e[1] + e[2] for e in src))
    runs = main_module_runs(modules or {}, *window)
    if any(runs.values()):
        window = (min(r[0][0] for r in runs.values() if r),
                  max(r[-1][1] for r in runs.values() if r))
    return window, max((len(r) for r in runs.values()), default=0)


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _inside(merged, starts, t):
    i = bisect.bisect_right(starts, t) - 1
    return i >= 0 and t < merged[i][1]


def fold(devices: dict, host_spans: list, modules=None, window=None) -> dict:
    """devices: {device: [(op name, start_ns, dur_ns, scope path)]} of the
    op lines; host_spans: [(name, start_ns, dur_ns)] of the ``train.*`` and
    ``bench.*`` annotations; modules as ``reduce_events`` takes them.
    Times come back in nanoseconds, the mean over the devices."""
    ops = [ev for evs in devices.values() for ev in evs]
    if not ops:
        raise ValueError("the trace holds no device operation")
    step_runs = 0
    if window is None:
        window, step_runs = interval_of(ops, host_spans, modules)
    lo, hi = window
    n = len(devices)
    loop = sorted((s, s + d, name) for name, s, d in host_spans
                  if LOOP_SPAN.match(name))
    loop_starts = [s for s, _, _ in loop]
    in_loop = _merged((s, e) for s, e, _ in loop)
    harness_own = _merged(
        (s, s + d) for name, s, d in host_spans
        if HARNESS_SPAN.match(name) and name != WINDOW_SPAN
        and name not in WRAPS_PROGRAM)
    loop_at, own_at = [m[0] for m in in_loop], [m[0] for m in harness_own]

    stage_ns, unscoped_by_op = {}, {}
    busy = unscoped = idle = host_bound = 0
    idle_by_span = {}
    for dev, evs in sorted(devices.items()):
        cursor, spans = lo, []
        for name, s, d, path in sorted(evs, key=lambda e: (e[1], e[2])):
            s, e = max(s, cursor), min(s + d, hi)
            if e <= s:
                continue
            cursor = e
            spans.append((s, e))
            busy += e - s
            stage = stage_of(path)
            if stage is None:
                unscoped += e - s
                op = short_name(name)
                unscoped_by_op[op] = unscoped_by_op.get(op, 0) + e - s
            else:
                stage_ns[stage] = stage_ns.get(stage, 0) + e - s
        for a, b in gaps_of(spans, lo, hi):
            mid = (a + b) // 2
            idle += b - a
            label = "outside the loop's spans"
            if _inside(harness_own, own_at, mid):
                label = "the harness's own"
            elif _inside(in_loop, loop_at, mid):
                host_bound += b - a
                # the innermost phase: the latest-started span that holds it
                # (a step annotation holds at most its four phases)
                i = bisect.bisect_right(loop_starts, mid)
                label = next((nm for s, e, nm in reversed(loop[max(0, i - 8):i])
                              if s <= mid < e), "train")
            idle_by_span[label] = idle_by_span.get(label, 0) + (b - a) / n
    spans_in = {}
    for name, s, d in host_spans:
        if LOOP_SPAN.match(name) and s >= lo and s + d <= hi:
            spans_in.setdefault(name, []).append(d)
    return {
        "window_ns": hi - lo,
        "step_runs": step_runs,
        "busy_ns": busy / n,
        "stage_ns": {k: stage_ns[k] / n for k in STAGES if k in stage_ns},
        "unscoped_ns": unscoped / n,
        "unscoped_ops": sorted(([op, t / n] for op, t in
                                unscoped_by_op.items()),
                               key=lambda r: -r[1])[:12],
        "idle_ns": idle / n,
        "host_bound_ns": host_bound / n,
        "idle_by_span": idle_by_span,
        "span_ns": {k: [len(v), sum(v) / len(v)]
                    for k, v in sorted(spans_in.items())},
    }


# -- the compiled programs the trace carries ---------------------------------
# jax.profiler.ProfileData shows events and their own stats, not the event
# metadata's, where the Hlo Proto sits; and the generated protobuf classes
# ship only with TensorFlow. The few fields needed are read off the wire
# (xplane.proto, xla/service/hlo.proto; numbers held by a test against a
# trace made on the spot).

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as int,
    anything else as a slice of the buffer."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        else:
            if kind == 2:
                size, i = _varint(buf, i)
            else:
                size = {1: 8, 5: 4}[kind]
            val, i = buf[i:i + size], i + size
        yield key >> 3, val


def _sub(buf, number):
    return [v for n, v in _fields(buf) if n == number]


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _instruction_paths(hlo_proto) -> dict:
    """HloProto.hlo_module(1).computations(3).instructions(2): name(1) ->
    metadata(7).op_name(2)."""
    out = {}
    for module in _sub(hlo_proto, 1):
        for comp in _sub(module, 3):
            for ins in _sub(comp, 2):
                name, path = None, ""
                for n, v in _fields(ins):
                    if n == 1:
                        name = _text(v)
                    elif n == 7:
                        path = "".join(_text(p) for p in _sub(v, 2))
                out[name] = path
    return out


def program_paths(xspace: bytes) -> dict:
    """{program as the "XLA Modules" line names it: {instruction name:
    scope path}} from XSpace.planes(1) named ``/host:metadata``:
    event_metadata(4) entries' value(2): name(2), stats(5).bytes_value(6)."""
    out = {}
    for plane in _sub(memoryview(xspace), 1):
        name, entries = None, []
        for n, v in _fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 4:
                entries.append(v)
        if name != METADATA_PLANE:
            continue
        for entry in entries:
            for meta in _sub(entry, 2):
                program, protos = None, []
                for n, v in _fields(meta):
                    if n == 2:
                        program = _text(v)
                    elif n == 5:
                        protos += _sub(v, 6)
                for proto in protos:
                    out.setdefault(program, {}).update(
                        _instruction_paths(proto))
    return out


def instruction_of(op: str) -> str:
    """The instruction's name in an op event's name: the TPU names an
    event by its whole HLO text (``%fusion.17 = bf16[...] fusion(...)``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def table_of(paths: dict, program: str) -> dict:
    """A program's instructions: by its full name, else (an executable
    loaded from the compile cache can run under another id than the one its
    Hlo Proto was filed under) by its name without the id, if that names
    one program."""
    if program in paths:
        return paths[program]
    base = program.split("(", 1)[0]
    same = [t for k, t in paths.items() if k.split("(", 1)[0] == base]
    return same[0] if len(same) == 1 else {}


def with_paths(ops, runs, paths):
    """ops: [(name, start_ns, dur_ns)] of one device; runs: its module line,
    [(program, start_ns, dur_ns)]; -> [(name, start, dur, scope path)], each
    op looked up in the program whose execution contains its start."""
    runs = sorted(runs, key=lambda r: r[1])
    starts = [r[1] for r in runs]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        table = (table_of(paths, runs[i][0])
                 if i >= 0 and s < runs[i][1] + runs[i][2] else {})
        out.append((name, s, d, table.get(instruction_of(name), "")))
    return out


def read_xplane(path: str, chips: int):
    """-> (devices, host_spans, modules): ``fold``'s arguments."""
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        paths = program_paths(f.read())
    devices, host, modules = {}, [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                got = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events] if line.name in (
                           OP_LINES + (MODULE_LINE,)) else []
                if line.name in OP_LINES:
                    devices.setdefault(plane.name, []).extend(got)
                elif got:
                    modules[plane.name] = got
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events
                         if LOOP_SPAN.match(e.name)
                         or HARNESS_SPAN.match(e.name)]
    if len(devices) > chips:  # a host may show chips the cell does not use
        used = sorted(devices, key=lambda d: -len(devices[d]))[:chips]
        devices = {d: devices[d] for d in used}
    modules = {d: modules.get(d, []) for d in devices}
    return ({d: with_paths(evs, modules[d], paths)
             for d, evs in devices.items()}, host, modules)


def of_run(run: dict):
    """The traced run's fold, parsed once and kept on the run; None where
    the run has no device trace (the CPU rehearsal)."""
    if not run.get("trace") or not run.get("work"):
        return None
    if CACHE_KEY not in run:
        paths = glob.glob(os.path.join(run["work"], "trace", "**",
                                       "*.xplane.pb"), recursive=True)
        run[CACHE_KEY] = fold(*read_xplane(
            max(paths, key=os.path.getmtime),
            run.get("chips", 1))) if paths else None
    return run[CACHE_KEY]


# -- what the readers under layer_metrics/ return ---------------------------

def stage_ms(run, group: str):
    """Device milliseconds per step of the group's stages; None where the
    program names no stage at all."""
    f = of_run(run)
    if not f or not f["stage_ns"] or not f["step_runs"]:
        return None
    return sum(f["stage_ns"].get(s, 0) for s in GROUPS[group]) / 1e6 / f[
        "step_runs"]


def unscoped_share(run):
    f = of_run(run)
    if not f or not f["stage_ns"]:
        return None
    return 100.0 * f["unscoped_ns"] / f["busy_ns"]


def span_ms(run, name: str):
    """Mean milliseconds of the loop's spans of that name that lie whole in
    the interval; None where the program emits none."""
    f = of_run(run)
    if not f or name not in f["span_ns"]:
        return None
    return f["span_ns"][name][1] / 1e6


def host_bound_share(run):
    f = of_run(run)
    if not f or not f["span_ns"]:
        return None
    return 100.0 * f["host_bound_ns"] / f["window_ns"]
