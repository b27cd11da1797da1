"""graftprof trace windows — programmatic jax.profiler capture + folding.

``tools/profile.py`` can capture a trace of a synthetic step, but the
numbers that matter come from REAL runs — and nobody restarts a 12-hour
train job under TensorBoard. This module arms a capture window inside
the run itself:

- ``--set obs.trace_at_step=K`` (with ``obs.trace_steps=N``, default 3)
  starts a ``jax.profiler`` trace just before global step K and stops it
  N completed steps later, saving under ``<obs dir>/trace``;
- the stall watchdog auto-arms ONE window when it fires (before the
  stack dump), so a mysteriously slow/hung run leaves a trace of what
  the host was doing during the stall — closed at the next completed
  step or at teardown;
- every closed window emits a ``trace`` event carrying the capture dir
  and the per-stage summary, so ``obs.report`` shows the breakdown
  without TensorBoard.

``summarize_trace`` folds the capture's ``.xplane.pb`` by STAGE: the step
program names its stages with ``jax.named_scope`` (``STAGES``; applied in
``models/faster_rcnn.py``, ``models/fpn.py`` and ``train/step.py`` through
``stage``), the compiler carries the scope path into each instruction's
``op_name``, and the capture carries every compiled program with it
(``program_paths``). A stage may sit anywhere in the path -
``transpose(jvp(roi_align))/dot_general`` is ``roi_align`` - and the
innermost one wins; a fusion has the path the compiler gave the fusion
instruction; what carries no stage (collectives, copies the compiler
hoists, families without scopes) is ``unscoped`` and is reported, not
hidden. The reduction the benchmark reads (``benchmarks/trace_scopes.py``)
applies the same rule to the same source.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import threading
from typing import Any, Dict, Optional

#: The step program's stages, in program order: the one closed list of
#: ``jax.named_scope`` names (``stage`` refuses any other). FPN and ViTDet
#: have a ``neck``; DETR carries none yet.
STAGES = ("backbone", "neck", "rpn_head", "rpn_targets", "rpn_loss",
          "proposal", "roi_sample", "roi_align", "box_head", "rcnn_loss",
          "update")

# a stage is one whole segment of the scope path, bare or wrapped by the
# transforms: "FasterRCNN.box_head" and "dynamic_update_slice" are neither
_STAGE_RX = re.compile(r"(?<![\w.\-])(" + "|".join(STAGES) + r")(?![\w.\-])")

#: The mask branch's scopes (Mask R-CNN, ViTDet's mask preset), in program
#: order: a second closed list beside ``STAGES`` and not part of it, so
#: that ``stage_of`` and every reader of ``STAGES`` see the branch as
#: before it had names - its pooling as ``roi_align`` (the scope
#: ``pyramid_roi_align`` opens inside ``mask_align``), the rest unscoped.
#: ``branch_of`` reads them.
BRANCH_STAGES = ("mask_align", "mask_head", "mask_targets", "mask_loss")

_BRANCH_RX = re.compile(
    r"(?<![\w.\-])(" + "|".join(BRANCH_STAGES) + r")(?![\w.\-])")

#: The attention trunk's scopes (models/vit.py), nested inside
#: ``backbone``: a block's windowed attention (norm1 through the window
#: unpartition), its global attention (norm1 through proj) and its MLP
#: (norm2 through mlp2). A third closed list, beside the other two and part
#: of neither, so ``stage_of`` still sees the whole trunk as ``backbone``;
#: ``vit_of`` reads them.
VIT_STAGES = ("window_attn", "global_attn", "vit_mlp")

_VIT_RX = re.compile(
    r"(?<![\w.\-])(" + "|".join(VIT_STAGES) + r")(?![\w.\-])")


def stage(name: str):
    """``jax.named_scope`` for one of ``STAGES``, ``BRANCH_STAGES`` or
    ``VIT_STAGES``: trace-time metadata on the ops traced under it,
    forward and (through ``jvp``/``transpose``) backward. Changes no
    instruction."""
    if name not in STAGES + BRANCH_STAGES + VIT_STAGES:
        raise ValueError(f"{name!r} is not one of STAGES {STAGES}, "
                         f"BRANCH_STAGES {BRANCH_STAGES} or VIT_STAGES "
                         f"{VIT_STAGES}")
    import jax

    return jax.named_scope(name)


def stage_of(path: str) -> Optional[str]:
    """The innermost stage in an op's scope path, or None."""
    hits = _STAGE_RX.findall(path)
    return hits[-1] if hits else None


def branch_of(path: str) -> Optional[str]:
    """The innermost of ``BRANCH_STAGES`` in an op's scope path, or None:
    asked beside ``stage_of``, not in its place."""
    hits = _BRANCH_RX.findall(path)
    return hits[-1] if hits else None


def vit_of(path: str) -> Optional[str]:
    """The innermost of ``VIT_STAGES`` in an op's scope path, or None."""
    hits = _VIT_RX.findall(path)
    return hits[-1] if hits else None


# -- the compiled programs a capture carries --------------------------------
# A device op event does not carry its instruction's ``op_name`` (read on
# the chip, PR 25: its name is the instruction's HLO text, its stats three
# timings). The profiler keeps every program that ran, instruction metadata
# included, as an ``Hlo Proto`` in the ``/host:metadata`` plane - in the
# event METADATA, which ``jax.profiler.ProfileData`` does not show, and the
# generated protobuf classes ship only with TensorFlow. The few fields
# needed are read off the wire (xplane.proto, xla/service/hlo.proto).

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


def _instruction_paths(hlo_proto) -> Dict[str, str]:
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


def program_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{program, named as the trace names its executions
    (``jit_step(<id>)``): {instruction name: scope path}}, from
    XSpace.planes(1) named ``/host:metadata``: event_metadata(4) entries'
    value(2): name(2), stats(5).bytes_value(6) = an HloProto."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(memoryview(xspace), 1):
        name, entries = None, []
        for n, v in _fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 4:
                entries.append(v)
        if name != "/host:metadata":
            continue
        for meta in (m for entry in entries for m in _sub(entry, 2)):
            program, protos = None, []
            for n, v in _fields(meta):
                if n == 2:
                    program = _text(v)
                elif n == 5:
                    protos += _sub(v, 6)
            for proto in protos:
                out.setdefault(program, {}).update(_instruction_paths(proto))
    return out


def _table(paths, program: str) -> Dict[str, str]:
    """A program's instructions: by its full name, else (an executable
    loaded from the compile cache can run under another id than the one
    its Hlo Proto was filed under) by its name without the id, if that
    names one program."""
    if program in paths:
        return paths[program]
    base = program.split("(", 1)[0]
    same = [t for k, t in paths.items() if k.split("(", 1)[0] == base]
    return same[0] if len(same) == 1 else {}


def _device_ops(data, paths):
    """(name, duration_ns, scope path) of the device planes' "XLA Ops"
    events, each looked up in the program whose execution on the "XLA
    Modules" line contains it."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                      for ev in getattr(lines.get("XLA Modules"),
                                        "events", ()))
        starts = [r[0] for r in runs]
        for ev in getattr(lines.get("XLA Ops"), "events", ()):
            i = bisect.bisect_right(starts, ev.start_ns) - 1
            table = (_table(paths, runs[i][2])
                     if i >= 0 and ev.start_ns < runs[i][1] else {})
            # the TPU names an op by its whole HLO text
            name = ev.name.split(" = ", 1)[0]
            out.append((name, float(ev.duration_ns),
                        table.get(name.lstrip("%"), "")))
    return out


def _host_ops(data, paths):
    """The same of a backend without device planes (the CPU), whose op
    events sit among the host's and name ``hlo_op``, ``hlo_module`` and
    ``program_id``."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "hlo_op" in stats:
                    table = _table(paths, "{}({})".format(
                        stats.get("hlo_module"), stats.get("program_id")))
                    out.append((ev.name, float(ev.duration_ns),
                                table.get(stats["hlo_op"], "")))
    return out


def summarize_trace(trace_dir: str,
                    top_n: int = 8) -> Optional[Dict[str, Any]]:
    """Fold the NEWEST ``*.xplane.pb`` under ``trace_dir`` into
    ``{stages: {stage: ms}, unscoped_ms, total_ms, events, top_ops,
    file}``: summed device time of the op events by the stage their scope
    path names. Returns None when no capture exists or it cannot be
    read."""
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        return None
    path = max(hits, key=os.path.getmtime)
    try:
        from jax.profiler import ProfileData

        with open(path, "rb") as fh:
            paths = program_paths(fh.read())
        data = ProfileData.from_file(path)
        # a TPU capture's host plane holds millions of events: read it
        # only where there is no device plane
        ops = _device_ops(data, paths) or _host_ops(data, paths)
    except Exception as exc:  # noqa: BLE001  # graftlint: disable=broad-except — a capture that cannot be parsed (truncated at a crash, a jax without ProfileData) must not take the run down
        from mx_rcnn_tpu.logger import logger

        logger.warning("graftprof: cannot read %s: %r", path, exc)
        return None
    stages: Dict[str, float] = {}
    per_op: Dict[str, float] = {}
    unscoped = 0.0
    for name, dur_ns, scope in ops:
        ms = dur_ns / 1e6
        found = stage_of(scope)
        if found is None:
            unscoped += ms
        else:
            stages[found] = stages.get(found, 0.0) + ms
        per_op[name] = per_op.get(name, 0.0) + ms
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top_n]
    return {
        "file": os.path.relpath(path, trace_dir),
        "events": len(ops),
        "total_ms": round(sum(stages.values()) + unscoped, 3),
        "stages": {k: round(stages[k], 3) for k in STAGES if k in stages},
        "unscoped_ms": round(unscoped, 3),
        "top_ops": [{"name": k, "ms": round(v, 3)} for k, v in top],
    }


class TraceController:
    """Arms/collects jax.profiler windows inside a run.

    Hot-path surface is ``step_completed(total_steps)``: one int compare
    when nothing is armed. ``stall_window()`` is the watchdog's hook
    (called from its thread — jax's profiler state is process-global, so
    cross-thread start/stop is fine); at most one stall window per run.
    ``close()`` force-stops an open window so the artifact survives the
    crash/teardown path."""

    def __init__(self, elog, out_dir: str, trace_at_step: int = 0,
                 trace_steps: int = 3):
        self.elog = elog
        self.out_dir = out_dir
        self.trace_steps = max(1, int(trace_steps))
        self._arm_at = int(trace_at_step)  # 0 = nothing armed
        self._lock = threading.Lock()
        self._active_dir: Optional[str] = None
        self._active_reason: Optional[str] = None
        self._stop_after: Optional[int] = None
        self._stall_used = False
        self._anomaly_used = False

    # -- capture plumbing ---------------------------------------------------

    def _start(self, sub: str, reason: str) -> bool:
        target = os.path.join(self.out_dir, sub)
        try:
            import jax.profiler

            os.makedirs(target, exist_ok=True)
            jax.profiler.start_trace(target)
        except Exception as exc:  # noqa: BLE001  # graftlint: disable=broad-except — a profiler that cannot start (already active elsewhere, unsupported build) must not take the run down
            from mx_rcnn_tpu.logger import logger

            logger.warning("graftprof: trace start failed: %r", exc)
            return False
        self._active_dir = target
        self._active_reason = reason
        return True

    def _stop_and_emit(self):
        target, reason = self._active_dir, self._active_reason
        self._active_dir = self._active_reason = None
        self._stop_after = None
        try:
            import jax.profiler

            jax.profiler.stop_trace()
        except Exception as exc:  # noqa: BLE001  # graftlint: disable=broad-except — same survival contract as _start
            from mx_rcnn_tpu.logger import logger

            logger.warning("graftprof: trace stop failed: %r", exc)
            return
        if self.elog.enabled:
            self.elog.emit("trace", dir=target, reason=reason,
                           summary=summarize_trace(target))

    # -- public surface -----------------------------------------------------

    def before_step(self, step: int):
        """Called just before dispatching global step ``step``: opens the
        armed window so the capture INCLUDES step ``trace_at_step`` —
        step 1 (the compile-heavy first dispatch) is capturable too.
        Nothing armed (``_arm_at`` changes here alone): one int compare,
        no lock."""
        if not self._arm_at or step < self._arm_at:
            return
        with self._lock:
            if self._active_dir is not None:
                return
            if self._arm_at and step >= self._arm_at:
                at = self._arm_at
                self._arm_at = 0  # one window per arming
                if self._start(f"step{at}", reason=f"step {at}"):
                    # window spans steps at..at+N-1 (N = trace_steps)
                    self._stop_after = step + self.trace_steps - 1

    def step_completed(self, step: int, outputs=None):
        """Called once per completed dispatch: closes the open window
        when its step budget is spent (a stall window, which has no
        budget, closes on the first completed step after it). A dispatch
        is asynchronous and the loop waits for none of its own, so before
        a window is closed, and only then, ``outputs`` (optional: device
        arrays this dispatch returned) are waited for: the capture holds
        the work of the steps it brackets. No window open: one attribute
        check, no lock (a stall window opened meanwhile by the watchdog's
        thread is closed at the next completed step)."""
        if self._active_dir is None:
            return
        with self._lock:
            if self._active_dir is not None and (
                    self._stop_after is None or step >= self._stop_after):
                if outputs is not None:
                    import jax

                    jax.block_until_ready(outputs)
                self._stop_and_emit()

    def anomaly_window(self):
        """graftpulse tripwire hook (obs/health.py): like stall_window,
        at most ONE anomaly window per run — armed before the anomaly
        event is written so the capture brackets whatever the diverging
        run does next; closed at the next completed step or at close()."""
        with self._lock:
            if self._anomaly_used or self._active_dir is not None:
                return
            self._anomaly_used = True
            self._start("anomaly", reason="anomaly")

    def stall_window(self):
        """Watchdog hook: open ONE trace window for the stall in flight.
        Closed at the next completed step (if the run recovers) or at
        close() (if it dies) — either way the capture lands on disk."""
        with self._lock:
            if self._stall_used or self._active_dir is not None:
                return
            self._stall_used = True
            self._start("stall", reason="stall")
            # no step budget: the next heartbeat (or teardown) closes it

    def close(self):
        with self._lock:
            if self._active_dir is not None:
                self._stop_and_emit()
