"""graftscope event stream — append-only JSONL telemetry records.

The repo's only runtime signal used to be the Speedometer samples/sec log
line; when a run stalled or died (BENCH_r05 rc=124) there was no artifact
saying which phase was at fault. This module is the sink every runtime
surface (train loop, eval, bench, profiler, watchdog) writes through:
one typed JSON record per line, machine-foldable by ``obs.report`` into
run summaries and BENCH-compatible blobs.

Design rules:

- **Typed records.** ``EVENT_TYPES`` is the closed schema; ``emit`` raises
  on anything else, and the graftlint rule ``obs-event-schema`` enforces
  literal, known type keys at lint time (new record kinds are a schema
  change, reviewed here, not ad-hoc strings at call sites).
- **No-op when disabled.** ``NullEventLog`` has the same surface and does
  nothing — the train hot path stays allocation-free when telemetry is
  off (``StepTimer.iterate`` degrades to ``enumerate``).
- **jax-free.** This module (and ``report``) imports only the stdlib, so
  a run's JSONL can be folded on any machine, including one without the
  accelerator stack.

Every record carries wall time (``t_wall``, epoch seconds — correlate
across hosts/logs), monotonic time (``t_mono`` — durations immune to NTP
steps), the emitting process index, and the global step counter at emit
time (``step`` — set by StepTimer; 0 before training starts).
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import threading
import time
from typing import Any, Dict, Optional

#: The closed record schema. Adding a kind here is a schema change:
#: update the README table and obs/report.py's folding in the same PR
#: (the obs-event-schema lint rule reads this tuple from the AST).
EVENT_TYPES = (
    "run_meta",    # once per run: config digest, mesh, versions, git sha
    "step",        # per train iteration (StepTimer) / per timed profile row;
                   # Speedometer windows carry samples_per_sec instead
    "epoch",       # epoch boundary with the drained MetricBag means
    "compile",     # one XLA compile (jax.monitoring), with shape signature
    "checkpoint",  # checkpoint save enqueued/written
    "eval",        # one evaluation pass (pred_eval) with its result dict
    "stall",       # watchdog: no step completed within the stall threshold
    "crash",       # unhandled exception in the train loop (re-raised)
    "bench",       # one bench.py config measurement
    "backend_retry",  # graftguard: transient backend failure; sleeping
                      # sleep_s before attempt+1 (resilience/backend.py)
    "backend_up",  # graftguard: backend acquired (attempts, waited_s)
    "preempt",     # SIGTERM/SIGINT honored at a step boundary; emergency
                   # checkpoint state in `saved` (resilience/preempt.py)
    "heal",        # graftheal: step-time backend loss recovered in-process
                   # (capture mode, downtime_s, devices before/after —
                   # resilience/heal.py)
    "snapshot",    # graftheal: one periodic host snapshot of the train
                   # state INSTALLED as the fallback — the position and the
                   # run's dispatch it was taken at, the dispatches it was
                   # in flight, the loop's own ms in it, its bytes
                   # (resilience/heal.py Healer.poll_snapshot)
    "rpn_targets", # once a run with obs.enabled: how far the first
                   # dispatch's anchor labelling engaged — gt slots walked
                   # (the loop's trip count: the most valid boxes of any
                   # image) of the slots padded, kept positives and
                   # negatives of the batch (tools/train.py)
    "roi_levels",  # pyramid families, once a run with obs.enabled: the
                   # share of the first dispatch's sampled rois that FPN
                   # Eq. 1 assigns to each pooled level, and the pooling's
                   # static form: the `canvas` (rows, columns) the levels
                   # are stacked into and the `poolings` a call makes of
                   # each roi (models/fpn.py::pyramid_roi_align)
    "mask_rois",   # the mask branch, once a run with obs.enabled: the live
                   # foreground rois of the first dispatch's `slots` branch
                   # slots an image (min / mean / max over the images) and
                   # their share on each pooled level (tools/train.py)
    "cost",        # graftprof: XLA cost/memory accounting for one
                   # compiled shape bucket (flops, hbm split — obs/costs.py)
    "trace",       # graftprof: one closed jax.profiler capture window
                   # (dir + coarse phase summary — obs/profile.py)
    "health",      # graftpulse: one cadenced numerics reading — loss (+
                   # z-score), per-buffer grad/param/update norms and
                   # nonfinite counts (obs/health.py HealthMonitor over
                   # train/health.py's in-graph reductions)
    "anomaly",     # graftpulse tripwire: a health reading crossed a
                   # tripwire (nonfinite, grad explosion, loss z-score)
                   # — reasons, emergency-checkpoint path, flight-dump
                   # path (obs/health.py)
    "quorum",      # graftquorum: one coordination round — kind
                   # (preempt/heal/excluded), hosts arrived/excluded,
                   # agreed boundary or topology (resilience/quorum.py
                   # via tools/train.py; the process stamp says which
                   # host's view this record is)
    "heartbeat",   # grafttower: cadenced liveness beacon from the
                   # watchdog thread (obs.heartbeat_every_s) — beat_age_s
                   # since the last completed step, stall count, and
                   # final=True exactly once at clean shutdown; a host
                   # whose stream ends with a STALE non-final heartbeat
                   # was killed, not slow (obs/watchdog.py, obs/fleet.py)
    "barrier",     # grafttower: one quorum barrier from THIS host's
                   # view — name, per-host wait_s, arrival order, who
                   # arrived last, timed_out (resilience/quorum.py; the
                   # fleet fold attributes everyone's wait to the last
                   # arriver)
    "data",        # graftfeed: one input-plane incident — kind
                   # quarantine (record id + reason + deterministic
                   # replacement), retry (transient IO flake ridden out
                   # under data.record_deadline_s), quarantine_applied
                   # (a resume re-armed a prior run's quarantine.jsonl),
                   # quarantine_cap (fraction cap tripped — the abort),
                   # stall (next() blew data.wait_deadline_s —
                   # DataStallError) (data/feedguard.py, data/loader.py)
    "data_worker", # graftfeed: one prefetch-worker death — worker name,
                   # the queue position its claim was requeued at,
                   # deaths so far vs data.worker_restart_max, and
                   # whether a replacement thread was spawned
                   # (data/loader.py worker supervision)
)

#: Buffered kinds — everything else flushes to disk immediately, so the
#: record survives the very hang/crash it is diagnosing.
_BUFFERED_TYPES = frozenset({"step", "compile"})


def _json_default(value: Any):
    """Last-resort JSON coercion: numpy scalars/arrays (via item/tolist)
    without importing numpy; everything else degrades to repr."""
    for attr in ("tolist", "item"):
        fn = getattr(value, attr, None)
        if callable(fn):
            try:
                return fn()
            except (TypeError, ValueError):
                continue
    return repr(value)


class NullEventLog:
    """The disabled sink: same surface as EventLog, does nothing.

    ``enabled`` is the branch guard consumers use to keep even kwargs
    construction off the hot path when telemetry is off.
    """

    enabled = False
    path: Optional[str] = None
    step = 0

    def emit(self, type_: str, **fields):
        return None

    def attach_ring(self, ring):
        return None

    def set_step(self, step: int):
        return None

    def flush(self):
        return None

    def close(self):
        return None


class EventLog:
    """Append-only JSONL sink with typed records.

    Thread-safe (the stall watchdog emits from its own thread). ``step``
    and ``compile`` records buffer up to ``flush_every`` lines; every
    other kind flushes immediately (see _BUFFERED_TYPES).
    """

    enabled = True

    def __init__(self, path: str, process_index: int = 0,
                 flush_every: int = 64):
        self.path = path
        self.process_index = int(process_index)
        self.flush_every = max(1, int(flush_every))
        self.step = 0
        self._lock = threading.Lock()
        self._buf: list = []
        self._ring = None
        self._fh: Optional[io.TextIOBase] = open(path, "a", encoding="utf-8")

    def attach_ring(self, ring):
        """graftpulse flight recorder (obs/health.py FlightRecorder):
        every emitted record is ALSO appended to ``ring`` — at emit time,
        before any disk buffering, so the crash-time dump holds the step/
        compile records the flush cadence has not written yet."""
        self._ring = ring

    def set_step(self, step: int):
        """Update the global step counter stamped on subsequent records
        (called by StepTimer after each completed iteration)."""
        self.step = int(step)

    def emit(self, type_: str, **fields):
        """Append one typed record. Raises ValueError on a type outside
        EVENT_TYPES — the schema is closed (see module docstring)."""
        if type_ not in EVENT_TYPES:
            raise ValueError(
                f"unknown event type {type_!r}; the graftscope schema is "
                f"{EVENT_TYPES} (extend obs/events.py::EVENT_TYPES to add "
                "a record kind)")
        record: Dict[str, Any] = {
            "type": type_,
            "t_wall": time.time(),
            "t_mono": time.monotonic(),
            "process": self.process_index,
            "step": self.step,
        }
        record.update(fields)
        if self._ring is not None:
            self._ring.append(record)
        line = json.dumps(record, default=_json_default)
        with self._lock:
            if self._fh is None:
                return
            self._buf.append(line)
            if (type_ not in _BUFFERED_TYPES
                    or len(self._buf) >= self.flush_every):
                self._flush_locked()

    def _flush_locked(self):
        if self._buf and self._fh is not None:
            self._fh.write("\n".join(self._buf) + "\n")
            self._fh.flush()
            self._buf.clear()

    def flush(self):
        with self._lock:
            self._flush_locked()

    def close(self):
        with self._lock:
            self._flush_locked()
            if self._fh is not None:
                self._fh.close()
                self._fh = None


def event_log_path(directory: str, process_index: int = 0) -> str:
    """events_p<k>.jsonl — one stream per process (JSONL appends from
    multiple writers interleave), including process 0: on a fleet every
    host's stream is a peer input to the grafttower merge, not a special
    case. report.py::load_events also folds the pre-grafttower names
    (events.jsonl / events.<i>.jsonl) so old run dirs stay readable."""
    return os.path.join(directory, f"events_p{process_index}.jsonl")


def open_event_log(directory: str, process_index: int = 0,
                   flush_every: int = 64, fresh: bool = False) -> EventLog:
    """Create ``directory`` and open this process's event log in it.

    ``fresh=True`` truncates an existing stream first — for per-run
    artifacts in a fixed directory (bench, profiler), where appending a
    second run would silently fold both runs into one report. Training
    keeps the append default: a resumed run IS the same run.
    """
    os.makedirs(directory, exist_ok=True)
    path = event_log_path(directory, process_index)
    if fresh and os.path.exists(path):
        os.remove(path)
    return EventLog(path, process_index=process_index,
                    flush_every=flush_every)


def _git_sha(start: str) -> Optional[str]:
    """Best-effort HEAD sha by reading .git directly (no subprocess)."""
    cur = os.path.abspath(start)
    while True:
        git = os.path.join(cur, ".git")
        if os.path.isdir(git):
            try:
                with open(os.path.join(git, "HEAD"), encoding="utf-8") as fh:
                    head = fh.read().strip()
                if head.startswith("ref: "):
                    ref = os.path.join(git, *head[5:].split("/"))
                    with open(ref, encoding="utf-8") as fh:
                        return fh.read().strip()
                return head or None
            except OSError:
                return None
        nxt = os.path.dirname(cur)
        if nxt == cur:
            return None
        cur = nxt


def _git_dirty(start: str) -> Optional[bool]:
    """Best-effort working-tree dirtiness: one ``git status --porcelain``
    capped at 5 s (the only subprocess in this module — the sha reader
    stays file-based). None when git is unavailable, times out, or the
    path is not a work tree: run_meta omits unknowns rather than guess."""
    import subprocess

    try:
        proc = subprocess.run(
            ["git", "-C", start, "status", "--porcelain"],
            capture_output=True, timeout=5)
    except (OSError, subprocess.SubprocessError):
        return None
    if proc.returncode != 0:
        return None
    return bool(proc.stdout.strip())


def env_fingerprint() -> Dict[str, Any]:
    """The environment-drift fields (graftpulse satellite): jax/jaxlib
    versions plus a ``git_dirty`` flag, so a cross-run regression in the
    perf ledger is attributable to environment change — an upgraded
    jaxlib or an uncommitted local patch — not just the git sha. Stamped
    into ``run_meta`` and into every bench/ledger row (bench.py)."""
    import jax  # the module only: no backend is touched here
    import jaxlib

    fields: Dict[str, Any] = {"jax_version": jax.__version__,
                              "jaxlib_version": jaxlib.__version__}
    dirty = _git_dirty(os.path.dirname(os.path.abspath(__file__)))
    if dirty is not None:
        fields["git_dirty"] = dirty
    return fields


def run_meta_fields(cfg=None, mesh=None, **extra) -> Dict[str, Any]:
    """The ``run_meta`` payload: config digest, mesh shape, jax/jaxlib
    versions, the device the process runs on, git sha + dirtiness
    (both omitted where the checkout is not a git repository — the
    chip tool's copy is not). ``cfg``/``mesh`` are optional so
    config-free tools can still stamp a run. Touches the backend: only
    a process that may own the chip calls this (bench.py's parent does
    not)."""
    fields: Dict[str, Any] = {}
    if cfg is not None:
        # repr of the frozen dataclass tree is a stable, total rendering
        # of every field — the digest changes iff the config does.
        fields["config_digest"] = hashlib.sha256(
            repr(cfg).encode("utf-8")).hexdigest()[:16]
        fields["network"] = cfg.network.name
        fields["dataset"] = cfg.dataset.name
    if mesh is not None:
        fields["mesh"] = dict(
            zip(mesh.axis_names, (int(s) for s in mesh.devices.shape)))
    fields.update(env_fingerprint())
    import jax

    fields["backend"] = jax.default_backend()
    fields["device_kind"] = jax.devices()[0].device_kind
    fields["device_count"] = jax.device_count()
    sha = _git_sha(os.path.dirname(os.path.abspath(__file__)))
    if sha:
        fields["git_sha"] = sha
    fields.update(extra)
    return fields
