"""graftpulse host layer — health folding, anomaly tripwires, flight recorder.

train/health.py computes the numerics signal INSIDE the compiled step
(whole-tree nonfinite counts + squared norms of grads/params/update,
plus the pooled loss, returned as extra step outputs). This module is
the host half:

- ``HealthMonitor`` stores the latest device-side health dict per
  dispatch (a reference — no sync) and, every ``obs.health_every``
  dispatches, pulls it to host, folds it into one ``health`` event
  (norms, nonfinite counts, loss z-score vs a trailing window) and runs
  the tripwires: any nonfinite count, a grad-norm explosion past
  ``obs.health_grad_factor`` × the trailing median, or a loss z-score
  beyond ``obs.health_loss_z``.
- A tripped wire becomes ACTION, not just a log line: an ``anomaly``
  event, a ``jax.profiler`` window (TraceController.anomaly_window), a
  graftguard-style emergency checkpoint of the last KNOWN-GOOD state
  (refreshed after each clean check — resumable with ``--resume auto``),
  and a flight-recorder dump; then ``obs.health_action="abort"`` raises
  :class:`NumericsAnomaly` (training on NaNs is worse than stopping)
  while ``"warn"`` keeps going.
- ``FlightRecorder`` is the last-K-events in-memory ring every EventLog
  record passes through (``EventLog.attach_ring``): on anomaly, stall,
  heal, preemption or crash the ring is dumped to
  ``<obs dir>/flight_<reason>.json`` — so every rc!=0 artifact says what
  the numbers were doing when it died, including buffered step/compile
  records the JSONL flush cadence had not written yet.

stdlib-only, like events/report: the monitor touches device values only
through ``float()`` at the cadence — no jax import, no numpy.
"""

from __future__ import annotations

import json
import math
import os
import statistics
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.obs.events import _json_default

#: the train/health.py key suffixes (kept literal here so this module
#: stays importable without jax — the contract is pinned by tests)
_NF = "/nf"
_SQ = "/sq"


class NumericsAnomaly(Exception):
    """Raised by HealthMonitor under ``obs.health_action="abort"`` AFTER
    the tripwire actions (anomaly event, trace window, emergency
    checkpoint, flight dump) have run. Deliberately NOT a RuntimeError:
    the graftheal session loop catches RuntimeError to classify backend
    loss, and a numerics anomaly must never enter that path — there is
    no backend to heal, only state to roll back."""


class FlightRecorder:
    """Last-K in-memory ring of emitted event records + crash-time dump.

    ``append`` is the EventLog hook (called on EVERY emit, under no
    lock contention worth caring about — one deque append); ``dump``
    writes the ring as ``<directory>/flight_<reason>.json`` (atomic
    tmp+rename — the dump itself can race the kill it is diagnosing).
    Repeat dumps for the same reason overwrite: the event log keeps the
    full history, the flight file is the "last moments" convenience."""

    def __init__(self, directory: str, capacity: int = 256):
        self.directory = directory
        self.capacity = max(1, int(capacity))
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()

    def append(self, record: Dict[str, Any]):
        with self._lock:
            self._ring.append(record)

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def path_for(self, reason: str) -> str:
        return os.path.join(self.directory, f"flight_{reason}.json")

    def dump(self, reason: str) -> Optional[str]:
        """Write the ring (possibly empty — an early crash is still a
        crash) and return the file path. Best-effort BY CONTRACT: every
        caller sits on a failure path (watchdog thread, heal recovery,
        the crash handler's re-raise, the anomaly abort) where an
        OSError from a full disk or unwritable obs dir must not replace
        the error being diagnosed, kill the watchdog thread, or crash a
        healed run — a failed dump logs and returns None."""
        events = self.snapshot()
        path = self.path_for(reason)
        payload = {
            "reason": reason,
            "t_wall": time.time(),
            "last_step": events[-1].get("step", 0) if events else 0,
            "events": events,
        }
        tmp = f"{path}.tmp-{os.getpid()}"
        try:
            os.makedirs(self.directory, exist_ok=True)
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(payload, fh, default=_json_default)
            os.replace(tmp, path)
        except OSError as exc:
            logger.warning("graftpulse: flight dump %r failed: %r",
                           reason, exc)
            return None
        return path


class HealthMonitor:
    """Folds the step's in-graph health outputs into ``health`` events
    and turns anomalies into action (see module docstring).

    ``capture`` (optional, ``() -> carry``) refreshes the known-good
    snapshot after each CLEAN check — one device_get per health interval,
    the documented cost of a resumable tripwire; ``save`` (optional,
    ``carry -> path``) writes it as the emergency checkpoint when a wire
    trips. ``observe`` returns the tripped reasons (a list) or None, so
    "warn" callers can see what fired."""

    #: minimum clean history before the relative tripwires arm (a cold
    #: window has no meaningful median/std)
    MIN_GRAD_HISTORY = 5
    MIN_LOSS_HISTORY = 8

    def __init__(self, elog, every: int = 50, window: int = 64,
                 grad_factor: float = 100.0, loss_z: float = 10.0,
                 action: str = "abort", tracer=None, recorder=None,
                 capture: Optional[Callable[[], Any]] = None,
                 save: Optional[Callable[[Any], Optional[str]]] = None):
        if action not in ("abort", "warn"):
            raise ValueError(
                f"obs.health_action must be 'abort' or 'warn', "
                f"got {action!r}")
        self.elog = elog
        self.every = max(1, int(every))
        self.grad_factor = float(grad_factor)
        self.loss_z = float(loss_z)
        self.action = action
        self.tracer = tracer
        self.recorder = recorder
        self._capture = capture
        self._save = save
        self._latest: Optional[Dict[str, Any]] = None
        self._pos = (0, 0)
        self._since = 0
        window = max(8, int(window))
        self._losses: deque = deque(maxlen=window)
        self._grad_norms: deque = deque(maxlen=window)
        self.good = None  # last known-good carry (HealCarry shape)
        self.checks = 0
        self.anomalies = 0

    # -- the per-dispatch surface -------------------------------------------

    def observe(self, health: Dict[str, Any], epoch: int,
                dispatch: int) -> Optional[List[str]]:
        """Store the latest device-side health dict (a reference — no
        host sync) and, at the ``obs.health_every`` cadence, pull and
        check it. Returns the tripped reasons when a check fired one."""
        self._latest = health
        self._pos = (int(epoch), int(dispatch))
        self._since += 1
        if self._since < self.every:
            return None
        self._since = 0
        return self.check()

    # -- folding + tripwires -------------------------------------------------

    def check(self) -> Optional[List[str]]:
        """Pull the stored reading to host (the ONE cadenced device→host
        read — it piggybacks on outputs the step already returned), fold
        it into a ``health`` event and run the tripwires."""
        if self._latest is None:
            return None
        vals = {k: float(v) for k, v in self._latest.items()}
        self._latest = None
        loss = vals.pop("loss", None)
        nonfinite = {k[:-len(_NF)]: int(v) for k, v in vals.items()
                     if k.endswith(_NF)}
        norms = {k[:-len(_SQ)]: (math.sqrt(v) if math.isfinite(v) and v >= 0
                                 else v)
                 for k, v in vals.items() if k.endswith(_SQ)}
        grad_sq = [v for k, v in vals.items()
                   if k.startswith("grad/") and k.endswith(_SQ)]
        grad_norm = None
        if grad_sq:
            total = sum(grad_sq)
            grad_norm = (math.sqrt(total)
                         if math.isfinite(total) and total >= 0 else total)

        reasons: List[str] = []
        bad_nf = {k: n for k, n in nonfinite.items() if n}
        if bad_nf:
            reasons.append("nonfinite:" + ",".join(
                f"{k}={n}" for k, n in sorted(bad_nf.items())))
        if loss is not None and not math.isfinite(loss):
            reasons.append(f"loss_nonfinite:{loss}")

        grad_median = (statistics.median(self._grad_norms)
                       if self._grad_norms else None)
        if grad_norm is not None and not math.isfinite(grad_norm):
            if not bad_nf:
                # every element finite but the f32 squared sum overflowed
                # — a blowup the count alone cannot see
                reasons.append("grad_norm_overflow")
        elif (grad_norm is not None and grad_median is not None
                and len(self._grad_norms) >= self.MIN_GRAD_HISTORY
                and grad_median > 0
                and grad_norm > self.grad_factor * grad_median):
            reasons.append(
                f"grad_explode:{grad_norm:.3g}>"
                f"{self.grad_factor:g}x median {grad_median:.3g}")

        z = None
        if (loss is not None and math.isfinite(loss)
                and len(self._losses) >= self.MIN_LOSS_HISTORY):
            mean = statistics.fmean(self._losses)
            std = statistics.pstdev(self._losses)
            if std > 1e-12:
                z = (loss - mean) / std
                if abs(z) > self.loss_z:
                    reasons.append(
                        f"loss_z:{z:.1f} (loss {loss:.4g} vs trailing "
                        f"{mean:.4g}±{std:.3g})")

        if self.elog.enabled:
            self.elog.emit(
                "health", epoch=self._pos[0], dispatch=self._pos[1],
                loss=loss,
                loss_z=round(z, 3) if z is not None else None,
                grad_norm=grad_norm, grad_median=grad_median,
                nonfinite=nonfinite,
                norm={k: round(v, 6) if math.isfinite(v) else v
                      for k, v in norms.items()})
        self.checks += 1

        if not reasons:
            # Only CLEAN readings extend the trailing windows — an
            # anomalous value folded into the median/std would drag the
            # baseline toward the fault and mask the next one.
            if loss is not None and math.isfinite(loss):
                self._losses.append(loss)
            if grad_norm is not None and math.isfinite(grad_norm):
                self._grad_norms.append(grad_norm)
            if self._capture is not None:
                self.good = self._capture()
            return None
        return self._trip(reasons, loss, nonfinite)

    def _trip(self, reasons: List[str], loss, nonfinite) -> List[str]:
        """Anomaly → action: trace window first (capture whatever the
        run does next), then the emergency save of the known-good state,
        then the ``anomaly`` event and the flight dump (the dump follows
        the emit so the ring includes the anomaly record itself)."""
        self.anomalies += 1
        if self.tracer is not None:
            self.tracer.anomaly_window()
        saved = None
        if self.good is not None and self._save is not None:
            try:
                saved = self._save(self.good)
            except Exception as exc:  # noqa: BLE001  # graftlint: disable=broad-except — the emergency save is best-effort inside an already-failing run; the anomaly event/abort below must not be masked by a save failure
                logger.warning(
                    "graftpulse: emergency save of the known-good state "
                    "failed: %r", exc)
        flight = None
        if self.elog.enabled:
            self.elog.emit(
                "anomaly", epoch=self._pos[0], dispatch=self._pos[1],
                reasons=reasons, loss=loss, nonfinite=nonfinite,
                saved=saved,
                good_epoch=getattr(self.good, "epoch", None),
                good_dispatch=getattr(self.good, "dispatch", None),
                flight=(self.recorder.path_for("anomaly")
                        if self.recorder is not None else None))
        if self.recorder is not None:
            flight = self.recorder.dump("anomaly")
        logger.error(
            "graftpulse ANOMALY at epoch %d dispatch %d: %s (emergency "
            "checkpoint: %s, flight dump: %s)", self._pos[0], self._pos[1],
            "; ".join(reasons), saved, flight)
        if self.action == "abort":
            raise NumericsAnomaly(
                f"numerics anomaly at epoch {self._pos[0]} dispatch "
                f"{self._pos[1]}: {'; '.join(reasons)} — last known-good "
                f"checkpoint: {saved or 'none'}; resume with --resume auto "
                "(runbook: OUTAGES.md, 'run went nonfinite')")
        return reasons
