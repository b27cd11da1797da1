"""Compile-event accounting via jax.monitoring.

Steady-state recompiles are the silent throughput killer on TPU: a shape
that drifts (an unpadded tail batch, a new pad bucket, a donation-layout
mismatch) costs minutes of XLA time that shows up only as a mysteriously
slow step. jax emits per-compile durations on its monitoring bus
(``/jax/core/compile/{jaxpr_trace,jaxpr_to_mlir_module,backend_compile}
_duration``); this module forwards them to the active EventLog as
``compile`` records, labelled with the shape signature of the batch most
recently handed to the train loop (StepTimer calls ``note_batch``) — the
prime recompile suspect.

jax's listener registry is append-only (no unregister), so the listener
is installed once per process and routed through a module-level active
sink; ``deactivate()`` just clears the sink. With no active sink the
listener is a two-comparison no-op.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from mx_rcnn_tpu.obs.events import EventLog

_lock = threading.Lock()
_active: Optional[EventLog] = None
_installed = False
_batch = None  # the most recently dispatched batch (a dict of arrays)

#: monitoring keys forwarded as compile events; the last path segment
#: (minus "_duration") becomes the record's ``phase`` field. Only
#: backend_compile is a real XLA compile — report counts those.
_COMPILE_SUFFIX = "_duration"
_COMPILE_MARKER = "/compile/"


def note_batch(batch) -> None:
    """Remember the batch about to be dispatched (cheap: one ref store).
    Read back only if a compile event actually fires."""
    global _batch
    _batch = batch


def shape_signature() -> Optional[Dict[str, Any]]:
    """Shapes of the last noted batch, or None before the first step
    (init/first-trace compiles have no triggering batch)."""
    batch = _batch
    if batch is None:
        return None
    try:
        return {k: list(getattr(v, "shape", ())) for k, v in batch.items()}
    except AttributeError:  # not a mapping — stringify the type instead
        return {"batch": [repr(type(batch))]}


class CompileCounter:
    """Tally of the executables built or fetched while registered —
    graftprof's per-bench-row compile accounting (``compile_s`` /
    ``n_executables``). jax 0.9 times ``compile_or_get_cached`` as a
    whole, so a persistent-cache hit fires a backend_compile event too
    (its duration is the retrieval). ``programs`` keeps each one's
    jitted-function name and seconds, so a caller can count the program
    it means (the train step is ``jit(step)``) rather than every
    helper."""

    def __init__(self):
        self.programs: List[Tuple[Optional[str], float]] = []

    @property
    def n(self) -> int:
        return len(self.programs)

    @property
    def seconds(self) -> float:
        return sum(s for _, s in self.programs)

    def count_of(self, fun: str) -> int:
        return sum(1 for f, _ in self.programs if f == fun)


_counters: list = []


def _on_event_duration(event: str, duration_secs: float, **kwargs) -> None:
    if _COMPILE_MARKER not in event:
        return
    phase = event.rsplit("/", 1)[-1]
    if phase.endswith(_COMPILE_SUFFIX):
        phase = phase[: -len(_COMPILE_SUFFIX)]
    fun = kwargs.get("fun_name")
    if phase == "backend_compile" and _counters:
        with _lock:
            for c in _counters:
                c.programs.append((fun, duration_secs))
    log = _active
    if log is None:
        return
    log.emit("compile", phase=phase, event=event, fun=fun,
             duration_ms=round(duration_secs * 1e3, 3),
             shapes=shape_signature())


def _ensure_installed() -> None:
    """Register the jax.monitoring listener once per process."""
    global _installed
    with _lock:
        if not _installed:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _installed = True


def count() -> "_CountContext":
    """Context manager tallying backend compiles in its window::

        with compile_track.count() as cc:
            ...  # compiles here
        row["compile_s"], row["n_executables"] = cc.seconds, cc.n

    Independent of any active EventLog (bench child processes count
    their own compiles with no sink attached); nested counters all see
    every compile in their window."""
    return _CountContext()


class _CountContext:
    def __enter__(self) -> CompileCounter:
        self.counter = CompileCounter()
        _ensure_installed()
        with _lock:
            _counters.append(self.counter)
        return self.counter

    def __exit__(self, *exc):
        with _lock:
            if self.counter in _counters:
                _counters.remove(self.counter)
        return False


def activate(log: EventLog) -> None:
    """Route compile events to ``log``."""
    global _active
    _ensure_installed()
    with _lock:
        _active = log


def deactivate() -> None:
    global _active, _batch
    with _lock:
        _active = None
        _batch = None
