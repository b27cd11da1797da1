"""Per-iteration phase timing for the train loop — no host syncs added.

The split follows the loop's own structure (tools/train.py::fit_detector):

  data_wait_ms  — time blocked in the loader's ``next()`` (host input
                  pipeline: decode/augment/stack).
  dispatch_ms   — from batch-in-hand to the train step's RETURN. The step
                  is an async dispatch, so in steady state this is the
                  host-side enqueue cost — UNLESS the device queue is
                  full, in which case dispatch blocks and absorbs device
                  time (backpressure).
  step_ms       — the full iteration wall time (data wait + dispatch +
                  callback/bookkeeping). In steady state the device is
                  the bottleneck iff step_ms ≈ device step time: device
                  time is never measured directly because that would
                  take a per-step host sync, which is exactly the
                  overhead this repo's lazy-drain discipline
                  (train/metrics.py::MetricBag) exists to avoid. The
                  loop reads nothing of its newest dispatch (Speedometer
                  logs the dispatches already done); back-pressure holds
                  it to the device's rate, so windowed step_ms is honest
                  end-to-end time.

  <phase>_ms    — the iteration's time in each phase of ``LOOP_SPANS``
                  it spent any in: ``key_ms`` (the rng key's ``fold_in``,
                  the iteration's first device dispatch: with the
                  device's queue full the loop blocks HERE - read on the
                  chip, PR 25 - so back-pressure lands in ``key_ms``, not
                  in ``enqueue_ms``), ``place_ms`` (``shard_batch``, the
                  host->device placement of the batch), ``enqueue_ms``
                  (the ``step_fn`` call: the host's cost of launching the
                  step), ``metrics_ms`` (``bag.update`` + Speedometer),
                  ``observe_ms`` (the obs and resilience hooks: cost
                  tracker, trace arming and closing, the first
                  dispatch's counters, the health monitor, chaos, the
                  preemption stopper), ``snapshot_ms`` (the healer's
                  progress note, snapshot poll and begin). The phases
                  tile the iteration: ``step_ms`` = ``data_wait_ms`` +
                  the phases' sum + the timer's own few microseconds.
  gc_ms         — Python's collector inside the iteration (any
                  generation, any thread: a collection holds every
                  thread that wants the interpreter). It runs INSIDE
                  whichever phase it interrupts, so it is counted there
                  too: it is not one more term of the sum above.

The timer also OWNS the loop's spans on the profiler's clock
(``jax.profiler.TraceAnnotation``): they land in the same xplane as the
device ops, whoever started the profiler, so a device-idle gap can be
laid to the phase the loop thread was in. ``iterate`` wraps the loader's
``next()`` in ``train.next_batch`` and the rest of the iteration, its
``step`` event included, in a ``StepTraceAnnotation`` named ``train``
whose ``step_num`` is the ``step`` of that iteration's ``step`` event;
the loop body marks its phases with ``timer.span(name)``, ``name`` one of
``LOOP_SPANS``, so that every statement of the body lies in exactly one
phase. A collection of generation 1 or 2 is a ``train.gc`` annotation
nested in whatever span it interrupted (``gc.callbacks``: registered by
an enabled timer's ``iterate`` and removed when it ends, or by
``close()``).

When the sink is disabled, ``iterate`` degrades to ``enumerate``,
``span()`` to one shared null context and ``dispatched()`` to one
attribute check: zero events, zero annotations, zero allocations, and no
collector hook.
"""

from __future__ import annotations

import contextlib
import gc
import time

from mx_rcnn_tpu.obs.events import EventLog

#: the step annotation (``StepTraceAnnotation``): one per dispatch, from
#: batch-in-hand to the end of the iteration
STEP_SPAN = "train"
#: the loop's phases, the one closed list ``span`` accepts:
#: ``next_batch`` (``iterate``'s own: blocked in the loader), ``key`` (the
#: dispatch's rng key, ``fold_in``: two tiny device programs, the
#: iteration's first dispatch and so the one that blocks while the device's
#: queue is full - back-pressure lands here, not in ``enqueue``), ``place``
#: (``shard_batch``), ``enqueue`` (the ``step_fn`` call), ``metrics``
#: (``bag.update`` + Speedometer, whose every-``frequent``-steps line reads
#: only dispatches already done: no host sync), ``checkpoint`` (the
#: epoch-end save), ``observe`` (the obs and resilience hooks, before and
#: after the enqueue), ``snapshot`` (the healer's periodic snapshot)
LOOP_SPANS = ("train.next_batch", "train.key", "train.place",
              "train.enqueue", "train.metrics", "train.checkpoint",
              "train.observe", "train.snapshot")
#: a collection of generation >= 1, nested in the span it interrupted
GC_SPAN = "train.gc"
_NO_SPAN = contextlib.nullcontext()


class _Span:
    """One loop phase: a ``TraceAnnotation`` on the profiler's clock, and
    its duration kept for the iteration's ``step`` event."""

    __slots__ = ("spent", "name", "annotation", "t0")

    def __init__(self, spent: dict, name: str):
        import jax.profiler

        self.spent, self.name = spent, name
        self.annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        self.annotation.__exit__(*exc)
        self.spent[self.name] = (self.spent.get(self.name, 0.0)
                                 + time.perf_counter() - self.t0)


class _Collector:
    """``gc.callbacks`` hook: every collection's time goes to the
    iteration's ``train.gc`` total, and one of generation 1 or 2 is also a
    ``train.gc`` annotation nested in whatever span it interrupted (a
    young collection takes microseconds, and there are dozens a step)."""

    __slots__ = ("spent", "t0", "span")

    def __init__(self, spent: dict):
        self.spent, self.t0, self.span = spent, None, None

    def __call__(self, phase, info):
        if phase == "start":
            if info["generation"] >= 1:
                self.span = _Span(self.spent, GC_SPAN).__enter__()
            else:
                self.t0 = time.perf_counter()
        elif self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
        elif self.t0 is not None:
            self.spent[GC_SPAN] = (self.spent.get(GC_SPAN, 0.0)
                                   + time.perf_counter() - self.t0)
            self.t0 = None


class StepTimer:
    """Times each train iteration and emits one ``step`` event for it.

    Usage (the fit_detector wiring: every statement of the body in one
    phase)::

        timer = StepTimer(event_log, watchdog=watchdog)
        try:
            for i, batch in timer.iterate(epoch, batches):
                with timer.span("train.key"):
                    key = jax.random.fold_in(rng, i)
                with timer.span("train.place"):
                    sharded = shard_batch(batch, mesh)
                with timer.span("train.observe"):
                    ...                  # hooks before the dispatch
                with timer.span("train.enqueue"):
                    state, metrics = step_fn(state, sharded, key)
                    timer.dispatched()   # marks the dispatch boundary
                with timer.span("train.metrics"):
                    ...                  # metrics/callbacks
                with timer.span("train.snapshot"):
                    ...                  # the healer's snapshot
                with timer.span("train.observe"):
                    ...                  # hooks after the dispatch
        finally:
            timer.close()

    Also drives the stall watchdog (one ``beat`` per completed iteration,
    carrying the iteration duration for the trailing-median threshold)
    and refreshes the compile tracker's shape signature so a recompile
    event can name the batch shapes that triggered it.
    """

    def __init__(self, log: EventLog, watchdog=None, track_shapes=True,
                 enrich=None):
        """``enrich``: optional ``batch -> dict`` of extra fields for each
        step event (graftprof attaches the canvas + pad-waste fraction —
        host-side numpy over im_info, no device touch). Only called when
        the sink is enabled; must never raise for a well-formed batch."""
        self.log = log
        self.watchdog = watchdog
        self.track_shapes = track_shapes
        self.enrich = enrich
        self.total_steps = 0
        self._t_dispatch = None
        self._spent = {}  # span name -> seconds, this iteration
        self._hooks = []  # the gc hook of each ``iterate`` running

    def close(self):
        """Remove the collector hooks: ``iterate`` removes its own when it
        ends, and ``fit_detector``'s ``finally`` any that an error left
        behind (a generator left by an exception ends only when it is
        collected, maybe after a healed session's loop has begun)."""
        while self._hooks:
            gc.callbacks.remove(self._hooks.pop())

    def span(self, name: str):
        """A context manager for one phase of the loop body (``name`` in
        ``LOOP_SPANS``): a profiler annotation whose duration also lands
        in the iteration's ``step`` event. The shared null context when
        the sink is disabled."""
        if not self.log.enabled:
            return _NO_SPAN
        if name not in LOOP_SPANS:
            raise ValueError(f"{name!r} is not one of LOOP_SPANS")
        return _Span(self._spent, name)

    def dispatched(self):
        """Record the train-step return time (the dispatch boundary)."""
        if self.log.enabled:
            self._t_dispatch = time.perf_counter()

    def iterate(self, epoch: int, batches, start: int = 0):
        """Yield ``(i, batch)`` like ``enumerate(batches, start)``, timing
        each iteration. Pass-through when the sink is disabled. ``start``
        offsets the index for a mid-epoch resume, so logged/emitted batch
        numbers continue where the interrupted run stopped instead of
        double-using the indices it already recorded."""
        if not self.log.enabled:
            yield from enumerate(batches, start)
            return
        hook = _Collector(self._spent)
        gc.callbacks.append(hook)
        self._hooks.append(hook)
        try:
            yield from self._timed(epoch, iter(batches), start)
        finally:
            if hook in self._hooks:
                self._hooks.remove(hook)
                gc.callbacks.remove(hook)

    def _timed(self, epoch, it, i):
        import jax.profiler

        from mx_rcnn_tpu.obs import compile_track

        while True:
            t0 = time.perf_counter()
            self._spent.clear()
            with jax.profiler.TraceAnnotation(LOOP_SPANS[0]):
                if self.watchdog is not None:
                    # Phase marks bracket the blocking next(): a stall
                    # event fired while we sit here is attributed to
                    # data-wait (the input plane), not dispatch (the
                    # device queue).
                    self.watchdog.note_phase("data_wait")
                try:
                    batch = next(it)
                except StopIteration:
                    return
            t1 = time.perf_counter()
            with jax.profiler.StepTraceAnnotation(
                    STEP_SPAN, step_num=self.total_steps + 1):
                if self.watchdog is not None:
                    self.watchdog.note_phase("dispatch")
                if self.track_shapes:
                    compile_track.note_batch(batch)
                self._t_dispatch = None
                yield i, batch
                t2 = time.perf_counter()
                self.total_steps += 1
                self.log.set_step(self.total_steps)
                self._emit(epoch, i, batch, t0, t1, t2)
                if self.watchdog is not None:
                    self.watchdog.beat(t2 - t0)
            i += 1

    def _emit(self, epoch, i, batch, t0, t1, t2):
        fields = {
            "epoch": epoch,
            "batch": i,
            "data_wait_ms": round((t1 - t0) * 1e3, 3),
            "step_ms": round((t2 - t0) * 1e3, 3),
        }
        if self._t_dispatch is not None:
            fields["dispatch_ms"] = round((self._t_dispatch - t1) * 1e3, 3)
        for name in LOOP_SPANS[1:] + (GC_SPAN,):
            if name in self._spent:
                fields[name[len(STEP_SPAN) + 1:] + "_ms"] = round(
                    self._spent[name] * 1e3, 3)
        if self.enrich is not None:
            fields.update(self.enrich(batch) or {})
        self.log.emit("step", **fields)
