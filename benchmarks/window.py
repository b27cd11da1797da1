"""The measured window, taken from the one place the train loop hands
control back: its loader.

``fit_detector`` is one call with no hook between steps except the iterator
it draws batches from (its own ``loader_factory`` argument). ``WindowLoader``
wraps the program's loader and, from inside ``next()``:

- serves the checked steps one per epoch (the loop's ``epoch_callback`` then
  sees the state after each), then the warm-up steps;
- opens the window at a drained device, counts every batch handed out (each
  is one optimizer step: the loop dispatches it before it asks again), and
  closes the window at a drained device once ``seconds`` have passed;
- times every request (gap to the previous one, time blocked in the inner
  loader) and, in a traced run, brackets ``trace_seconds`` of steady state
  just before the window with the profiler.

Draining: a tiny program enqueued on each device after the steps and waited
for. A TPU core runs its programs in launch order, so it ends only when all
the steps before it have.
"""

from __future__ import annotations

import time

import jax
import numpy as np

EPOCH_LEN = 1 << 20  # what len() reports: the schedule never reaches a boundary


class Drain:
    def __init__(self, devices):
        self.devices = list(devices)
        self.ones = [jax.device_put(np.float32(1), d) for d in self.devices]
        self.bump = jax.jit(lambda x: x + 1)
        self()

    def __call__(self):
        for o in [self.bump(x) for x in self.ones]:
            o.block_until_ready()


class WindowLoader:
    def __init__(self, inner, *, checked_steps, warmup_steps, seconds,
                 devices, trace_dir=None, trace_seconds=0.0, on_open=None):
        self.inner = inner
        self.checked = int(checked_steps)
        self.warmup = int(warmup_steps)
        self.seconds = float(seconds)
        self.trace_dir, self.trace_seconds = trace_dir, float(trace_seconds)
        self.on_open = on_open
        self.drain = Drain(devices)
        self.epoch = 0
        self.first_batches = []   # the checked steps' batches, as served
        self.requests = []        # (t_request, t_served) inside the window
        self.t_open = self.t_close = None
        self.steps = 0
        self.trace = None         # (t0, t1, steps) of the traced interval
        inner.set_epoch(0)
        self._it = iter(inner)

    # -- what fit_detector asks of a loader --------------------------------
    def __len__(self):
        return EPOCH_LEN

    def set_epoch(self, epoch):
        self.epoch = int(epoch)

    def close(self):
        if self.epoch >= self.checked:
            self.inner.close()

    def __iter__(self):
        if self.epoch < self.checked:
            batch = next(self._it)
            self.first_batches.append(
                {k: np.array(v) for k, v in batch.items() if k != "image"}
                | {"image": batch["image"]})
            yield batch
            return
        yield from self._last_epoch()

    # -- warm-up, trace, window -------------------------------------------
    def _serve(self):
        with jax.profiler.TraceAnnotation("bench.loader_next"):
            return next(self._it, None)

    def _last_epoch(self):
        for _ in range(self.warmup):
            batch = self._serve()
            if batch is None:
                return
            yield batch
        if self.trace_dir and self.trace_seconds > 0:
            yield from self._traced()
        self.drain()
        if self.on_open:
            self.on_open()
        self.t_open, self.t_open_mono = time.perf_counter(), time.monotonic()
        while True:
            t_req = time.perf_counter()
            if t_req - self.t_open >= self.seconds:
                break
            batch = self._serve()
            if batch is None:
                break
            self.requests.append((t_req, time.perf_counter()))
            self.steps += 1
            yield batch
        self.drain()
        self.t_close, self.t_close_mono = time.perf_counter(), time.monotonic()

    def _traced(self):
        """``trace_seconds`` of steady state under the profiler. The profiler
        takes a second or more to start (the chip idles meanwhile), so two
        lead-in steps run first; the traced interval opens at a drained
        device after them and is marked by the ``bench.traced`` span, which
        is what the reduction takes as its window."""
        self.drain()
        jax.profiler.start_trace(self.trace_dir)
        for _ in range(2):
            batch = self._serve()
            if batch is None:
                break
            yield batch
        self.drain()
        t0, n = time.perf_counter(), 0
        with jax.profiler.TraceAnnotation("bench.traced"):
            while time.perf_counter() - t0 < self.trace_seconds:
                batch = self._serve()
                if batch is None:
                    break
                n += 1
                with jax.profiler.TraceAnnotation("bench.step_dispatch"):
                    yield batch
            with jax.profiler.TraceAnnotation("bench.drain"):
                self.drain()
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        self.trace = (t0, t1, n)

    # -- what the window measured -----------------------------------------
    @property
    def window_s(self):
        return self.t_close - self.t_open

    def gaps_ms(self):
        t = [r[0] for r in self.requests]
        return [(b - a) * 1e3 for a, b in zip(t, t[1:])]

    def wait_s(self):
        return sum(served - asked for asked, served in self.requests)
