"""Speedometer — the reference's only perf instrumentation, kept log-compatible.

Reference: rcnn/core/callback.py::Speedometer(batch_size, frequent) logging
'Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec\t%s' — the samples/sec line is
the throughput number BASELINE.md tracks, so the format is preserved.
"""

from __future__ import annotations

import time

from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.train.metrics import MetricBag


class Speedometer:
    """Logs the reference-format throughput line and, when a graftscope
    event log is attached, also emits each window as a ``step`` event
    carrying ``samples_per_sec`` (obs/report.py prefers these measured
    windows). The line's metrics are the bag's READY-ONLY means
    (train/metrics.py): read from dispatches already done, never waited
    for, so they trail the loop by at most the depth of the device's
    queue. ``Speed`` is dispatches per host second; with the queue full the
    loop is held to the device's rate by back-pressure (at ``train.key``,
    obs/timing.py), so it stays honest end-to-end throughput."""

    def __init__(self, batch_size: int, frequent: int = 20, event_log=None):
        self.batch_size = batch_size
        self.frequent = frequent
        self.event_log = event_log
        # monotonic, not wall: an NTP step inside a window would corrupt
        # the samples/sec line (wall-time-duration lint rule).
        self._tic = time.monotonic()
        self._count = 0

    def __call__(self, epoch: int, batch: int, metrics: MetricBag):
        self._count += 1
        if self._count % self.frequent == 0:
            speed = (self.frequent * self.batch_size
                     / (time.monotonic() - self._tic))
            logger.info(
                "Epoch[%d] Batch [%d]\tSpeed: %.2f samples/sec\t%s",
                epoch, batch, speed, metrics.format(ready_only=True),
            )
            if self.event_log is not None and self.event_log.enabled:
                self.event_log.emit("step", epoch=epoch, batch=batch,
                                    samples_per_sec=round(speed, 3),
                                    window=self.frequent)
            self._tic = time.monotonic()
            return speed
        return None
