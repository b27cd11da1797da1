"""Host-side running metric accumulators.

Reference: rcnn/core/metric.py — RPNAccMetric, RPNLogLossMetric,
RPNL1LossMetric, RCNNAccMetric, RCNNLogLossMetric, RCNNL1LossMetric, each an
mx.metric.EvalMetric reading fixed output-group slots and keeping
(sum, count) running averages printed by Speedometer.

Here the per-batch values are computed on device inside the train step
(train/step.py::_metrics_from_aux) and this bag just averages scalars —
no per-batch device→host sync of full tensors.
"""

from __future__ import annotations

from typing import Dict, Iterable

METRIC_NAMES = (
    "RPNAcc", "RPNLogLoss", "RPNL1Loss",
    "RCNNAcc", "RCNNLogLoss", "RCNNL1Loss",
    "TotalLoss",  # not one of the reference's 6 — kept for the epoch log
)


class MetricBag:
    """Accumulates per-batch metric dicts LAZILY: update() stores the device
    scalars without forcing a host sync; conversion happens at a drain. The
    drain has two forms. The FULL one (``get()``, ``format()``,
    ``snapshot()``) converts every pending entry and so waits for the
    newest dispatch: for the epoch's end and for a capture that needs the
    sums now. The READY-ONLY one (``ready_only=True``: Speedometer's line)
    folds, in order, the pending entries whose scalars are already there
    and stops at the first that is not, so the train loop never waits on
    the dispatch it has just made; the means it gives trail the loop by at
    most the depth of the device's queue. Both fold in dispatch order, so
    the sums do not depend on which drains ran in between."""

    def __init__(self, names: Iterable[str] = METRIC_NAMES):
        self.names = tuple(names)
        self.reset()

    def reset(self):
        self._pending = []
        self._sums = {n: 0.0 for n in self.names}
        self._counts = {n: 0 for n in self.names}

    def update(self, metrics: Dict):
        self._pending.append(metrics)

    def _is_ready(self, m: Dict) -> bool:
        """Would ``float()`` of this entry's scalars return without waiting
        for the device? (Host numbers have no ``is_ready`` and always are.)"""
        return all(v.is_ready() for n in self.names
                   if hasattr(v := m.get(n), "is_ready"))

    def _drain(self, ready_only: bool = False):
        done = 0
        for m in self._pending:
            if ready_only and not self._is_ready(m):
                break
            for n in self.names:
                if n in m:
                    self._sums[n] += float(m[n])
                    self._counts[n] += 1
            done += 1
        del self._pending[:done]

    def fork(self) -> "MetricBag":
        """The bag as it stands at this dispatch, as a bag of its own: the
        sums so far plus the pending list (the same device scalars, not
        yet read). The deferred form of ``snapshot()``: once ``ready()``,
        the fork's ``snapshot(ready_only=True)`` returns exactly what this
        bag's ``snapshot()`` would have returned here."""
        twin = MetricBag(self.names)
        twin._pending = list(self._pending)
        twin._sums, twin._counts = dict(self._sums), dict(self._counts)
        return twin

    def ready(self) -> bool:
        """True when a full drain would not wait for the device."""
        return all(self._is_ready(m) for m in self._pending)

    def snapshot(self, ready_only: bool = False):
        """Drained (sums, counts) as host floats — the graftheal carry:
        captured with the train state so a healed mid-epoch resume keeps
        accounting for the pre-loss dispatches (and a snapshot-rollback
        replay re-adds exactly the dispatches it replays). ``ready_only``
        is for a fork that is ``ready()``: the same result, and no read
        that could wait."""
        self._drain(ready_only)
        return dict(self._sums), dict(self._counts)

    def restore(self, snap):
        """Inverse of snapshot() onto a fresh bag."""
        sums, counts = snap
        self._pending = []
        self._sums = {n: float(sums.get(n, 0.0)) for n in self.names}
        self._counts = {n: int(counts.get(n, 0)) for n in self.names}

    def get(self, ready_only: bool = False) -> Dict[str, float]:
        """Per-slot running means of the metrics ACTUALLY SEEN — each slot
        averages over the updates that carried it (the reference
        EvalMetrics' (sum_metric, num_inst) semantics), so a model family
        that doesn't emit a slot (DETR has no RPN) doesn't log zeros for
        it and an intermittent slot isn't diluted.

        Contract: slots never seen are OMITTED — including from an empty
        bag, which returns {} (one rule, no empty-epoch special case).
        Fixed-key consumers should use ``bag.get().get(name, default)``.

        ``ready_only``: the means over the dispatches already done (see the
        class docstring) instead of waiting for all of them."""
        self._drain(ready_only)
        return {n: self._sums[n] / c
                for n in self.names if (c := self._counts[n]) > 0}

    def format(self, ready_only: bool = False) -> str:
        return "\t".join(f"Train-{n}={v:.6f}"
                         for n, v in self.get(ready_only).items())
