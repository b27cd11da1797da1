"""MetricBag accumulation semantics (train/metrics.py).

The reference's six EvalMetrics (rcnn/core/metric.py) keep (sum, count)
running averages printed by Speedometer; MetricBag is the lazy host-side
analog. These tests pin the family-aware slot reporting added for DETR.
"""

import numpy as np
import pytest

from mx_rcnn_tpu.train.metrics import MetricBag


def test_running_means():
    bag = MetricBag()
    bag.update({"TotalLoss": 2.0, "RPNAcc": 0.5})
    bag.update({"TotalLoss": 4.0, "RPNAcc": 1.0})
    got = bag.get()
    assert got["TotalLoss"] == 3.0
    assert got["RPNAcc"] == 0.75


def test_unseen_slots_are_omitted():
    """A family that never emits a slot (DETR: no RPN, no accuracies)
    must not log zeros for it."""
    bag = MetricBag()
    bag.update({"TotalLoss": 5.0, "RCNNLogLoss": 0.7, "RCNNL1Loss": 4.3})
    got = bag.get()
    assert set(got) == {"TotalLoss", "RCNNLogLoss", "RCNNL1Loss"}
    assert "RPNAcc" not in got and "RCNNAcc" not in got


def test_empty_bag_returns_empty_dict():
    """No updates at all (empty epoch): unseen slots are omitted — the
    SAME rule as mid-training, so a fixed-key consumer that works on an
    empty epoch cannot start KeyError-ing once updates arrive."""
    bag = MetricBag()
    assert bag.get() == {}


def test_intermittent_slot_uses_per_slot_count():
    """A slot present in only some updates averages over THOSE updates
    (the reference EvalMetrics' (sum, count) pairs), not the global
    update count — no dilution."""
    bag = MetricBag()
    bag.update({"TotalLoss": 2.0, "RPNAcc": 0.5})
    bag.update({"TotalLoss": 4.0})
    got = bag.get()
    assert got["TotalLoss"] == 3.0
    assert got["RPNAcc"] == 0.5  # 0.5/1, not 0.5/2


def test_reset_clears_seen_and_sums():
    bag = MetricBag()
    bag.update({"TotalLoss": 2.0})
    bag.get()
    bag.reset()
    assert bag.get() == {}  # back to the empty-bag shape
    bag.update({"RPNLogLoss": 1.0})
    assert set(bag.get()) == {"RPNLogLoss"}


def test_lazy_drain_accepts_device_scalars():
    """update() must not force conversion; get() converts anything
    float()-able (device scalars, 0-d numpy)."""
    bag = MetricBag()
    bag.update({"TotalLoss": np.float32(1.5)})
    bag.update({"TotalLoss": np.asarray(2.5)})
    assert bag.get()["TotalLoss"] == 2.0


def test_format_is_speedometer_style():
    bag = MetricBag()
    bag.update({"TotalLoss": 1.0, "RPNAcc": 0.5})
    s = bag.format()
    assert "Train-TotalLoss=1.000000" in s
    assert "Train-RPNAcc=0.500000" in s
    assert "RCNNAcc" not in s


# ---------------------------------------------------------------------------
# the ready-only drain and the deferred snapshot (the loop's deferred reads)
# ---------------------------------------------------------------------------

class _Scalar:
    """A device scalar's surface: ``is_ready()`` and ``float()``. Reading
    one that is not ready is the blocking read the loop must not make."""

    def __init__(self, value, ready=True):
        self.value, self.ready = value, ready

    def is_ready(self):
        return self.ready

    def __float__(self):
        if not self.ready:
            raise AssertionError("float() of a scalar that is not ready")
        return float(self.value)


#: losses whose float sum depends on the order of the additions
_LOSSES = (1e8, 1.0, -1e8, 0.1, 3.3, 7e-9)


def _entries(ready):
    return [{"TotalLoss": _Scalar(v, r), "RPNAcc": _Scalar(v / 2, r)}
            for v, r in zip(_LOSSES, ready)]


def test_ready_only_drain_stops_at_the_first_entry_not_ready():
    """Entries fold in order and only while ready: one that is ready
    BEHIND one that is not waits its turn, and nothing that is not ready
    is ever converted."""
    bag = MetricBag()
    entries = _entries([True, True, False, True, False, False])
    for m in entries:
        bag.update(m)
    got = bag.get(ready_only=True)
    assert got["TotalLoss"] == (1e8 + 1.0) / 2
    assert len(bag._pending) == 4  # the fourth is ready but not next
    assert "Train-TotalLoss=50000000.5" in bag.format(ready_only=True)
    entries[2]["TotalLoss"].ready = entries[2]["RPNAcc"].ready = True
    assert bag.get(ready_only=True)["TotalLoss"] == (1e8 + 1.0 - 1e8 + 0.1) / 4
    assert not bag.ready()
    with pytest.raises(AssertionError, match="not ready"):
        bag.get()  # the full drain is the blocking one


def test_ready_only_drain_of_nothing_ready_is_the_empty_bag():
    bag = MetricBag()
    bag.update({"TotalLoss": _Scalar(1.0, ready=False)})
    assert bag.get(ready_only=True) == {} and bag.format(ready_only=True) == ""


@pytest.mark.parametrize("drains_at", [(), (1,), (2, 3), (0, 1, 2, 3, 4, 5)])
def test_get_after_ready_only_drains_equals_a_whole_drain(drains_at):
    """Whatever ready-only drains ran in between, the final means are those
    of a bag that was only ever drained whole: the folds are in dispatch
    order either way, so the float sums are the same to the last bit."""
    whole = MetricBag()
    for v in _LOSSES:
        whole.update({"TotalLoss": v, "RPNAcc": v / 2})
    bag, entries = MetricBag(), _entries([False] * len(_LOSSES))
    for i, m in enumerate(entries):
        bag.update(m)
        if i in drains_at:
            for done in entries[:max(i - 1, 0)]:  # the loop runs ahead
                done["TotalLoss"].ready = done["RPNAcc"].ready = True
            bag.get(ready_only=True)
    for m in entries:
        m["TotalLoss"].ready = m["RPNAcc"].ready = True
    assert bag.get() == whole.get()


def test_fork_resolves_to_the_snapshot_of_its_dispatch():
    """The deferred token: taken at a dispatch without reading anything,
    it later gives exactly what ``snapshot()`` would have returned there —
    not what the bag holds by the time it is resolved."""
    bag, entries = MetricBag(), _entries([True, True, False, False, False,
                                          False])
    for m in entries[:2]:
        bag.update(m)
    bag.get(ready_only=True)  # two folded into the sums, none pending
    for m in entries[2:4]:
        bag.update(m)
    token = bag.fork()  # "dispatch 4": sums of two + two pending
    for m in entries[4:]:
        bag.update(m)
    assert not token.ready()
    for m in entries:
        m["TotalLoss"].ready = m["RPNAcc"].ready = True
    assert token.ready()

    at_dispatch_4 = MetricBag()
    for v in _LOSSES[:4]:
        at_dispatch_4.update({"TotalLoss": v, "RPNAcc": v / 2})
    assert token.snapshot(ready_only=True) == at_dispatch_4.snapshot()
    # the fork is a bag of its own: resolving it took nothing from the live one
    assert bag.snapshot()[1]["TotalLoss"] == 6
