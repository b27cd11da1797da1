"""The train loop never waits on its newest dispatch.

fit_detector's two cadenced host reads — the heal snapshot
(resilience/heal.py) and Speedometer's line (train/callback.py) — are
deferred reads: begun at their dispatch, finished at a later one from
values already there. The blocking forms stay for whoever needs the live
state now (the epoch's end, ``Healer.recover``): ``MetricBag._drain`` in
full and ``host_tree_copy``. This gate runs the real loop across a
snapshot and three Speedometer lines and holds that neither blocking form
is reached from inside the dispatch loop.
"""

import logging

import pytest

from mx_rcnn_tpu.data.loader import AnchorLoader
from mx_rcnn_tpu.obs import report
from mx_rcnn_tpu.resilience import heal as heal_mod
from mx_rcnn_tpu.tools import train as train_mod
from mx_rcnn_tpu.train.metrics import MetricBag

import _resilience_driver as driver

DISPATCHES, FREQUENT, SNAPSHOT_EVERY = 36, 10, 12


class _MarkedLoader:
    """The program's loader, marking when the loop's BODY runs: the
    generator is suspended at its ``yield`` exactly while fit_detector
    works on the batch it was handed, and the epoch's end comes after its
    last resumption."""

    def __init__(self, inner, where):
        self.inner, self.where = inner, where

    def __len__(self):
        return len(self.inner)

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __iter__(self):
        for batch in self.inner:
            self.where["in_body"] = True
            yield batch
            self.where["in_body"] = False


@pytest.mark.compile_heavy
def test_no_blocking_read_inside_the_dispatch_loop(tmp_path, monkeypatch,
                                                   caplog):
    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset

    where, reads = {"in_body": False}, []
    full_drain, tree_copy = MetricBag._drain, heal_mod.host_tree_copy

    def drain(self, ready_only=False):
        if not ready_only:
            reads.append(("MetricBag._drain", where["in_body"]))
        return full_drain(self, ready_only)

    def copy(tree):
        reads.append(("host_tree_copy", where["in_body"]))
        return tree_copy(tree)

    monkeypatch.setattr(MetricBag, "_drain", drain)
    monkeypatch.setattr(heal_mod, "host_tree_copy", copy)
    monkeypatch.setattr(train_mod, "host_tree_copy", copy)

    def factory(roidb, cfg, n_shards, **kw):
        return _MarkedLoader(
            AnchorLoader(roidb, cfg, num_shards=n_shards, seed=0, **kw),
            where)

    ds = SyntheticDataset("train", num_images=DISPATCHES, image_size=64,
                          max_objects=1, min_size_frac=3, max_size_frac=2)
    obs_dir = str(tmp_path / "obs")
    cfg = driver.tiny_config(obs_dir, over_extra={
        "resilience.heal_snapshot_dispatches": SNAPSHOT_EVERY})
    with caplog.at_level(logging.INFO, logger="mx_rcnn_tpu"):
        train_mod.fit_detector(cfg, ds.gt_roidb(), prefix=str(tmp_path / "m"),
                               end_epoch=1, frequent=FREQUENT, seed=0,
                               loader_factory=factory)

    # the run held what the gate is about: every Speedometer line, with
    # means read from dispatches already done, and a snapshot installed
    lines = [r.getMessage() for r in caplog.records
             if "samples/sec" in r.getMessage()]
    assert len(lines) == DISPATCHES // FREQUENT
    assert "Train-TotalLoss=" in lines[-1]
    snap = [e for e in report.load_events(obs_dir)
            if e["type"] == "snapshot"][0]
    assert snap["taken_at"] == SNAPSHOT_EVERY and snap["in_flight"] >= 1
    assert any("snapshot taken at dispatch 12" in r.getMessage()
               for r in caplog.records)

    # the probes are live (the epoch's end drains in full, the starting
    # fallback is a blocking copy) and the loop's body reached neither
    assert ("MetricBag._drain", False) in reads
    assert ("host_tree_copy", False) in reads
    assert not [r for r in reads if r[1]], reads
