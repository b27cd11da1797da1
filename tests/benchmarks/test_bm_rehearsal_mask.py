"""The rehearsal of ``mask_r101_train`` kept as a test: the benchmark's
command steered to a tiny size on the CPU (R-50, a 128x192 canvas, 2 images,
64 candidates a level, 64 rois of which 16 slots are the mask branch's) runs
the cell's control flow end to end through ``fit_detector``: the loader
serves ``gt_masks`` for records that carry none, the step trains the mask
head, the reference follows with its fifth loss; with the branch broken
underneath, ``correct`` comes out false. The seed is fixed, as in
``test_bm_rehearsal_fpn.py`` and for its reason."""

import ast
import json
import logging
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, run  # noqa: E402
from bm_tiny_mask import tiny_mask  # noqa: E402
from test_bm_rehearsal import _broken  # noqa: E402

pytestmark = pytest.mark.compile_heavy
CELL = "mask_r101_train"
BM = manifest.load()
DEVICE_METRICS = {m["name"] for m in BM["per_layer"]
                  if m["source"] == "device_trace"} | {
                      "step.mfu.train", "step.mfu.train.pyramid",
                      "step.mfu.train.mask"}


def _run(trace, seed=2 ** 31 + 11):
    return run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.5", "--trace", str(trace)], platform="cpu",
                    **tiny_mask())


def test_traced_rehearsal_is_correct_and_names_no_device_metric(capsys,
                                                                caplog):
    with caplog.at_level(logging.INFO, logger="mx_rcnn_tpu"):
        out = _run(trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == out["run"]["images"] > 0
    assert "loop.dispatch_ms.train" in out["metrics"]
    assert not DEVICE_METRICS & set(out["metrics"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(last)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in last["compared"].values())
    # the mask head's leaves are among the compared: none was skipped as
    # having no gradient, and the reference's loss holds a fifth part
    assert not [k for k in out["run"]["where"]["skipped"]
                if k.startswith("mask_head/")]
    # obs.enabled: the branch's live rois at the first dispatch, once a run
    lines = [r.getMessage() for r in caplog.records
             if "mask branch at dispatch" in r.getMessage()]
    assert len(lines) == 1
    got = ast.literal_eval(lines[0].split(": ", 1)[1])
    assert got["slots"] == 16
    assert 0 <= got["per_image_min"] <= got["per_image_mean"] <= got[
        "per_image_max"] <= 16
    assert got["per_image_max"] >= 1 and len(got["share"]) == 4
    assert sum(got["share"]) == pytest.approx(1.0, abs=1e-3)


def test_a_step_without_the_mask_loss_is_not_correct(monkeypatch):
    """The planted fault on the PROGRAM's side: the step trains on batches
    whose ``gt_masks`` are nought, so every target is 0 and the head learns
    an empty mask; the head's leaves' first gradient is far from the
    reference's and ``grad1`` refuses."""
    import jax.numpy as jnp

    _broken(monkeypatch, lambda step, state, batch, key: step(
        state, dict(batch, gt_masks=jnp.zeros_like(batch["gt_masks"])), key))
    out = _run(trace=0)
    assert out["correct"] is False
    failed = [k for k, v in out["compared"].items() if v["value"] > v["limit"]]
    assert "grad1" in failed or "grad1_med" in failed, out["compared"]
    assert out["run"]["where"]["grad1"].startswith("mask_head/"), out["run"]
    assert set(out["metrics"]) == {"setup_s", "train_img_per_s_chip"}
