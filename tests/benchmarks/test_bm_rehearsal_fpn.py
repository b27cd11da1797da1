"""The rehearsal of ``fpn_r101_train`` kept as a test: the benchmark's
command steered to a tiny size on the CPU (R-50, a 128x192 canvas, 2 images,
64 candidates a level, 32 rois) runs the pyramid cell's control flow end to
end through ``fit_detector``; with the timed path broken underneath,
``correct`` comes out false. The seed is fixed: with 32 rois an image one roi
sampled otherwise moves the worst leaf's gradient far more than at 512 (this
seed reads ``grad1`` 0.14 against the cell's 0.3; seed 5 reads 0.46)."""

import json
import logging
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, run  # noqa: E402
from bm_tiny_fpn import tiny_fpn  # noqa: E402
from test_bm_rehearsal import _broken  # noqa: E402

pytestmark = pytest.mark.compile_heavy
CELL = "fpn_r101_train"
BM = manifest.load()
DEVICE_METRICS = {m["name"] for m in BM["per_layer"]
                  if m["source"] == "device_trace"} | {
                      "step.mfu.train", "step.mfu.train.pyramid"}


def _run(trace, seed=2 ** 31 + 11):
    return run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.5", "--trace", str(trace)], platform="cpu",
                    **tiny_fpn())


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
    # obs.enabled: the first dispatch's sampled rois by level, once a run
    lines = [r.getMessage() for r in caplog.records
             if "sampled rois by pyramid level" in r.getMessage()]
    assert len(lines) == 1
    share = json.loads(lines[0].split(": ", 1)[1])
    assert len(share) == 4 and sum(share) == pytest.approx(1.0, abs=1e-3)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    _broken(monkeypatch, lambda step, state, batch, key:
            (state, step(state, batch, key)[1]))
    out = _run(trace=0)
    assert out["correct"] is False
    assert out["compared"]["dw3"]["value"] == pytest.approx(1.0)
    assert set(out["metrics"]) == {"setup_s", "train_img_per_s_chip"}
    assert out["run"]["images"] == 2 * out["run"]["steps"]


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    def half(step, state, batch, key):
        h = batch["image"].shape[0] // 2
        return step(state, {k: jnp.concatenate([v[:h], v[:h]])
                            for k, v in batch.items()}, key)

    _broken(monkeypatch, half)
    out = _run(trace=0)
    assert out["correct"] is False
    failed = [k for k, v in out["compared"].items() if v["value"] > v["limit"]]
    assert "grad1_rpn" in failed or "dw3_rpn" in failed, out["compared"]
