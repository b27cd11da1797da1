"""The rehearsal of ``vitdet_b_mask_train`` kept as a test: the benchmark's
command steered to a tiny size on the CPU (tests/benchmarks/
bm_tiny_vitdet.py: width 64, 8 blocks alternating windowed and global, a
128x128 canvas; one image a step, as the cell, and 32 rois) runs the
cell's control flow end to end through ``fit_detector``: AdamW with its
layer-wise rates, the first gradient read from its first moment, the
reference following with AdamW (with the relative-position bias left out,
the reference's planted fault, ``grad1`` reads 1.0:
``test_bm_vitdet_reference.py``). Both sides see the same pixels
and the same arithmetic, so the comparison is the arithmetic's: the program
computes in float32 here (``train.compute_dtype=f32``), and every image's
long side is the canvas's 128 (scale 1: the packed uint8 image is the
source's own, where a resize would round it to uint8 on the program's side
alone). At this size either difference moves the proposals and the worst
leaf by tens of percent (bfloat16: ``grad1`` 0.66; the uint8 rounding of a
resized image: 0.33), which says nothing of the control flow rehearsed;
with neither, ``grad1`` reads 3e-5. The seed is fixed, as in
``test_bm_rehearsal_fpn.py`` and for its reason."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, run  # noqa: E402
from bm_tiny_vitdet import tiny_vitdet  # noqa: E402

pytestmark = pytest.mark.compile_heavy
CELL = "vitdet_b_mask_train"
BM = manifest.load()
DEVICE_METRICS = {m["name"] for m in BM["per_layer"]
                  if m["source"] == "device_trace"}


def _run(trace, seed=2 ** 31 + 11):
    t = tiny_vitdet()
    t["overrides"].update({"train.compute_dtype": "f32",
                           "train.batch_images": 1, "train.batch_rois": 32})
    t["spec_overrides"].update(compute_dtype="f32", batch_images=1)
    t["spec_overrides"]["train"] = dict(t["spec_overrides"]["train"],
                                        batch_rois=32)
    t["mix_overrides"]["long_side"] = [128, 129]
    return run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "0.5", "--trace", str(trace)], platform="cpu", **t)


def test_traced_rehearsal_is_correct_and_names_no_device_metric(capsys):
    out = _run(trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == out["run"]["images"] > 0
    assert "loop.dispatch_ms.train" in out["metrics"]
    assert not DEVICE_METRICS & set(out["metrics"])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(last)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in last["compared"].values())
    # the trunk's tables are among the compared: none was skipped as
    # having no gradient
    assert not [k for k in out["run"]["where"]["skipped"]
                if k.endswith(("rel_pos_h", "rel_pos_w"))]

