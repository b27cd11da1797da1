"""Room for a later PR, for the ViTDet cell's check: a copy of
``BENCHMARK.json`` with one more configuration, cell and per-layer metric
appended still passes it (test_bm_manifest_room.py holds the other
readers' checks the same way)."""

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from benchmarks import manifest  # noqa: E402
from test_bm_trace_vitdet import the_vitdet_metrics_hold  # noqa: E402

CELL = "next_config_train"


def _with_room(lists: str) -> dict:
    """A later configuration and its cell appended, the cell on every
    metric's list (``every``) or on those of the metrics every cell
    reports (``loop``), and a metric of its own at the end."""
    bm = copy.deepcopy(manifest.load())
    bm["configs"].append({"name": "next_config",
                          "source": "a later configuration",
                          "file": "benchmarks/configs/next_config.json",
                          "reduced": [], "why": "a later configuration"})
    bm["workloads"].append({"name": CELL, "config": "next_config",
                            "traffic": "train_packed_landscape", "chips": 1,
                            "why": "a later cell on the pyramid heads"})
    cells = [w["name"] for w in bm["workloads"]]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" not in m:
            continue
        if lists == "every" or sorted(m["workloads"] + [CELL]) == sorted(
                cells):
            m["workloads"].append(CELL)
    bm["per_layer"].append({
        "name": "attn_roofline.windowed", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_img_per_s_chip", "workloads": [CELL]})
    return bm


@pytest.mark.parametrize("lists", ["every", "loop"])
def test_a_cell_and_a_metric_appended_pass_the_vitdet_check(lists):
    bm = _with_room(lists)
    assert bm["per_layer"][-1]["name"] == "attn_roofline.windowed"
    the_vitdet_metrics_hold(bm)
