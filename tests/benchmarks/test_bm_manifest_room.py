"""Room for a later PR: a copy of ``BENCHMARK.json`` with one more cell and
one more per-layer metric appended still passes every check the readers'
tests make of the manifest. Each check holds a metric to its own cells, not
to the position or the length of a list."""

import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from benchmarks import manifest  # noqa: E402
from test_bm_roi_align_taps import the_taps_metric_holds  # noqa: E402
from test_bm_trace_fpn import the_pyramid_metrics_hold  # noqa: E402
from test_bm_trace_idle import the_four_hold  # noqa: E402
from test_bm_trace_mask import the_mask_metrics_hold  # noqa: E402
from test_bm_trace_scopes import the_ten_hold  # noqa: E402

CHECKS = (the_four_hold, the_pyramid_metrics_hold, the_ten_hold,
          the_mask_metrics_hold, the_taps_metric_holds)
CELL = "vitdet_b_mask_train"


def _with_room(lists: str) -> dict:
    """The manifest as a change that adds a configuration would leave it: a
    configuration, a cell appended to ``workloads`` and to the lists of the metrics it
    reports (``every``: every metric's list; ``loop``: the end-to-end rate
    and the metrics of every cell's loop alone), and a metric of its own
    appended at the end of ``per_layer``."""
    bm = copy.deepcopy(manifest.load())
    bm["configs"].append({"name": "vitdet_b_mask_coco",
                          "source": "arXiv:2203.16527",
                          "file": "benchmarks/configs/vitdet_b_mask_coco.json",
                          "reduced": [], "why": "the attention trunk"})
    bm["workloads"].append({"name": CELL, "config": "vitdet_b_mask_coco",
                            "traffic": "train_packed_landscape", "chips": 1,
                            "why": "the attention trunk on the pyramid heads"})
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
@pytest.mark.parametrize("check", CHECKS, ids=lambda c: c.__name__)
def test_a_cell_and_a_metric_appended_pass_every_check(check, lists):
    bm = _with_room(lists)
    assert bm["per_layer"][-1]["name"] == "attn_roofline.windowed"
    check(bm)
