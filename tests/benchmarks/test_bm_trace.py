"""The reduction from a trace to metrics: exact on hand-made intervals, and
steady on the small trace recorded on the chip that is kept beside it."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import manifest, trace_reduce as tr  # noqa: E402

MS = 1_000_000


def test_union_and_gaps_by_hand():
    spans = [(0, 10), (5, 20), (30, 40), (35, 38)]
    assert tr.union_seconds(spans) == 30 / 1e9
    assert tr.gaps_of(spans, 0, 50) == [(20, 30), (40, 50)]
    assert tr.gaps_of([], 0, 5) == [(0, 5)]


def test_reduction_by_hand():
    dev = {"/device:TPU:0": [("%conv.1 = f32[8] x", 0, 40 * MS),
                             ("%all-reduce.1 = f32[4] y", 30 * MS, 30 * MS),
                             ("%conv.2 = f32[8] z", 80 * MS, 20 * MS)]}
    host = [("bench.traced", 0, 100 * MS), ("bench.loader_next", 58 * MS, 30 * MS)]
    s = tr.reduce_events(dev, host)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.08)            # 0-60 and 80-100
    assert s["collective_s"] == pytest.approx(0.03)
    assert s["collective_exposed_s"] == pytest.approx(0.02)   # 40-60 is alone
    assert s["idle_gaps"] == [["bench.loader_next", pytest.approx(0.02)]]
    assert s["device_ops"][0] == ["%conv.1 f32[8]", pytest.approx(0.04)]
    assert tr.kernel_seconds(s, r"all-reduce") == pytest.approx(0.03)
    assert tr.kernel_seconds(s, r"no_such_kernel") is None


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(tr.NoDeviceOps):
        tr.reduce_events({}, [("bench.traced", 0, 5)])


def test_short_names_keep_what_tells_ops_apart():
    op = ('%jvp__.1 = f32[8,1,12032]{2,1,0:T(1,128)S(1)} custom-call(f32[8,12032,4]'
          '{2,1,0} %pad.0), custom_call_target="tpu_custom_call", operand_layout')
    assert tr.short_name(op) == "%jvp__.1 f32[8,1,12032] tpu_custom_call"
    reader = manifest.load_module("layer_metrics", "nms_roofline")
    import re
    assert re.search(reader.kernel_pattern(12000), tr.short_name(op))
    assert not re.search(reader.kernel_pattern(6000), tr.short_name(op))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "small_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_what_it_did_on_the_chip(recorded):
    devices = {k: [tuple(e) for e in v] for k, v in recorded["devices"].items()}
    host = [tuple(h) for h in recorded["host"]]
    s = tr.reduce_events(devices, host, window=tuple(recorded["window"]))
    assert s["window_s"] == pytest.approx(recorded["window"][1] / 1e9)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] == pytest.approx(recorded["expected"]["busy_s"], rel=1e-9)
    top = s["device_ops"][0]
    assert top[0] == recorded["expected"]["top_op"]
    assert top[1] == pytest.approx(recorded["expected"]["top_op_s"], rel=1e-9)
    reader = manifest.load_module("layer_metrics", "nms_roofline")
    spent = tr.kernel_seconds(s, reader.kernel_pattern(12000))
    assert spent == pytest.approx(recorded["expected"]["nms_s"], rel=1e-9)
    assert sum(t for _, t in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-6)
