"""The control of ``correct``, kept at a size a test run can hold: the plain
reference put in the program's place, computed in fp8 (the precision below
the configuration's bfloat16), comes out as NOT correct under the cell's own
limits; so do the planted faults; the reference against itself is correct."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import compare, manifest, traffic  # noqa: E402
from benchmarks.drivers import train as driver  # noqa: E402
from bm_tiny import tiny  # noqa: E402

pytestmark = pytest.mark.compile_heavy
SEED = 2 ** 31 + 5


@pytest.fixture(scope="module")
def readings():
    conf = manifest.load_json("configs", "c4_r101_coco")
    t = tiny()
    spec = dict(conf["spec"], **t["spec_overrides"])
    spec["train"] = dict(conf["spec"]["train"], **t["spec_overrides"]["train"])
    mix = dict(manifest.load_json("traffic", "train_packed_landscape"),
               **t["mix_overrides"])
    ref = manifest.load_module("reference", conf["reference"])
    raw = traffic.make_roidb(mix, SEED)
    rows = [[(0, False), (1, True)], [(2, False), (3, False)],
            [(4, True), (5, False)]]
    batches = [driver.reference_batch(ref, r, raw, spec) for r in rows]
    limits = manifest.load_json("limits", "c4_r101_train")["limits"]

    def follow(**kw):
        got = driver.follow(ref, spec, SEED, 11, batches, **kw)
        got["input_gap"] = 0.0
        return got

    truth = follow()

    def judged(**kw):
        numbers, _ = driver.numbers_of(follow(**kw), truth)
        return compare.verdict(numbers, limits) + (numbers,)

    return judged


def test_reference_against_itself_is_correct(readings):
    ok, table, numbers = readings()
    assert ok and max(numbers.values()) == 0.0


def test_fp8_control_is_not_correct(readings):
    ok, table, numbers = readings(precision="fp8")
    assert not ok
    failed = [k for k, v in table.items() if v["value"] > v["limit"]]
    assert "grad1_med" in failed or "dw3_med" in failed, numbers


def test_half_of_the_batch_left_out_is_not_correct(readings):
    ok, table, numbers = readings(rows=[0])
    assert not ok and numbers["grad1_rpn"] > table["grad1_rpn"]["limit"]


def test_state_left_unchanged_is_not_correct(readings):
    ok, table, numbers = readings(frozen_state=True)
    assert not ok and numbers["dw3"] == pytest.approx(1.0)


def test_a_leaf_one_side_lacks_reads_infinite():
    gaps = compare.leaf_gaps({"a": 1.0}, {"a": 1.0, "b": 2.0})
    assert gaps["a"] == 0.0 and np.isinf(gaps["b"])
    ok, _ = compare.verdict({"x": float("nan")}, {"x": 1.0})
    assert not ok
