"""BENCHMARK.json against the contract's limits on names, units, keys and
cells, and against the files it names."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bm():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


BM = _bm()
METRICS = BM["end_to_end"] + BM["per_layer"]


def test_top_level_keys_and_size():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BM["run_seconds"] <= 51 and isinstance(BM["run_seconds"], int)
    assert len(BM["command"]) <= 32
    assert BM["command"][1].startswith(tuple(p + "/" for p in BM["paths"]))


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    e2e = m in BM["end_to_end"]
    allowed = ({"name", "unit", "better", "bound", "source", "workloads"}
               if e2e else {"name", "unit", "better", "source", "layer",
                            "moves", "workloads"})
    assert set(m) <= allowed
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


@pytest.mark.parametrize("m", BM["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_moves_a_metric_its_cells_report(m):
    moved = {e["name"]: e for e in BM["end_to_end"]}[m["moves"]]
    cells = [w["name"] for w in BM["workloads"]]
    reporting = set(moved.get("workloads", cells))
    assert set(m.get("workloads", reporting)) <= reporting
    assert set(m.get("workloads", [])) <= set(cells)
    assert os.path.isfile(os.path.join(
        REPO, "benchmarks", "layer_metrics", m["name"] + ".py"))


@pytest.mark.parametrize("w", BM["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["config"] in [c["name"] for c in BM["configs"]]
    for kind, name in (("traffic", w["traffic"]), ("limits", w["name"])):
        assert os.path.isfile(os.path.join(REPO, "benchmarks", kind,
                                           name + ".json"))
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           w["traffic"] + ".json")) as f:
        kind = json.load(f)["driver"]
    assert os.path.isfile(os.path.join(REPO, "benchmarks", "drivers",
                                       kind + ".py"))
    reports = [m["name"] for m in BM["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
    assert "setup_s" in reports and len(reports) >= 2
    assert any(w["name"] in m.get("workloads", [w["name"]])
               for m in BM["per_layer"])


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_entry_and_its_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
    assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    assert any(w["config"] == c["name"] for w in BM["workloads"])
    assert c["file"].startswith("benchmarks/")
    with open(os.path.join(REPO, c["file"])) as f:
        conf = json.load(f)
    assert conf["reduced"] == c["reduced"]
    widths = re.compile(r"(_dim|_rank|hidden|intermediate|channels)$")
    assert not any(widths.search(k) for k in c["reduced"])


def test_cells_and_names_are_unique_and_four_chip_share():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BM[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    # a pair of configuration and traffic is ONE cell, whatever its chips
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 4)
    assert "setup_s" in [m["name"] for m in BM["end_to_end"]]


def test_paths_hold_only_wellnamed_files():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in BM["paths"]:
        assert ok.match(p) and len(p) <= 200
        for d, _, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in d:
                continue
            for f in files:
                assert ok.match(os.path.relpath(os.path.join(d, f), REPO)), f
