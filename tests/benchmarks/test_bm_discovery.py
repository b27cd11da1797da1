"""Driven by data: a configuration, a traffic mix, a driver kind, a cell and
a per-layer metric dropped in as NEW files are found by name and run, and no
file that was there is edited."""

import hashlib
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import run  # noqa: E402

DRIVER = '''
def run(ctx):
    """A driver kind of its own: no model, a canned window."""
    assert ctx["conf"]["spec"]["depth"] == 18 and ctx["mix"]["rate"] == 3
    return {"correct": True, "compared": {"echo": {"value": 0.0, "limit": 0.0}},
            "attempted": ctx["mix"]["rate"], "failed": 0, "window_s": ctx["seconds"],
            "steps": 1, "images": 3, "setup_s": 0.25, "memory_peak_bytes": 0,
            "end_to_end": {"echo_per_s": 3.0 / ctx["seconds"]},
            "trace": {"busy_s": 0.5, "window_s": 1.0, "device_ops": [["op", 0.5]],
                      "idle_gaps": [["host", 0.5]], "by_name": {"op": 0.5},
                      "step_runs": 1}}
'''
READER = '''
def read(run):
    return 100.0 * run["trace"]["busy_s"] / run["trace"]["window_s"]
'''
SILENT = '''
def read(run):
    return None  # finds nothing to read: the harness leaves the metric out
'''


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha1(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_files_are_found_and_run_without_editing_old_ones(tmp_path, capsys):
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(REPO, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digest(root / "benchmarks")
    b = root / "benchmarks"
    (b / "configs" / "toy_r18.json").write_text(json.dumps(
        {"source": "a test", "spec": {"depth": 18}, "reduced": []}))
    (b / "traffic" / "echo_mix.json").write_text(json.dumps(
        {"driver": "echo", "rate": 3}))
    (b / "drivers" / "echo.py").write_text(DRIVER)
    (b / "layer_metrics" / "echo.busy_share.py").write_text(READER)
    (b / "layer_metrics" / "echo.silent.py").write_text(SILENT)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["configs"].append({"name": "toy_r18", "source": "a test",
                          "file": "benchmarks/configs/toy_r18.json",
                          "reduced": [], "why": "discovery"})
    bm["workloads"].append({"name": "toy_echo", "config": "toy_r18",
                            "traffic": "echo_mix", "chips": 1, "why": "discovery"})
    bm["end_to_end"].append({"name": "echo_per_s", "unit": "1/s",
                             "better": "higher", "bound": 0.01,
                             "source": "host_clock", "workloads": ["toy_echo"]})
    for name in ("echo.busy_share", "echo.silent"):
        bm["per_layer"].append({"name": name, "unit": "%", "better": "higher",
                                "source": "device_trace", "layer": "echo",
                                "moves": "echo_per_s", "workloads": ["toy_echo"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    argv = ["--workload", "toy_echo", "--seed", "1", "--seconds", "2"]
    e2e = run.main(argv + ["--trace", "0"], platform="cpu", root=str(root))
    assert set(e2e["metrics"]) == {"setup_s", "echo_per_s"}  # this cell's only
    assert e2e["metrics"]["echo_per_s"] == {"value": 1.5, "unit": "1/s"}
    traced = run.main(argv + ["--trace", "1"], platform="cpu", root=str(root))
    assert traced["metrics"] == {"echo.busy_share": {"value": 50.0, "unit": "%"}}
    assert traced["device"]["busy_s"] == 0.5
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert list(json.loads(last))[-1] == "compared"  # the limits come last
    after = _digest(root / "benchmarks")
    assert {k: after[k] for k in before} == before  # nothing there was edited
