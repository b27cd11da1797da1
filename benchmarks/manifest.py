"""BENCHMARK.json and the files it names, found BY NAME: a cell's
configuration is ``configs/<config>.json``, its traffic
``traffic/<traffic>.json``, the driver of its kind ``drivers/<kind>.py``, its
limits ``limits/<cell>.json``, a per-layer metric's reader
``layer_metrics/<name>.py``, a reference ``reference/<name>.py``. A later PR
adds files and entries and edits none that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(kind: str, name: str, base: str = HERE) -> dict:
    with open(os.path.join(base, kind, name + ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str, base: str = HERE):
    path = os.path.join(base, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                     f"{[w['name'] for w in manifest['workloads']]}")


def metrics_of(manifest: dict, group: str, cell_name: str) -> list:
    """The metrics of ``end_to_end`` / ``per_layer`` that this cell reports:
    those without a ``workloads`` key, or that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def tuples(x):
    """JSON lists -> tuples, all the way down (the program's config holds
    tuples)."""
    if isinstance(x, list):
        return tuple(tuples(v) for v in x)
    if isinstance(x, dict):
        return {k: tuples(v) for k, v in x.items()}
    return x
