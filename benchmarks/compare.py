"""The comparison that decides ``correct``: the program's numbers against the
plain reference's, each beside a limit of its own.

A leaf is measured by the GAP BETWEEN THE TWO NORMS (not the norm of the
difference), against the reference's norm of that leaf or of the median leaf,
whichever is larger: some gradients are all but zero.
"""

from __future__ import annotations

import numpy as np


def norms(tree: dict) -> dict:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in tree.items()}


def leaf_gaps(prog: dict, ref: dict, skip=()) -> dict:
    """{path: gap}; both arguments {path: norm}. A leaf that one side lacks,
    or a norm that is not finite, reads infinite."""
    med = float(np.median(list(ref.values())))
    gaps = {}
    for k in set(prog) | set(ref):
        if k in skip:
            continue
        if k not in prog or k not in ref:
            gaps[k] = float("inf")
            continue
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        gaps[k] = gap if np.isfinite(gap) else float("inf")
    return gaps


def worst(gaps: dict):
    """(gap, path) of the worst leaf."""
    if not gaps:
        return float("inf"), "no leaf"
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def rel(a: float, b: float) -> float:
    if not (np.isfinite(a) and np.isfinite(b)):
        return float("inf")
    return abs(a - b) / max(abs(b), 1e-30)


def verdict(numbers: dict, limits: dict):
    """The numbers that have a limit are compared (a limit without its
    number is an error); the others are readings. Returns (correct,
    {name: {"value", "limit"}}) - the table printed beside the result."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers[name]
        table[name] = {"value": value, "limit": limit}
        ok = ok and bool(np.isfinite(value)) and value <= limit
    return ok, table
