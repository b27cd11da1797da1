"""Deadline isolation — run one measurement in a killable child process.

One hung compile can consume a whole bench timeout, and every completed
config's number then dies with the parent. A deadline can only be enforced against work you can kill, and a
hung XLA compile holds the GIL-adjacent native stack — in-process timers
can't interrupt it. So each config runs in a ``spawn`` child (fresh
process, fresh backend handle — the child owns the chip while it lives,
which is why the parent must never touch jax: bench.py);
the parent waits at most ``timeout_s``, then kills the child and records
a structured timeout row instead of losing the sweep. bench.py::run_sweep
is the consumer.

stdlib-only (multiprocessing) — the child pays the jax import, not this
module. The callable and its argument must be picklable (module-level
functions + the frozen Config tree both are). Chaos hook: the child calls
``maybe_hang(label)`` before the work, so a tier-1 test can hang one
named config and watch the sweep survive (chaos.py ``hang_bench``).
"""

from __future__ import annotations

import multiprocessing as mp
from typing import Any, Callable, Dict


def deadline_row(timeout_s: float) -> Dict[str, Any]:
    """The structured row recorded for a config that outlived its
    deadline. ``timeout_s``'s presence IS the marker consumers test for
    (run_sweep retries error rows but never retries a timeout — a hung
    compile would just hang again)."""
    return {"error": f"timeout: config exceeded the {timeout_s:g}s "
                     "per-config deadline (child killed)",
            "timeout_s": timeout_s}


def _child_entry(fn: Callable, label: str, arg, conn):
    """Child body: chaos hook, the work, one row through the pipe. Every
    failure becomes a row — the parent must always learn SOMETHING."""
    from mx_rcnn_tpu.resilience import chaos

    try:
        chaos.from_env().maybe_hang(label)
        row = fn(arg)
    except BaseException as e:  # noqa: BLE001  # graftlint: disable=broad-except — the child's last act is reporting the error as a row
        row = {"error": f"{type(e).__name__}: {e}"}
    try:
        conn.send(row)
    finally:
        conn.close()


def run_with_deadline(fn: Callable, arg, timeout_s: float,
                      label: str = "", grace_s: float = 10.0) -> Dict[str, Any]:
    """Run ``fn(arg)`` in a spawn child; return its row dict, or the
    ``deadline_row`` if it doesn't report within ``timeout_s`` seconds.

    The deadline covers the child end-to-end (interpreter start + jax
    import + compile + measurement) — exactly the budget a sweep config
    gets. A child that dies without reporting (OOM kill, crash) yields an
    error row carrying its exit code.
    """
    ctx = mp.get_context("spawn")  # no fork: nothing of the parent's
    parent_conn, child_conn = ctx.Pipe(duplex=False)  # state leaks in
    proc = ctx.Process(target=_child_entry,
                       args=(fn, label, arg, child_conn), daemon=True)
    proc.start()
    child_conn.close()  # parent's copy; EOF detection needs it closed
    row = None
    try:
        if parent_conn.poll(timeout_s):
            try:
                row = parent_conn.recv()
            except EOFError:
                row = {"error": "child died without reporting "
                                f"(exitcode {proc.exitcode})"}
    finally:
        parent_conn.close()
    if row is None:
        row = deadline_row(timeout_s)
        proc.terminate()  # SIGTERM first: lets the child's runtime unwind
        proc.join(grace_s)
        if proc.is_alive():
            proc.kill()  # wedged in native code
            proc.join(grace_s)
        if proc.is_alive():  # unkillable (D-state): abandon, don't hang
            row["error"] += " [child unkillable; abandoned]"
        return row
    proc.join(grace_s)  # reported: normal exit is imminent
    if proc.is_alive():
        proc.kill()
        proc.join(grace_s)
    return row
