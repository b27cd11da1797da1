"""unbounded-retry: blind sleep-retry loops with no deadline or backoff.

The round-5 outage (TPU_OUTAGE_r5.log) was survived by a hand-rolled
watcher: ``while True: try: jax.devices() except: time.sleep(540)`` —
25+ fixed-cadence probes over ~11 hours, no backoff, no deadline, no
error classification, and no structured record. graftguard
(resilience/backend.py) is the sanctioned shape: exponential backoff +
jitter under a configurable deadline. This rule flags the anti-pattern
so it cannot grow back: a ``while`` loop (or a ``for`` over an unbounded
iterator) that retries through an exception handler and sleeps with
neither

- a **deadline**: some clock read inside the loop (``time.monotonic`` /
  ``time.time`` / ``perf_counter`` / an injected ``clock()``) that a
  bounded loop compares against, nor
- a **backoff**: a sleep duration that the loop body actually updates
  (``delay *= 2`` and friends) or computes per-iteration.

``for`` loops over ``range(...)`` (or any finite collection) are bounded
retry — never flagged.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional

from mx_rcnn_tpu.analysis.engine import FileContext, Finding

NAME = "unbounded-retry"
RATIONALE = ("a retry loop that sleeps without a deadline or backoff "
             "(the TPU_OUTAGE_r5 watcher shape) — use "
             "resilience/backend.py's classified acquire instead")

#: Callable names whose invocation inside the loop counts as reading a
#: clock — evidence the loop tracks elapsed time against a deadline.
#: ``clock`` covers the injectable-clock idiom (resilience/backend.py).
_CLOCK_NAMES = {"monotonic", "time", "perf_counter", "perf_counter_ns",
                "monotonic_ns", "clock"}

#: Iterator factories that make a ``for`` loop unbounded.
_UNBOUNDED_ITERS = {"count", "cycle", "repeat"}


def check(ctx: FileContext) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.While):
            loop_body = node.body
        elif isinstance(node, ast.For) and _unbounded_for(node.iter):
            loop_body = node.body
        else:
            continue
        sleeps = _sleep_calls(loop_body)
        if not sleeps:
            continue
        if not _has_retry_handler(loop_body):
            continue  # a poll/wait loop, not a retry loop
        if _reads_clock(loop_body):
            continue  # deadline evidence
        if any(_is_backoff_arg(call, loop_body) for call in sleeps):
            continue  # backoff evidence
        yield ctx.finding(
            NAME, node,
            "retry loop sleeps with no deadline and no backoff — an "
            "outage spins here forever at a fixed cadence; bound it with "
            "a clock check (or use resilience.backend.acquire_backend)")


def _unbounded_for(iter_node: ast.expr) -> bool:
    """``for _ in itertools.count()`` and friends — a while-True in
    disguise. ``range(...)``/finite collections are bounded retry."""
    if not isinstance(iter_node, ast.Call):
        return False
    return _call_name(iter_node) in _UNBOUNDED_ITERS


def _call_name(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _walk_body(body: List[ast.stmt]) -> Iterator[ast.AST]:
    for stmt in body:
        yield from ast.walk(stmt)


def _sleep_calls(body: List[ast.stmt]) -> List[ast.Call]:
    return [n for n in _walk_body(body)
            if isinstance(n, ast.Call) and _call_name(n) == "sleep"]


def _has_retry_handler(body: List[ast.stmt]) -> bool:
    """An except handler that lets the loop continue (anything but an
    unconditional re-raise) — the failure path loops back around."""
    for n in _walk_body(body):
        if not isinstance(n, ast.ExceptHandler):
            continue
        if not all(isinstance(s, ast.Raise) for s in n.body):
            return True
    return False


def _reads_clock(body: List[ast.stmt]) -> bool:
    return any(isinstance(n, ast.Call) and _call_name(n) in _CLOCK_NAMES
               for n in _walk_body(body))


def _is_backoff_arg(call: ast.Call, body: List[ast.stmt]) -> bool:
    """True when the sleep duration can change between iterations: a
    non-constant expression (``sleep(delay * 2)``, ``sleep(min(d, cap))``)
    or a plain name the loop body reassigns/augments. A constant —
    including constant arithmetic like ``sleep(9 * 60)``, the literal
    round-5 watcher cadence — or a name the loop never touches is a
    fixed cadence."""
    if not call.args:
        return False  # sleep() — malformed; not our concern
    arg = call.args[0]
    if _is_constant_expr(arg):
        return False
    if isinstance(arg, ast.Name):
        return _assigned_in(arg.id, body)
    return True  # computed per-iteration: treated as backoff


def _is_constant_expr(node: ast.expr) -> bool:
    """``540``, ``9 * 60``, ``-(5)``: arithmetic over literals folds to
    the same value every iteration."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.BinOp):
        return _is_constant_expr(node.left) and _is_constant_expr(node.right)
    if isinstance(node, ast.UnaryOp):
        return _is_constant_expr(node.operand)
    return False


def _assigned_in(name: str, body: List[ast.stmt]) -> bool:
    for n in _walk_body(body):
        if isinstance(n, ast.AugAssign):
            t = n.target
            if isinstance(t, ast.Name) and t.id == name:
                return True
        elif isinstance(n, ast.Assign):
            for t in n.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    return True
        elif isinstance(n, (ast.AnnAssign, ast.NamedExpr)):
            t = n.target
            if isinstance(t, ast.Name) and t.id == name:
                return True
    return False
