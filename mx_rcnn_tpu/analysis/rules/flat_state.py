"""flat-state-access: no index-poking into optimizer state in traced code.

A jit-reachable ``opt_state[...]`` subscript hard-codes optax's wrapper
order (chain position / namedtuple index, e.g.
``opt_state[1][0].trace``), which silently breaks the moment optax
re-arranges its wrappers or ``build_optimizer`` changes its chain. Inside
traced code, optimizer state may only be touched through a whole-tree
``tree_map``, which does not depend on the layout.

Host-side code (checkpoint conversion, tests) may still index: the rule
only fires inside jit-reachable functions (tracing.py reachability).
Recognized receivers (syntactic): any name/attribute path whose final
segment is ``opt_state`` or ends in ``_opt_state`` — the repo's naming
convention for optimizer-state bindings (``state.opt_state``,
``new_opt_state``); names that merely CONTAIN the words (templates like
``opt_state_template``) are out of scope.
"""

from __future__ import annotations

import ast
from typing import Iterator, Optional

from mx_rcnn_tpu.analysis.engine import FileContext, Finding
from mx_rcnn_tpu.analysis.tracing import dotted_name

NAME = "flat-state-access"
RATIONALE = ("a jit-reachable `opt_state[...]` subscript hard-codes "
             "optax's wrapper order; go through a whole-tree tree_map")


def _subscript_root(node: ast.AST) -> Optional[str]:
    """Dotted name under a (possibly nested) Subscript chain:
    ``state.opt_state[1][0]`` → 'state.opt_state'."""
    while isinstance(node, ast.Subscript):
        node = node.value
    return dotted_name(node)


def _is_opt_state(name: Optional[str]) -> bool:
    if not name:
        return False
    last = name.rsplit(".", 1)[-1]
    return last == "opt_state" or last.endswith("_opt_state")


def check(ctx: FileContext) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Subscript):
            continue
        # report only the OUTERMOST subscript of an opt_state[...][...]
        # chain — one finding per access site
        parent = ctx.parents.get(node)
        if isinstance(parent, ast.Subscript) and parent.value is node:
            continue
        if not _is_opt_state(_subscript_root(node.value)):
            continue
        if not ctx.traced.in_traced_code(node):
            continue
        yield ctx.finding(
            NAME, node,
            "optimizer state indexed by position inside jit-reachable "
            "code — hard-codes optax's wrapper order; go through a "
            "whole-tree tree_map")
