"""host-sync-in-jit: host round-trips inside traced code.

``.item()``, ``float()``/``int()`` on a traced value, ``np.asarray`` /
``np.array``, and ``jax.device_get`` all force the accelerator pipeline to
drain so the host can materialize a value. Outside jit that is a
performance bug (the device drains and the host waits, once per array);
*inside* jit it either fails to trace or — worse — silently
constant-folds a value that should be data-dependent. The repo's design
rule is "no host round-trips inside the compiled step" (package
docstring); this rule makes it mechanical.

float()/int() need care: ``int(cfg.train.rpn_min_size)`` on static config
is fine anywhere. Only conversions whose argument mentions a parameter of
an enclosing traced function (the syntactic stand-in for "a traced
value") are flagged.
"""

from __future__ import annotations

import ast
from typing import Iterator

from mx_rcnn_tpu.analysis.engine import FileContext, Finding
from mx_rcnn_tpu.analysis.tracing import dotted_name

NAME = "host-sync-in-jit"
RATIONALE = ("`.item()`/`float()`/`np.asarray`/`jax.device_get` on traced "
             "values inside jit fail to trace or silently constant-fold")

_NP_SYNCS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}
_DEVICE_SYNCS = {"jax.device_get", "jax.device_put"}


def check(ctx: FileContext) -> Iterator[Finding]:
    traced = ctx.traced
    if not traced.traced:
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if not traced.in_traced_code(node):
            continue
        # x.item() — a zero-arg method call; this syntactic shape has no
        # other common meaning in numeric code.
        if (isinstance(node.func, ast.Attribute) and node.func.attr == "item"
                and not node.args and not node.keywords):
            yield ctx.finding(NAME, node,
                              "`.item()` forces a device→host sync inside "
                              "traced code")
            continue
        name = dotted_name(node.func)
        if name in _NP_SYNCS:
            yield ctx.finding(NAME, node,
                              f"`{name}` materializes a host array inside "
                              "traced code (use jnp, or hoist to the host "
                              "side of the jit boundary)")
        elif name in _DEVICE_SYNCS:
            yield ctx.finding(NAME, node,
                              f"`{name}` inside traced code is a host "
                              "round-trip (move it outside the jit)")
        elif (name in ("float", "int", "bool") and node.args
              and _mentions_traced_value(node.args[0], traced, node)):
            yield ctx.finding(NAME, node,
                              f"`{name}()` on a traced value concretizes it "
                              "(TracerConversionError at best; use jnp "
                              "casts/astype)")


#: attribute/call accesses on a tracer that yield STATIC python values —
#: `int(x.shape[0])` / `len(x)` inside jit are fine (shapes are static)
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "itemsize"}


def _mentions_traced_value(expr: ast.AST, traced, at_node: ast.AST) -> bool:
    tainted = _tainted_names(traced, at_node)
    static_names = set()
    for n in ast.walk(expr):
        if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
            static_names.update(
                id(sub) for sub in ast.walk(n.value)
                if isinstance(sub, ast.Name))
        elif (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
              and n.func.id == "len"):
            static_names.update(
                id(sub) for sub in ast.walk(n)
                if isinstance(sub, ast.Name) and sub is not n.func)
    return any(isinstance(n, ast.Name) and n.id in tainted
               and id(n) not in static_names
               for n in ast.walk(expr))


def _tainted_names(traced, at_node: ast.AST):
    """Params of the enclosing traced functions plus names assigned (even
    indirectly) from them — two fixpoint passes cover the straight-line
    chains that occur in practice; no kill-set (over-taint is fine, the
    conversion still deserves a look). Cached on the per-file
    TraceAnalysis so nothing outlives the file."""
    cache = getattr(traced, "_taint_cache", None)
    if cache is None:
        cache = traced._taint_cache = {}
    fn = traced.enclosing_function(at_node)
    if fn in cache:
        return cache[fn]
    tainted = set(traced.traced_param_names(at_node))
    if fn is not None:
        assigns = [n for n in ast.walk(fn) if isinstance(n, ast.Assign)]
        for _ in range(2):
            for a in assigns:
                if any(isinstance(n, ast.Name) and n.id in tainted
                       for n in ast.walk(a.value)):
                    for tgt in a.targets:
                        tainted.update(
                            n.id for n in ast.walk(tgt)
                            if isinstance(n, ast.Name))
    cache[fn] = tainted
    return tainted
