"""trace-guard: every tracer span emission must sit behind ``trace.enabled``.

The PR 9 tracing convention keeps the null-tracer decode path allocation-free
by guarding every span call site::

    if self.trace.enabled:
        self.trace.add("decode.dispatch", t0, tr.now())

    t0 = tr.now() if tr.enabled else 0.0

    if etr is None or not etr.enabled:
        return
    etr.add(...)

An unguarded emission pays attribute lookups, float math and (for real
tracers) list appends on every decode step even when tracing is off — the
exact overhead the ``test_gate_null_tracer_zero_allocations_on_decode_path``
perf gate exists to prevent.

The one sanctioned unguarded call is ``lane`` (``with self.trace.lane(
"dispatch", "dispatch", horizon=H):``): the engine-lane helper of
``engine_trace.py`` is a `jax.profiler.TraceAnnotation` on every tracer, the
null one included, so that a profiler session sees the engine's seams with no
knob turned. With no session the null tracer answers it with one shared no-op
object (the perf gate runs over it). Guarding it would take the spans out of
the profiler's trace whenever the ring is off, which is always in production.

The rule matches calls of span methods (``add``/``instant``/``open``/
``close``/``mark``/``span_since_mark``/``now``/``finish``) on receivers that
look like tracers (``tr``, ``tracer``, ``*.trace``, ``*_tracer`` ...) and
checks for an ``.enabled`` test in an ancestor ``if``/ternary/``and`` chain or
an earlier early-return guard in the same function.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from ray_tpu._private.lint.core import FileContext, Finding, Rule, expr_text, register

_SPAN_METHODS = {
    "add",
    "instant",
    "open",
    "close",
    "mark",
    "span_since_mark",
    "now",
    "finish",
}

# Tracer methods that are meant to be called without a guard (see above).
_UNGUARDED_BY_DESIGN = {"lane"}
assert not _UNGUARDED_BY_DESIGN & _SPAN_METHODS

_TRACER_NAMES = {"tr", "tracer", "etr", "trace"}


def _is_tracer_receiver(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id in _TRACER_NAMES or "trace" in node.id
    if isinstance(node, ast.Attribute):
        attr = node.attr
        return (
            attr in ("trace", "tracer")
            or attr.endswith("_trace")
            or attr.endswith("_tracer")
        )
    return False


def _mentions_enabled(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr == "enabled":
            return True
        if isinstance(sub, ast.Name) and sub.id == "enabled":
            return True
    return False


@register
class TraceGuardRule(Rule):
    name = "trace-guard"
    description = "tracer span emitted without a trace.enabled guard"

    def check(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if not isinstance(func, ast.Attribute):
                continue
            if func.attr not in _SPAN_METHODS:
                continue
            if not _is_tracer_receiver(func.value):
                continue
            if self._is_guarded(ctx, node):
                continue
            findings.append(
                ctx.finding(
                    self.name,
                    node,
                    f"tracer span `{expr_text(func)}(...)` emitted without a "
                    "`.enabled` guard (wrap in `if trace.enabled:` or an "
                    "early-return guard)",
                )
            )
        return findings

    def _is_guarded(self, ctx: FileContext, call: ast.Call) -> bool:
        # (1) ancestor if / while / ternary / boolop testing .enabled
        prev: ast.AST = call
        for anc in ctx.ancestors(call):
            if isinstance(anc, (ast.If, ast.While)) and _mentions_enabled(anc.test):
                return True
            if isinstance(anc, ast.IfExp) and _mentions_enabled(anc.test):
                return True
            if isinstance(anc, ast.BoolOp) and isinstance(anc.op, ast.And):
                # `tr.enabled and tr.add(...)` — guard must precede the call
                for value in anc.values:
                    if value is prev:
                        break
                    if _mentions_enabled(value):
                        return True
            if isinstance(anc, ast.Assert) and _mentions_enabled(anc.test):
                return True
            prev = anc
        # (2) earlier early-return guard in the enclosing function:
        #     if tr is None or not tr.enabled: return
        fn = ctx.enclosing_function(call)
        if fn is not None:
            for stmt in fn.body:
                if stmt.lineno >= call.lineno:
                    break
                if (
                    isinstance(stmt, ast.If)
                    and _mentions_enabled(stmt.test)
                    and stmt.body
                    and isinstance(stmt.body[-1], (ast.Return, ast.Raise, ast.Continue))
                ):
                    return True
        return False
