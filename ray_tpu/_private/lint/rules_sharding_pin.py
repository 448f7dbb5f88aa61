"""sharding-pin: host-updated donated carries must be re-pinned.

The fused dispatch donates its carries (``pool_k/v``, a
quantized pool's ``scale_k/v`` slabs — which ride the layer scan's carry
beside the pool they dequantize — ``last_logits``, draft-plane twins);
inside jit every carry is re-pinned
with ``with_sharding_constraint`` so tensor-parallel layouts survive the
donation.  The hazard is the HOST side: when the engine rebuilds a carry
between dispatches (``jnp.zeros`` at init, ``.at[row].set(...)`` on swap-in,
an ``np``->``jnp`` round trip), the fresh array materialises with default
(replicated / single-device) placement — and the next dispatch silently
runs with a decayed layout, correct but devastating for tp throughput.
The repo convention is an immediate explicit pin::

    self._last_logits = self._last_logits.at[row].set(...)
    if self._shardings is not None:
        self._last_logits = jax.device_put(self._last_logits,
                                           self._shardings.logits)

This rule checks every assignment to a donated-carry attribute
(``self._pool_k``, ``self._last_logits`` ...).  The value is considered pinned
when it is:

* a call to a module-level **jitted** function (pins internally via
  ``with_sharding_constraint`` — that side is the jit's contract), also
  through tuple-unpack targets;
* a call carrying an explicit ``sharding=``/``shardings=`` kwarg
  (``init_pool(..., sharding=self._shardings.pool)``);
* ``jax.device_put(...)`` / ``with_sharding_constraint(...)`` — the pin
  itself;
* a plain name/attribute copy, ``None``/constant, or a conditional whose
  branches are each pinned.

Anything else is host-side compute and must be followed, later in the
same function, by a re-pin of the same attribute
(``self.<attr> = jax.device_put(self.<attr>, ...)``).  Unpinned
host-updated carries are findings.

Fires only on files that use the sharding plumbing (``_EngineShardings``/
``_shardings`` appears in the source) or under ``force_hot``.
"""

from __future__ import annotations

import ast
from typing import Dict, List

from ray_tpu._private.lint.core import (
    FileContext,
    Finding,
    Rule,
    collect_jitted,
    dotted_name,
    register,
)

CARRY_ATTRS = frozenset({
    "_last_logits",
    "_d_last_logits",
    "_pool_k",
    "_pool_v",
    "_pool_dk",
    "_pool_dv",
    "_scale_k",
    "_scale_v",
    "_scale_dk",
    "_scale_dv",
})

_PIN_TAILS = ("device_put", "with_sharding_constraint")
_SHARDING_KWARGS = ("sharding", "shardings", "out_shardings")


def _self_carry(node: ast.AST) -> str:
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self" \
            and node.attr in CARRY_ATTRS:
        return node.attr
    return ""


@register
class ShardingPinRule(Rule):
    name = "sharding-pin"
    description = (
        "host-rebuilt donated jit carries must re-pin their sharding "
        "(device_put/with_sharding_constraint) before the next dispatch"
    )

    def check(self, ctx: FileContext) -> List[Finding]:
        if not ctx.config.force_hot and "_shardings" not in ctx.source:
            return []
        jitted = set(collect_jitted(ctx.tree))
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                findings.extend(self._check_function(ctx, node, jitted))
        return findings

    def _check_function(self, ctx: FileContext, fn: ast.AST,
                        jitted: set) -> List[Finding]:
        # attr -> line of a later `self.attr = device_put/wsc(...)` re-pin
        repin_lines: Dict[str, List[int]] = {}
        assigns: List[tuple] = []   # (lineno, node, attrs, value)
        for node in self._own_nodes(fn):
            if not isinstance(node, ast.Assign):
                continue
            attrs = []
            for tgt in node.targets:
                if isinstance(tgt, ast.Tuple):
                    attrs.extend(a for a in
                                 (_self_carry(e) for e in tgt.elts) if a)
                else:
                    a = _self_carry(tgt)
                    if a:
                        attrs.append(a)
            if not attrs:
                continue
            if self._is_pin_call(node.value):
                for a in attrs:
                    repin_lines.setdefault(a, []).append(node.lineno)
            assigns.append((node.lineno, node, attrs, node.value))
        out: List[Finding] = []
        for lineno, node, attrs, value in sorted(assigns,
                                                 key=lambda t: t[0]):
            if self._value_pinned(value, jitted):
                continue
            for attr in attrs:
                if any(l > lineno for l in repin_lines.get(attr, ())):
                    continue       # re-pinned later in this function
                out.append(ctx.finding(
                    self.name,
                    node,
                    f"`self.{attr}` is rebuilt on the host without a "
                    "sharding pin; follow with jax.device_put(self."
                    f"{attr}, self._shardings.*) (or produce it inside "
                    "jit) so the tp layout does not decay to replicated",
                ))
        return out

    # -- value classification ------------------------------------------------

    def _is_pin_call(self, value: ast.AST) -> bool:
        if isinstance(value, ast.Call):
            fn = dotted_name(value.func)
            return fn.split(".")[-1] in _PIN_TAILS
        return False

    def _value_pinned(self, value: ast.AST, jitted: set) -> bool:
        if isinstance(value, ast.Call):
            fn = dotted_name(value.func)
            tail = fn.split(".")[-1] if fn else ""
            if tail in _PIN_TAILS:
                return True
            if fn in jitted:
                return True
            if any(kw.arg in _SHARDING_KWARGS for kw in value.keywords
                   if kw.arg is not None):
                return True
            return False
        if isinstance(value, ast.IfExp):
            return self._value_pinned(value.body, jitted) and \
                self._value_pinned(value.orelse, jitted)
        if isinstance(value, (ast.Name, ast.Attribute)):
            return True            # plain move of an already-placed array
        if isinstance(value, ast.Constant):
            return True            # None / scalar sentinel
        return False

    @staticmethod
    def _own_nodes(fn: ast.AST):
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))
