"""jit-hygiene: retrace and donation hazards at jitted call sites.

Three mechanical hazards around ``jax.jit`` that have bitten serving PRs:

1. **jit-in-loop** — constructing a jit wrapper inside a ``for``/``while``
   body creates a fresh cache per iteration and recompiles every call.
2. **donated-buffer reuse** — reading a buffer after passing it to a donated
   parameter (``donate_argnames``/``donate_argnums``) is undefined once XLA
   aliases the storage; the engine convention is to rebind the result over
   the donated expression on the same statement
   (``self._pool_k, ... = _decode_multi_paged(self.params, self._pool_k,
   ...)``).
3. **static-varying scalar** — passing an obviously per-call-varying Python
   scalar (a ``len(...)``, ``.shape[...]`` access, or an enclosing loop
   variable) as a *static* jit arg keys a new compile per distinct value.

The rule resolves module-level jitted functions (decorated with ``jax.jit`` /
``functools.partial(jax.jit, ...)`` or bound via ``f = jax.jit(g, ...)``) and
checks their call sites.  Calls using ``*args`` splats skip the positional
donation/static mapping (alignment is unknowable statically).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu._private.lint.core import (
    FileContext,
    Finding,
    JitInfo,
    Rule,
    collect_jitted,
    dotted_name,
    expr_text,
    register,
)


@register
class JitHygieneRule(Rule):
    name = "jit-hygiene"
    description = "retrace/donation hazards at jax.jit call sites"

    def check(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        jitted = collect_jitted(ctx.tree)
        findings.extend(self._check_jit_in_loop(ctx))
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            # method-style tails (self._decode = jax.jit(...) then
            # self._decode(...)) resolve on the final component.
            tail = name.rsplit(".", 1)[-1] if name else ""
            info = jitted.get(name) or jitted.get(tail)
            if info is None:
                continue
            has_splat = any(isinstance(a, ast.Starred) for a in node.args)
            findings.extend(self._check_donated_reuse(ctx, node, info, has_splat))
            findings.extend(self._check_static_varying(ctx, node, info, has_splat))
        return findings

    # -- (1) jit() constructed inside a loop body ---------------------------

    def _check_jit_in_loop(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if dotted_name(node.func) not in ("jax.jit", "jit"):
                continue
            for anc in ctx.ancestors(node):
                if isinstance(anc, (ast.For, ast.While)):
                    findings.append(
                        ctx.finding(
                            self.name,
                            node,
                            "jax.jit(...) constructed inside a loop body builds "
                            "a fresh compile cache per iteration; hoist the "
                            "jitted function to module scope",
                        )
                    )
                    break
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    break  # nested defs reset the loop context
        return findings

    # -- donated/static argument mapping ------------------------------------

    def _bound_args(
        self, call: ast.Call, info: JitInfo, has_splat: bool
    ) -> List[Tuple[str, Optional[int], ast.expr]]:
        """(param_name_or_"", positional_index_or_None, expr) per call arg."""
        bound: List[Tuple[str, Optional[int], ast.expr]] = []
        if not has_splat:
            for idx, arg in enumerate(call.args):
                pname = info.params[idx] if idx < len(info.params) else ""
                bound.append((pname, idx, arg))
        for kw in call.keywords:
            if kw.arg is not None:
                bound.append((kw.arg, None, kw.value))
        return bound

    def _check_donated_reuse(
        self, ctx: FileContext, call: ast.Call, info: JitInfo, has_splat: bool
    ) -> List[Finding]:
        if not (info.donate_names or info.donate_positions):
            return []
        donated: List[ast.expr] = []
        for pname, idx, arg in self._bound_args(call, info, has_splat):
            if (pname and pname in info.donate_names) or (
                idx is not None and idx in info.donate_positions
            ):
                donated.append(arg)
        fn = ctx.enclosing_function(call)
        if fn is None or not donated:
            return []
        findings: List[Finding] = []
        call_line = getattr(call, "end_lineno", call.lineno)
        for arg in donated:
            if not isinstance(arg, (ast.Name, ast.Attribute)):
                continue
            text = expr_text(arg)
            if not text:
                continue
            reuse = self._first_reuse(fn, text, call.lineno, call_line)
            if reuse is not None:
                findings.append(
                    ctx.finding(
                        self.name,
                        reuse,
                        f"`{text}` was donated to `{info.name}` on line "
                        f"{call.lineno} and is read afterwards; XLA may have "
                        "aliased its buffer — rebind the jit result first",
                    )
                )
        return findings

    def _first_reuse(
        self, fn: ast.FunctionDef, text: str, call_start: int, call_end: int
    ) -> Optional[ast.AST]:
        """First Load of `text` after the call with no intervening rebind.

        The sanctioned pattern rebinds the jit result over the donated
        expression on the call statement itself (a Store at ``call_start``),
        which clears all later loads.
        """
        loads: List[ast.AST] = []
        stores: List[int] = []
        for node in ast.walk(fn):
            if isinstance(node, (ast.Name, ast.Attribute)) and expr_text(node) == text:
                c = getattr(node, "ctx", None)
                if isinstance(c, ast.Store):
                    stores.append(node.lineno)
                elif isinstance(c, ast.Load):
                    loads.append(node)
        for load in sorted(loads, key=lambda n: (n.lineno, n.col_offset)):
            if load.lineno <= call_end:
                continue
            if any(call_start <= s <= load.lineno for s in stores):
                return None
            return load
        return None

    # -- (3) varying python scalar into a static parameter ------------------

    def _check_static_varying(
        self, ctx: FileContext, call: ast.Call, info: JitInfo, has_splat: bool
    ) -> List[Finding]:
        if not info.static_names:
            return []
        loop_vars = self._enclosing_loop_vars(ctx, call)
        one_hop = self._local_assignments(ctx, call)
        findings: List[Finding] = []
        for pname, _idx, arg in self._bound_args(call, info, has_splat):
            if pname not in info.static_names:
                continue
            exprs = [arg]
            if isinstance(arg, ast.Name) and arg.id in one_hop:
                exprs.append(one_hop[arg.id])
            for expr in exprs:
                hazard = self._varying_reason(expr, loop_vars)
                if hazard:
                    findings.append(
                        ctx.finding(
                            self.name,
                            arg,
                            f"static jit arg `{pname}` of `{info.name}` is fed "
                            f"a per-call-varying value ({hazard}); every "
                            "distinct value triggers a recompile",
                        )
                    )
                    break
        return findings

    def _varying_reason(self, expr: ast.AST, loop_vars: Set[str]) -> str:
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                fn = dotted_name(node.func)
                if fn == "len":
                    return "len(...)"
            if isinstance(node, ast.Attribute) and node.attr == "shape":
                return ".shape access"
            if isinstance(node, ast.Name) and node.id in loop_vars:
                return f"loop variable `{node.id}`"
        return ""

    def _enclosing_loop_vars(self, ctx: FileContext, call: ast.Call) -> Set[str]:
        out: Set[str] = set()
        for anc in ctx.ancestors(call):
            if isinstance(anc, ast.For):
                for node in ast.walk(anc.target):
                    if isinstance(node, ast.Name):
                        out.add(node.id)
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
        return out

    def _local_assignments(
        self, ctx: FileContext, call: ast.Call
    ) -> Dict[str, ast.expr]:
        """name -> last assigned expression before the call, one hop only."""
        fn = ctx.enclosing_function(call)
        out: Dict[str, ast.expr] = {}
        if fn is None:
            return out
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            if getattr(node, "lineno", 0) >= call.lineno:
                continue
            for target in node.targets:
                if isinstance(target, ast.Name):
                    out[target.id] = node.value
        return out
