"""graftlint v2 interprocedural layer: call graph + function summaries.

The r09 analyzers were strictly intraprocedural: taint, ownership and
dominance facts died at every call boundary, so `host-sync` could not see
that a helper forces a pull on its argument and no rule could see that
a helper leaks a block acquired two frames up.  This module is the
shared v2 substrate:

* a **function table** over one module's AST — every ``def`` (functions,
  methods, nested defs) keyed by dotted qualname, with a tail-name index
  for method-style call resolution;
* **call resolution** — ``helper(...)`` to a module-level function,
  ``self.m(...)``/``cls.m(...)`` to a method of the enclosing class,
  ``Class(...)`` to ``Class.__init__`` (constructor stores count as
  ownership transfer);
* **per-function summaries**, each computed intrinsically first and then
  propagated **one level** through direct callees (the ISSUE-16 contract:
  taint and ownership flow through helper calls, but not through arbitrary
  call chains — deeper facts must be re-established by the callee's own
  summary at its own call sites):

  ===================  ====================================================
  ``returns_device``   the return value is derived from ``jnp.*``/``jax.*``
                       /``lax.*`` expressions, module-level jitted calls,
                       or (one level) a local callee that returns one
  ``sync_params``      parameter names the body forces a device->host sync
                       on (``np.asarray``, ``float()``/``int()``/``bool()``,
                       ``.item()``/``.tolist()``, truthiness, device_get)
  ``stores_params``    parameter names the body stores into longer-lived
                       storage (``self.attr = p``, ``self.tbl[i] = p``,
                       ``self.lst.append(p)``) — ownership transfer sinks
  ``releases_params``  parameter names the body passes to a release call
                       (``decref``)
  ``returns_acquired`` the function returns the (possibly None-checked)
                       result of an acquire call (``alloc``/one-level
                       acquired-returning callee) — calling it IS acquiring
  ``calls_flush``      the body calls ``_flush_pipeline`` (directly or one
                       level down)
  ===================  ====================================================

Summaries are resolved lazily and memoised per :class:`ModuleSummaries`,
which is itself cached on the :class:`~.core.FileContext` (``ctx.summaries``)
so the host-sync, kv-refcount, flush-order and sharding-pin analyzers share
one pass worth of work per file.  Resolution is module-local by design:
cross-module imports are NOT followed (a summary for an imported helper
would need whole-program analysis; the per-module invariants the rules
encode don't).
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Set, Tuple

from ray_tpu._private.lint.core import collect_jitted, dotted_name

#: dotted tails whose call allocates refcounted block handles
ACQUIRE_TAILS = ("alloc",)
#: dotted tails whose call adds a holder to already-allocated blocks
INCREF_TAILS = ("incref",)
#: dotted tails whose call drops a holder
RELEASE_TAILS = ("decref",)
#: method names that flush the async dispatch ring
FLUSH_TAILS = ("_flush_pipeline",)

_SYNC_BUILTINS = {"float", "int", "bool"}
_SYNC_METHODS = {"item", "tolist"}
_NP_SYNC_TAILS = {"asarray", "array", "ascontiguousarray"}
_DEVICE_ROOTS = {"jnp", "jax", "lax"}


def call_tail(call: ast.Call) -> str:
    """Final attribute/name component of a call target
    (``self.kv_pool.alloc`` -> "alloc", ``helper`` -> "helper")."""
    fn = call.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return ""


@dataclasses.dataclass
class FunctionInfo:
    """One ``def`` in the module, with enough signature context to map
    call-site arguments back onto parameter names."""

    qualname: str
    name: str
    node: ast.AST                      # FunctionDef | AsyncFunctionDef
    params: List[str]                  # positional params, ``self`` dropped
    is_method: bool
    class_name: str = ""

    def bind_args(self, call: ast.Call) -> List[Tuple[str, ast.expr]]:
        """(param_name, argument_expr) pairs for a call site; positional
        args past the known params and ``*args`` splats are skipped."""
        bound: List[Tuple[str, ast.expr]] = []
        for idx, arg in enumerate(call.args):
            if isinstance(arg, ast.Starred):
                break
            if idx < len(self.params):
                bound.append((self.params[idx], arg))
        for kw in call.keywords:
            if kw.arg is not None and kw.arg in self.params:
                bound.append((kw.arg, kw.value))
        return bound


class ModuleSummaries:
    """Function table + memoised one-level summaries for one parsed module."""

    def __init__(self, tree: ast.Module,
                 sync_exempt: frozenset = frozenset()):
        self.tree = tree
        self.sync_exempt = sync_exempt
        self.jitted = set(collect_jitted(tree))
        self.functions: Dict[str, FunctionInfo] = {}
        self.by_tail: Dict[str, List[FunctionInfo]] = {}
        self._classes: Dict[str, ast.ClassDef] = {}
        self._collect(tree, prefix="", class_name="")
        self._returns_device: Dict[str, bool] = {}
        self._sync_params: Dict[str, Set[str]] = {}
        self._stores_params: Dict[str, Set[str]] = {}
        self._releases_params: Dict[str, Set[str]] = {}
        self._returns_acquired: Dict[str, bool] = {}
        self._calls_flush: Dict[str, bool] = {}

    # -- table construction --------------------------------------------------

    def _collect(self, node: ast.AST, prefix: str, class_name: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                self._classes[child.name] = child
                qual = f"{prefix}{child.name}"
                self._collect(child, prefix=qual + ".",
                              class_name=child.name)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{child.name}"
                params = [a.arg for a in child.args.posonlyargs] + \
                         [a.arg for a in child.args.args]
                is_method = bool(class_name) and not any(
                    dotted_name(d) == "staticmethod"
                    for d in child.decorator_list)
                if is_method and params:
                    params = params[1:]        # drop self/cls
                params += [a.arg for a in child.args.kwonlyargs]
                info = FunctionInfo(qualname=qual, name=child.name,
                                    node=child, params=params,
                                    is_method=is_method,
                                    class_name=class_name)
                self.functions[qual] = info
                self.by_tail.setdefault(child.name, []).append(info)
                self._collect(child, prefix=qual + ".",
                              class_name=class_name)

    # -- call resolution -----------------------------------------------------

    def resolve_call(self, call: ast.Call,
                     scope: Optional[FunctionInfo] = None
                     ) -> Optional[FunctionInfo]:
        """Map a call site to a module-local FunctionInfo, or None.

        ``helper(...)``        module function (or unique tail)
        ``self.m(...)``        method ``m`` of the enclosing class (scope)
        ``Class(...)``         ``Class.__init__``
        ``obj.m(...)``         unique in-module method named ``m`` — tail
                               fallback, same heuristic jit-hygiene uses
        """
        fn = call.func
        if isinstance(fn, ast.Name):
            name = fn.id
            if name in self._classes:
                return self.functions.get(f"{name}.__init__")
            info = self.functions.get(name)
            if info is not None:
                return info
            cands = [i for i in self.by_tail.get(name, ())
                     if "." not in i.qualname]
            return cands[0] if len(cands) == 1 else None
        if isinstance(fn, ast.Attribute):
            tail = fn.attr
            recv = fn.value
            if isinstance(recv, ast.Name) and recv.id in ("self", "cls") \
                    and scope is not None and scope.class_name:
                info = self.functions.get(f"{scope.class_name}.{tail}")
                if info is not None:
                    return info
            if isinstance(recv, ast.Name) and recv.id in self._classes:
                return self.functions.get(f"{recv.id}.{tail}")
            cands = self.by_tail.get(tail, ())
            return cands[0] if len(cands) == 1 else None
        return None

    def info_for(self, node: ast.AST) -> Optional[FunctionInfo]:
        """FunctionInfo for a specific def node (identity match)."""
        name = getattr(node, "name", "")
        for info in self.by_tail.get(name, ()):
            if info.node is node:
                return info
        return None

    def scope_of(self, node: ast.AST,
                 parents: Dict[ast.AST, ast.AST]) -> Optional[FunctionInfo]:
        """FunctionInfo of the def enclosing ``node`` (via a parent map)."""
        cur = parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for info in self.by_tail.get(cur.name, ()):
                    if info.node is cur:
                        return info
            cur = parents.get(cur)
        return None

    # -- summary: returns_device --------------------------------------------

    def returns_device(self, info: FunctionInfo) -> bool:
        """Does the function return a device-derived value?  One level:
        returns of calls to local callees use the callee's *intrinsic*
        fact, so taint crosses exactly one helper boundary."""
        if info.qualname not in self._returns_device:
            self._returns_device[info.qualname] = \
                self._compute_returns_device(info, follow=True)
        return self._returns_device[info.qualname]

    def _compute_returns_device(self, info: FunctionInfo,
                                follow: bool) -> bool:
        if info.name in self.sync_exempt:
            # Choke points (``_device_get``) exist to RETURN host copies.
            return False
        device_locals: Set[str] = set()
        changed = True
        while changed:            # _own_nodes is unordered: iterate to fixpoint
            changed = False
            for node in self._own_nodes(info):
                if isinstance(node, ast.Assign):
                    if self._expr_device(node.value, device_locals, info,
                                         follow):
                        for tgt in node.targets:
                            for n in ast.walk(tgt):
                                if isinstance(n, ast.Name) and \
                                        n.id not in device_locals:
                                    device_locals.add(n.id)
                                    changed = True
        for node in self._own_nodes(info):
            if isinstance(node, ast.Return) and node.value is not None:
                if self._expr_device(node.value, device_locals, info,
                                     follow):
                    return True
        return False

    def _expr_device(self, expr: ast.AST, device_locals: Set[str],
                     scope: FunctionInfo, follow: bool) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Name) and node.id in device_locals:
                return True
            if isinstance(node, ast.Call):
                fn = dotted_name(node.func)
                root = fn.split(".", 1)[0] if fn else ""
                if root in _DEVICE_ROOTS or fn in self.jitted:
                    return True
                if follow:
                    callee = self.resolve_call(node, scope)
                    if callee is not None and callee is not scope and \
                            self._intrinsic_returns_device(callee):
                        return True
        return False

    def _intrinsic_returns_device(self, info: FunctionInfo) -> bool:
        key = "~" + info.qualname
        if key not in self._returns_device:
            self._returns_device[key] = False      # cycle guard
            self._returns_device[key] = \
                self._compute_returns_device(info, follow=False)
        return self._returns_device[key]

    # -- summary: sync_params ------------------------------------------------

    def sync_params(self, info: FunctionInfo) -> Set[str]:
        """Parameter names the body forces a host sync on (intrinsic
        only — the call-site rule provides the one level of propagation
        by reporting at the tainted caller)."""
        if info.qualname not in self._sync_params:
            self._sync_params[info.qualname] = self._compute_sync(info)
        return self._sync_params[info.qualname]

    def _compute_sync(self, info: FunctionInfo) -> Set[str]:
        if info.name in self.sync_exempt:
            return set()
        names = set(info.params)
        if not names:
            return set()
        synced: Set[str] = set()

        def param_rooted(expr: ast.AST) -> Optional[str]:
            cur = expr
            while isinstance(cur, (ast.Attribute, ast.Subscript)):
                if isinstance(cur, ast.Attribute) and cur.attr in (
                        "shape", "ndim", "dtype", "size", "nbytes",
                        "sharding", "device", "itemsize"):
                    return None
                cur = cur.value
            if isinstance(cur, ast.Name) and cur.id in names:
                return cur.id
            return None

        for node in self._own_nodes(info):
            if isinstance(node, ast.Call):
                fn = dotted_name(node.func)
                tail = call_tail(node)
                if fn in ("jax.device_get", "jax.block_until_ready") \
                        and node.args:
                    p = param_rooted(node.args[0])
                    if p:
                        synced.add(p)
                elif tail in _NP_SYNC_TAILS and \
                        fn.split(".", 1)[0] in ("np", "numpy") and node.args:
                    p = param_rooted(node.args[0])
                    if p:
                        synced.add(p)
                elif isinstance(node.func, ast.Name) and \
                        node.func.id in _SYNC_BUILTINS and \
                        len(node.args) == 1:
                    p = param_rooted(node.args[0])
                    if p:
                        synced.add(p)
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _SYNC_METHODS:
                    p = param_rooted(node.func.value)
                    if p:
                        synced.add(p)
            elif isinstance(node, (ast.If, ast.While)):
                test = node.test
                if isinstance(test, ast.Compare) and all(
                        isinstance(op, (ast.Is, ast.IsNot))
                        for op in test.ops):
                    continue                       # `p is None` never syncs
                if isinstance(test, (ast.Name, ast.Attribute,
                                     ast.Subscript)):
                    p = param_rooted(test)
                    if p:
                        synced.add(p)
        return synced

    # -- summary: stores / releases -----------------------------------------

    def stores_params(self, info: FunctionInfo) -> Set[str]:
        """Params stored into attribute/subscript targets rooted at
        ``self`` (or any non-local receiver) or appended/extended into
        one — the ownership-transfer sinks for kv-refcount."""
        if info.qualname not in self._stores_params:
            self._stores_params[info.qualname] = self._compute_stores(info)
        return self._stores_params[info.qualname]

    def _compute_stores(self, info: FunctionInfo) -> Set[str]:
        names = set(info.params)
        if not names:
            return set()
        stored: Set[str] = set()

        def mentions(expr: ast.AST) -> Set[str]:
            return {n.id for n in ast.walk(expr)
                    if isinstance(n, ast.Name) and n.id in names}

        locals_seen: Set[str] = set()
        for node in self._own_nodes(info):
            if isinstance(node, ast.Assign):
                hit = mentions(node.value)
                for tgt in node.targets:
                    if isinstance(tgt, (ast.Attribute, ast.Subscript)):
                        root = tgt
                        while isinstance(root, (ast.Attribute,
                                                ast.Subscript)):
                            root = root.value
                        if not (isinstance(root, ast.Name)
                                and root.id in locals_seen):
                            stored |= hit
                    elif isinstance(tgt, ast.Name):
                        locals_seen.add(tgt.id)
            elif isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr in ("append", "extend", "add",
                                       "setdefault", "update"):
                root = node.func.value
                while isinstance(root, (ast.Attribute, ast.Subscript)):
                    root = root.value
                if isinstance(root, ast.Name) and root.id in locals_seen:
                    continue
                for arg in node.args:
                    stored |= mentions(arg)
        return stored

    def releases_params(self, info: FunctionInfo) -> Set[str]:
        if info.qualname not in self._releases_params:
            out: Set[str] = set()
            names = set(info.params)
            for node in self._own_nodes(info):
                if isinstance(node, ast.Call) and \
                        call_tail(node) in RELEASE_TAILS:
                    for arg in node.args:
                        for n in ast.walk(arg):
                            if isinstance(n, ast.Name) and n.id in names:
                                out.add(n.id)
            self._releases_params[info.qualname] = out
        return self._releases_params[info.qualname]

    # -- summary: returns_acquired ------------------------------------------

    def returns_acquired(self, info: FunctionInfo) -> bool:
        """True when calling this function hands the caller freshly
        acquired block handles: the body returns the (possibly
        None-checked) result of an acquire call, or — one level — of a
        local callee that intrinsically returns one."""
        if info.qualname not in self._returns_acquired:
            self._returns_acquired[info.qualname] = False   # cycle guard
            self._returns_acquired[info.qualname] = \
                self._compute_returns_acquired(info)
        return self._returns_acquired[info.qualname]

    def _compute_returns_acquired(self, info: FunctionInfo) -> bool:
        acquired_locals: Set[str] = set()

        def is_acquire(call: ast.Call) -> bool:
            if call_tail(call) in ACQUIRE_TAILS:
                return True
            callee = self.resolve_call(call, info)
            return (callee is not None and callee is not info
                    and self.returns_acquired(callee))

        for node in self._own_nodes(info):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call) and \
                    is_acquire(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        acquired_locals.add(tgt.id)
        for node in self._own_nodes(info):
            if isinstance(node, ast.Return) and node.value is not None:
                v = node.value
                if isinstance(v, ast.Call) and is_acquire(v):
                    return True
                for n in ast.walk(v):
                    if isinstance(n, ast.Name) and n.id in acquired_locals:
                        return True
        return False

    # -- summary: calls_flush ------------------------------------------------

    def calls_flush(self, info: FunctionInfo) -> bool:
        """Body calls ``_flush_pipeline`` — directly or one level down."""
        if info.qualname not in self._calls_flush:
            self._calls_flush[info.qualname] = False        # cycle guard
            hit = False
            for node in self._own_nodes(info):
                if isinstance(node, ast.Call):
                    if call_tail(node) in FLUSH_TAILS:
                        hit = True
                        break
                    callee = self.resolve_call(node, info)
                    if callee is not None and callee is not info and \
                            self._intrinsic_calls_flush(callee):
                        hit = True
                        break
            self._calls_flush[info.qualname] = hit
        return self._calls_flush[info.qualname]

    def _intrinsic_calls_flush(self, info: FunctionInfo) -> bool:
        key = "~" + info.qualname
        if key not in self._calls_flush:
            self._calls_flush[key] = any(
                isinstance(n, ast.Call) and call_tail(n) in FLUSH_TAILS
                for n in self._own_nodes(info))
        return self._calls_flush[key]

    # -- helpers -------------------------------------------------------------

    def _own_nodes(self, info: FunctionInfo):
        """Walk a function's body EXCLUDING nested def/class scopes."""
        stack = list(ast.iter_child_nodes(info.node))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))
