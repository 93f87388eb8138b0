"""R003 host-sync-in-hot-loop.

The hot loops overlap dispatch with host work through exactly one
blessed transfer: the packed ``(3, B)`` i32 host view (``host_view``,
and the pool's ``(5, W)`` supervision ``_view``).  Any other device→host
sync inside a turn loop — ``.item()``, ``.tolist()``, ``.block_until_ready()``,
``np.asarray`` on device values, ``jax.device_get``, ``float()``/``int()``
on device leaves — serializes the pipeline and silently destroys the
double-buffered overlap PR 6 measured.

Scope is deliberately tight: loop bodies of the configured hot-loop
functions (``run_hot``, ``step_pool``) only.  Values derived from a
blessed view call are host data and may be inspected freely.
"""

from __future__ import annotations

import ast
from typing import List, Set

from ..context import FileContext, Project, assigned_names
from ..registry import Finding, Rule, register
from . import _shared

_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
_SYNC_CALLS = {"numpy.asarray", "numpy.array", "jax.device_get"}
_CAST_CALLS = {"float", "int"}


def _root_name(node: ast.AST) -> str:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return ""


class _HotFn:
    def __init__(self, fc: FileContext, fn: ast.FunctionDef, cfg):
        self.fc = fc
        self.fn = fn
        self.view_pat = cfg.blessed_view_pattern
        self.device_roots = set(cfg.device_roots)
        self.blessed = self._collect_blessed()

    _HOST_BUILTINS = {
        "int", "float", "bool", "min", "max", "len", "abs", "sum", "any",
        "all", "range", "sorted", "enumerate", "zip", "list", "tuple",
    }

    def _is_view_call(self, call: ast.Call) -> bool:
        seg = _shared.last_segment(call.func)
        return seg is not None and self.view_pat in seg

    def _expr_blessed(self, expr: ast.AST) -> bool:
        """Structurally host data: pulled through a blessed view call, or
        a host-side (numpy/builtin) combination of blessed values.  A
        dispatch or any other unknown call BLOCKS propagation — its result
        is a fresh device value."""
        if isinstance(expr, ast.Name):
            return expr.id in self.blessed
        if isinstance(expr, (ast.Attribute, ast.Subscript, ast.Starred)):
            return self._expr_blessed(expr.value)
        if isinstance(expr, ast.Call):
            if self._is_view_call(expr):
                return True
            args = list(expr.args) + [kw.value for kw in expr.keywords]
            canon = self.fc.call_canonical(expr) or ""
            if canon.startswith("numpy."):
                return any(self._expr_blessed(a) for a in args)
            if (isinstance(expr.func, ast.Name)
                    and expr.func.id in self._HOST_BUILTINS):
                return any(self._expr_blessed(a) for a in args)
            if isinstance(expr.func, ast.Attribute):
                # host method on blessed data: done.all(), vh[0].max()
                return self._expr_blessed(expr.func.value)
            return False
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            return any(self._expr_blessed(e) for e in expr.elts)
        if isinstance(expr, ast.BinOp):
            return self._expr_blessed(expr.left) or self._expr_blessed(expr.right)
        if isinstance(expr, ast.BoolOp):
            return any(self._expr_blessed(v) for v in expr.values)
        if isinstance(expr, ast.UnaryOp):
            return self._expr_blessed(expr.operand)
        if isinstance(expr, ast.Compare):
            return (self._expr_blessed(expr.left)
                    or any(self._expr_blessed(c) for c in expr.comparators))
        if isinstance(expr, ast.IfExp):
            return (self._expr_blessed(expr.body)
                    and self._expr_blessed(expr.orelse))
        return False

    def _collect_blessed(self) -> Set[str]:
        """Names holding host data pulled through the blessed view, to a
        fixpoint so chains (``vh = host_view(...)``, ``view =
        np.asarray(vh)``, ``done, _, fills = view``) stay blessed."""
        self.blessed: Set[str] = set()
        for _ in range(6):
            grew = False
            for stmt in _shared.walk_pruned(self.fn):
                if not isinstance(stmt, ast.Assign):
                    continue
                if not self._expr_blessed(stmt.value):
                    continue
                for t in stmt.targets:
                    for name in assigned_names(t):
                        if name not in self.blessed:
                            self.blessed.add(name)
                            grew = True
            if not grew:
                break
        return self.blessed

    def scan(self) -> List[Finding]:
        findings: List[Finding] = []
        seen = set()

        def flag(node: ast.AST, what: str) -> None:
            key = (node.lineno, node.col_offset, what)
            if key in seen:
                return
            seen.add(key)
            findings.append(Finding(
                "R003", self.fc.path, node.lineno, node.col_offset,
                f"{what} inside the hot turn loop of "
                f"'{self.fn.name}' — only the packed (3,B) host view may "
                "cross to host per turn; route this through the view or "
                "hoist it out of the loop [gate: hot-path-parity + "
                "double-buffered overlap, DESIGN.md §sharded hot loop]"))

        for loop in [n for n in _shared.walk_pruned(self.fn)
                     if isinstance(n, (ast.For, ast.AsyncFor, ast.While))]:
            for node in _shared.walk_pruned(loop):
                if not isinstance(node, ast.Call):
                    continue
                # method-style syncs: x.item(), x.tolist(), ...
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr in _SYNC_ATTRS):
                    root = _root_name(node.func.value)
                    if root not in self.blessed:
                        flag(node, f"device sync '.{node.func.attr}()'")
                    continue
                canon = self.fc.call_canonical(node)
                seg = _shared.last_segment(node.func)
                if canon in _SYNC_CALLS:
                    arg = node.args[0] if node.args else None
                    if arg is not None and not self._expr_blessed(arg):
                        flag(node, f"device transfer '{seg}(...)'")
                    continue
                if (isinstance(node.func, ast.Name)
                        and node.func.id in _CAST_CALLS and node.args):
                    arg = node.args[0]
                    if self._expr_blessed(arg):
                        continue
                    root = _root_name(arg)
                    if root in self.device_roots:
                        flag(node, f"host cast '{node.func.id}()' on device "
                                   f"value '{root}'")
        return findings


@register(Rule(
    id="R003",
    name="host-sync-in-hot-loop",
    gate="hot-path overlap (DESIGN.md §sharded hot loop; "
         "benchmarks/engine_sweep.py double-buffered host loop)",
    summary=".item()/.tolist()/np.asarray/.block_until_ready on device "
            "values inside run_hot/pool turn loops, outside the blessed "
            "(3,B) view transfer",
))
def check(fc: FileContext, project: Project) -> List[Finding]:
    cfg = project.config
    hot_names = set(cfg.hot_loop_functions)
    findings: List[Finding] = []
    for _, fn in _shared.iter_functions(fc.tree):
        if fn.name in hot_names:
            findings.extend(_HotFn(fc, fn, cfg).scan())
    return findings
