"""Shared AST infrastructure for the lint rules.

One :class:`FileContext` per linted file: the parsed tree, source lines,
import-alias canonicalization, inline-disable comments, and the
module-level facts several rules share — which names are bound to
``jax.jit`` wrappers (with their resolved ``static_argnames`` /
``donate_argnames``), and which functions run in a traced context.

Everything here is conservative by construction: a rule only fires on
facts it can prove from this file (plus, for R006, a sibling ``ref.py``),
never on "might be".
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

_DISABLE_RE = re.compile(
    r"#\s*lint:\s*disable=([A-Za-z0-9,\s]+?)\s*(?:\(([^)#]*)\))?\s*(?:#.*)?$"
)


@dataclasses.dataclass
class Disable:
    line: int                   # line the comment sits on
    rules: Set[str]
    reason: str                 # may be "" — enforced as META finding


@dataclasses.dataclass
class JitBinding:
    """``name = jax.jit(fn, static_argnames=..., donate_argnames=...)``."""

    name: str
    wrapped: Optional[str]              # wrapped function name if a Name
    static_names: Set[str]
    static_resolved: bool               # False → could not resolve statics
    donated_params: Set[str]            # by param name (resolved)
    donated_nums: Set[int]              # by positional index


def dotted_name(node: ast.AST) -> Optional[str]:
    """Render a Name/Attribute chain as ``a.b.c``; None otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    """Resolve a literal tuple/list of strings (or a lone string)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    return None


def int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            if isinstance(elt, ast.Constant) and isinstance(elt.value, int):
                out.append(elt.value)
            else:
                return None
        return tuple(out)
    return None


class FileContext:
    def __init__(self, path: str, src: str, tree: ast.Module):
        self.path = path
        self.src = src
        self.tree = tree
        self.lines = src.splitlines()
        self.aliases = self._collect_aliases(tree)
        self.disables = self._collect_disables(self.lines)
        self.module_constants = self._collect_module_constants(tree)
        self.functions = self._collect_functions(tree)
        self.jit_bindings = self._collect_jit_bindings(tree)

    # -- source helpers --------------------------------------------------

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1]
        return ""

    # -- imports ---------------------------------------------------------

    @staticmethod
    def _collect_aliases(tree: ast.Module) -> Dict[str, str]:
        """Map local alias → canonical dotted module path."""
        aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    aliases[a.asname or a.name] = f"{node.module}.{a.name}"
        return aliases

    def canonical(self, node: ast.AST) -> Optional[str]:
        """Canonical dotted name of a call target, alias-resolved.

        ``pl.program_id`` → ``jax.experimental.pallas.program_id`` when the
        module did ``from jax.experimental import pallas as pl``.
        """
        name = dotted_name(node)
        if name is None:
            return None
        head, _, rest = name.partition(".")
        base = self.aliases.get(head, head)
        return f"{base}.{rest}" if rest else base

    def call_canonical(self, call: ast.Call) -> Optional[str]:
        return self.canonical(call.func)

    # -- disable comments ------------------------------------------------

    @staticmethod
    def _collect_disables(lines: Sequence[str]) -> Dict[int, Disable]:
        out: Dict[int, Disable] = {}
        for i, text in enumerate(lines, start=1):
            if "lint:" not in text:
                continue
            m = _DISABLE_RE.search(text)
            if not m:
                continue
            rules = {r.strip().upper() for r in m.group(1).split(",") if r.strip()}
            reason = (m.group(2) or "").strip()
            out[i] = Disable(line=i, rules=rules, reason=reason)
        return out

    def disable_for(self, lineno: int) -> Optional[Disable]:
        """Disable applying to ``lineno``: same line, or a bare comment line
        immediately above."""
        d = self.disables.get(lineno)
        if d is not None:
            return d
        d = self.disables.get(lineno - 1)
        if d is not None and self.line_text(d.line).lstrip().startswith("#"):
            return d
        return None

    # -- module constants ------------------------------------------------

    @staticmethod
    def _collect_module_constants(tree: ast.Module) -> Dict[str, ast.AST]:
        consts: Dict[str, ast.AST] = {}
        for node in tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                t = node.targets[0]
                if isinstance(t, ast.Name):
                    consts[t.id] = node.value
        return consts

    def resolve_str_tuple(self, node: ast.AST) -> Optional[Tuple[str, ...]]:
        got = str_tuple(node)
        if got is not None:
            return got
        if isinstance(node, ast.Name) and node.id in self.module_constants:
            return str_tuple(self.module_constants[node.id])
        return None

    # -- functions -------------------------------------------------------

    @staticmethod
    def _collect_functions(tree: ast.Module) -> Dict[str, ast.FunctionDef]:
        """All function defs by name, outermost wins on collision."""
        fns: Dict[str, ast.FunctionDef] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fns.setdefault(node.name, node)
        return fns

    def param_names(self, fn: ast.FunctionDef) -> List[str]:
        a = fn.args
        return (
            [p.arg for p in a.posonlyargs]
            + [p.arg for p in a.args]
            + [p.arg for p in a.kwonlyargs]
        )

    def positional_params(self, fn: ast.FunctionDef) -> List[str]:
        a = fn.args
        return [p.arg for p in a.posonlyargs] + [p.arg for p in a.args]

    # -- jax.jit bindings ------------------------------------------------

    _JIT_NAMES = {"jax.jit", "jit", "jax.api.jit"}

    def _is_jit_call(self, call: ast.Call) -> bool:
        name = self.call_canonical(call)
        return name in self._JIT_NAMES

    def _jit_binding_from(self, target: str, call: ast.Call) -> JitBinding:
        wrapped = None
        if call.args and isinstance(call.args[0], ast.Name):
            wrapped = call.args[0].id
        static_names: Set[str] = set()
        static_resolved = True
        donated_params: Set[str] = set()
        donated_nums: Set[int] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                got = self.resolve_str_tuple(kw.value)
                if got is None:
                    static_resolved = False
                else:
                    static_names |= set(got)
            elif kw.arg == "static_argnums":
                nums = int_tuple(kw.value)
                if nums is None:
                    static_resolved = False
                elif wrapped and wrapped in self.functions:
                    pos = self.positional_params(self.functions[wrapped])
                    for n in nums:
                        if 0 <= n < len(pos):
                            static_names.add(pos[n])
            elif kw.arg == "donate_argnames":
                got = self.resolve_str_tuple(kw.value)
                if got is not None:
                    donated_params |= set(got)
            elif kw.arg == "donate_argnums":
                nums = int_tuple(kw.value)
                if nums is not None:
                    donated_nums |= set(nums)
        # Resolve donated param names to positional indices when we know
        # the wrapped function's signature.
        if wrapped and wrapped in self.functions:
            pos = self.positional_params(self.functions[wrapped])
            for p in donated_params:
                if p in pos:
                    donated_nums.add(pos.index(p))
        return JitBinding(
            name=target,
            wrapped=wrapped,
            static_names=static_names,
            static_resolved=static_resolved,
            donated_params=donated_params,
            donated_nums=donated_nums,
        )

    def _collect_jit_bindings(self, tree: ast.Module) -> Dict[str, JitBinding]:
        """``X = jax.jit(fn, ...)`` assignments anywhere in the module,
        plus one level of alias propagation (``Y = X`` / conditional)."""
        out: Dict[str, JitBinding] = {}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if not isinstance(t, ast.Name):
                continue
            v = node.value
            if isinstance(v, ast.Call) and self._is_jit_call(v):
                out[t.id] = self._jit_binding_from(t.id, v)
        # alias pass: Y = X, Y = X if c else Z — donate/statics union
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if not isinstance(t, ast.Name) or t.id in out:
                continue
            v = node.value
            sources: List[str] = []
            if isinstance(v, ast.Name):
                sources = [v.id]
            elif isinstance(v, ast.IfExp):
                for side in (v.body, v.orelse):
                    if isinstance(side, ast.Name):
                        sources.append(side.id)
            hits = [out[s] for s in sources if s in out]
            if hits:
                out[t.id] = JitBinding(
                    name=t.id,
                    wrapped=hits[0].wrapped,
                    static_names=set().union(*(h.static_names for h in hits)),
                    static_resolved=all(h.static_resolved for h in hits),
                    donated_params=set().union(*(h.donated_params for h in hits)),
                    donated_nums=set().union(*(h.donated_nums for h in hits)),
                )
        return out

    # -- traced-context detection (R005) ---------------------------------

    _TRACING_WRAPPERS = {
        "jax.jit",
        "jax.vmap",
        "jax.pmap",
        "jax.grad",
        "jax.value_and_grad",
        "jax.experimental.shard_map.shard_map",
        "shard_map.shard_map",
        "jax.lax.while_loop",
        "jax.lax.scan",
        "jax.lax.cond",
        "jax.lax.fori_loop",
        "lax.while_loop",
        "lax.scan",
        "lax.cond",
        "lax.fori_loop",
    }

    _FAMILY_RECORDS = {"repro.engine.hotloop.Family"}

    def _decorator_statics(self, fn: ast.FunctionDef) -> Optional[Set[str]]:
        """If ``fn`` is traced via decorator, return its static param names
        (None → not traced via decorator)."""
        for dec in fn.decorator_list:
            name = self.canonical(dec) if not isinstance(dec, ast.Call) else None
            if name in self._TRACING_WRAPPERS:
                return set()
            if isinstance(dec, ast.Call):
                cname = self.canonical(dec.func)
                if cname in self._TRACING_WRAPPERS:
                    return self._statics_from_kwargs(dec, fn)
                if cname in {"functools.partial", "partial"} and dec.args:
                    inner = self.canonical(dec.args[0])
                    if inner in self._TRACING_WRAPPERS:
                        return self._statics_from_kwargs(dec, fn)
        return None

    def _statics_from_kwargs(
        self, call: ast.Call, fn: ast.FunctionDef
    ) -> Optional[Set[str]]:
        statics: Set[str] = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                got = self.resolve_str_tuple(kw.value)
                if got is None:
                    return None          # unresolvable → skip function
                statics |= set(got)
            elif kw.arg == "static_argnums":
                nums = int_tuple(kw.value)
                if nums is None:
                    return None
                pos = self.positional_params(fn)
                for n in nums:
                    if 0 <= n < len(pos):
                        statics.add(pos[n])
        return statics

    def traced_functions(self) -> Dict[str, Optional[Set[str]]]:
        """Functions that run under a tracer in this module.

        Maps function name → set of *static* (python-value) param names,
        or None when the statics could not be resolved (rule should skip
        such functions rather than guess).
        """
        traced: Dict[str, Optional[Set[str]]] = {}
        # (a) decorated defs
        for name, fn in self.functions.items():
            statics = self._decorator_statics(fn)
            if statics is not None or self._has_tracing_decorator(fn):
                traced[name] = statics
        # (b) module-level jax.jit(fn, ...) wrappings
        for b in self.jit_bindings.values():
            if b.wrapped and b.wrapped in self.functions:
                statics = b.static_names if b.static_resolved else None
                prev = traced.get(b.wrapped)
                if prev is not None and statics is not None:
                    statics = set(prev) | statics
                traced[b.wrapped] = statics
        # (c) protocol family records: the hot loop's program factory jits
        # ``Family(step=fn, statics=...)``'s step with exactly those statics
        for node in ast.walk(self.tree):
            if (not isinstance(node, ast.Call)
                    or self.call_canonical(node) not in self._FAMILY_RECORDS):
                continue
            kw = {k.arg: k.value for k in node.keywords}
            fn = kw.get("step")
            if isinstance(fn, ast.Name) and fn.id in self.functions:
                got = (self.resolve_str_tuple(kw["statics"])
                       if "statics" in kw else None)
                traced[fn.id] = set(got) if got is not None else None
        # Callbacks handed positionally to lax control flow / shard_map /
        # vmap are resolved slot-aware by R005 itself.
        return traced

    def _has_tracing_decorator(self, fn: ast.FunctionDef) -> bool:
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            if self.canonical(target) in self._TRACING_WRAPPERS:
                return True
        return False


class Project:
    """The set of files under lint, plus on-demand sibling parsing (R006
    matches kernel entry points against a ``ref.py`` that may or may not be
    part of the linted path set)."""

    def __init__(self, files: Iterable[FileContext], config=None):
        from .config import LintConfig

        self.config = config if config is not None else LintConfig(root=os.getcwd())
        self.files = list(files)
        self._by_path = {os.path.abspath(fc.path): fc for fc in self.files}
        self._sibling_cache: Dict[str, Optional[FileContext]] = {}

    def sibling(self, path: str, module: str) -> Optional[FileContext]:
        """FileContext for ``<dir(path)>/<module>.py``, linted or not."""
        target = os.path.abspath(os.path.join(os.path.dirname(path), module + ".py"))
        if target in self._by_path:
            return self._by_path[target]
        if target in self._sibling_cache:
            return self._sibling_cache[target]
        fc: Optional[FileContext] = None
        if os.path.isfile(target):
            try:
                with open(target, "r", encoding="utf-8") as fh:
                    src = fh.read()
                fc = FileContext(target, src, ast.parse(src))
            except (OSError, SyntaxError):
                fc = None
        self._sibling_cache[target] = fc
        return fc


# ---------------------------------------------------------------------------
# Statement-order walking shared by the dataflow rules (R001/R004)
# ---------------------------------------------------------------------------


def assigned_names(target: ast.AST) -> Set[str]:
    """Names (re)bound by an assignment target, incl. tuple unpacking."""
    out: Set[str] = set()
    for node in ast.walk(target):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Store):
            out.add(node.id)
    return out


def iter_calls(node: ast.AST) -> Iterable[ast.Call]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            yield sub
