"""R001 use-after-donate — the static twin of tests/test_hotloop_donate.py.

A donated buffer is single-consumer: once a name is passed in a donated
position of a dispatch jitted with ``donate_argnames``/``donate_argnums``,
XLA may alias its memory for the outputs, and any later read of that name
observes garbage.  The runtime gate catches this only on exercised paths;
here we track it as dataflow over the function body.

Donating callees are resolved two ways:

* precisely, from ``X = jax.jit(fn, donate_arg...)`` bindings in the same
  module (including one alias hop, e.g. ``step_d = step_don if donate
  else step_plain`` — donating if ANY reaching binding donates);
* by configured name pattern (``donating_patterns``) for factory-made
  dispatches whose jit call is out of view (``full_j``/``sub_j`` from
  ``hotloop.programs``); there the donated argument is any bare name
  argument listed in ``donated_arg_names``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..context import FileContext, Project, assigned_names
from ..registry import Finding, Rule, register
from . import _shared

# state maps name -> None (live) | (donor_name, line) (donated, unread)


class _Walker(_shared.StmtRule):
    def __init__(self, fc: FileContext, cfg):
        self.fc = fc
        self.cfg = cfg
        self.donating_pats = _shared.compile_patterns(cfg.donating_patterns)
        self.donated_args = set(cfg.donated_arg_names)
        self.findings: List[Finding] = []
        self._seen: Set[Tuple[int, int, str]] = set()

    # -- donation resolution --------------------------------------------

    def _donated_in_call(self, call: ast.Call) -> List[str]:
        seg = _shared.last_segment(call.func)
        if seg is None:
            return []
        out: List[str] = []
        binding = self.fc.jit_bindings.get(seg)
        if binding is not None and (binding.donated_nums or binding.donated_params):
            for idx in binding.donated_nums:
                if idx < len(call.args) and isinstance(call.args[idx], ast.Name):
                    out.append(call.args[idx].id)
            for kw in call.keywords:
                if kw.arg in binding.donated_params and isinstance(kw.value, ast.Name):
                    out.append(kw.value.id)
            return out
        if _shared.matches_any(seg, self.donating_pats):
            for a in call.args:
                if isinstance(a, ast.Name) and a.id in self.donated_args:
                    out.append(a.id)
            for kw in call.keywords:
                if (kw.arg in self.donated_args
                        and isinstance(kw.value, ast.Name)):
                    out.append(kw.value.id)
        return out

    # -- events ----------------------------------------------------------

    def _check_reads(self, node: ast.AST, state: dict) -> None:
        for name in _shared.load_names(node):
            dead = state.get(name.id)
            if dead is not None:
                key = (name.lineno, name.col_offset, name.id)
                if key in self._seen:
                    continue
                self._seen.add(key)
                donor, line = dead
                self.findings.append(Finding(
                    "R001", self.fc.path, name.lineno, name.col_offset,
                    f"'{name.id}' is read after being donated to "
                    f"'{donor}' (line {line}); donated buffers are "
                    "single-consumer — rebind the name first "
                    "[gate: tests/test_hotloop_donate.py]"))

    def _apply_donations(self, node: ast.AST, state: dict) -> None:
        for call in ast.walk(node):
            if isinstance(call, ast.Call):
                for name in self._donated_in_call(call):
                    state[name] = (_shared.last_segment(call.func), call.lineno)

    def on_expr(self, expr: ast.AST, state: dict) -> None:
        self._check_reads(expr, state)
        self._apply_donations(expr, state)

    def on_bind(self, target: ast.AST, state: dict) -> None:
        for name in assigned_names(target):
            state[name] = None

    def on_stmt(self, stmt: ast.stmt, state: dict) -> None:
        if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            value = stmt.value
            if value is not None:
                self._check_reads(value, state)
                self._apply_donations(value, state)
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    self.on_bind(t, state)
            elif stmt.target is not None:
                if isinstance(stmt, ast.AugAssign):
                    self._check_reads(stmt.target, state)
                self.on_bind(stmt.target, state)
        else:
            self._check_reads(stmt, state)
            self._apply_donations(stmt, state)

    def copy(self, state: dict) -> dict:
        return dict(state)

    def merge(self, state: dict, branches: List[dict]) -> None:
        # A name stays donated only if every branch left it donated —
        # under-approximate so exclusive branches never cross-talk.
        names = set(state)
        for b in branches:
            names |= set(b)
        for n in names:
            marks = [b.get(n) for b in branches]
            if all(m is not None for m in marks):
                state[n] = marks[0]
            else:
                state[n] = None


@register(Rule(
    id="R001",
    name="use-after-donate",
    gate="tests/test_hotloop_donate.py",
    summary="a name passed in a donated position of a jitted dispatch must "
            "not be read again before rebinding",
))
def check(fc: FileContext, project: Project) -> List[Finding]:
    cfg = project.config
    findings: List[Finding] = []
    for qual, fn in _shared.iter_functions(fc.tree):
        walker = _Walker(fc, cfg)
        _shared.walk_body(fn.body, {}, walker)
        findings.extend(walker.findings)
    return findings
