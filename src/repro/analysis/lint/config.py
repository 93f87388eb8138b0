"""Lint configuration: ``[tool.repro-lint]`` in pyproject.toml.

Two halves:

* tuning knobs for the heuristic rules (hot-loop function names, the
  blessed host-view pattern, dispatch/donating name patterns, static
  kwarg names) — all default to the engine's committed conventions so the
  tool works on a bare checkout; and
* the **exclusion manifest**: an explicit committed list of seed
  model-stack paths outside the protocol-engine contract.  Every entry
  MUST carry a one-line ``reason`` — silent path filtering is exactly
  what the satellite forbids — and a missing reason is a one-line config
  error, same convention as ``benchmarks/check_bench_schema.py``.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional, Tuple

import tomllib as _toml


class LintConfigError(Exception):
    """Raised with a single human-readable line; the CLI prints it as-is."""


@dataclasses.dataclass
class Exclude:
    path: str       # posix-relative to the config file's directory
    reason: str


_DEFAULTS: Dict[str, object] = {
    # R003: functions that ARE the hot loop; syncs are judged only inside
    # loop bodies of these.
    "hot_loop_functions": ["run_hot", "step_pool"],
    # R003: a call whose target name contains this substring produces the
    # blessed packed-(3,B) host view; derived values may cross to host.
    "blessed_view_pattern": "view",
    # R003: parameter names holding device pytrees inside hot loops.
    "device_roots": ["state", "data", "s", "sub"],
    # R002: kwargs that feed the (n_pad, width, warm) compile key or
    # otherwise determine shapes inside a dispatch.
    "static_kwargs": [
        "trans_width", "width", "n_pad", "first_turn", "use_warm", "warm",
        "per_node", "k", "cap",
    ],
    # R002: passing a value through one of these blesses it (quantized /
    # pinned to the compile-key lattice).
    "quantizers": ["_round_up", "round_up"],
    # R002/R001: call-target name patterns that mark a jitted dispatch
    # when the jit binding itself is out of view (factory-made sharded
    # dispatches, dispatch closures passed as parameters).
    "dispatch_patterns": [
        r"^dispatch", r"_jit\b", r"_don$", r"_hot_turn", r"^full_j$",
        r"^sub_j$", r"^step_d$", r"^turn_d$",
    ],
    # R001: patterns for donating callees whose jit binding is out of
    # view; the donated argument is any bare name in donated_arg_names.
    "donating_patterns": [r"_don$", r"^full_j$", r"^sub_j$", r"^dispatch"],
    "donated_arg_names": ["state", "s", "sub"],
    # R006: only packages matching this path fragment owe a jnp ref
    # counterpart in a sibling ref.py.
    "kernels_fragment": "kernels",
}


@dataclasses.dataclass
class LintConfig:
    root: str                       # directory the config was loaded from
    excludes: List[Exclude] = dataclasses.field(default_factory=list)
    hot_loop_functions: List[str] = dataclasses.field(
        default_factory=lambda: list(_DEFAULTS["hot_loop_functions"]))
    blessed_view_pattern: str = str(_DEFAULTS["blessed_view_pattern"])
    device_roots: List[str] = dataclasses.field(
        default_factory=lambda: list(_DEFAULTS["device_roots"]))
    static_kwargs: List[str] = dataclasses.field(
        default_factory=lambda: list(_DEFAULTS["static_kwargs"]))
    quantizers: List[str] = dataclasses.field(
        default_factory=lambda: list(_DEFAULTS["quantizers"]))
    dispatch_patterns: List[str] = dataclasses.field(
        default_factory=lambda: list(_DEFAULTS["dispatch_patterns"]))
    donating_patterns: List[str] = dataclasses.field(
        default_factory=lambda: list(_DEFAULTS["donating_patterns"]))
    donated_arg_names: List[str] = dataclasses.field(
        default_factory=lambda: list(_DEFAULTS["donated_arg_names"]))
    kernels_fragment: str = str(_DEFAULTS["kernels_fragment"])

    def excluded(self, path: str) -> Optional[Exclude]:
        """Match ``path`` against the manifest (file or subtree prefix)."""
        rel = os.path.relpath(os.path.abspath(path), self.root)
        rel = rel.replace(os.sep, "/")
        for ex in self.excludes:
            p = ex.path.rstrip("/")
            if rel == p or rel.startswith(p + "/"):
                return ex
        return None


_LIST_KEYS = (
    "hot_loop_functions", "device_roots", "static_kwargs", "quantizers",
    "dispatch_patterns", "donating_patterns", "donated_arg_names",
)


def load_config(pyproject: Optional[str]) -> LintConfig:
    """Load ``[tool.repro-lint]``; a missing file or table means defaults.

    All failure modes diagnose in one line (LintConfigError), never a
    traceback: unreadable TOML, a non-table entry, an exclude without a
    path, and — deliberately hard — an exclude without a reason.
    """
    if pyproject is None or not os.path.exists(pyproject):
        root = os.getcwd() if pyproject is None else os.path.dirname(
            os.path.abspath(pyproject)) or os.getcwd()
        return LintConfig(root=root)
    try:
        with open(pyproject, "rb") as fh:
            data = _toml.load(fh)
    except OSError as e:
        raise LintConfigError(f"lint config error: {pyproject}: unreadable ({e})")
    except _toml.TOMLDecodeError as e:
        raise LintConfigError(
            f"lint config error: {pyproject}: invalid TOML ({e}) — "
            "fix the [tool.repro-lint] table")
    table = data.get("tool", {}).get("repro-lint", {})
    if not isinstance(table, dict):
        raise LintConfigError(
            f"lint config error: {pyproject}: [tool.repro-lint] is not a table")
    cfg = LintConfig(root=os.path.dirname(os.path.abspath(pyproject)))
    for key in _LIST_KEYS:
        if key in table:
            val = table[key]
            if not isinstance(val, list) or not all(isinstance(x, str) for x in val):
                raise LintConfigError(
                    f"lint config error: {pyproject}: {key} must be a list "
                    "of strings")
            setattr(cfg, key, list(val))
    for key in ("blessed_view_pattern", "kernels_fragment"):
        if key in table:
            if not isinstance(table[key], str):
                raise LintConfigError(
                    f"lint config error: {pyproject}: {key} must be a string")
            setattr(cfg, key, table[key])
    raw_excludes = table.get("exclude", [])
    if not isinstance(raw_excludes, list):
        raise LintConfigError(
            f"lint config error: {pyproject}: exclude must be an array of "
            "tables ([[tool.repro-lint.exclude]])")
    for i, entry in enumerate(raw_excludes):
        if not isinstance(entry, dict) or "path" not in entry:
            raise LintConfigError(
                f"lint config error: {pyproject}: exclude[{i}] needs a "
                "'path' key")
        reason = entry.get("reason", "")
        if not isinstance(reason, str) or not reason.strip():
            raise LintConfigError(
                f"lint config error: {pyproject}: exclude[{i}] "
                f"({entry['path']}) has no 'reason' — every manifest entry "
                "must say why it is outside the lint contract")
        cfg.excludes.append(Exclude(path=str(entry["path"]), reason=reason.strip()))
    return cfg


def find_pyproject(start: str) -> Optional[str]:
    """Walk up from ``start`` to the nearest pyproject.toml."""
    cur = os.path.abspath(start)
    while True:
        cand = os.path.join(cur, "pyproject.toml")
        if os.path.exists(cand):
            return cand
        parent = os.path.dirname(cur)
        if parent == cur:
            return None
        cur = parent
