"""repro.analysis.lint — tracing-invariant static analyzer.

Enforces the engine's dispatch-key, donation, and RNG contracts at lint
time.  Every rule is the static twin of a named runtime gate (see
DESIGN.md §Static invariants): R001 mirrors tests/test_hotloop_donate.py,
R002 mirrors tests/test_recompile.py + the session pool's pinned-key
determinism, R003 the blessed packed-(3,B) host-view transfer, R004 the
FaultSchedule statelessness discipline, R005 jit-tracing soundness, and
R006 the Pallas-kernel / jnp-ref parity contract.

Pure stdlib ``ast`` and ``tomllib`` — no third-party dependencies.
"""

from .registry import REGISTRY, Finding, Rule, register
from .engine import lint_paths, lint_tree
from .config import LintConfig, LintConfigError, load_config
from .baseline import Baseline, BaselineError, load_baseline

__all__ = [
    "REGISTRY",
    "Finding",
    "Rule",
    "register",
    "lint_paths",
    "lint_tree",
    "LintConfig",
    "LintConfigError",
    "load_config",
    "Baseline",
    "BaselineError",
    "load_baseline",
]
