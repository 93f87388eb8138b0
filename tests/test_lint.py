"""repro.analysis.lint gates (ISSUE 8).

Four layers, mirroring the bench-tool tests' shape:

* per-rule fixture contracts — every registered rule detects its seeded
  known-bad fixture and stays silent on its known-clean twin;
* registry completeness — every rule has both fixtures and a DESIGN.md
  anchor, so a new rule cannot land undocumented or untested;
* the CLI driven end-to-end on temp trees — a synthetic new violation
  fails the build, the baseline ratchet only shrinks, inline disables
  demand a reason;
* one-line diagnostics for config/baseline failure modes (the
  ``check_bench_schema.py`` convention), plus the merge-state pin: the
  committed tree lints clean against the committed (empty) baseline.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.analysis.lint import (  # noqa: E402
    LintConfig,
    LintConfigError,
    load_config,
)
from repro.analysis.lint.baseline import (  # noqa: E402
    BaselineError,
    load_baseline,
    write_baseline,
)
from repro.analysis.lint.cli import main  # noqa: E402
from repro.analysis.lint.engine import lint_paths, lint_tree  # noqa: E402
from repro.analysis.lint.registry import all_rules  # noqa: E402

FIXTURES = os.path.join(ROOT, "tests", "fixtures", "lint")
RULE_IDS = ("R001", "R002", "R003", "R004", "R005", "R006")


def _default_config() -> LintConfig:
    return LintConfig(root=ROOT)


def _lint_fixture(rule_id: str, which: str):
    path = os.path.join(FIXTURES, rule_id, which)
    return lint_paths([path], _default_config())


# ---------------------------------------------------------------------------
# per-rule fixture contracts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_rule_detects_known_bad(rule_id):
    findings = _lint_fixture(rule_id, "bad")
    hits = [f for f in findings if f.rule == rule_id]
    assert hits, f"{rule_id} missed its seeded known-bad fixture"


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_rule_passes_known_clean(rule_id):
    findings = _lint_fixture(rule_id, "clean")
    assert findings == [], [f.render() for f in findings]


def test_bad_fixtures_have_no_cross_rule_noise():
    """A bad fixture for rule X may only trip rule X — anything else means
    a rule is firing outside its contract."""
    for rule_id in RULE_IDS:
        findings = _lint_fixture(rule_id, "bad")
        other = [f.render() for f in findings if f.rule != rule_id]
        assert other == [], other


# ---------------------------------------------------------------------------
# registry completeness
# ---------------------------------------------------------------------------


def test_registry_has_at_least_six_rules():
    assert len(all_rules()) >= 6


def test_every_rule_has_both_fixtures():
    for rule in all_rules():
        for which in ("bad", "clean"):
            d = os.path.join(FIXTURES, rule.id, which)
            assert os.path.isdir(d), f"{rule.id} lacks a {which} fixture"
            assert any(f.endswith(".py") for _, _, fs in os.walk(d)
                       for f in fs), f"{rule.id}/{which} has no .py files"


def test_every_rule_has_a_design_anchor():
    design = open(os.path.join(ROOT, "DESIGN.md"), encoding="utf-8").read()
    assert "Static invariants" in design
    for rule in all_rules():
        assert rule.id in design, f"{rule.id} is undocumented in DESIGN.md"


def test_every_rule_names_its_runtime_gate():
    for rule in all_rules():
        assert rule.gate.strip(), f"{rule.id} has no runtime-gate mapping"
        assert rule.summary.strip()


# ---------------------------------------------------------------------------
# CLI on a temp tree: the CI story end-to-end
# ---------------------------------------------------------------------------

_VIOLATION = (
    "import jax\n\n\n"
    "def f(key):\n"
    "    a = jax.random.normal(key, (3,))\n"
    "    b = jax.random.uniform(key, (3,))\n"
    "    return a, b\n"
)

_SECOND_VIOLATION = (
    "import jax\n\n\n"
    "def g(key):\n"
    "    x = jax.random.bernoulli(key, 0.5)\n"
    "    ks = jax.random.split(key)\n"
    "    return x, ks\n"
)


def _tmp_tree(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    (src / "mod.py").write_text(_VIOLATION)
    cfg = tmp_path / "pyproject.toml"
    cfg.write_text("[tool.repro-lint]\n")
    return src, cfg


def test_cli_fails_on_synthetic_violation(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    rc = main([str(src), "--config", str(cfg)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "R004" in out and "1 finding(s)" in out


def test_cli_baseline_accepts_then_fails_on_new_violation(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    base = tmp_path / ".lint-baseline.json"
    rc = main([str(src), "--config", str(cfg), "--baseline", str(base),
               "--write-baseline"])
    assert rc == 0
    # baselined: the committed debt passes
    rc = main([str(src), "--config", str(cfg), "--baseline", str(base)])
    assert rc == 0
    # a NEW violation fails the build even with the baseline in place
    (src / "mod2.py").write_text(_SECOND_VIOLATION)
    rc = main([str(src), "--config", str(cfg), "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "mod2.py" in out


def test_cli_stale_baseline_forces_shrink(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    base = tmp_path / ".lint-baseline.json"
    assert main([str(src), "--config", str(cfg), "--baseline", str(base),
                 "--write-baseline"]) == 0
    (src / "mod.py").write_text("x = 1\n")        # debt fixed
    rc = main([str(src), "--config", str(cfg), "--baseline", str(base)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "stale baseline" in out and "shrink" in out


def test_write_baseline_refuses_growth(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    base = tmp_path / ".lint-baseline.json"
    assert main([str(src), "--config", str(cfg), "--baseline", str(base),
                 "--write-baseline"]) == 0
    (src / "mod2.py").write_text(_SECOND_VIOLATION)
    rc = main([str(src), "--config", str(cfg), "--baseline", str(base),
               "--write-baseline"])
    out = capsys.readouterr().out
    assert rc == 2
    assert "refusing to grow" in out
    # --allow-growth is the explicit escape hatch
    rc = main([str(src), "--config", str(cfg), "--baseline", str(base),
               "--write-baseline", "--allow-growth"])
    assert rc == 0


def test_baseline_shrinks_budget_monotonically(tmp_path):
    src, cfg = _tmp_tree(tmp_path)
    base = tmp_path / ".lint-baseline.json"
    assert main([str(src), "--config", str(cfg), "--baseline", str(base),
                 "--write-baseline"]) == 0
    assert load_baseline(str(base)).budget == 1
    (src / "mod.py").write_text("x = 1\n")
    assert main([str(src), "--config", str(cfg), "--baseline", str(base),
                 "--write-baseline"]) == 0
    assert load_baseline(str(base)).budget == 0
    # a hand-grown baseline (entries > budget) is rejected on load
    data = json.loads(base.read_text())
    data["findings"] = [{"rule": "R004", "path": "x.py", "hash": "ab"}] * 3
    base.write_text(json.dumps(data))
    with pytest.raises(BaselineError, match="may only shrink"):
        load_baseline(str(base))


def test_cli_json_format_and_annotations(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    rc = main([str(src), "--config", str(cfg), "--format", "json",
               "--annotate"])
    out = capsys.readouterr().out
    assert rc == 1
    json_part = out[: out.index("::error")]
    payload = json.loads(json_part)
    assert payload["ok"] is False
    assert payload["counts"]["findings"] == 1
    assert payload["findings"][0]["rule"] == "R004"
    assert "::error file=" in out and "title=R004" in out


def test_cli_module_entry_point(tmp_path):
    """`python -m repro.analysis.lint` — the exact CI invocation shape."""
    src, cfg = _tmp_tree(tmp_path)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", str(src),
         "--config", str(cfg)],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 1
    assert "R004" in proc.stdout


# ---------------------------------------------------------------------------
# inline disables: mandatory reasons
# ---------------------------------------------------------------------------


def test_disable_with_reason_suppresses(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    (src / "mod.py").write_text(
        "import jax\n\n\n"
        "def f(key):\n"
        "    a = jax.random.normal(key, (3,))\n"
        "    b = jax.random.uniform(key, (3,))"
        "  # lint: disable=R004 (deliberate correlated draw for the test)\n"
        "    return a, b\n")
    rc = main([str(src), "--config", str(cfg)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "1 suppressed" in out


def test_disable_on_preceding_comment_line(tmp_path):
    src, cfg = _tmp_tree(tmp_path)
    (src / "mod.py").write_text(
        "import jax\n\n\n"
        "def f(key):\n"
        "    a = jax.random.normal(key, (3,))\n"
        "    # lint: disable=R004 (correlated draw is the point here)\n"
        "    b = jax.random.uniform(key, (3,))\n"
        "    return a, b\n")
    assert main([str(src), "--config", str(cfg)]) == 0


def test_disable_without_reason_is_rejected(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    (src / "mod.py").write_text(
        "import jax\n\n\n"
        "def f(key):\n"
        "    a = jax.random.normal(key, (3,))\n"
        "    b = jax.random.uniform(key, (3,))  # lint: disable=R004\n"
        "    return a, b\n")
    rc = main([str(src), "--config", str(cfg)])
    out = capsys.readouterr().out
    assert rc == 1
    # the suppression is void AND the malformed comment is its own finding
    assert "R004" in out
    assert "R000" in out and "without a reason" in out


# ---------------------------------------------------------------------------
# one-line diagnostics (check_bench_schema.py convention)
# ---------------------------------------------------------------------------


def test_corrupt_baseline_is_one_clear_error(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    base = tmp_path / ".lint-baseline.json"
    base.write_text('{"version": 1, "budget": 0, "findings": [{"rule"')
    rc = main([str(src), "--config", str(cfg), "--baseline", str(base)])
    out = capsys.readouterr().out.strip()
    assert rc == 2
    assert len(out.splitlines()) == 1
    assert "unreadable or truncated" in out


def test_wrong_version_baseline_is_one_clear_error(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    base = tmp_path / ".lint-baseline.json"
    base.write_text('{"version": 99, "budget": 0, "findings": []}')
    rc = main([str(src), "--config", str(cfg), "--baseline", str(base)])
    out = capsys.readouterr().out.strip()
    assert rc == 2
    assert len(out.splitlines()) == 1 and "version" in out


def test_missing_baseline_is_one_clear_error(tmp_path, capsys):
    src, cfg = _tmp_tree(tmp_path)
    rc = main([str(src), "--config", str(cfg), "--baseline",
               str(tmp_path / "nope.json")])
    out = capsys.readouterr().out.strip()
    assert rc == 2
    assert len(out.splitlines()) == 1
    assert "not found" in out and "--write-baseline" in out


def test_invalid_toml_is_one_clear_error(tmp_path, capsys):
    src, _ = _tmp_tree(tmp_path)
    cfg = tmp_path / "pyproject.toml"
    cfg.write_text("[tool.repro-lint\nbroken")
    rc = main([str(src), "--config", str(cfg)])
    out = capsys.readouterr().out.strip()
    assert rc == 2
    assert len(out.splitlines()) == 1
    assert "invalid TOML" in out and "[tool.repro-lint]" in out


def test_exclude_without_reason_is_one_clear_error(tmp_path, capsys):
    src, _ = _tmp_tree(tmp_path)
    cfg = tmp_path / "pyproject.toml"
    cfg.write_text('[[tool.repro-lint.exclude]]\npath = "src/x.py"\n')
    rc = main([str(src), "--config", str(cfg)])
    out = capsys.readouterr().out.strip()
    assert rc == 2
    assert len(out.splitlines()) == 1
    assert "no 'reason'" in out


def test_exclude_manifest_skips_with_rationale(tmp_path, capsys):
    src, _ = _tmp_tree(tmp_path)
    cfg = tmp_path / "pyproject.toml"
    cfg.write_text(
        "[[tool.repro-lint.exclude]]\n"
        'path = "src/mod.py"\n'
        'reason = "fixture stack outside the contract"\n')
    rc = main([str(src), "--config", str(cfg), "-v"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "skipped (manifest)" in out
    assert "outside the contract" in out


# ---------------------------------------------------------------------------
# merge-state pins: the committed tree and its committed baseline
# ---------------------------------------------------------------------------


def test_committed_tree_is_clean():
    """engine/ + kernels/ + benchmarks lint clean under the committed
    config — the ISSUE 8 acceptance criterion, pinned as a test."""
    cfg = load_config(os.path.join(ROOT, "pyproject.toml"))
    findings = lint_paths(
        [os.path.join(ROOT, "src"), os.path.join(ROOT, "benchmarks")], cfg)
    assert findings == [], [f.render() for f in findings]


def test_committed_baseline_is_empty():
    b = load_baseline(os.path.join(ROOT, ".lint-baseline.json"))
    assert b.budget == 0
    assert b.entries == []


def test_committed_manifest_excludes_only_seed_stack():
    cfg = load_config(os.path.join(ROOT, "pyproject.toml"))
    assert cfg.excludes, "manifest should be explicit, not empty"
    for ex in cfg.excludes:
        assert ex.reason.strip(), f"{ex.path} has no rationale"
        # the protocol engine is never excluded
        assert not ex.path.startswith("src/repro/engine")
        assert ex.path not in ("src/repro/kernels", "src")
    excluded = {ex.path for ex in cfg.excludes}
    for required in ("src/repro/kernels/mamba.py",
                     "src/repro/kernels/rwkv6.py",
                     "src/repro/kernels/flash_attention.py",
                     "src/repro/models",
                     "src/repro/configs"):
        assert required in excluded, f"manifest lost {required}"


def test_engine_modules_are_genuinely_scanned():
    """Zero findings must mean 'clean', not 'blind': the analyzer resolves
    the real donating dispatches (the turn loop's ``full_j``/``sub_j``
    calls in engine/hotloop.py, which donate the state ``s``) and each
    family's traced ``step`` with its static names (engine/median.py,
    maxmarg.py, unified.py, through their ``hotloop.Family`` records)."""
    import ast as ast_mod

    from repro.analysis.lint.context import FileContext
    from repro.analysis.lint.rules.r001_use_after_donate import _Walker

    def context(name):
        path = os.path.join(ROOT, "src", "repro", "engine", name)
        src = open(path, encoding="utf-8").read()
        return FileContext(path, src, ast_mod.parse(src))

    fc = context("hotloop.py")
    walker = _Walker(fc, load_config(os.path.join(ROOT, "pyproject.toml")))
    donating = {}
    for node in ast_mod.walk(fc.functions["run_family"]):
        if isinstance(node, ast_mod.Call) and isinstance(node.func,
                                                         ast_mod.Name):
            donated = walker._donated_in_call(node)
            if donated:
                donating[node.func.id] = donated
    assert donating == {"full_j": ["s"], "sub_j": ["s"]}, donating
    for name in ("median.py", "maxmarg.py", "unified.py"):
        traced = context(name).traced_functions()
        assert "step" in traced and traced["step"] is not None, name
        assert "trans_width" in traced["step"], name
