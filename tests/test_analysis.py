"""Tests for ``repro.analysis`` — the AST invariant linter.

Each rule gets at least one true-positive fixture and one clean fixture
(``tests/fixtures/analysis/``); the suppression contract, JSON output,
CLI exit codes, and the repo-wide clean gate are covered end-to-end.
The RL001 mutation test reintroduces the PR 2 double-psum bug into a
copy of ``core/propagation.py`` and asserts the linter catches it.
"""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from repro.analysis import Finding, LintEngine, RULE_CLASSES, build_rules
from repro.analysis.rules.telemetry_drift import TelemetryCatalogRule

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
FIXTURES = os.path.join(HERE, "fixtures", "analysis")

# The fixture corpus is excluded from real runs by default; tests lint it
# on purpose, so drop that exclude (keep __pycache__).
FIXTURE_EXCLUDES = ("__pycache__",)


def lint_fixture(*names, select=None):
    engine = LintEngine(build_rules(REPO, select=select), root=REPO,
                        excludes=FIXTURE_EXCLUDES)
    return engine.run([os.path.join(FIXTURES, n) for n in names])


def rule_ids(result):
    return [f.rule for f in result.findings]


# ---------------------------------------------------------------------------
# per-rule: true positive + clean fixture
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule_id,bad,clean,min_hits", [
    ("RL001", "rl001_bad.py", "rl001_clean.py", 1),
    ("RL002", "rl002_bad.py", "rl002_clean.py", 4),
    ("RL003", "rl003_bad.py", "rl003_clean.py", 2),
    ("RL004", "rl004_bad.py", "rl004_clean.py", 4),
    ("RL004", "rl004_scalar_bad.py", "rl004_scalar_clean.py", 3),
    ("RL006", "rl006_bad.py", "rl006_clean.py", 2),
])
def test_rule_fires_on_bad_and_passes_clean(rule_id, bad, clean, min_hits):
    bad_res = lint_fixture(bad, select=[rule_id])
    hits = [f for f in bad_res.findings if f.rule == rule_id]
    assert len(hits) >= min_hits, bad_res.format_human()
    assert bad_res.exit_code == 1

    clean_res = lint_fixture(clean, select=[rule_id])
    assert [f for f in clean_res.findings if f.rule == rule_id] == [], \
        clean_res.format_human()


def test_rl002_catches_each_pinning_form():
    res = lint_fixture("rl002_bad.py", select=["RL002"])
    lines = sorted(f.line for f in res.findings)
    # backend call, environ.get call, environ subscript, transitive helper
    assert len(lines) >= 4 and len(set(lines)) >= 4, res.format_human()


def test_rl004_flags_each_shape_class():
    res = lint_fixture("rl004_bad.py", select=["RL004"])
    msgs = "\n".join(f.message for f in res.findings)
    assert "not 128-lane aligned" in msgs
    assert "not 8-sublane aligned" in msgs
    assert "last dim is 1" in msgs
    assert "exceeds" in msgs and "budget" in msgs


def test_rl004_scalar_accumulator_idiom_is_narrow():
    """The (rows, 1) column exemption must not leak: misaligned rows and
    3-D blocks or scratches with a trailing 1 all still fire."""
    res = lint_fixture("rl004_scalar_bad.py", select=["RL004"])
    col_hits = [f for f in res.findings if "last dim is 1" in f.message]
    assert len(col_hits) >= 2, res.format_human()
    assert any(f.message.startswith("BlockSpec") for f in col_hits)
    assert any("not 8-sublane aligned" in f.message for f in res.findings)


def _run_rl005(tree):
    root = os.path.join(FIXTURES, tree)
    rule = TelemetryCatalogRule(
        doc_path=os.path.join(root, "docs", "observability.md"))
    engine = LintEngine([rule], root=root, excludes=FIXTURE_EXCLUDES)
    return engine.run([os.path.join(root, "src")])


def test_rl005_flags_both_drift_directions():
    res = _run_rl005("rl005_bad")
    msgs = [f.message for f in res.findings]
    assert any("app_shiny_new_total" in m and "missing" in m for m in msgs)
    assert any("app_removed_total" in m and "stale" in m.lower()
               or "app_removed_total" in m and "registered" in m
               for m in msgs)
    assert res.exit_code == 1


def test_rl005_clean_catalog_passes():
    res = _run_rl005("rl005_clean")
    assert res.findings == [], res.format_human()


# ---------------------------------------------------------------------------
# suppression contract
# ---------------------------------------------------------------------------

def test_justified_suppression_silences_finding():
    res = lint_fixture("suppress_justified.py")
    assert res.findings == [], res.format_human()
    assert [f.rule for f in res.suppressed] == ["RL006"]
    assert res.exit_code == 0


def test_bare_suppression_suppresses_nothing_and_is_flagged():
    res = lint_fixture("suppress_bare.py")
    ids = rule_ids(res)
    assert "RL006" in ids          # the finding survives
    assert "RL000" in ids          # the bare disable is itself flagged
    assert res.suppressed == []
    assert res.exit_code == 1


def test_rl000_is_never_suppressible(tmp_path):
    bad = tmp_path / "m.py"
    # a bare disable with a justified wildcard disable on the same line
    # range must STILL report the RL000
    bad.write_text(
        "# repro-lint: disable=* -- blanket\n"
        "# repro-lint: disable=RL006\n"
        "x = 1\n")
    engine = LintEngine(build_rules(REPO), root=str(tmp_path),
                        excludes=FIXTURE_EXCLUDES)
    res = engine.run([str(bad)])
    assert "RL000" in rule_ids(res)


# ---------------------------------------------------------------------------
# findings model / JSON
# ---------------------------------------------------------------------------

def test_finding_json_round_trip():
    f = Finding("RL003", "a/b.py", 17, "msg with `ticks`")
    assert Finding.from_dict(json.loads(json.dumps(f.to_dict()))) == f
    assert f.format() == "a/b.py:17: error RL003 msg with `ticks`"


def _cli(args, cwd=REPO):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        cwd=cwd, env=env, capture_output=True, text=True)


def test_cli_json_report_and_exit_code_on_findings():
    proc = _cli(["--json", "--root", REPO,
                 os.path.join(FIXTURES, "rl006_bad.py")])
    # the fixture dir is default-excluded: single files passed explicitly
    # are still excluded, so point the CLI at a tmp-free copy instead
    assert proc.returncode == 0    # excluded -> nothing linted -> clean
    report = json.loads(proc.stdout)
    assert report["files_checked"] == 0


def test_cli_json_on_fixture_copy(tmp_path):
    dst = tmp_path / "rl006_case.py"
    shutil.copy(os.path.join(FIXTURES, "rl006_bad.py"), dst)
    proc = _cli(["--json", "--root", str(tmp_path), str(dst)])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    found = [Finding.from_dict(d) for d in report["findings"]]
    assert {f.rule for f in found} == {"RL006"}
    assert report["files_checked"] == 1


def test_cli_list_rules_and_bad_select():
    proc = _cli(["--list-rules"])
    assert proc.returncode == 0
    listed = {line.split()[0] for line in proc.stdout.splitlines()}
    assert listed == set(RULE_CLASSES)
    proc = _cli(["--select", "RL999", "src"])
    assert proc.returncode == 2


# ---------------------------------------------------------------------------
# repo-wide gates
# ---------------------------------------------------------------------------

def test_real_tree_is_clean():
    """The merged tree must lint clean — this is the CI gate."""
    proc = _cli(["--json", "src", "tests"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["findings"] == []
    # the deliberate exceptions are visible, not invisible (the RL004
    # per-row columns are codified in the rule, so only the RL001
    # replicated-loss exceptions remain suppressed)
    assert len(report["suppressed"]) >= 2


def test_rl001_mutation_catches_pr2_double_psum(tmp_path):
    """Reintroduce the PR 2 bug into a copy of core/propagation.py and
    assert RL001 fires; the unmutated original must be RL001-clean."""
    src = os.path.join(REPO, "src", "repro", "core", "propagation.py")
    original = open(src, encoding="utf-8").read()
    target = "return jnp.sum((logz - gold) * lmask) / cnt"
    assert target in original, "mutation anchor moved: update this test"
    mutant_text = original.replace(
        target,
        "return jax.lax.psum(jnp.sum((logz - gold) * lmask) / cnt, AXIS)")
    mutant = tmp_path / "propagation.py"
    mutant.write_text(mutant_text)

    engine = LintEngine(build_rules(REPO, select=["RL001"]),
                        root=str(tmp_path), excludes=FIXTURE_EXCLUDES)
    res = engine.run([str(mutant)])
    hits = [f for f in res.findings if f.rule == "RL001"]
    assert hits, "linter missed the reintroduced double-psum"
    assert any("psum" in f.message for f in hits)

    clean = LintEngine(build_rules(REPO, select=["RL001"]), root=REPO,
                       excludes=FIXTURE_EXCLUDES).run([src])
    assert [f for f in clean.findings if f.rule == "RL001"] == [], \
        clean.format_human()
