"""oryxlint: fixture-driven checker tests, suppression semantics, the
CLI contract, and the repo-wide self-lint gate.

Fixture protocol (tests/lint_fixtures/): `*_pos.py` files mark every
expected finding line with `# expect: <rule>` and the test asserts the
finding set matches EXACTLY (no false positives on the rest of the
file); `*_suppressed.py` must produce zero findings but a nonzero
suppressed count; `*_clean.py` must produce zero findings and zero
suppressions.
"""

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

from oryx_tpu.analysis import make_checkers, run_lint
from oryx_tpu.analysis.runner import default_files

ROOT = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "lint_fixtures"
_EXPECT = re.compile(r"#\s*expect:\s*([a-z][a-z0-9\-]*)")


def lint_sources(*sources: tuple[str, str], rules: str | None = None):
    res = run_lint(list(sources), make_checkers(rules))
    assert not res.errors, res.errors
    return res


def lint_file(path: Path, rules: str | None = None):
    return lint_sources((str(path), path.read_text()), rules=rules)


def expected_findings(path: Path) -> set[tuple[int, str]]:
    out = set()
    for i, line in enumerate(path.read_text().splitlines(), start=1):
        for m in _EXPECT.finditer(line):
            out.add((i, m.group(1)))
    return out


# ---------------------------------------------------------------------------
# Fixtures: positive / suppressed / clean, per rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name", sorted(p.name for p in FIXTURES.glob("*_pos.py"))
)
def test_positive_fixture_exact_findings(name):
    path = FIXTURES / name
    want = expected_findings(path)
    assert want, f"{name} has no # expect: markers"
    res = lint_file(path)
    got = {(f.line, f.rule) for f in res.findings}
    assert got == want, (
        f"{name}: findings != expectations\n  extra: {sorted(got - want)}"
        f"\n  missing: {sorted(want - got)}\n  all:\n    "
        + "\n    ".join(f.format() for f in res.findings)
    )


@pytest.mark.parametrize(
    "name", sorted(p.name for p in FIXTURES.glob("*_suppressed.py"))
)
def test_suppressed_fixture_is_quiet_but_counted(name):
    res = lint_file(FIXTURES / name)
    assert not res.findings, "\n".join(f.format() for f in res.findings)
    assert res.suppressed > 0, (
        f"{name} should demonstrate at least one suppression"
    )


@pytest.mark.parametrize(
    "name", sorted(p.name for p in FIXTURES.glob("*_clean.py"))
)
def test_clean_fixture_has_nothing(name):
    res = lint_file(FIXTURES / name)
    assert not res.findings, "\n".join(f.format() for f in res.findings)
    assert res.suppressed == 0


def test_every_rule_has_fixture_coverage():
    rules_with_pos = {
        rule
        for p in FIXTURES.glob("*_pos.py")
        for _, rule in expected_findings(p)
    }
    all_rules = {c.name for c in make_checkers()}
    assert rules_with_pos == all_rules, (
        f"rules without a positive fixture: {all_rules - rules_with_pos}"
    )


# ---------------------------------------------------------------------------
# Cross-module behavior (the reason for the two-pass design)
# ---------------------------------------------------------------------------


def test_donation_registry_spans_modules():
    defs = (
        "from functools import partial\n"
        "import jax\n"
        "@partial(jax.jit, donate_argnames=('kv',))\n"
        "def consume(params, kv):\n"
        "    return kv\n"
    )
    caller = (
        "from defs import consume\n"
        "def use(params, kv):\n"
        "    out = consume(params, kv)\n"
        "    return kv\n"
    )
    res = lint_sources(
        ("defs.py", defs), ("caller.py", caller),
        rules="use-after-donate",
    )
    assert [(f.path, f.line) for f in res.findings] == [("caller.py", 4)]


def test_metric_kind_conflict_across_modules():
    a = "def f(reg):\n    reg.counter('split_brain_x')\n"
    b = "def g(metrics):\n    metrics.set_gauge('split_brain_x', 1)\n"
    res = lint_sources(("a.py", a), ("b.py", b), rules="metric-name")
    assert {f.path for f in res.findings} == {"a.py", "b.py"}
    assert all("one family, one kind" in f.message for f in res.findings)


def test_jit_assignment_form_static_operand():
    src = (
        "import jax\n"
        "def fn(x, mode):\n"
        "    return x\n"
        "step = jax.jit(fn, static_argnums=(1,))\n"
        "def caller(x):\n"
        "    return step(x, ['a'])\n"
    )
    res = lint_sources(("m.py", src), rules="recompile-hazard")
    assert [f.line for f in res.findings] == [6]
    assert "list literal" in res.findings[0].message


# ---------------------------------------------------------------------------
# Suppression semantics
# ---------------------------------------------------------------------------


def test_file_level_disable():
    src = (
        "# oryxlint: disable-file=metric-name\n"
        "def f(reg):\n"
        "    reg.counter('BadName')\n"
    )
    res = lint_sources(("m.py", src))
    assert not res.findings
    assert res.suppressed == 1


def test_region_off_on():
    src = (
        "import numpy as np\n"
        "# hot-path\n"
        "def f(a, b):\n"
        "    # oryxlint: off=host-sync\n"
        "    x = np.asarray(a)\n"
        "    # oryxlint: on=host-sync\n"
        "    y = np.asarray(b)\n"
        "    return x, y\n"
    )
    res = lint_sources(("m.py", src), rules="host-sync")
    assert [f.line for f in res.findings] == [7]
    assert res.suppressed == 1


def test_unrelated_rule_suppression_does_not_mask():
    src = (
        "def f(reg):\n"
        "    reg.counter('BadName')  # oryxlint: disable=host-sync\n"
    )
    res = lint_sources(("m.py", src), rules="metric-name")
    assert [f.rule for f in res.findings] == ["metric-name"]


def test_parse_error_reported_not_crash():
    res = run_lint([("broken.py", "def f(:\n")], make_checkers())
    assert res.errors and res.errors[0][0] == "broken.py"
    assert not res.findings


# ---------------------------------------------------------------------------
# CLI contract (subprocess: stubs oryx_tpu, never imports jax)
# ---------------------------------------------------------------------------


def _cli(*args: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, str(ROOT / "scripts" / "run_oryxlint.py"),
         *args],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
    )


def test_cli_strict_fails_on_each_positive_fixture():
    for path in sorted(FIXTURES.glob("*_pos.py")):
        out = _cli("--strict", str(path))
        assert out.returncode == 1, (path, out.stdout, out.stderr)
        rules = {rule for _, rule in expected_findings(path)}
        for rule in rules:
            assert f"[{rule}]" in out.stdout, (path, rule, out.stdout)


def test_cli_clean_fixture_exits_zero_and_json_shape():
    path = FIXTURES / "donate_clean.py"
    out = _cli("--strict", "--json", str(path))
    assert out.returncode == 0, (out.stdout, out.stderr)
    payload = json.loads(out.stdout)
    assert payload["findings"] == []
    assert payload["files"] == 1


def test_lockorder_interprocedural_across_modules():
    """The may-acquire-while-holding graph must cross module AND call
    boundaries: holding s._cond while calling a method (of a typed
    attribute, defined in another file) that acquires t._lock is an
    inversion when the manifest says t._lock < s._cond."""
    defs = (
        "from oryx_tpu.analysis.sanitizers import named_lock\n"
        "class Trace:\n"
        "    def __init__(self):\n"
        "        self._lock = named_lock('t._lock')\n"
        "    def finish(self):\n"
        "        with self._lock:\n"
        "            pass\n"
    )
    caller = (
        "# lock-order: t._lock < s._cond\n"
        "from oryx_tpu.analysis.sanitizers import named_lock\n"
        "from defs import Trace\n"
        "class Sched:\n"
        "    def __init__(self):\n"
        "        self._cond = named_lock('s._cond', kind='condition')\n"
        "        self.trace = Trace()\n"
        "    def run(self):\n"
        "        with self._cond:\n"
        "            self.trace.finish()\n"
    )
    res = lint_sources(
        ("defs.py", defs), ("caller.py", caller), rules="lock-order"
    )
    assert len(res.findings) == 1, [f.format() for f in res.findings]
    f = res.findings[0]
    assert f.path == "caller.py" and "inverts" in f.message
    assert "t._lock" in f.message and "finish" in f.message
    # Reordering the manifest legalizes the same nesting.
    fixed = caller.replace(
        "# lock-order: t._lock < s._cond",
        "# lock-order: s._cond < t._lock",
    )
    res = lint_sources(
        ("defs.py", defs), ("caller.py", fixed), rules="lock-order"
    )
    assert not res.findings, [f.format() for f in res.findings]


def test_cli_list_rules_names_all_rules():
    out = _cli("--list-rules")
    assert out.returncode == 0
    for rule in ("lock-discipline", "lock-order", "atomicity",
                 "use-after-donate", "host-sync", "recompile-hazard",
                 "metric-name", "swallowed-exception", "key-linearity",
                 "terminal-path", "replay-taint"):
        assert rule in out.stdout


def test_cli_max_suppressions_ratchet(tmp_path):
    """`--max-suppressions N` is the CI ratchet: a file whose
    suppression count exceeds N exits 1 even with zero findings."""
    path = FIXTURES / "atomicity_suppressed.py"
    ok = _cli(str(path), "--max-suppressions", "5")
    assert ok.returncode == 0, (ok.stdout, ok.stderr)
    over = _cli(str(path), "--max-suppressions", "0")
    assert over.returncode == 1
    assert "exceed the --max-suppressions ratchet" in (
        over.stdout + over.stderr
    )


def test_cli_json_per_rule_breakdown():
    """The JSON artifact carries a per-rule finding/suppression
    breakdown so the CI ratchet can pin individual rules."""
    out = _cli("--json", str(FIXTURES / "keylin_pos.py"),
               str(FIXTURES / "keylin_suppressed.py"))
    assert out.returncode == 1  # the pos fixture's findings
    payload = json.loads(out.stdout)
    br = payload["by_rule"]["key-linearity"]
    assert br["findings"] == len(
        expected_findings(FIXTURES / "keylin_pos.py")
    )
    assert br["suppressed"] == 1


def test_cli_max_suppressions_per_rule():
    """`--max-suppressions-per-rule RULE=N` pins a single rule's
    escape count independently of the global ratchet."""
    path = FIXTURES / "taint_suppressed.py"
    ok = _cli(str(path), "--max-suppressions-per-rule", "replay-taint=1")
    assert ok.returncode == 0, (ok.stdout, ok.stderr)
    # Pinning an unrelated rule at 0 doesn't trip on this file...
    other = _cli(str(path), "--max-suppressions-per-rule",
                 "key-linearity=0")
    assert other.returncode == 0, (other.stdout, other.stderr)
    # ...but pinning the suppressed rule at 0 does.
    over = _cli(str(path), "--max-suppressions-per-rule",
                "replay-taint=0")
    assert over.returncode == 1
    assert "per-rule ratchet" in over.stdout + over.stderr
    # Malformed or unknown specs are a usage error, not a silent pass.
    bad = _cli(str(path), "--max-suppressions-per-rule", "replay-taint")
    assert bad.returncode != 0
    unknown = _cli(str(path), "--max-suppressions-per-rule",
                   "no-such-rule=0")
    assert unknown.returncode != 0


def test_cli_time_budget_gate(monkeypatch):
    """`--time-budget` compares the lint wall time against the budget
    via the runner._monotonic seam (monkeypatched to a fake clock so
    the test is deterministic)."""
    from oryx_tpu.analysis import runner

    ticks = iter([100.0, 107.5])
    monkeypatch.setattr(runner, "_monotonic", lambda: next(ticks))
    rc = runner.main(
        [str(FIXTURES / "donate_clean.py"), "--time-budget", "5.0"]
    )
    assert rc == 1
    ticks = iter([100.0, 100.9])
    monkeypatch.setattr(runner, "_monotonic", lambda: next(ticks))
    rc = runner.main(
        [str(FIXTURES / "donate_clean.py"), "--time-budget", "5.0"]
    )
    assert rc == 0


def test_default_files_leave_git_ignored_scratch_alone(tmp_path):
    """What `.gitignore` lists is not the tree: a second checkout under
    `.bench_check/`, a chip call's outputs, the caches and a run's
    `benchmark/out/` are not linted (nor timed, nor counted against the
    suppression ratchet); a directory that is merely NAMED `out` is."""
    from oryx_tpu.analysis import runner

    scratch = [
        ".bench_check/parent/oryx_tpu", "chiprun_out/ab", ".jax_cache",
        ".smoke_tmp", "benchmark/out/cell",
    ]
    kept = ["oryx_tpu", "benchmark/runners", "scripts/out"]
    for d in scratch + kept:
        (tmp_path / d).mkdir(parents=True)
        (tmp_path / d / "mod.py").write_text("x = 1\n")
    found = {
        str(Path(f).relative_to(tmp_path).parent)
        for f in runner.default_files(str(tmp_path))
    }
    assert found == set(kept)


def test_cli_json_out_writes_artifact(tmp_path):
    """`--json-out` writes the machine-readable report (the CI
    artifact) regardless of the stdout format."""
    report = tmp_path / "report.json"
    path = FIXTURES / "lockorder_pos.py"
    out = _cli(str(path), "--json-out", str(report))
    assert out.returncode == 1  # findings still fail the run
    payload = json.loads(report.read_text())
    assert payload["files"] == 1
    assert {f["rule"] for f in payload["findings"]} == {"lock-order"}
    assert "[lock-order]" in out.stdout  # stdout stayed human-readable


def test_changed_files_widens_on_linter_or_fixture_change(monkeypatch):
    """The --changed-only fast path must widen to a full check when a
    rule module OR a lint fixture changed: either can move findings in
    files that did not change (fixtures pin a rule's contract via
    FIXTURE_RULE_MODULES)."""
    from oryx_tpu.analysis import runner

    def fake_run(changed: list[str]):
        def run(cmd, **kw):
            out = "\n".join(changed) if "diff" in cmd else ""
            return subprocess.CompletedProcess(cmd, 0, stdout=out,
                                               stderr="")
        return run

    # A plain source change keeps the fast path narrow.
    monkeypatch.setattr(
        runner.subprocess, "run",
        fake_run(["oryx_tpu/utils/trace.py"]),
    )
    narrow = runner.changed_files(str(ROOT))
    assert narrow == [str(ROOT / "oryx_tpu" / "utils" / "trace.py")]
    # A rule-module change invalidates per-file checking entirely.
    monkeypatch.setattr(
        runner.subprocess, "run",
        fake_run(["oryx_tpu/analysis/lockorder.py"]),
    )
    assert runner.changed_files(str(ROOT)) is None
    # So does a fixture change — the mapped rule module's contract
    # moved even though the module file itself didn't.
    monkeypatch.setattr(
        runner.subprocess, "run",
        fake_run(["tests/lint_fixtures/atomicity_pos.py",
                  "oryx_tpu/utils/trace.py"]),
    )
    assert runner.changed_files(str(ROOT)) is None
    # And the CLI entry point itself.
    monkeypatch.setattr(
        runner.subprocess, "run",
        fake_run(["scripts/run_oryxlint.py"]),
    )
    assert runner.changed_files(str(ROOT)) is None
    # The dataflow-tier fixtures are in the map too: touching any of
    # them must widen exactly like touching their rule module.
    for fixture in ("tests/lint_fixtures/keylin_pos.py",
                    "tests/lint_fixtures/obligation_suppressed.py",
                    "tests/lint_fixtures/taint_clean.py"):
        monkeypatch.setattr(
            runner.subprocess, "run", fake_run([fixture])
        )
        assert runner.changed_files(str(ROOT)) is None, fixture


def test_fixture_rule_map_covers_every_fixture_prefix():
    """Every fixture on disk maps to a real rule module — a new rule's
    fixtures can't silently fall out of the dependency map."""
    from oryx_tpu.analysis.runner import FIXTURE_RULE_MODULES

    analysis_dir = ROOT / "oryx_tpu" / "analysis"
    for p in FIXTURES.glob("*.py"):
        prefix = p.stem
        for suffix in ("_pos", "_suppressed", "_clean"):
            prefix = prefix.removesuffix(suffix)
        assert prefix in FIXTURE_RULE_MODULES, (
            f"{p.name}: fixture prefix {prefix!r} missing from "
            "FIXTURE_RULE_MODULES"
        )
        assert (analysis_dir / FIXTURE_RULE_MODULES[prefix]).exists()


def test_cli_unknown_rule_errors():
    out = _cli("--rules", "no-such-rule")
    assert out.returncode != 0
    assert "unknown rule" in out.stdout + out.stderr


# ---------------------------------------------------------------------------
# Self-lint: the whole repo is clean (the check_tier1.sh gate)
# ---------------------------------------------------------------------------


@pytest.mark.lint
def test_self_lint_repo_is_clean():
    files = default_files(str(ROOT))
    assert any(f.endswith("scheduler.py") for f in files)
    assert not any("lint_fixtures" in f for f in files)
    res = run_lint(
        ((f, Path(f).read_text()) for f in files), make_checkers()
    )
    assert not res.errors, res.errors
    assert not res.findings, (
        "self-lint regressions:\n"
        + "\n".join(f.format() for f in res.findings)
    )
    # The repo demonstrably USES the machinery: guarded-by fields and
    # hot-path markers exist and deliberate escapes are documented.
    assert res.suppressed > 0


# ---------------------------------------------------------------------------
# Review-pass regressions: directives and markers must live in real
# comments, and suppressed sites must not poison cross-module state
# ---------------------------------------------------------------------------


def test_directives_inside_strings_are_inert():
    src = (
        '"""Docs quoting the syntax: # oryxlint: disable-file=metric-name"""\n'
        "def f(reg):\n"
        "    reg.counter('BadName')\n"
    )
    res = lint_sources(("m.py", src), rules="metric-name")
    assert [f.rule for f in res.findings] == ["metric-name"]


def test_core_module_not_self_disabled_by_its_docstring():
    from oryx_tpu.analysis import core as core_mod

    path = Path(core_mod.__file__)
    pm = core_mod.ParsedModule(str(path), path.read_text())
    assert pm.file_disables == set()


def test_guarded_by_marker_inside_string_is_inert():
    src = (
        "class C:\n"
        '    """docs: self._x = 1  # guarded-by: _lock"""\n'
        "    def f(self):\n"
        "        return self._x\n"
    )
    res = lint_sources(("m.py", src), rules="lock-discipline")
    assert not res.findings


def test_hot_path_marker_between_decorators_and_def():
    """Regression: a marker between the decorator stack and `def` —
    the natural spot when a hot function later gains a decorator —
    was silently ignored, turning the rule off for that function."""
    src = (
        "import functools\n"
        "import numpy as np\n"
        "@functools.cache\n"
        "# hot-path\n"
        "def f(a):\n"
        "    return np.asarray(a)\n"
    )
    res = lint_sources(("m.py", src), rules="host-sync")
    assert [(f.line, f.rule) for f in res.findings] == [(6, "host-sync")]


def test_check_only_restricts_findings_but_not_the_scan():
    """Regression: the `--changed-only` fast path fed only changed
    files into BOTH passes, so a changed caller of an unchanged
    donating callee built an empty donation registry and linted
    clean locally while failing in CI's full run."""
    defs = (
        "from functools import partial\n"
        "import jax\n"
        "@partial(jax.jit, donate_argnames=('kv',))\n"
        "def consume(params, kv):\n"
        "    return kv\n"
    )
    caller = (
        "from defs import consume\n"
        "def use(params, kv):\n"
        "    out = consume(params, kv)\n"
        "    return kv\n"
    )
    sources = [("defs.py", defs), ("caller.py", caller)]
    res = run_lint(
        sources, make_checkers("use-after-donate"),
        check_only={"caller.py"},
    )
    assert [(f.path, f.line) for f in res.findings] == [("caller.py", 4)]
    # Restricting the check pass to the (clean) defs module reports
    # nothing — the caller's finding belongs to the caller's file.
    res = run_lint(
        sources, make_checkers("use-after-donate"),
        check_only={"defs.py"},
    )
    assert not res.findings


def test_suppressed_clash_site_does_not_poison_kind_map():
    a = (
        "def deliberate_clash(reg):\n"
        "    reg.counter('family_y')  # oryxlint: disable=metric-name\n"
        "    reg.gauge('family_y')  # oryxlint: disable=metric-name\n"
    )
    b = "def correct_usage(reg):\n    reg.gauge('family_y')\n"
    res = lint_sources(("a.py", a), ("b.py", b), rules="metric-name")
    assert not res.findings, [f.format() for f in res.findings]
