"""oryxlint runner: file discovery + CLI (the body of
`scripts/run_oryxlint.py`).

Kept inside the package so tests drive `main()` in-process; kept free
of jax (and of the rest of oryx_tpu) so the script can stub the parent
package and lint the tree in well under a second.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
from typing import Iterable

from oryx_tpu.analysis.core import (
    Checker,
    render_json,
    render_text,
    run_lint,
)
from oryx_tpu.analysis.determinism import ReplayTaintChecker
from oryx_tpu.analysis.donation import UseAfterDonateChecker
from oryx_tpu.analysis.hostsync import HostSyncChecker
from oryx_tpu.analysis.keylin import KeyLinearityChecker
from oryx_tpu.analysis.lockorder import AtomicityChecker, LockOrderChecker
from oryx_tpu.analysis.locks import LockDisciplineChecker
from oryx_tpu.analysis.metric_names import MetricNameChecker
from oryx_tpu.analysis.obligations import ObligationChecker
from oryx_tpu.analysis.recompile import RecompileHazardChecker
from oryx_tpu.analysis.swallow import SwallowedExceptionChecker

ALL_CHECKERS: tuple[type[Checker], ...] = (
    LockDisciplineChecker,
    LockOrderChecker,
    AtomicityChecker,
    UseAfterDonateChecker,
    HostSyncChecker,
    RecompileHazardChecker,
    MetricNameChecker,
    SwallowedExceptionChecker,
    KeyLinearityChecker,
    ObligationChecker,
    ReplayTaintChecker,
)

# Seam for the --time-budget gate's unit test: tests monkeypatch this
# to a fake clock; production is the monotonic wall clock.
_monotonic = time.monotonic

# Fixture prefix -> the rule module whose behavior it pins. A change to
# EITHER invalidates the `--changed-only` fast path: a rule edit can
# introduce findings in files that did not change, and a fixture edit
# means the rule's contract moved — both must lint (and be tested
# against) the whole tree.
FIXTURE_RULE_MODULES: dict[str, str] = {
    "lock": "locks.py",
    "lockorder": "lockorder.py",
    "atomicity": "lockorder.py",
    "donate": "donation.py",
    "hostsync": "hostsync.py",
    "recompile": "recompile.py",
    "metric": "metric_names.py",
    "swallow": "swallow.py",
    "keylin": "keylin.py",
    "obligation": "obligations.py",
    "taint": "determinism.py",
}

# Directories that are not our python (vendored assets, fixtures that
# are DELIBERATELY dirty, caches, CI-dropped snapshots of older trees
# — linting a frozen copy double-counts every suppression against the
# ratchet), and the scratch that `.gitignore` lists: a second checkout
# under `.bench_check/` or a chip call's outputs are not the tree.
_EXCLUDE_DIRS = {
    ".git", "__pycache__", ".claude", "native", "assets",
    "lint_fixtures", ".seedcheck", ".bench_check", "chiprun_out",
    ".jax_cache", ".smoke_tmp",
}
# By path from the root: `out` alone would name too much.
_EXCLUDE_PATHS = {os.path.join("benchmark", "out")}


def default_files(root: str) -> list[str]:
    out: list[str] = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(
            d for d in dirnames
            if d not in _EXCLUDE_DIRS and os.path.relpath(
                os.path.join(dirpath, d), root) not in _EXCLUDE_PATHS
        )
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.join(dirpath, fn))
    return out


def changed_files(root: str) -> list[str] | None:
    """Working-tree python files touched vs HEAD (plus untracked) —
    the `--changed-only` fast path for local pre-commit runs.

    Returns None ("check everything") when the change set invalidates
    per-file checking: an edit to the linter itself
    (oryx_tpu/analysis/*) or to a lint fixture (which pins a rule
    module's contract, per FIXTURE_RULE_MODULES) can change findings
    in files that did not change, so the fast path must widen to the
    full tree instead of silently passing."""
    files: set[str] = set()
    for cmd in (
        ["git", "diff", "--name-only", "HEAD"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            res = subprocess.run(
                cmd, cwd=root, capture_output=True, text=True,
                timeout=30, check=True,
            )
        except (OSError, subprocess.SubprocessError):
            return None  # no git: fall back to full
        files.update(
            line.strip() for line in res.stdout.splitlines()
            if line.strip().endswith(".py")
        )
    rule_modules = set()
    for f in files:
        norm = f.replace(os.sep, "/")
        base = os.path.basename(norm)
        if "oryx_tpu/analysis/" in norm or norm.endswith(
            "scripts/run_oryxlint.py"
        ):
            return None
        if "lint_fixtures/" in norm:
            prefix = base.removesuffix(".py")
            for suffix in ("_pos", "_suppressed", "_clean"):
                prefix = prefix.removesuffix(suffix)
            rule_modules.add(
                FIXTURE_RULE_MODULES.get(prefix, base)
            )
    if rule_modules:
        # A fixture changed -> its rule module's contract changed ->
        # same blast radius as editing the rule module itself.
        return None
    allowed = set(default_files(root))
    return sorted(
        p
        for f in files
        if (p := os.path.join(root, f)) in allowed and os.path.exists(p)
    )


def _sources(paths: Iterable[str]):
    for path in paths:
        try:
            with open(path, encoding="utf-8") as f:
                yield path, f.read()
        except OSError as e:
            print(f"oryxlint: cannot read {path}: {e}", file=sys.stderr)


def make_checkers(rules: str | None = None) -> list[Checker]:
    selected = (
        {r.strip() for r in rules.split(",") if r.strip()}
        if rules
        else None
    )
    out = []
    for cls in ALL_CHECKERS:
        if selected is None or cls.name in selected:
            out.append(cls())
    if selected:
        known = {c.name for c in out}
        unknown = selected - known
        if unknown:
            raise SystemExit(
                f"oryxlint: unknown rule(s) {sorted(unknown)}; "
                f"known: {sorted(c.name for c in ALL_CHECKERS)}"
            )
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="run_oryxlint.py",
        description=(
            "oryxlint: JAX-aware static analysis (lock-discipline, "
            "lock-order, atomicity, use-after-donate, host-sync, "
            "recompile-hazard, metric-name, swallowed-exception, "
            "key-linearity, terminal-path, replay-taint). "
            "Exits 1 on any finding; --strict (the CI gate) "
            "additionally fails on files that don't parse; "
            "--max-suppressions N fails when justified suppressions "
            "exceed the recorded ratchet."
        ),
    )
    parser.add_argument(
        "paths", nargs="*",
        help="files/dirs to lint (default: the whole repo)",
    )
    parser.add_argument(
        "--root", default=None,
        help="repo root (default: two levels above this package)",
    )
    parser.add_argument(
        "--strict", action="store_true",
        help="CI gate mode: also exit 1 when a file fails to parse "
        "(findings exit 1 in every mode)",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="machine-readable JSON report on stdout",
    )
    parser.add_argument(
        "--changed-only", action="store_true",
        help="lint only files changed vs HEAD (+ untracked) — the "
        "fast local loop",
    )
    parser.add_argument(
        "--rules", default=None,
        help="comma-separated rule subset (default: all)",
    )
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print rule ids and exit",
    )
    parser.add_argument(
        "--max-suppressions", type=int, default=None, metavar="N",
        help="fail (exit 1) when more than N findings are suppressed "
        "via `# oryxlint: disable=` — the CI ratchet that keeps "
        "justified escapes from silently accumulating",
    )
    parser.add_argument(
        "--max-suppressions-per-rule", action="append", default=[],
        metavar="RULE=N", dest="per_rule_caps",
        help="fail when rule RULE has more than N suppressions "
        "(repeatable) — pins NEW rules at 0 escapes independently "
        "of the global --max-suppressions ratchet",
    )
    parser.add_argument(
        "--time-budget", type=float, default=None, metavar="SECONDS",
        help="fail when the lint run (parse + scan + check over the "
        "selected tree) exceeds this wall time — the CI gate that "
        "keeps the dataflow fixpoint passes from creeping",
    )
    parser.add_argument(
        "--json-out", default=None, metavar="PATH",
        help="also write the JSON report to PATH (the CI artifact; "
        "stdout keeps whichever format --json selects)",
    )
    args = parser.parse_args(argv)

    per_rule_caps: dict[str, int] = {}
    known_rules = {cls.name for cls in ALL_CHECKERS}
    for spec in args.per_rule_caps:
        rule, sep, cap = spec.partition("=")
        if not sep or not cap.strip().isdigit() \
                or rule.strip() not in known_rules:
            raise SystemExit(
                f"oryxlint: bad --max-suppressions-per-rule {spec!r} "
                f"(want RULE=N with RULE in {sorted(known_rules)})"
            )
        per_rule_caps[rule.strip()] = int(cap.strip())

    if args.list_rules:
        for cls in ALL_CHECKERS:
            doc = (sys.modules[cls.__module__].__doc__ or "").strip()
            first = doc.splitlines()[0] if doc else ""
            print(f"{cls.name}: {first}")
        return 0

    root = args.root or os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    check_only = None
    if args.paths:
        files = []
        for p in args.paths:
            if os.path.isdir(p):
                files.extend(default_files(p))
            else:
                files.append(p)
    elif args.changed_only:
        # Findings only for changed files, but the scan pass must see
        # the WHOLE tree: the donation registry and metric kind map are
        # cross-module, and a changed caller of an unchanged donating
        # callee must still lint correctly. changed_files returns None
        # when the linter or a fixture changed — then the fast path
        # widens to a full check.
        files = default_files(root)
        changed = changed_files(root)
        check_only = None if changed is None else set(changed)
    else:
        files = default_files(root)

    t0 = _monotonic()
    result = run_lint(
        _sources(files), make_checkers(args.rules), check_only=check_only
    )
    elapsed = _monotonic() - t0
    print(render_json(result) if args.as_json else render_text(result))
    if args.json_out:
        with open(args.json_out, "w", encoding="utf-8") as f:
            f.write(render_json(result) + "\n")
    rc = 0
    if result.findings:
        rc = 1
    if args.strict and result.errors:
        rc = 1
    if (
        args.max_suppressions is not None
        and result.suppressed > args.max_suppressions
    ):
        print(
            f"oryxlint: {result.suppressed} suppressions exceed the "
            f"--max-suppressions ratchet ({args.max_suppressions}); "
            "either fix the new site or consciously bump the ratchet "
            "in scripts/check_tier1.sh with a justification",
            file=sys.stderr,
        )
        rc = 1
    for rule, cap in sorted(per_rule_caps.items()):
        seen = result.suppressed_by_rule.get(rule, 0)
        if seen > cap:
            print(
                f"oryxlint: rule {rule} has {seen} suppression(s), "
                f"over its per-rule ratchet ({cap}); fix the site or "
                "consciously bump the pin in scripts/check_tier1.sh",
                file=sys.stderr,
            )
            rc = 1
    if args.time_budget is not None and elapsed > args.time_budget:
        print(
            f"oryxlint: run took {elapsed:.2f}s, over the "
            f"--time-budget gate ({args.time_budget:.2f}s); a "
            "fixpoint pass is creeping — profile the new rule before "
            "raising the budget",
            file=sys.stderr,
        )
        rc = 1
    return rc
