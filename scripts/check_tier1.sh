#!/usr/bin/env bash
# One-command local tier-1 gate: runs the ROADMAP "Tier-1 verify"
# command VERBATIM (in a subshell, so its trailing `exit $rc` is its
# own exit code), then fails on any regression vs the recorded
# DOTS_PASSED baseline below.
#
# Bump BASELINE_DOTS deliberately when green tests are ADDED; never
# lower it to paper over a regression. Override for experiments with
# ORYX_TIER1_BASELINE=<n>.
set -u
cd "$(dirname "$0")/.."

# 700 = the count of PR 19's day (~731 observed then) with headroom for
# load-dependent flakes; the suite has grown since, and what a PR is
# held to now is the driver's `floor` less `allowance` on the lines of
# PERF_LEDGER.jsonl (1,732 less 17 at PR 47), not this number.
BASELINE_DOTS=${ORYX_TIER1_BASELINE:-700}

# --- oryxlint static analysis (fast, jax-free: fail before pytest) ----------
# Repo-wide by default; ORYX_LINT_CHANGED=1 lints only files changed vs
# HEAD (+ untracked) for the quick local loop (the fast path widens to
# the full tree automatically when the linter or a fixture changed).
#
# Suppression ratchet: 41 = the 22 justified sites recorded at PR 5/6,
# the 3 single-consumer queue-pop `atomicity` suppressions in
# ContinuousScheduler._admit (PR 8), the 6 host-sync lines of
# `_harvest_spec` (PR 11) — the speculative engine's ONE deliberate
# sync point per step, the exact same contract `_harvest_chunk`'s
# region already documents — the identity-re-checked timeout
# clear in `request_profile` (PR 13; the guard is the `is holder`
# re-check under the second lock acquisition, which the atomicity
# rule's check/mutation pairing cannot see), and the 9 `key-linearity`
# sites from the dataflow tier (PR 20): deliberate key reuse for
# verified bit-identity (drafter host-vs-device parity, replay
# determinism tests) or fold_in-style per-lane derivation the linear
# model cannot prove. Bump ONLY with a justification comment at the
# new suppression site; never to paper over a lazy disable. The
# per-rule caps below pin each rule's count separately so a new
# suppression under one rule cannot hide behind slack freed up under
# another; the dataflow rules terminal-path and replay-taint are
# pinned at ZERO suppressions — their escapes are the `# discharges:`
# and `# replay-exempt:` annotations, not disables. --time-budget
# backs the "whole-tree lint stays interactive" contract (the shared
# walk index + AST-span comment scanner keep the full strict run
# around 4s on one CI core). The JSON report lands at
# $ORYX_LINT_REPORT as the CI artifact (findings, per-rule counts,
# suppression totals).
ORYX_LINT_REPORT=${ORYX_LINT_REPORT:-/tmp/oryxlint_report.json}
lint_args=(--strict --max-suppressions 41 --json-out "$ORYX_LINT_REPORT"
           --max-suppressions-per-rule key-linearity=9
           --max-suppressions-per-rule terminal-path=0
           --max-suppressions-per-rule replay-taint=0
           --time-budget 5.0)
if [ "${ORYX_LINT_CHANGED:-0}" != "0" ]; then
    lint_args+=(--changed-only)
fi
echo "running oryxlint (${lint_args[*]})"
if ! timeout -k 10 120 python scripts/run_oryxlint.py "${lint_args[@]}"; then
    echo "ORYXLINT FAILED (static analysis findings above)" >&2
    exit 1
fi
echo "oryxlint report artifact: $ORYX_LINT_REPORT"

# --- ROADMAP.md "Tier-1 verify", verbatim -----------------------------------
bash -c "set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 960 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=\${PIPESTATUS[0]}; echo DOTS_PASSED=\$(grep -aE '^[.FEsxX]+( *\[ *[0-9]+%\])?\$' /tmp/_t1.log | tr -cd . | wc -c); exit \$rc"
rc=$?
# ----------------------------------------------------------------------------

dots=$(grep -aE '^[.FEsxX]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
echo "tier-1: $dots passed (baseline $BASELINE_DOTS, pytest rc=$rc)"
if [ "$dots" -lt "$BASELINE_DOTS" ]; then
    echo "TIER-1 REGRESSION: $dots < baseline $BASELINE_DOTS" >&2
    exit 1
fi
echo "tier-1 OK: no regression vs recorded baseline"

# --- concurrency suites under the runtime sanitizers -------------------------
# Second pass over the scheduler/containment suites with
# ORYX_LOCK_SANITIZER=1: every named lock is instrumented (ordering
# violations / guarded-field races raise at the faulty access, and the
# conftest fixture fails any test whose violations were swallowed by
# failure containment). This is the runtime proof the declared lock
# order in oryx_tpu/concurrency.py matches what the code actually does.
echo "checking concurrency suites under ORYX_LOCK_SANITIZER=1"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    ORYX_LOCK_SANITIZER=1 python -m pytest \
    tests/test_scheduler.py tests/test_containment.py \
    tests/test_trace.py tests/test_metrics_registry.py \
    tests/test_prefix_cache.py tests/test_lock_sanitizer.py \
    tests/test_router.py tests/test_ragged_attention.py \
    tests/test_speculative.py tests/test_pagemap.py \
    tests/test_forensics.py tests/test_device_time.py \
    tests/test_audit.py tests/test_numerics.py \
    tests/test_journal.py \
    -q -m 'not slow' \
    -p no:cacheprovider -p no:xdist -p no:randomly; then
    echo "LOCK SANITIZER SUITE FAILED (a concurrency violation above)" >&2
    exit 1
fi

# --- serving observability surface ------------------------------------------
# Boot a short-lived CPU server and verify /healthz + /readyz, /metrics
# (content type, oryx_serving_ name prefix, build_info gauge, HBM
# gauges) and the /debug flight recorder + trace endpoints are
# well-formed.
echo "checking serving endpoints (/healthz, /readyz, /metrics, /debug/*)"
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python scripts/check_serving_endpoints.py; then
    echo "SERVING ENDPOINT CHECK FAILED" >&2
    exit 1
fi

# --- output-quality observatory gate ----------------------------------------
# The ISSUE-14 acceptance bar: an --audit-sample-every 1 replica under a
# sequential greedy burst — every sampled request audits verdict=pass on
# the fp path, the /debug/audit ring reconciles exactly with
# oryx_audit_total{verdict=}, kind="audit" wide events validate against
# the schema registry, and live-traffic reply bytes + dispatch counters
# are identical to an unarmed twin (the auditor observes, never
# perturbs).
echo "checking output-quality observatory (--audit-smoke)"
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python scripts/check_serving_endpoints.py --audit-smoke; then
    echo "AUDIT OBSERVATORY CHECK FAILED" >&2
    exit 1
fi

# --- engine flight-recorder gate ---------------------------------------------
# The ISSUE-18 acceptance bar: a --journal armed replica under a
# sequential burst — /debug/journal well-formed and reconciled, the
# journal FILE replays offline byte-exactly (replay_journal.py:
# decision-for-decision stream equality + reply fingerprints), and
# live-traffic reply bytes + dispatch counters are identical to an
# unarmed twin (the journal observes, never perturbs).
echo "checking engine flight recorder (--journal-smoke)"
if ! timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python scripts/check_serving_endpoints.py --journal-smoke; then
    echo "JOURNAL FLIGHT-RECORDER CHECK FAILED" >&2
    exit 1
fi

# --- 2-replica router smoke --------------------------------------------------
# Two tiny replicas behind the prefix-affinity router
# (serve/router.py): the full endpoint gate runs against the ROUTER
# (merged /debug, replica-labeled /metrics/aggregate, upstream-TTFB
# quantiles), then a shared-prefix burst must show AFFINITY — one
# replica's oryx_serving_prefix_cache_hit_tokens_total dominates the
# fleet total.
echo "checking 2-replica router smoke (affinity + merged endpoints)"
if ! timeout -k 10 420 env JAX_PLATFORMS=cpu \
    python scripts/check_serving_endpoints.py --router-smoke; then
    echo "ROUTER SMOKE FAILED" >&2
    exit 1
fi

# --- prefix-cache perf gate --------------------------------------------------
# Repeated-system-prompt workload through the continuous scheduler,
# cache off vs on: replies must stay bit-identical and prefill tokens
# computed must drop >= 2x (the PR-4 acceptance bar; TTFT is reported
# but not gated in smoke mode — wall clock on shared CI is noisy).
echo "checking prefix-cache perf (bench_prefix_cache.py --smoke)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python scripts/bench_prefix_cache.py --smoke > /dev/null; then
    echo "PREFIX CACHE PERF CHECK FAILED" >&2
    exit 1
fi

# --- ragged paged-attention + speculation gate -------------------------------
# The fused one-dispatch engine path (--ragged) against the split
# path: dispatches/step must be EXACTLY 1 on the ragged engine (the
# oryx_serving_dispatches_total{kind=} counters are the proof), zero
# recompiles after warmup under recompile_watchdog (static dispatch
# shape across live-slot mixes), and replies byte-identical split vs
# ragged. The speculation cell (repetitive-text fixture through
# --speculate) additionally gates accepted-tokens/step > 1.5,
# dispatches/step still 1.0 (kind="spec" only) and byte parity vs the
# plain ragged engine.
echo "checking ragged paged attention (bench_paged_attention.py --smoke)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python scripts/bench_paged_attention.py --smoke > /dev/null; then
    echo "RAGGED PAGED ATTENTION CHECK FAILED" >&2
    exit 1
fi

# --- chaos suite: fault injection + failure containment ----------------------
# Every named fault scenario (injected page-pool OOM, engine-thread
# crash, the same crash journaled + replayed offline bit-for-bit,
# hung dispatch vs deadline, mid-stream client disconnect,
# checkpoint-save failure) against a live tiny server: pool invariants
# hold, zero leaked pages/refcounts, /readyz returns to 200, and
# oryx_faults_injected_total reconciles against the injection schedule.
# Runs with the lock sanitizer armed: restart/drain/hung-dispatch are
# the rarely-trodden lock paths, and the suite fails on any ordering
# violation, race, or re-entrant scheduler._cond acquire it records.
echo "checking failure containment (chaos_suite.py, lock sanitizer armed)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    ORYX_LOCK_SANITIZER=1 python scripts/chaos_suite.py; then
    echo "CHAOS SUITE FAILED (a fault escaped containment)" >&2
    exit 1
fi

# --- open-loop capacity harness ----------------------------------------------
# Seeded Poisson sweep against a self-booted tiny continuous-engine
# server with the SLO detectors armed: the report must be schema-valid,
# a saturation knee must exist, zero ttft_slo/queue_depth_slo firings
# at/below the knee, and every finished request must carry a complete
# cost ledger (prefill/cached tokens, decode steps, page-seconds).
echo "checking capacity harness (loadgen.py --smoke)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python scripts/loadgen.py --smoke > /dev/null; then
    echo "LOADGEN CAPACITY CHECK FAILED" >&2
    exit 1
fi

# --- bench regression sentinel -----------------------------------------------
# The loadgen smoke above regenerated BENCH_loadgen.json; diff it (and
# BENCH_paged_attention.json) against the committed baselines/ with
# noise-aware per-metric-class tolerances. A moved knee, collapsed
# accepted-tokens/step, >1 dispatches/step or flipped byte parity
# fails CI with the offending series named; non-comparable runs
# (backend or sweep-config drift) are refused, not diffed. Refresh
# baselines deliberately with `bench_compare.py --update-baselines`.
# (Runs BEFORE the router sweep below, which rewrites the artifact
# with its router-flavored config.)
echo "checking bench regression sentinel (bench_compare.py --gate)"
if ! timeout -k 10 120 python scripts/bench_compare.py --gate; then
    echo "BENCH REGRESSION SENTINEL FAILED (see the verdict table)" >&2
    exit 1
fi

# --- router capacity harness -------------------------------------------------
# The same seeded sweep through a 2-replica prefix-affinity fleet:
# schema + knee + zero SLO firings below it (summed across replicas),
# per-replica goodput split recorded, router-level 503/retries
# classified apart from backend errors, and the sweep-wide affinity
# hit rate must clear 0.5 on the shared-prefix mix. (Knee-vs-single
# comparison is recorded in the report; gating it needs multi-core
# hosts — see docs/OBSERVABILITY.md.)
echo "checking router capacity harness (loadgen.py --smoke --router 2)"
if ! timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python scripts/loadgen.py --smoke --router 2 > /dev/null; then
    echo "ROUTER LOADGEN CHECK FAILED" >&2
    exit 1
fi

# --- trainer telemetry exporter ---------------------------------------------
# Short CPU train with the /metrics exporter attached: /readyz must flip
# 503 -> 200 while the step loop runs, and the exposition must be
# well-formed (oryx_train_ prefix, no duplicate families, the
# loss/tokens_per_sec/mfu/goodput_ratio/hbm_live_bytes series present).
echo "checking trainer telemetry exporter (/metrics, /healthz, /readyz)"
if ! timeout -k 10 400 env JAX_PLATFORMS=cpu \
    python scripts/check_train_telemetry.py; then
    echo "TRAIN TELEMETRY CHECK FAILED" >&2
    exit 1
fi
