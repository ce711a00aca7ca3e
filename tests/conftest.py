"""Test config: force an 8-device CPU platform before jax initializes.

This simulates the multi-chip mesh (SURVEY.md §4 "Distributed") so FSDP /
shard_map / tp tests run anywhere with no TPU. Must run before any
`import jax` in the test session, hence top of conftest.
"""

import os
import sys

# Force, don't setdefault: tests always run on the forced-host CPU mesh,
# never on an accelerator the environment may point at.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from oryx_tpu.utils import compile_cache  # noqa: E402

# Persistent compilation cache, placed by the repo's one rule
# (utils/compile_cache.py); exported so that child processes the tests
# start share it.
os.environ[compile_cache.ENV_VAR] = compile_cache.configure_compile_cache()

# fp32 matmuls on CPU for parity tests (defensive; CPU default is
# highest).
jax.config.update("jax_default_matmul_precision", "highest")


# ---------------------------------------------------------------------------
# Opt-in recompile watchdog for the paged-decode parity tests: set
# ORYX_RECOMPILE_WATCHDOG=<budget> (a bare "1" means budget 16) and the
# shape-bucketing contract of the paged decode path is enforced while
# those tests run — a parity refactor that starts recompiling per chunk
# fails loudly here instead of surfacing as a TPU TTFT regression.
# Off by default: the parity suite deliberately sweeps many geometries,
# and an unconditionally armed watchdog would gate on compile counts
# that legitimately vary with test parametrization.
# ---------------------------------------------------------------------------

import time  # noqa: E402

import pytest  # noqa: E402  (after the platform-pinning prologue)

_WATCHDOG_FILES = ("test_paged_decode.py", "test_prefix_cache.py")


@pytest.fixture(autouse=True)
def _opt_in_recompile_watchdog(request):
    spec = os.environ.get("ORYX_RECOMPILE_WATCHDOG", "").strip().lower()
    # "0"/"off"/"false" disable, matching ORYX_LINT_CHANGED's
    # 0-means-off convention; any other value arms it ("1"/non-numeric
    # = the default budget, an integer > 1 = that budget).
    if spec in ("", "0", "off", "false") or os.path.basename(
        str(request.fspath)
    ) not in _WATCHDOG_FILES:
        yield
        return
    from oryx_tpu.analysis.sanitizers import recompile_watchdog

    budget = int(spec) if spec.isdigit() and int(spec) > 1 else 16
    with recompile_watchdog(budget=budget, action="raise"):
        yield


# ---------------------------------------------------------------------------
# Opt-in lock-order sanitizer + race detector for the concurrency
# suites: ORYX_LOCK_SANITIZER=1 (same 0/off/false convention) arms
# both for the scheduler/containment/trace/metrics/prefix-cache tests
# — every named lock created during the test is instrumented (ordering
# violations and guarded-field races raise at the faulty access), and
# the fixture additionally fails the test if anything was RECORDED but
# swallowed by failure containment (an engine-thread violation turns
# into a contained request error; the assert here keeps it loud).
# check_tier1.sh runs these files a second time with the variable set.
# ---------------------------------------------------------------------------

_LOCK_SAN_FILES = (
    "test_scheduler.py",
    "test_containment.py",
    "test_trace.py",
    "test_metrics_registry.py",
    "test_prefix_cache.py",
    "test_ragged_attention.py",
    "test_speculative.py",
    "test_pagemap.py",
    "test_forensics.py",
    "test_device_time.py",
    "test_journal.py",
)


@pytest.fixture(autouse=True)
def _opt_in_lock_sanitizer(request):
    spec = os.environ.get("ORYX_LOCK_SANITIZER", "").strip().lower()
    if spec in ("", "0", "off", "false") or os.path.basename(
        str(request.fspath)
    ) not in _LOCK_SAN_FILES:
        yield
        return
    from oryx_tpu.analysis.sanitizers import lock_sanitizer, race_violations

    with lock_sanitizer(action="raise") as san:
        yield
        assert not san.stats.violations, (
            "lock-order sanitizer recorded violations during this "
            f"test: {san.stats.violations}"
        )
        assert not race_violations(), (
            "race detector recorded violations during this test: "
            f"{race_violations()}"
        )


# ---------------------------------------------------------------------------
# What a cache kind's engine does not refuse, it serves
# (`scheduler._CACHE_KINDS`): the one body of
# `test_the_engine_serves_what_it_does_not_refuse` in the block, latent,
# recurrent and window engines' tests, each on its own tiny fixture.
# ---------------------------------------------------------------------------


@pytest.fixture
def serves_like_the_default():
    def quiesce(sched, timeout=120.0):
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            if sched.queue_len() == 0 and all(r is None for r in sched.slots):
                return
            time.sleep(0.02)
        raise TimeoutError("the engine did not come to rest")

    def check(build, option, question, cap):
        """One greedy request through `build(**option)` is, byte for
        byte, what `build()` serves, and the option did its work: the
        host tier gave the prompt's pages back, the auditor's replay
        passed, the probe took a sample."""
        def ask(sched):
            return sched.submit({"question": question}, cap, None).result(
                timeout=600)

        plain = build()
        plain.start()
        want = ask(plain)
        plain.close()
        sched = build(**option)
        sched.start()
        try:
            assert ask(sched) == want
            quiesce(sched)
            reg = sched.metrics.registry
            if option.get("host_cache_bytes"):
                cache = sched.prefix_cache
                cache.evict(cache.evictable_pages())
                assert cache.spilled_pages > 0 and cache.pages == 0
                assert ask(sched) == want
                assert reg.get(
                    "oryx_cache_reload_hit_total", raw_name=True) == 1
                quiesce(sched)
                sched._check_pool_invariant()
            if option.get("audit_sample_every"):
                end = time.monotonic() + 300
                while time.monotonic() < end and not sched.auditor.to_dict()[
                        "total"]:
                    time.sleep(0.05)
                assert sched.auditor.to_dict()["verdicts"] == {
                    "pass": 1, "drift": 0, "fail": 0}
            if option.get("numerics_every"):
                assert reg.get(
                    "oryx_numerics_samples_total", raw_name=True) >= 1
                assert reg.get(
                    "oryx_numerics_logits_finite_frac", raw_name=True) == 1
        finally:
            sched.close()

    return check
