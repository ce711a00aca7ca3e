#!/usr/bin/env python
"""Ragged paged-attention bench: decode steps/s and DISPATCHES PER
ENGINE STEP, split engine vs the fused ragged engine, swept over
batch x context x prefill-chunk.

The fused path's whole claim is structural: a mixed prefill+decode
engine step pays ONE device dispatch (`paged_ragged_step`) instead of
an interleaved `_prefill_step` + `_step_chunk` pair, with a STATIC
dispatch shape across any live-slot mix. Both halves are measured, not
asserted:

  * dispatches/step — from the oryx_serving_dispatches_total{kind=}
    counters divided by decode beats (`chunks` counter). Ragged mode
    must be exactly 1.0; split mode pays 1 + prefills/beat.
  * zero recompiles after warmup — the measured phase runs under
    `recompile_watchdog` (analysis/sanitizers.py); ANY compile after
    the warmup workload is a failed shape-stability claim.
  * byte parity — every cell's replies are compared split vs ragged
    (the perf mode must not be a different model).

A SPECULATION cell rides the same harness (--speculate K): a
repetitive-text fixture through the spec engine vs the plain ragged
engine, gating accepted-tokens/step > 1.5, dispatches/step still
exactly 1.0 (kind="spec" only), zero recompiles after warmup, and
byte parity — "speculation changes nothing but speed", measured.

Writes BENCH_paged_attention.json. On a CPU host the numbers are a
labeled cpu_proxy (structure claims — dispatch counts, recompiles,
parity — are backend-independent; steps/s is not).

    JAX_PLATFORMS=cpu python scripts/bench_paged_attention.py \
        [--batches 2,4] [--contexts 48,160] [--prefill-chunks 8,32] \
        [--max-new 8] [--json BENCH_paged_attention.json]
    python scripts/bench_paged_attention.py --smoke   # CI gate
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class _CharTokenizer:
    def encode(self, text, add_special_tokens=False):
        return [min(ord(c), 500) for c in text]

    def decode(self, ids, skip_special_tokens=True):
        return "".join(chr(i) for i in ids if 0 < i < 500)


def _prompts(batch: int, context: int) -> list[str]:
    """`batch` distinct prompts of ~`context` characters (distinct so
    the prefix cache can't collapse the sweep into one prefill)."""
    base = "please summarize the following numbers for me now "
    out = []
    for i in range(batch):
        body = (base + f"request {i} ") * (context // len(base) + 1)
        out.append(body[: max(8, context)])
    return out


DISPATCH_KINDS = ("ragged", "spec", "prefill", "decode")


def _counter(metrics, kind: str) -> float:
    fam = metrics.registry.counter("dispatches_total", ("kind",))
    return fam.labels(kind=kind).value


def _accept_state(metrics) -> tuple[float, float]:
    """(sum, count) of the accepted-tokens-per-step histogram; two
    snapshots subtract into the measured-phase mean (the speculation
    headline, warmup excluded)."""
    from oryx_tpu.utils.metrics import parse_prom_histogram

    h = parse_prom_histogram(
        metrics.render(), "oryx_serving_accepted_tokens_per_step"
    )
    return (0.0, 0.0) if h is None else (h[3], float(h[2]))


def _run_mode(pipe, prompts, max_new, *, ragged, prefill_chunk,
              num_slots, watch, speculate=0):
    """One measured cell: fresh scheduler, warmup workload (compiles
    the shape classes), then the measured burst under the recompile
    watchdog. Returns (result dict, replies)."""
    from oryx_tpu.analysis.sanitizers import recompile_watchdog
    from oryx_tpu.serve.scheduler import ContinuousScheduler
    from oryx_tpu.utils.metrics import ServingMetrics

    metrics = ServingMetrics()
    sched = ContinuousScheduler(
        pipe, num_slots=num_slots, page_size=16, chunk=4, max_ctx=1024,
        metrics=metrics, autostart=False, prefill_chunk=prefill_chunk,
        ragged=ragged, speculate=speculate,
    )
    sched.start()
    # Warmup: one short and one long admission so both shape classes
    # (prefill lanes present / absent) and the COW path compile.
    for q, cap in (("warm up the compiler", 5), (prompts[0], 2)):
        sched.submit({"question": q}, cap).result(timeout=600)
    stats = None
    t0 = time.monotonic()
    dsteps0 = metrics.get("decode_steps_total")
    chunks0 = metrics.get("chunks")
    disp0 = {k: _counter(metrics, k) for k in DISPATCH_KINDS}
    acc0 = _accept_state(metrics)
    replies = []
    if watch:
        with recompile_watchdog(budget=1, action="record") as stats:
            handles = [
                sched.submit({"question": q}, max_new) for q in prompts
            ]
            results = [h.result(timeout=600) for h in handles]
    else:
        handles = [
            sched.submit({"question": q}, max_new) for q in prompts
        ]
        results = [h.result(timeout=600) for h in handles]
    replies = [r[0] for r in results]
    new_tokens = sum(r[2][1] for r in results)
    wall = time.monotonic() - t0
    beats = metrics.get("chunks") - chunks0
    disp = {
        k: _counter(metrics, k) - disp0[k] for k in DISPATCH_KINDS
    }
    acc1 = _accept_state(metrics)
    accept_mean = (
        (acc1[0] - acc0[0]) / (acc1[1] - acc0[1])
        if acc1[1] > acc0[1] else None
    )
    sched.close()
    total_disp = sum(disp.values())
    out = {
        "wall_s": round(wall, 4),
        "decode_steps": metrics.get("decode_steps_total") - dsteps0,
        "decode_steps_per_s": round(
            (metrics.get("decode_steps_total") - dsteps0)
            / max(wall, 1e-9),
            2,
        ),
        "engine_steps": beats,
        "new_tokens": new_tokens,
        "dispatches": disp,
        "dispatches_per_step": round(total_disp / max(beats, 1), 4),
        "recompiles_after_warmup": (
            dict(stats.counts) if stats is not None else None
        ),
    }
    if speculate:
        out["speculate"] = speculate
        out["accepted_tokens_per_step"] = (
            round(accept_mean, 4) if accept_mean is not None else None
        )
        out["draft_proposed"] = metrics.get("draft_proposed_total")
        out["draft_accepted"] = metrics.get("draft_accepted_total")
    return out, replies


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batches", default="2,4")
    ap.add_argument("--contexts", default="48,160")
    ap.add_argument("--prefill-chunks", default="8,32")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--json", default="BENCH_paged_attention.json")
    ap.add_argument(
        "--speculate", type=int, default=6, metavar="K",
        help="draft depth for the speculation cell (repetitive-text "
        "fixture, spec engine vs plain ragged; 0 skips the cell)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="one tiny cell + hard gates (dispatches/step == 1 on the "
        "ragged path AND the speculative path, accepted-tokens/step "
        "> 1.5 on the repetitive fixture, zero recompiles after "
        "warmup, byte parity); wired into scripts/check_tier1.sh",
    )
    args = ap.parse_args(argv)
    if args.smoke:
        args.batches, args.contexts = "3", "64"
        args.prefill_chunks = "8"
        args.max_new = 6
        args.num_slots = 2
        args.json = None

    import jax

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import oryx
    from oryx_tpu.serve.pipeline import OryxInference

    cfg = cfg_lib.oryx_tiny()
    params = oryx.init_params(cfg, jax.random.key(0))
    pipe = OryxInference(_CharTokenizer(), params, cfg)
    backend = jax.default_backend()

    cells = []
    failures = []
    for pc in [int(x) for x in args.prefill_chunks.split(",")]:
        for batch in [int(x) for x in args.batches.split(",")]:
            for ctx in [int(x) for x in args.contexts.split(",")]:
                prompts = _prompts(batch, ctx)
                split, r_split = _run_mode(
                    pipe, prompts, args.max_new, ragged=False,
                    prefill_chunk=pc, num_slots=args.num_slots,
                    watch=True,
                )
                ragg, r_ragg = _run_mode(
                    pipe, prompts, args.max_new, ragged=True,
                    prefill_chunk=pc, num_slots=args.num_slots,
                    watch=True,
                )
                parity = r_split == r_ragg
                cell = {
                    "batch": batch, "context_chars": ctx,
                    "prefill_chunk": pc,
                    "split": split, "ragged": ragg,
                    "replies_bit_identical": parity,
                }
                cells.append(cell)
                # Gates (structural claims; backend-independent).
                if not parity:
                    failures.append(f"cell {batch}x{ctx}x{pc}: replies differ")
                if ragg["dispatches_per_step"] != 1.0:
                    failures.append(
                        f"cell {batch}x{ctx}x{pc}: ragged paid "
                        f"{ragg['dispatches_per_step']} dispatches/step"
                    )
                if ragg["dispatches"]["prefill"] or ragg["dispatches"]["decode"]:
                    failures.append(
                        f"cell {batch}x{ctx}x{pc}: split-path dispatches "
                        f"leaked into ragged mode: {ragg['dispatches']}"
                    )
                for mode, res in (("split", split), ("ragged", ragg)):
                    rc = res["recompiles_after_warmup"]
                    if rc:
                        failures.append(
                            f"cell {batch}x{ctx}x{pc} {mode}: recompiled "
                            f"after warmup: {rc}"
                        )
    spec_cell = None
    if args.speculate:
        # Speculation cell (repetitive-text fixture): the spec engine's
        # whole claim is fewer SEQUENTIAL steps at one dispatch each —
        # gate accepted-tokens/step > 1.5, dispatches/step still 1.0
        # (kind="spec" only), zero recompiles after warmup, and byte
        # parity vs the plain ragged engine on the same prompts.
        rep = ("the quick brown fox jumps over the lazy dog " * 3).strip()
        prompts = [rep, rep + " again", rep + " and again"]
        # Long enough that the repetitive continuation dominates the
        # mean (the first few steps pay cold drafts); the fixture and
        # decode budget are fixed so the gate margin is stable.
        spec_new = max(args.max_new, 48)
        plain, r_plain = _run_mode(
            pipe, prompts, spec_new, ragged=True, prefill_chunk=8,
            num_slots=args.num_slots, watch=True,
        )
        spec, r_spec = _run_mode(
            pipe, prompts, spec_new, ragged=True, prefill_chunk=8,
            num_slots=args.num_slots, watch=True,
            speculate=args.speculate,
        )
        spec_cell = {
            "prompts": len(prompts), "max_new": spec_new,
            "speculate": args.speculate,
            "plain_ragged": plain, "spec": spec,
            "replies_bit_identical": r_plain == r_spec,
        }
        if r_plain != r_spec:
            failures.append("speculation cell: replies differ vs ragged")
        if spec["dispatches_per_step"] != 1.0:
            failures.append(
                f"speculation cell: {spec['dispatches_per_step']} "
                "dispatches/step (must stay 1.0)"
            )
        if (
            spec["dispatches"]["ragged"] or spec["dispatches"]["prefill"]
            or spec["dispatches"]["decode"]
        ):
            failures.append(
                "speculation cell: non-spec dispatch kinds leaked: "
                f"{spec['dispatches']}"
            )
        accept = spec.get("accepted_tokens_per_step")
        if accept is None or accept <= 1.5:
            failures.append(
                f"speculation cell: accepted-tokens/step {accept} "
                "(gate: > 1.5 on the repetitive fixture)"
            )
        if spec["recompiles_after_warmup"]:
            failures.append(
                "speculation cell: recompiled after warmup: "
                f"{spec['recompiles_after_warmup']}"
            )
    out = {
        "bench": "paged_attention_ragged",
        "backend": backend if backend == "tpu" else "cpu_proxy",
        "geometry": {
            "num_slots": args.num_slots, "page_size": 16, "chunk": 4,
            "max_new": args.max_new,
        },
        "cells": cells,
        "speculation": spec_cell,
        "gates": {"failures": failures, "passed": not failures},
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
    return out


def main(argv=None) -> int:
    out = run(argv)
    print(json.dumps(out, indent=2))
    if not out["gates"]["passed"]:
        print(
            "BENCH GATE FAILED: " + "; ".join(out["gates"]["failures"]),
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
