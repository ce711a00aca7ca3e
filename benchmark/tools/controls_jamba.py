"""The controls of the long-reasoning cell's comparison: programs that
MUST fail `correctness_jamba.logit_check`, each a one-line fault or a
step down in precision put into the served path while the reference
stays as it is.

    python benchmark/tools/controls_jamba.py [--seed N] [--rehearse 1]
        [--only <control>] [--prompt-tokens 300,700,40]

runs the comparison on the program as it is (must pass) and on every
control (must fail), at the configuration's published widths on the
chip (or the tiny preset on the CPU with --rehearse 1), and writes the
readings to chiprun_out/controls.jamba.json. No engine runs here: the
prompts are seeded ones and the "served" streams are the decode
program's as the engine dispatches it (the cell itself compares what
its window served; correctness_jamba's docstring). Run once by the
builder; PERF.md section 6 (PR 40) holds the readings the limits were
set from.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def controls(params, cfg) -> dict:
    """name -> (context manager that puts the fault into oryx_tpu,
    (llm params, OryxConfig) the program runs with, further arguments
    of the comparison)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate, mamba

    prefill, step = mamba.mixer_prefill, mamba.mixer_step
    rows_state, window_after = mamba.rows_state, mamba.window_after
    rms_norm = mamba.rms_norm
    R = cfg.llm.mamba_dt_rank
    # (a convert to bfloat16 and back is folded away on the chip:
    # xla_allow_excess_precision; reduce_precision is kept.)
    bf16 = lambda h: jax.lax.reduce_precision(h, 8, 7)  # noqa: E731

    def bf16_prefill(*a, **kw):
        out, (conv, h) = prefill(*a, **kw)
        return out, (conv, bf16(h))

    def bf16_step(*a, **kw):
        out, (conv, h) = step(*a, **kw)
        return out, (conv, bf16(h))

    def bf16_state():
        stack = contextlib.ExitStack()
        stack.enter_context(
            mock.patch.object(mamba, "mixer_prefill", bf16_prefill))
        stack.enter_context(mock.patch.object(mamba, "mixer_step", bf16_step))
        return stack

    def padding_moves(cfg_, lp, u, state, valid, **kw):
        return prefill(cfg_, lp, u, state, jnp.ones_like(valid), **kw)

    def never_zeroed(conv_l, ssm_l, slots, fresh, shape):
        return rows_state(conv_l, ssm_l, slots, jnp.zeros_like(fresh), shape)

    def late_window(win, n, K):
        return window_after(win, jnp.maximum(n - 1, 0), K)

    def no_dt_norm(x, w, eps):
        return x if w.shape[-1] == R and x.shape[-1] == R else rms_norm(
            x, w, eps)

    def all_live(cfg_, lp, u, state, live):
        return step(cfg_, lp, u, state, jnp.ones_like(live))

    # What is dispatched is not what is compared: the decode chunk
    # jitted a second time and traced without dt's norm.
    wrong_decode = jax.jit(
        generate.paged_decode_chunk.__wrapped__,
        static_argnames=("cfg", "chunk", "eos", "attn_impl",
                         "compute_dtype"))

    def other_program(*args, **kw):
        with mock.patch.object(mamba, "rms_norm", no_dt_norm):
            return wrong_decode(*args, **kw)

    fp8 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.dtype == jnp.bfloat16 and a.ndim >= 3 else a, params["llm"])
    patch = mock.patch.object
    same = (params["llm"], cfg)
    return {
        "the state kept in bfloat16": (bf16_state, same, {}),
        "a padded chunk that moves the state":
            (lambda: patch(mamba, "mixer_prefill", padding_moves), same, {}),
        "a reused slot not zeroed":
            (lambda: patch(mamba, "rows_state", never_zeroed), same, {}),
        "the conv window one token late":
            (lambda: patch(mamba, "window_after", late_window), same, {}),
        "rotary positions switched on":
            (contextlib.nullcontext, (params["llm"], dataclasses.replace(
                cfg, llm=dataclasses.replace(cfg.llm, use_rope=True))), {}),
        "dt's norm left out":
            (lambda: patch(mamba, "rms_norm", no_dt_norm), same, {}),
        "a finished lane's state advanced":
            (lambda: patch(mamba, "mixer_step", all_live), same, {}),
        "the weights rounded to fp8 (e4m3)":
            (contextlib.nullcontext, (fp8, cfg), {}),
        "a dispatched program that is not the compared one":
            (contextlib.nullcontext, same, {"dispatched": other_program}),
    }


def run_all(params, cfg, seed: int, only=None, **check_kw) -> dict:
    """{"as served": reading, <control>: reading, ...}; every jitted
    program is traced anew under each fault. only: that control alone
    beside the program as served."""
    import jax

    from benchmark import correctness_jamba

    out = {"as served": correctness_jamba.logit_check(
        params["llm"], cfg, seed, **check_kw)}
    for name, (fault, program, more) in controls(params, cfg).items():
        if only not in (None, name):
            continue
        jax.clear_caches()
        with fault():
            out[name] = correctness_jamba.logit_check(
                params["llm"], cfg, seed, program=program, **more,
                **check_kw)
    jax.clear_caches()
    return out


KEEP = ("ok", "passed", "head_rms_rel", "head_max_rel", "tail_rms_rel",
        "tail_max_rel", "tail_over_head", "state_bf16_share",
        "served_ref_agree", "served_twin_agree",
        "served_ref_agree_swapped", "rms_rel_by_stream")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--only", default=None)
    ap.add_argument("--prompt-tokens", default=None,
                    help="comma-separated, instead of the configuration's")
    args = ap.parse_args(argv)

    from benchmark import program, run
    from benchmark.runners import serve_reasoning_child as child

    conf = run.resolve(run.load_json(
        ROOT, "benchmark", "configs", "jamba2-3b-serve.json"),
        bool(args.rehearse))
    cfg = child.build_config(conf)
    program.configure_cache()
    program.device_record(1, rehearse=bool(args.rehearse))
    lay = conf["layout"]
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    about = conf["logit_check"]  # `sample` is the cell's alone
    check_kw = {k: about[k] for k in
                ("prompt_tokens", "decode_chunks", "head", "tail")}
    if args.prompt_tokens:
        check_kw["prompt_tokens"] = tuple(
            int(n) for n in args.prompt_tokens.split(","))
    readings = run_all(
        params, cfg, args.seed, only=args.only,
        sizes=child.ref_sizes(conf, cfg), page_size=lay["page_size"],
        prefill_chunk=lay["prefill_chunk"], decode_chunk=lay["decode_chunk"],
        max_ctx=lay["max_ctx"], **check_kw)
    for name, r in readings.items():
        print(json.dumps({"program": name, **{k: r[k] for k in KEEP}}),
              flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls.jamba.json"),
              "w") as f:
        json.dump({"seed": args.seed, "readings": readings}, f)
    wrong = [n for n, r in readings.items()
             if r["ok"] != (n == "as served")]
    print(json.dumps({"ok": not wrong, "wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
