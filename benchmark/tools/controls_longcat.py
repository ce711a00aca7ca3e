"""The controls of the latent-attention cell's comparison: programs
that MUST fail `correctness_longcat.logit_check`, each a one-line fault
or a step down in precision put into the served path while the
reference stays as it is.

    python benchmark/tools/controls_longcat.py [--seed N] [--rehearse 1]
        [--only <control>]

runs the comparison on the program as it is (must pass) and on every
control (must fail), at the configuration's published widths on the
chip (or the tiny preset on the CPU with --rehearse 1), and writes the
readings to chiprun_out/controls.longcat.json. Run once by the builder;
PERF.md section 6 (PR 31) holds the readings the limits were set from.
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


def _int8_rows(x):
    """Rows rounded through int8 with one scale a row."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    q = jnp.round(x.astype(jnp.float32) / jnp.where(scale == 0, 1.0, scale))
    return (q * scale).astype(x.dtype)


def controls(cfg) -> dict:
    """name -> (context manager that puts the fault into oryx_tpu,
    OryxConfig the program runs with, further arguments of the
    comparison)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate, qwen2
    from oryx_tpu.ops import paged_kv
    from oryx_tpu.ops.pallas import paged_attention as ppa

    grouped_dot, moe = qwen2._grouped_dot, qwen2._moe
    write_pages = paged_kv.write_pages

    def int8_grouped(rows, kernels, groups, impl):
        return grouped_dot(_int8_rows(rows), kernels, groups, impl)

    def fp8_latent(cache_layer, new, *a, **kw):
        return write_pages(
            cache_layer, new.astype(jnp.float8_e4m3fn).astype(new.dtype),
            *a, **kw)

    def no_zero_term(c, x, router_kernel, experts, layer, impl="xla",
                     router_bias=None):
        y, routing = moe(c, x, router_kernel, experts, layer, impl,
                         router_bias)
        w, idx = qwen2.moe_route(c, x, router_kernel, router_bias)
        zero_w = jnp.sum(jnp.where(idx >= c.num_experts, w, 0.0), axis=-1)
        y = y.astype(jnp.float32) - zero_w[:, None] * x.astype(jnp.float32)
        return y.astype(x.dtype), routing

    def other_slot(fn):
        def walk(q, pages, tables, lengths, **kw):
            return fn(q, pages, jnp.roll(tables, 1, axis=0), lengths, **kw)
        return walk

    unscaled = dataclasses.replace(cfg, llm=dataclasses.replace(
        cfg.llm, mla_scale_kv_lora=False))
    patch = mock.patch.object

    # What is dispatched is not what is compared: the decode chunk
    # jitted a second time and traced under the fault above.
    wrong_decode = jax.jit(
        generate.paged_decode_chunk.__wrapped__,
        static_argnames=("cfg", "chunk", "eos", "attn_impl",
                         "compute_dtype"))

    def other_program(*args, **kw):
        with wrong_pages():
            return wrong_decode(*args, **kw)

    def wrong_pages():  # the Pallas walk and its XLA twin alike
        stack = contextlib.ExitStack()
        for module in (ppa, paged_kv):
            stack.enter_context(patch(
                module, "latent_decode_attention",
                other_slot(module.latent_decode_attention)))
        return stack

    return {
        "int8 activations in the grouped products":
            (lambda: patch(qwen2, "_grouped_dot", int8_grouped), cfg, {}),
        "the latent stored in fp8":
            (lambda: patch(paged_kv, "write_pages", fp8_latent), cfg, {}),
        "the zero-compute term left out":
            (lambda: patch(qwen2, "_moe", no_zero_term), cfg, {}),
        "sqrt(hidden / kv_lora_rank) left out":
            (contextlib.nullcontext, unscaled, {}),
        "a decode that walks another slot's pages": (wrong_pages, cfg, {}),
        "a dispatched program that is not the compared one":
            (contextlib.nullcontext, cfg, {"timed": other_program}),
    }


def run_all(params, cfg, seed: int, only=None, **check_kw) -> dict:
    """{"as served": reading, <control>: reading, ...}; every jitted
    program is traced anew under each fault. only: that control alone
    beside the program as served."""
    import jax

    from benchmark import correctness_longcat

    out = {"as served": correctness_longcat.logit_check(
        params["llm"], cfg, seed, **check_kw)}
    for name, (fault, p_cfg, more) in controls(cfg).items():
        if only not in (None, name):
            continue
        jax.clear_caches()
        with fault():
            out[name] = correctness_longcat.logit_check(
                params["llm"], cfg, seed,
                program=(params["llm"], p_cfg), **more, **check_kw)
    jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    from benchmark import program, run
    from benchmark.runners import serve_latent_child as child

    conf = run.resolve(run.load_json(
        ROOT, "benchmark", "configs", "longcat-flash-ep32-serve.json"),
        bool(args.rehearse))
    cfg = child.build_config(conf)
    program.configure_cache()
    program.device_record(1, rehearse=bool(args.rehearse))
    lay = conf["layout"]
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    readings = run_all(
        params, cfg, args.seed, only=args.only, page_size=lay["page_size"],
        prefill_chunk=lay["prefill_chunk"], decode_chunk=lay["decode_chunk"],
        **conf.get("logit_check", {}))
    keep = ("ok", "passed", "forced_rms_rel", "forced_max_rel",
            "forced_rms_rel_by_phase", "expert_rms_rel", "routing_agree",
            "timed_token_agree", "logit_rms_diff", "ref_rms")
    for name, r in readings.items():
        print(json.dumps({"program": name, **{k: r[k] for k in keep}}),
              flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls.longcat.json"),
              "w") as f:
        json.dump({"seed": args.seed, "readings": readings}, f)
    wrong = [n for n, r in readings.items()
             if r["ok"] != (n == "as served")]
    print(json.dumps({"ok": not wrong, "wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
