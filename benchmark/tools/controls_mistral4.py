"""The controls of the single-latent-block cell's comparison: programs
that MUST fail `correctness_mistral4.logit_check`, each a one-line
fault or a step down in precision put into the served path while the
reference stays as it is.

    python benchmark/tools/controls_mistral4.py [--seed N] [--rehearse 1]
        [--only <control>] [--prompt-tokens 9000,700,40]

runs the comparison on the program as it is (must pass) and on every
control (must fail), at the configuration's published widths on the
chip (or the tiny preset on the CPU with --rehearse 1), and writes the
readings to chiprun_out/controls.mistral4.json. No engine runs here:
the prompts are seeded ones and the "served" streams are the decode
program's as the engine dispatches it (the cell itself compares what
its window served; correctness_mistral4's docstring). Run once by the
builder; PERF.md section 6 (PR 33) holds the readings the limits were
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


def controls(cfg) -> dict:
    """name -> (context manager that puts the fault into oryx_tpu,
    OryxConfig the program runs with, further arguments of the
    comparison)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu import config as cfg_lib
    from oryx_tpu.models import generate, qwen2
    from oryx_tpu.ops import paged_kv
    from oryx_tpu.ops.pallas import paged_attention as ppa

    from benchmark.tools.controls_longcat import _int8_rows

    grouped_dot, moe = qwen2._grouped_dot, qwen2._moe
    write_pages = paged_kv.write_pages

    def int8_grouped(rows, kernels, groups, impl):
        return grouped_dot(_int8_rows(rows), kernels, groups, impl)

    def fp8_latent(cache_layer, new, *a, **kw):
        return write_pages(
            cache_layer, new.astype(jnp.float8_e4m3fn).astype(new.dtype),
            *a, **kw)

    def no_shared(*args, shared=None, **kw):
        return moe(*args, shared=None, **kw)

    def other_slot(fn):
        def walk(q, pages, tables, lengths, **kw):
            return fn(q, pages, jnp.roll(tables, 1, axis=0), lengths, **kw)
        return walk

    def wrong_pages():  # the Pallas walk and its XLA twin alike
        stack = contextlib.ExitStack()
        for module in (ppa, paged_kv):
            stack.enter_context(mock.patch.object(
                module, "latent_decode_attention",
                other_slot(module.latent_decode_attention)))
        return stack

    # What is dispatched is not what is compared: the decode chunk
    # jitted a second time and traced under the fault above.
    wrong_decode = jax.jit(
        generate.paged_decode_chunk.__wrapped__,
        static_argnames=("cfg", "chunk", "eos", "attn_impl",
                         "compute_dtype"))

    def other_program(*args, **kw):
        with wrong_pages():
            return wrong_decode(*args, **kw)

    # The three conventions live in LLMConfig alone, so a program that
    # lacks one is the program run with a config that lacks it.
    class NoMscale(cfg_lib.LLMConfig):
        @property
        def softmax_scale(self):
            return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def with_llm(llm):
        return dataclasses.replace(cfg, llm=llm)

    fields = {f.name: getattr(cfg.llm, f.name)
              for f in dataclasses.fields(cfg.llm)}
    patch = mock.patch.object
    return {
        "the shared expert left out":
            (lambda: patch(qwen2, "_moe", no_shared), cfg, {}),
        "YaRN replaced by plain RoPE":
            (lambda: patch(qwen2, "yarn_frequencies",
                           lambda d, theta, **kw: None), cfg, {}),
        "m * m left out of the softmax scale":
            (contextlib.nullcontext, with_llm(NoMscale(**fields)), {}),
        "the query's scale by position left out":
            (contextlib.nullcontext, with_llm(dataclasses.replace(
                cfg.llm, llama4_scaling_beta=0.0)), {}),
        "the latent stored in fp8":
            (lambda: patch(paged_kv, "write_pages", fp8_latent), cfg, {}),
        "int8 activations in the grouped products":
            (lambda: patch(qwen2, "_grouped_dot", int8_grouped), cfg, {}),
        "a decode that walks another slot's pages": (wrong_pages, cfg, {}),
        "a dispatched program that is not the compared one":
            (contextlib.nullcontext, cfg, {"dispatched": other_program}),
    }


def run_all(params, cfg, seed: int, only=None, **check_kw) -> dict:
    """{"as served": reading, <control>: reading, ...}; every jitted
    program is traced anew under each fault. only: that control alone
    beside the program as served."""
    import jax

    from benchmark import correctness_mistral4

    out = {"as served": correctness_mistral4.logit_check(
        params["llm"], cfg, seed, **check_kw)}
    for name, (fault, p_cfg, more) in controls(cfg).items():
        if only not in (None, name):
            continue
        jax.clear_caches()
        with fault():
            out[name] = correctness_mistral4.logit_check(
                params["llm"], cfg, seed,
                program=(params["llm"], p_cfg), **more, **check_kw)
    jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--only", default=None)
    ap.add_argument("--prompt-tokens", default=None,
                    help="comma-separated, instead of the configuration's")
    args = ap.parse_args(argv)

    from benchmark import program, run
    from benchmark.runners import serve_docqa_child as child

    conf = run.resolve(run.load_json(
        ROOT, "benchmark", "configs", "mistral-small-4-ep4-serve.json"),
        bool(args.rehearse))
    cfg = child.build_config(conf)
    program.configure_cache()
    program.device_record(1, rehearse=bool(args.rehearse))
    lay = conf["layout"]
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    about = conf["logit_check"]  # `sample` is the cell's alone
    check_kw = {k: about[k] for k in ("prompt_tokens", "decode_chunks")}
    if args.prompt_tokens:
        check_kw["prompt_tokens"] = tuple(
            int(n) for n in args.prompt_tokens.split(","))
    readings = run_all(
        params, cfg, args.seed, only=args.only, page_size=lay["page_size"],
        prefill_chunk=lay["prefill_chunk"], decode_chunk=lay["decode_chunk"],
        max_ctx=lay["max_ctx"], **check_kw)
    keep = ("ok", "passed", "forced_rms_rel", "forced_max_rel",
            "forced_rms_rel_by_phase", "forced_rms_rel_by_prompt",
            "expert_rms_rel", "routing_agree", "served_ref_agree",
            "served_twin_agree", "served_ref_agree_swapped",
            "logit_rms_diff", "ref_rms", "table_positions")
    for name, r in readings.items():
        print(json.dumps({"program": name, **{k: r[k] for k in keep}}),
              flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls.mistral4.json"),
              "w") as f:
        json.dump({"seed": args.seed, "readings": readings}, f)
    wrong = [n for n, r in readings.items()
             if r["ok"] != (n == "as served")]
    print(json.dumps({"ok": not wrong, "wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
