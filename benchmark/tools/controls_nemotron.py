"""The controls of the agent-reasoning cell's comparison: programs that
MUST fail `correctness_nemotron.logit_check`, each a one-line fault or a
step down in precision put into the served path while the reference
stays as it is.

    python benchmark/tools/controls_nemotron.py [--seed N] [--rehearse 1]
        [--only <control>[,<control>...]]

runs the comparison on the program as it is (must pass) and on every
control (must fail), at the configuration's published widths on the
chip (or the tiny preset on the CPU with --rehearse 1), and writes the
readings to chiprun_out/controls.nemotron.seed<N>.json. No engine runs
here: the prompts are seeded ones and the "served" streams are the
decode program's as the engine dispatches it (the cell itself compares
what its window served; correctness_nemotron's docstring). Run once by
the builder; PERF.md section 6 (PR 60) holds the readings the limits
were set from.
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
    (llm params, OryxConfig) the program runs with, or a function that
    makes the pair when its turn comes)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import mamba2, qwen2
    from oryx_tpu.ops.pallas import ssd_step

    select = qwen2.moe_select
    chunked, step_xla, step_kernel = (
        mamba2.ssd_chunked, ssd_step.ssd_step_xla, ssd_step.ssd_step)
    # (reduce_precision, not a pair of converts: the chip's compiler
    # takes a float32 -> bfloat16 -> float32 round trip away.)
    bf16 = lambda a: jax.lax.reduce_precision(a, 8, 7)  # noqa: E731

    # S kept in bfloat16: rounded wherever a program leaves it.
    def chunked_bf16(*a, **kw):
        y, S = chunked(*a, **kw)
        return y, bf16(S)

    def step_xla_bf16(*a, **kw):
        y, S = step_xla(*a, **kw)
        return y, bf16(S)

    def step_kernel_bf16(a, dtx, bc, live, ssm_pl, li, G):
        y, ssm_pl = step_kernel(a, dtx, bc, live, ssm_pl, li, G)
        rows = jax.lax.dynamic_index_in_dim(ssm_pl, li, keepdims=False)
        return y, jax.lax.dynamic_update_index_in_dim(
            ssm_pl, bf16(rows), li, 0)

    @contextlib.contextmanager
    def bf16_state():
        with mock.patch.object(mamba2, "ssd_chunked", chunked_bf16), \
                mock.patch.object(ssd_step, "ssd_step_xla", step_xla_bf16), \
                mock.patch.object(ssd_step, "ssd_step", step_kernel_bf16):
            yield

    def top_21(cfg_, r, router_bias=None):
        """`qwen2.moe_select` with the LAST of a token's experts left
        out: its weight 0 and the others' over the sum of K - 1."""
        w, idx = select(
            dataclasses.replace(cfg_, routed_scaling_factor=1.0,
                                norm_topk_prob=False), r, router_bias)
        w = w.at[..., -1].set(0.0)
        w = w / jnp.sum(w, axis=-1, keepdims=True)
        return w * cfg_.routed_scaling_factor, idx

    def unscaled(cfg_, r, router_bias=None):
        w, idx = select(cfg_, r, router_bias)
        return w / cfg_.routed_scaling_factor, idx

    def no_shared(cfg_, x, p):
        return jnp.zeros_like(x)

    def gate_after_norm(cfg_, lp, y, z):
        """The group norm on y alone, the gate behind it."""
        G = cfg_.mamba_n_groups
        g = y.astype(jnp.float32).reshape(*y.shape[:-1], G, -1)
        g = g * jax.lax.rsqrt(
            jnp.mean(g * g, axis=-1, keepdims=True) + cfg_.rms_norm_eps)
        out = g.reshape(y.shape) * lp["norm"]["weight"].astype(jnp.float32)
        return (out * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)

    def no_w_up():
        """`W_up` skipped: the summed latent laid into the first columns
        of the hidden state as it is."""
        up = params["llm"]["layers"]["latent"]["up"]["kernel"]
        eye = jnp.broadcast_to(
            jnp.eye(up.shape[1], up.shape[2], dtype=up.dtype), up.shape)
        layers = dict(params["llm"]["layers"],
                      latent=dict(params["llm"]["layers"]["latent"],
                                  up={"kernel": eye}))
        return dict(params["llm"], layers=layers), cfg

    def with_rope():
        return params["llm"], dataclasses.replace(
            cfg, llm=dataclasses.replace(cfg.llm, use_rope=True))

    patch = mock.patch.object
    same = (params["llm"], cfg)
    return {
        "a bfloat16 state": (bf16_state, same),
        "top 21 for top 22":
            (lambda: patch(qwen2, "moe_select", top_21), same),
        "the shared expert left out":
            (lambda: patch(qwen2, "_expert_mlp", no_shared), same),
        "W_up skipped": (contextlib.nullcontext, no_w_up),
        "the scale 5 left out":
            (lambda: patch(qwen2, "moe_select", unscaled), same),
        "the gate applied after the group norm":
            (lambda: patch(mamba2, "gated_norm", gate_after_norm), same),
        "a position term switched on": (contextlib.nullcontext, with_rope),
    }


def run_all(params, cfg, seed: int, only=None, **check_kw) -> dict:
    """{"as served": reading, <control>: reading, ...}; every jitted
    program is traced anew under each fault. only: those controls
    alone (comma-separated) beside the program as served."""
    import jax

    from benchmark import correctness_nemotron

    out = {"as served": correctness_nemotron.logit_check(
        params["llm"], cfg, seed, **check_kw)}
    for name, (fault, program) in controls(params, cfg).items():
        if only is not None and name not in only.split(","):
            continue
        jax.clear_caches()
        with fault():
            pair = program() if callable(program) else program
            out[name] = correctness_nemotron.logit_check(
                params["llm"], cfg, seed, program=pair, **check_kw)
        del pair
    jax.clear_caches()
    return out


KEEP = ("ok", "passed", "head_rms_rel", "head_max_rel", "tail_rms_rel",
        "tail_max_rel", "state_bf16_share", "router_error", "routing_agree",
        "expert_rms_rel", "served_ref_agree", "served_twin_agree",
        "served_ref_agree_swapped", "rms_rel_by_stream")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    from benchmark import program, run
    from benchmark.runners import serve_reasoning_moe_holder as child

    conf = run.resolve(run.load_json(
        ROOT, "benchmark", "configs", "nemotron-3-super-ep4-serve.json"),
        bool(args.rehearse))
    cfg = child.build_config(conf)
    program.configure_cache()
    program.device_record(1, rehearse=bool(args.rehearse))
    lay = conf["layout"]
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    about = conf["logit_check"]  # `sample` is the cell's alone
    check_kw = {k: about[k] for k in
                ("prompt_tokens", "decode_chunks", "head", "tail")}
    readings = run_all(
        params, cfg, args.seed, only=args.only,
        sizes=child.ref_sizes(conf, cfg), page_size=lay["page_size"],
        prefill_chunk=lay["prefill_chunk"], decode_chunk=lay["decode_chunk"],
        max_ctx=lay["max_ctx"], **check_kw)
    for name, r in readings.items():
        print(json.dumps({"program": name, **{k: r[k] for k in KEEP}}),
              flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(
            ROOT, "chiprun_out",
            f"controls.nemotron.seed{args.seed}.json"), "w") as f:
        json.dump({"seed": args.seed, "readings": readings}, f)
    wrong = [n for n, r in readings.items()
             if r["ok"] != (n == "as served")]
    print(json.dumps({"ok": not wrong, "wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
