"""The controls of the agent-session cell's comparison: programs that
MUST fail `correctness_lfm2.logit_check`, each a one-line fault or a
step down in precision put into the served path while the reference
stays as it is.

    python benchmark/tools/controls_lfm2.py [--seed N] [--rehearse 1]
        [--only <control>[,<control>...]]

runs the comparison on the program as it is (must pass) and on every
control (must fail), at the configuration's published widths on the
chip (or the tiny preset on the CPU with --rehearse 1, where the
precision controls only read above the program), and writes the
readings to chiprun_out/controls.lfm2.seed<N>.json. No engine runs here: the
prompts are seeded ones with hits of the configuration's
`cached_tokens`, and the "served" streams are the decode program's as
the engine dispatches it (the cell itself compares what its window
served; correctness_lfm2's docstring). Run once by the builder; PERF.md
section 6 (PR 56) holds the readings the limits were set from.
"""

from __future__ import annotations

import argparse
import contextlib
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
    makes the pair when its turn comes (a second set of weights lives
    for its own control alone), further arguments of the
    comparison)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import generate, qwen2, short_conv
    from oryx_tpu.ops import paged_kv

    llm = cfg.llm
    rms_norm, page_edges = qwen2.rms_norm, short_conv.page_edges
    select = qwen2.moe_select
    conv = paged_kv.SLOT_PLANES[0]

    def page_before(kv, page, slot):
        return paged_kv.handover_state(kv, page - 1, slot)

    def zero_state(kv, page, slot):
        return {**kv, conv: kv[conv].at[:, slot].set(0)}

    def early_edges(*a, **kw):
        pages, n = page_edges(*a, **kw)
        return pages, jnp.maximum(n - 1, 0)

    def biased_weights(cfg_, r, router_bias=None):
        """`qwen2.moe_select` with the bias left in the weights."""
        p = jax.nn.sigmoid(r) + router_bias.astype(jnp.float32)
        w, idx = jax.lax.top_k(p, cfg_.num_experts_per_tok)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg_.norm_topk_eps)
        return w, idx.astype(jnp.int32)

    def unbiased_choice(cfg_, r, router_bias=None):
        return select(cfg_, r, None)

    def one_in_five_wrong(cfg_, r, router_bias=None):
        """`qwen2.moe_select` with the last of a token's experts
        replaced by the next best, at one token in five (told by the
        token's own first logit, so wherever the token lies in a chunk
        or a step): a program that picks wrongly now and then."""
        K = cfg_.num_experts_per_tok
        p = jax.nn.sigmoid(r)
        _, idx = jax.lax.top_k(p + router_bias.astype(jnp.float32), K + 1)
        wrong = (jnp.abs(r[..., 0]) * 4096).astype(jnp.int32) % 5 == 0
        idx = idx.at[..., K - 1].set(
            jnp.where(wrong, idx[..., K], idx[..., K - 1]))[..., :K]
        w = jnp.take_along_axis(p, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + cfg_.norm_topk_eps)
        return w, idx.astype(jnp.int32)

    def no_qk_norm(x, w, eps):
        heads = x.ndim == 4 and w.shape[-1] == llm.head_dim
        return x if heads else rms_norm(x, w, eps)

    def bf16_router(x, kernel):
        return (x.astype(jnp.bfloat16) @ kernel.astype(jnp.bfloat16)).astype(
            jnp.float32)

    # What is dispatched is not what is compared: the decode chunk
    # jitted a second time and traced without q/k norm.
    wrong_decode = jax.jit(
        generate.paged_decode_chunk.__wrapped__,
        static_argnames=("cfg", "chunk", "eos", "attn_impl",
                         "compute_dtype"))

    def other_program(*args, **kw):
        with mock.patch.object(qwen2, "rms_norm", no_qk_norm):
            return wrong_decode(*args, **kw)

    def with_leaves(edit):
        return jax.tree_util.tree_map_with_path(edit, params["llm"])

    def named(path, *words):
        return all(f"'{w}'" in jax.tree_util.keystr(path) for w in words)

    # Every weight but the experts' own kernels: a second copy of those
    # (9.7 GB) does not fit beside the first, and the reference needs
    # the first. The operators, the dense FFNs, the routers' inputs and
    # the embedding (so the head) are a twelfth of the bytes and all of
    # every token's path.
    def fp8():
        return with_leaves(
            lambda path, a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.dtype == jnp.bfloat16 and a.ndim >= 2
            and not named(path, "experts") else a), cfg

    # The experts' own kernels in the precision below: the first expert
    # layer's alone, for the `experts` clause alone (`expert_error`
    # reads that layer and no other), so no second copy of all experts.
    def fp8_first_experts():
        layers = params["llm"]["layers"]
        return {"layers": {
            "router": layers["router"],
            "experts": jax.tree_util.tree_map(
                lambda a: a[:1].astype(jnp.float8_e4m3fn).astype(a.dtype),
                layers["experts"])}}, cfg

    def reversed_taps():
        return with_leaves(
            lambda path, a: a[:, ::-1] if named(path, "conv", "mixer")
            and a.ndim == 3 else a), cfg

    patch = mock.patch.object
    same = (params["llm"], cfg)
    return {
        "the snapshot of the page before":
            (contextlib.nullcontext, same, {"handover": page_before}),
        "a zero state at a hit":
            (contextlib.nullcontext, same, {"handover": zero_state}),
        "the snapshot taken one token early":
            (lambda: patch(short_conv, "page_edges", early_edges), same, {}),
        "the bias in the weights":
            (lambda: patch(qwen2, "moe_select", biased_weights), same, {}),
        "the selection without its bias":
            (lambda: patch(qwen2, "moe_select", unbiased_choice), same, {}),
        "one selection in five replaced by the next best":
            (lambda: patch(qwen2, "moe_select", one_in_five_wrong), same, {}),
        "q/k norm left out":
            (lambda: patch(qwen2, "rms_norm", no_qk_norm), same, {}),
        "the conv taps in reverse order":
            (contextlib.nullcontext, reversed_taps, {}),
        "a bfloat16 router":
            (lambda: patch(qwen2, "router_logits", bf16_router), same, {}),
        "the weights but the experts' rounded to fp8 (e4m3)":
            (contextlib.nullcontext, fp8, {}),
        "the first expert layer's kernels rounded to fp8 (e4m3)":
            (contextlib.nullcontext, same,
             {"expert_program": fp8_first_experts}),
        "a dispatched program that is not the compared one":
            (contextlib.nullcontext, same, {"dispatched": other_program}),
    }


def run_all(params, cfg, seed: int, only=None, **check_kw) -> dict:
    """{"as served": reading, <control>: reading, ...}; every jitted
    program is traced anew under each fault. only: those controls
    alone (comma-separated) beside the program as served."""
    import jax

    from benchmark import correctness_lfm2

    out = {"as served": correctness_lfm2.logit_check(
        params["llm"], cfg, seed, **check_kw)}
    for name, (fault, program, more) in controls(params, cfg).items():
        if only is not None and name not in only.split(","):
            continue
        jax.clear_caches()
        with fault():
            out[name] = correctness_lfm2.logit_check(
                params["llm"], cfg, seed,
                program=program() if callable(program) else program,
                **more, **check_kw)
    jax.clear_caches()
    return out


KEEP = ("ok", "passed", "head_rms_rel", "head_max_rel", "tail_rms_rel",
        "tail_max_rel", "handover_rms_rel", "handover_max_rel",
        "router_error", "routing_agree", "expert_rms_rel",
        "served_ref_agree", "served_twin_agree", "served_ref_agree_swapped",
        "rms_rel_by_stream")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483999)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--only", default=None)
    args = ap.parse_args(argv)

    from benchmark import program, run
    from benchmark.runners import serve_agent_holder as child

    conf = run.resolve(run.load_json(
        ROOT, "benchmark", "configs", "lfm2-24b-a2b-serve.json"),
        bool(args.rehearse))
    cfg = child.build_config(conf)
    program.configure_cache()
    program.device_record(1, rehearse=bool(args.rehearse))
    lay = conf["layout"]
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    about = conf["logit_check"]  # `sample` is the cell's alone
    check_kw = {k: about[k] for k in
                ("prompt_tokens", "cached_tokens", "decode_chunks", "head",
                 "tail")}
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
            ROOT, "chiprun_out", f"controls.lfm2.seed{args.seed}.json"),
            "w") as f:
        json.dump({"seed": args.seed, "readings": readings}, f)
    wrong = [n for n, r in readings.items()
             if r["ok"] != (n == "as served")]
    print(json.dumps({"ok": not wrong, "wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
