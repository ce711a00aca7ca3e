"""The controls of the learned-sparse-attention cell's comparison:
programs that MUST fail `correctness_glm5.logit_check`, each a one-line
fault or a step down in precision put into the served path while the
reference stays as it is, and each named with the clause that has to
refuse it.

    python benchmark/tools/controls_glm5.py [--seed N] [--rehearse 1]
        [--only <control>] [--prompt-tokens 6000,300]

runs the comparison on the program as it is (must pass) and on every
control (must fail, by the clause named for it), at the configuration's
published widths on the chip (or the tiny preset on the CPU with
--rehearse 1, where the precision controls only read above the
program), and writes the readings to chiprun_out/controls.glm5.json. No
engine runs here: the prompts are seeded ones and the "served" streams
are the decode program's as the engine dispatches it. Run once by the
builder; PERF.md section 6 (PR 49) holds the readings the limits were
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

# Positions whose index keys the stale-page control replaces: one page
# of 64 behind the first thousand, so only a context past it shows it.
STALE = (1024, 1088)


def controls(cfg, max_ctx: int) -> dict:
    """name -> (context manager that puts the fault into oryx_tpu,
    OryxConfig the program runs with, (llm params -> llm params) or
    None, the clause that must refuse it)."""
    import jax
    import jax.numpy as jnp

    from oryx_tpu.models import qwen2
    from oryx_tpu.ops import paged_kv
    from oryx_tpu.ops.pallas import paged_attention as ppa

    patch = mock.patch.object
    llm = cfg.llm
    Di = llm.index_head_dim
    index_inputs = qwen2._index_inputs
    write_pages, latent_block = paged_kv.write_pages, qwen2._latent_block
    moe_select = qwen2.moe_select
    topk_mask, topk_indices = paged_kv.topk_mask, paged_kv.topk_indices

    def with_llm(**kw):
        return dataclasses.replace(cfg, llm=dataclasses.replace(llm, **kw))

    def many(*patches):
        def enter():
            stack = contextlib.ExitStack()
            for p in patches:
                stack.enter_context(p())
            return stack
        return enter

    # The indexer's scores in another form: `paged_kv.index_tile` is the
    # one copy of their arithmetic in XLA ops (the prefill's and the
    # decode twin's), and the decode kernel gives way to that twin.
    def scores_as(fn):
        def tile(q, w, keys):
            s = jnp.einsum("bthd,bkd->bthk", q, keys,
                           preferred_element_type=jnp.float32)
            return fn(s, w.astype(jnp.float32))

        return many(
            lambda: patch(paged_kv, "index_tile", tile),
            lambda: patch(ppa, "index_scores",
                          lambda *a, interpret=None: paged_kv.index_scores(*a)))

    no_relu = scores_as(lambda s, w: jnp.einsum("bthk,bth->btk", s, w))

    def inputs_as(change):
        def inputs(cfg_, a, cq, p, cos, sin):
            return change(index_inputs(cfg_, a, cq, p, cos, sin),
                          (cfg_, a, cq, p, cos, sin))
        return lambda: patch(qwen2, "_index_inputs", inputs)

    def unroped_keys(out, args):
        cfg_, a, cq, p, cos, sin = args
        _, ki, _ = index_inputs(cfg_, a, cq, p, jnp.ones_like(cos),
                                jnp.zeros_like(sin))
        return out[0], ki, out[2]

    def most_recent(scores):
        u = jnp.arange(scores.shape[-1], dtype=jnp.float32)
        return jnp.where(jnp.isfinite(scores), u, -jnp.inf)

    recent = many(
        lambda: patch(paged_kv, "topk_mask",
                      lambda s, k: topk_mask(most_recent(s), k)),
        lambda: patch(paged_kv, "topk_indices",
                      lambda s, k: topk_indices(most_recent(s), k)))

    def index_rows(change):
        """`write_pages` with the index plane's new rows changed
        (`change(rows [B, T, 1, Di], their slots [B, T])`)."""
        def write(cache_layer, new, tables, start, **kw):
            if new.shape[-1] == Di and new.shape[-2] == 1:
                slots = start[:, None] + jnp.arange(new.shape[1])[None]
                new = change(new, slots)
            return write_pages(cache_layer, new, tables, start, **kw)
        return lambda: patch(paged_kv, "write_pages", write)

    def stale(new, slots):  # another context's keys on one page
        old = (slots >= STALE[0]) & (slots < STALE[1])
        return jnp.where(old[..., None, None], jnp.roll(new, 7, axis=1), new)

    def fp8(new, slots):
        return new.astype(jnp.float8_e4m3fn).astype(new.dtype)

    def biased_weights(cfg_, r, router_bias=None):
        w, idx = moe_select(cfg_, r, router_bias)
        if router_bias is None:
            return w, idx
        p = jax.nn.sigmoid(r) + router_bias
        w = jnp.take_along_axis(p, idx, axis=-1)
        return (cfg_.routed_scaling_factor * w
                / jnp.sum(w, axis=-1, keepdims=True)), idx

    def no_dense_ffn(cfg_, h, lp, cos, sin, *, experts, **kw):
        if experts is not None:
            return latent_block(cfg_, h, lp, cos, sin, experts=experts, **kw)
        zero = dict(lp, down_proj={"kernel": 0 * lp["down_proj"]["kernel"]})
        return latent_block(cfg_, h, zero, cos, sin, experts=None, **kw)

    def fp8_weights(params):
        # Every matrix but the float32 router and the held experts'
        # stacks: 4.8 GB that do not fit the chip a second time, and
        # that clause (E) reads alone.
        return jax.tree_util.tree_map_with_path(
            lambda path, a: a if a.ndim < 2 or any(
                k in str(path) for k in ("router", "experts"))
            else a.astype(jnp.float8_e4m3fn).astype(a.dtype), params)

    none = contextlib.nullcontext
    return {
        "no selection (dense attention)":
            (none, with_llm(index_topk=max_ctx), None, "selection"),
        "the top 1,024 (half the keys)":
            (none, with_llm(index_topk=llm.index_topk // 2), None,
             "selection"),
        "the most RECENT keys":
            (recent, cfg, None, "selection"),
        "relu left out of the index scores":
            (no_relu, cfg, None, "selection"),
        "the heads' weights left out":
            (inputs_as(lambda out, _: (out[0], out[1],
                                       jnp.ones_like(out[2]))),
             cfg, None, "selection"),
        "no RoPE on the index keys":
            (inputs_as(unroped_keys), cfg, None, "selection"),
        "a page of index keys stale after a prefix hit":
            (index_rows(stale), cfg, None, "selection"),
        "index keys in fp8":
            (index_rows(fp8), cfg, None, "selection"),
        "softmax for sigmoid":
            (none, with_llm(router_scoring="softmax"), None, "experts"),
        "the bias in the weights":
            (lambda: patch(qwen2, "moe_select", biased_weights), cfg, None,
             "experts"),
        "the scaling factor left out":
            (none, with_llm(routed_scaling_factor=1.0), None, "experts"),
        "the dense layer's FFN left out":
            (lambda: patch(qwen2, "_latent_block", no_dense_ffn), cfg, None,
             "forced"),
        "fp8 (e4m3) weights outside the held experts":
            (none, cfg, fp8_weights, "forced"),
    }


def run_all(params, cfg, seed: int, only=None, **check_kw) -> dict:
    """{"as served": reading, <control>: reading, ...}; every jitted
    program is traced anew under each fault. only: that control alone
    beside the program as served."""
    import jax

    from benchmark import correctness_glm5

    keep = ("ok", "passed", "clause", "forced_rms_rel", "forced_max_rel",
            "forced_rms_rel_by_prompt", "select_gap", "select_agree",
            "select_miscounted_rows", "free_rms_rel", "routing_agree",
            "expert_rms_rel", "served_twin_agree", "table_positions")

    def said(name, r):  # as it comes: a call may be cut before the end
        print(json.dumps({"program": name,
                          **{k: r[k] for k in keep if k in r}}), flush=True)
        return r

    out = {"as served": said("as served", correctness_glm5.logit_check(
        params["llm"], cfg, seed, **check_kw))}
    table = controls(cfg, check_kw["max_ctx"])
    for name, (fault, p_cfg, change, clause) in table.items():
        if only not in (None, name):
            continue
        jax.clear_caches()
        p_params = params["llm"] if change is None else change(params["llm"])
        with fault():
            out[name] = said(name, dict(correctness_glm5.logit_check(
                params["llm"], cfg, seed, program=(p_params, p_cfg),
                **check_kw), clause=clause))
        del p_params
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
    from benchmark.runners import serve_longctx_child as child

    conf = run.resolve(run.load_json(
        ROOT, "benchmark", "configs", "glm-5-ep16-serve.json"),
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
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls.glm5.json"),
              "w") as f:
        json.dump({"seed": args.seed, "readings": readings}, f)
    # A control has to fail, and by the clause named for it.
    wrong = [n for n, r in readings.items()
             if (r["ok"] if n != "as served" else not r["ok"])
             or ("clause" in r and r["passed"][r["clause"]])]
    print(json.dumps({"ok": not wrong, "wrong": wrong}))
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
