"""The process that holds the chip in the agent-reasoning cell of a
Mamba-2 hybrid with latent experts (NVIDIA-Nemotron-3-Super-120B-A12B).

    configuration -> seeded weights (no vision tower) -> OryxInference
    -> api_server.build_server(engine="continuous") -> serve_forever

and then the same one-line commands on stdin and JSON events on stdout
as runners/serve_reasoning_child.py, whose shape this is: between `arm`
and `disarm` every request handed to the engine is kept with its handle
(serve_docqa_child.Served); on `stop` the server is closed and its pool
given back, a sample of the requests the window FINISHED is taken
(`sample_served`) and the tokens the engine streamed for them go to
correctness_nemotron.logit_check with their prompts; the `logit_check`
event follows `stop`, before `stopped`. Nothing of the comparison is
inside `setup_s`. What differs from serve_reasoning_child: the
configuration keys, the comparison, and the tokenizer
(serve_latent_child.NoStopPrefixTokenizer: no emitted id is the
template's stop, so every request runs to its `max_tokens`). A program
that lacks the configuration's preset (the parent commit) leaves at
once, before it touches the device.

NOT named `*_child.py`: the accepted
tests/benchmark/test_bench_no_process_left.py pins the number of those
at seven (serve_agent.py's docstring);
tests/benchmark/test_bench_rehearsal_reasoning_moe.py holds this one to
the same rules.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.runners import lifeline  # noqa: E402
from benchmark.runners.lifeline import say  # noqa: E402
from benchmark.runners.serve_docqa_child import _TOKEN, Served  # noqa: E402
from benchmark.runners.serve_latent_child import (  # noqa: E402
    NoStopPrefixTokenizer,
)

T_START = time.monotonic()
TINY = "nemotron3_tiny"  # the rehearsal: no width holds

# Configuration-file key (the source's own name) -> the program's, for
# what program.check_widths does not know: a file whose layer order,
# mixer or expert geometry the program would not run is refused.
_KEYS = {
    "num_hidden_layers": "num_layers",
    "mamba_num_heads": "mamba_num_heads",
    "mamba_head_dim": "mamba_head_dim",
    "n_groups": "mamba_n_groups",
    "ssm_state_size": "mamba_d_state",
    "conv_kernel": "mamba_d_conv",
    "chunk_size": "mamba_chunk_size",
    "use_conv_bias": "mamba_conv_bias",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "moe_intermediate_size": "moe_intermediate_size",
    "moe_latent_size": "moe_latent_size",
    "moe_shared_expert_intermediate_size":
        "moe_shared_expert_intermediate_size",
    "n_shared_experts": "n_shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob",
    "tie_word_embeddings": "tie_word_embeddings",
    "layer_norm_epsilon": "rms_norm_eps",
    "max_position_embeddings": "max_position_embeddings",
    "attention_bias": "attention_bias",
}


def build_config(conf: dict):
    """The named preset with the file's layout, through program.py's own
    builder; then the file's pattern, mixer and expert keys against
    what the program will run."""
    from oryx_tpu import config as cfg_lib

    from benchmark import program

    lay = conf["layout"]
    if not hasattr(cfg_lib, lay["preset"]):
        raise SystemExit(
            f"config {conf.get('name')}: this program has no preset "
            f"{lay['preset']!r}"
        )
    tiny = lay["preset"] == TINY
    cfg = program.build_config(
        {k: v for k, v in conf.items() if k not in program._WIDTHS}
        if tiny else conf
    )
    if not tiny:
        llm = cfg.llm
        have = {key: getattr(llm, attr) for key, attr in _KEYS.items()}
        have["hybrid_override_pattern"] = llm.hybrid_override_pattern[
            :llm.num_layers]
        have["experts_first"], have["experts_held"] = llm.held
        have["mlp_hidden_act"] = llm.moe_activation
        for key, got in have.items():
            if key in conf and conf[key] != got:
                raise SystemExit(
                    f"config {conf.get('name')}: {key} {conf[key]} in the "
                    f"file, {got} in the program"
                )
    return cfg


def ref_sizes(conf: dict, cfg) -> dict:
    """What the plain reference reads: the configuration file's
    published keys (the tiny preset's own in the rehearsal, where the
    file's widths do not hold)."""
    from benchmark.reference import nemotron_h_ref

    if conf["layout"]["preset"] != TINY:
        return nemotron_h_ref.sizes_from_keys(conf)
    llm = cfg.llm
    return nemotron_h_ref.sizes_from_keys({
        "hybrid_override_pattern": llm.hybrid_override_pattern,
        "num_hidden_layers": llm.num_layers,
        "num_attention_heads": llm.num_heads,
        "num_key_value_heads": llm.num_kv_heads, "head_dim": llm.head_dim,
        "mamba_num_heads": llm.mamba_num_heads,
        "mamba_head_dim": llm.mamba_head_dim, "n_groups": llm.mamba_n_groups,
        "ssm_state_size": llm.mamba_d_state,
        "layer_norm_epsilon": llm.rms_norm_eps,
        "n_routed_experts": llm.num_experts,
        "num_experts_per_tok": llm.num_experts_per_tok,
        "routed_scaling_factor": llm.routed_scaling_factor,
        "norm_topk_prob": llm.norm_topk_prob,
        "experts_first": llm.held[0], "experts_held": llm.held[1],
    })


def sample_served(served: Served, pipe, *, long_answer: int,
                  prefill_chunk: int, max_positions: int):
    """(prompts, streams, what each is): of the requests the window
    finished in full, each a request of its own, while their positions
    fit `max_positions`: the LONGEST answer of `long_answer` tokens or
    more, then the shortest request with a prompt of more than one
    prefill chunk (the state carried from chunk to chunk through the
    chunked scan, the last right-padded) and the shortest with a prompt
    of one. The lists are the same at every seed, so the sample is too,
    as far as the window gets."""
    done = []
    for request, max_new, h in served.items:
        if not h.done.is_set() or h.error is not None or h.cancelled \
                or h.finish_reason != "length":
            continue
        stream = [int(t) for t in _TOKEN.findall(h.reply or "")]
        if len(stream) != max_new:
            continue
        ids = pipe._prepare_request(request)[0]
        done.append(([int(t) for t in ids], stream))
    size = lambda r: len(r[0]) + len(r[1])  # noqa: E731
    kinds = (
        ("long_answer", lambda r: len(r[1]) >= long_answer,
         lambda r: -len(r[1])),
        ("multi_chunk", lambda r: len(r[0]) > prefill_chunk, size),
        ("one_chunk", lambda r: len(r[0]) <= prefill_chunk, size),
    )
    prompts, streams, what, left = [], [], [], max_positions
    for kind, fits, order in kinds:
        for r in sorted(done, key=order):
            if fits(r) and size(r) <= left:
                done.remove(r)
                prompts.append(r[0])
                streams.append(r[1])
                what.append({"kind": kind, "prompt_tokens": len(r[0]),
                             "served_tokens": len(r[1])})
                left -= size(r)
                break
    return prompts, streams, what


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)  # resolved json, inline
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--trace-dir", default="")
    lifeline.add_parent_pid(ap)
    args = ap.parse_args(argv)
    lifeline.tie_to_parent(args.parent_pid)  # before jax, before the chip
    conf = json.loads(args.config)

    from benchmark import program

    cfg = build_config(conf)  # leaves here where the preset is missing
    cache_dir = program.configure_cache()
    device = program.device_record(args.chips, rehearse=bool(args.rehearse))
    from oryx_tpu.ops import packing

    say(event="device", device=device, cache_dir=cache_dir,
        embed_buckets=list(packing.DEFAULT_BUCKETS),
        t=time.monotonic() - T_START)

    import jax

    from oryx_tpu.serve import api_server
    from oryx_tpu.serve.pipeline import OryxInference

    from benchmark import correctness_nemotron

    lay = conf["layout"]
    t0 = time.monotonic()
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    say(event="init", seconds=time.monotonic() - t0,
        params=int(sum(x.size for x in jax.tree.leaves(params))))

    pipe = OryxInference(NoStopPrefixTokenizer(cfg.llm.vocab_size), params,
                         cfg, template="plain")
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=lay["num_slots"],
        page_size=lay["page_size"], decode_chunk=lay["decode_chunk"],
        max_ctx=lay["max_ctx"], prefill_chunk=lay["prefill_chunk"],
        kv_dtype=lay.get("kv_dtype", "bf16"),
        prefix_cache=bool(lay.get("prefix_cache", False)),
        max_tokens_limit=lay["max_ctx"], max_queue=lay.get("max_queue", 256),
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    served = Served(srv.scheduler, sys.stdin)
    sys.stdin = served  # serve_commands reads its lines through it
    say(event="ready", port=srv.server_address[1],
        t=time.monotonic() - T_START)
    if not lifeline.serve_until_stopped(srv, args.trace_dir):
        return lifeline.ORPHANED
    if served.window_closed:
        # The engine's pool goes before the reference's float32 layers
        # and the twin's own pool come.
        srv.scheduler.kv_pages = None
        del srv
        gc.collect()
        about = conf["logit_check"]
        t0 = time.monotonic()
        prompts, streams, what = sample_served(
            served, pipe, prefill_chunk=lay["prefill_chunk"],
            **about["sample"])
        if prompts:
            check = correctness_nemotron.logit_check(
                params["llm"], cfg, args.seed, sizes=ref_sizes(conf, cfg),
                page_size=lay["page_size"],
                prefill_chunk=lay["prefill_chunk"],
                decode_chunk=lay["decode_chunk"], max_ctx=lay["max_ctx"],
                head=about["head"], tail=about["tail"],
                prompts=prompts, served=streams,
            )
        else:
            check = {"ok": False, "passed": {"sampled": False}}
        say(event="logit_check", seconds=time.monotonic() - t0,
            finished_in_window=sum(
                1 for _, _, h in served.items if h.done.is_set()),
            sample=what, **check)
    say(event="stopped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
