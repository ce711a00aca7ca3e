"""The process that holds the chip in the agent-session cell of a
gated-short-convolution hybrid with experts (LFM2-24B-A2B).

    configuration -> seeded weights (no vision tower) -> OryxInference
    -> api_server.build_server(engine="continuous") -> serve_forever

and then the same one-line commands on stdin and JSON events on stdout
as runners/serve_longctx_child.py, whose `Served` and command loop it
uses as they are (with serve_latent_child's tokenizer in the form whose
stop string no emitted id can match): between `arm` and `disarm` every
request handed to the engine is kept with its handle; on `stop` the
server is closed and its pool given back, a sample of the requests the
window FINISHED is taken (`sample_served`: the longest reply, a
session's first turn, the deepest turn over a long hit, each with the
tokens the engine took from its prefix cache) and goes to
correctness_lfm2.logit_check; the `logit_check` event follows `stop`,
before `stopped`. A program that lacks the configuration's preset (the
parent commit) leaves at once, before it touches the device.
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
TINY = "lfm2_tiny"  # the rehearsal: no width holds

# Configuration-file key (the source's own name) -> the program's, for
# what program.check_widths does not know: a file whose layer list,
# conv, expert or router geometry the program would not run is refused.
_KEYS = {
    "num_hidden_layers": "num_layers",
    "num_dense_layers": "dense_layers",
    "conv_L_cache": "conv_L_cache",
    "moe_intermediate_size": "moe_intermediate_size",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "use_expert_bias": "router_bias",
    "norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_position_embeddings",
}


def build_config(conf: dict):
    """The named preset with the file's layout, through program.py's own
    builder; then the file's layer list, conv, expert, router and RoPE
    keys against what the program will run."""
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
        have["layer_types"] = list(llm.layer_types[:llm.num_layers])
        have["rope_parameters.rope_theta"] = llm.rope_theta
        have["conv_bias"] = False  # the program's conv has none
        want = dict(conf)
        want["rope_parameters.rope_theta"] = conf["rope_parameters"][
            "rope_theta"]
        if conf["rope_parameters"]["rope_type"] != "default" or llm.yarn:
            raise SystemExit(
                f"config {conf.get('name')}: rope_type "
                f"{conf['rope_parameters']['rope_type']!r} in the file, "
                "unscaled RoPE in the program")
        for key, got in have.items():
            if key in want and want[key] != got:
                raise SystemExit(
                    f"config {conf.get('name')}: {key} {want[key]} in the "
                    f"file, {got} in the program"
                )
    return cfg


def ref_sizes(conf: dict, cfg) -> dict:
    """What the plain reference reads: the configuration file's
    published keys (the tiny preset's own in the rehearsal, where the
    file's widths do not hold)."""
    from benchmark.reference import lfm2_ref

    if conf["layout"]["preset"] != TINY:
        return lfm2_ref.sizes_from_keys(
            {**conf, **conf["rope_parameters"]})
    llm = cfg.llm
    return lfm2_ref.sizes_from_keys({
        "hidden_size": llm.hidden_size, "num_attention_heads": llm.num_heads,
        "num_hidden_layers": llm.num_layers,
        "layer_types": list(llm.layer_types),
        "num_dense_layers": llm.dense_layers,
        "conv_L_cache": llm.conv_L_cache,
        "num_key_value_heads": llm.num_kv_heads,
        "rope_theta": llm.rope_theta, "norm_eps": llm.rms_norm_eps,
        "num_experts_per_tok": llm.num_experts_per_tok,
        "norm_topk_prob": llm.norm_topk_prob,
        "routed_scaling_factor": llm.routed_scaling_factor,
        "use_expert_bias": llm.router_bias,
    })


def sample_served(served: Served, pipe, *, deep_hit: int,
                  max_positions: int, min_tokens: int):
    """(prompts, cached, streams, what each is): of the requests the
    window finished in full, while their positions fit `max_positions`:
    the DEEPEST turn (the longest hit) whose prefix hit passed
    `deep_hit` tokens, then the LONGEST reply, then the shortest first
    turn of a session with a hit (the shared system prompt alone).
    `cached` is what the engine's own ledger says it spliced for the
    request (`handle.debug["cost"]`). The lists are the same at every
    seed, so the sample is too, as far as the window gets."""
    done = []
    for request, max_new, h in served.items:
        if not h.done.is_set() or h.error is not None or h.cancelled \
                or h.finish_reason != "length":
            continue
        stream = [int(t) for t in _TOKEN.findall(h.reply or "")]
        if len(stream) != max_new or len(stream) < min_tokens:
            continue
        ids = [int(t) for t in pipe._prepare_request(request)[0]]
        hit = int((h.debug.get("cost") or {}).get("cached_tokens", 0))
        done.append({"ids": ids, "stream": stream, "cached": hit,
                     "first": not request.get("history")})
    size = lambda r: len(r["ids"]) + len(r["stream"])  # noqa: E731
    kinds = (
        ("deep_turn", lambda r: r["cached"] >= deep_hit,
         lambda r: -r["cached"]),
        ("long_reply", lambda r: True,
         lambda r: (-len(r["stream"]), len(r["ids"]))),
        ("first_turn", lambda r: r["first"] and r["cached"] > 0, size),
    )
    out, what, left = [], [], max_positions
    for kind, fits, order in kinds:
        for r in sorted(done, key=order):
            if fits(r) and size(r) <= left:
                done.remove(r)
                out.append(r)
                what.append({"kind": kind, "prompt_tokens": len(r["ids"]),
                             "cached_tokens": r["cached"],
                             "served_tokens": len(r["stream"])})
                left -= size(r)
                break
    return ([r["ids"] for r in out], [r["cached"] for r in out],
            [r["stream"] for r in out], what)


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

    from benchmark import correctness_lfm2

    lay = conf["layout"]
    t0 = time.monotonic()
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    say(event="init", seconds=time.monotonic() - t0,
        params=int(sum(x.size for x in jax.tree.leaves(params))))

    # No id the model can emit is the template's stop: every request
    # runs to its max_tokens, so every seed serves the same work.
    pipe = OryxInference(NoStopPrefixTokenizer(cfg.llm.vocab_size), params,
                         cfg, template="plain")
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=lay["num_slots"],
        page_size=lay["page_size"], decode_chunk=lay["decode_chunk"],
        max_ctx=lay["max_ctx"], prefill_chunk=lay["prefill_chunk"],
        kv_dtype=lay.get("kv_dtype", "bf16"),
        prefix_cache=bool(lay.get("prefix_cache", True)),
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
        prompts, cached, streams, what = sample_served(
            served, pipe, **about["sample"])
        if prompts:
            check = correctness_lfm2.logit_check(
                params["llm"], cfg, args.seed, sizes=ref_sizes(conf, cfg),
                page_size=lay["page_size"],
                prefill_chunk=lay["prefill_chunk"],
                decode_chunk=lay["decode_chunk"], max_ctx=lay["max_ctx"],
                head=about["head"], tail=about["tail"],
                prompts=prompts, cached=cached, served=streams,
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
