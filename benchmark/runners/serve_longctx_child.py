"""The process that holds the chip in the long-context session cell of a
learned-sparse-attention model (GLM-5).

    configuration -> seeded weights (no vision tower) -> OryxInference
    -> api_server.build_server(engine="continuous") -> serve_forever

and then the same one-line commands on stdin and JSON events on stdout
as runners/serve_docqa_child.py, whose `Served` and `sample_served` it
uses as they are (with serve_latent_child's tokenizer, in the form
whose stop string no emitted id can match, and its command loop):
between `arm` and `disarm` every request handed to the engine is kept
with its handle; on `stop` the server is closed and its pool given
back, a sample of the requests the window FINISHED is taken and the
tokens the engine streamed for them go to correctness_glm5.logit_check
with their prompts; the `logit_check` event follows `stop`, before
`stopped`. A program that lacks the configuration's preset (the parent
commit) leaves at once, before it touches the device.
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
from benchmark.runners.serve_docqa_child import (  # noqa: E402
    Served, sample_served,
)
from benchmark.runners.serve_latent_child import (  # noqa: E402
    NoStopPrefixTokenizer,
)

T_START = time.monotonic()
TINY = "glm5_tiny"  # the rehearsal: no width holds

# Configuration-file key (the source's own name) -> the program's, for
# what program.check_widths does not know: a file whose latent, indexer,
# expert or router geometry the program would not run is refused.
_KEYS = {
    "num_hidden_layers": "num_layers",
    "first_k_dense_replace": "dense_layers",
    "moe_intermediate_size": "moe_intermediate_size",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "index_n_heads": "index_heads",
    "index_head_dim": "index_head_dim",
    "index_topk": "index_topk",
    "routed_scaling_factor": "routed_scaling_factor",
    "scoring_func": "router_scoring",
    "n_routed_experts": "num_experts",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "rope_interleave": "rope_interleaved",
    "indexer_rope_interleave": "rope_interleaved",
    "attention_bias": "attention_bias",
    "tie_word_embeddings": "tie_word_embeddings",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_position_embeddings",
}


def build_config(conf: dict):
    """The named preset with the file's layout, through program.py's own
    builder; then the file's latent, indexer, expert, router and RoPE
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
        have = {key: getattr(cfg.llm, attr) for key, attr in _KEYS.items()}
        have["experts_held"] = cfg.llm.held[1]
        have["rope_parameters.rope_theta"] = cfg.llm.rope_theta
        want = dict(conf)
        want["rope_parameters.rope_theta"] = conf["rope_parameters"][
            "rope_theta"]
        if conf["rope_parameters"]["rope_type"] != "default" or cfg.llm.yarn:
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

    from benchmark import correctness_glm5

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
        # The engine's pool goes before the reference's float32
        # forwards come: both do not fit beside the weights.
        srv.scheduler.kv_pages = None
        del srv
        gc.collect()
        about = conf["logit_check"]
        steps = about["decode_chunks"] * lay["decode_chunk"]
        t0 = time.monotonic()
        prompts, streams, what = sample_served(
            served, pipe, min_tokens=steps + 1, **about["sample"])
        if prompts:
            check = correctness_glm5.logit_check(
                params["llm"], cfg, args.seed, page_size=lay["page_size"],
                prefill_chunk=lay["prefill_chunk"],
                decode_chunk=lay["decode_chunk"], max_ctx=lay["max_ctx"],
                decode_chunks=about["decode_chunks"], prompts=prompts,
                served=streams,
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
