"""The process that holds the chip in a block-diffusion serve cell.

    configuration -> seeded weights (no vision tower) -> the block logit
    check against the plain reference -> OryxInference ->
    api_server.build_server(engine="continuous") -> serve_forever

and then the same one-line commands on stdin and JSON events on stdout
as runners/serve_child.py (`arm`, `trace_start`, `trace_stop`,
`disarm`, `stop`). A program that lacks the configuration's preset (the
parent commit) leaves at once, before it touches the device.
"""

from __future__ import annotations

import argparse
import dataclasses
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

T_START = time.monotonic()

# Configuration-file keys (the source's own names, which are also the
# program's) that program.check_widths does not know: a file whose expert
# widths or block settings the program would not run is refused.
_EXPERT_KEYS = (
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "norm_topk_prob", "attention_bias", "tie_word_embeddings",
)


class SpreadTokenizer:
    """Tokenizer stand-in for a model whose layers depend on WHICH
    tokens arrive. As program.IdTokenizer, one id per character in (a
    prompt of N characters is N tokens, which the traffic's lengths
    count on) and `<id>` per token out; but the id is a hash of the
    whole text and of the text up to the character, spread over the
    vocabulary below `hi`, where IdTokenizer's is the character's code
    point. So a prompt is a sequence of ids drawn like uniformly random
    ones, as the logit check's own prompts are, and two prompts differ
    from their first id on.

    Why that matters here and nowhere else: under seeded random weights
    a layer's attention is close to a mean over what a position may
    see, and seven such means in a row weigh a sequence's FIRST tokens
    thousands of times over its later ones. Give every slot the same
    first tokens (a chat template's head; the traffic's text, fifteen
    words in 27 code points, opens eleven ways) and every position of
    every slot ends near one direction, which the router sends to the
    same few experts: 26 to 32 of 128 hit a layer-forward at an
    imbalance of 14 to 15, whatever the rest of the prompt holds
    (measured with three tokenizers behind the template's head; PERF.md
    section 6, PR 26). Prompts that differ from the first id on keep
    the slots apart (115 hit, imbalance 3.2). A trained model has no
    such sensitivity; the ids of a real tokenizer repeat inside a
    prompt and across prompts. The price: no two texts share a prefix
    of ids, so the prefix cache finds nothing here (this cell's prompts
    are single turns that share no page of 64 tokens anyway); a cell of
    sessions that re-send their history needs ids that a prefix keeps.
    """

    def __init__(self, hi: int):
        self.span = hi - 3  # ids 3..hi-1: the special ids stay out

    def encode(self, text, add_special_tokens=False):
        import hashlib

        m = (1 << 64) - 1
        h = int.from_bytes(
            hashlib.blake2b(text.encode(), digest_size=8).digest(), "little")
        out = []
        for ch in text:
            h = (h * 1_000_003 + ord(ch) + 1) & m
            out.append(3 + ((h * 0x9E3779B97F4A7C15 & m) >> 24) % self.span)
        return out

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


def build_config(conf: dict):
    """The named preset with the file's layout, through program.py's own
    builder, plus the server-side block settings; then the expert keys
    the file states against what the program will run."""
    from oryx_tpu import config as cfg_lib

    from benchmark import program

    lay = conf["layout"]
    if not hasattr(cfg_lib, lay["preset"]):
        raise SystemExit(
            f"config {conf.get('name')}: this program has no preset "
            f"{lay['preset']!r}"
        )
    tiny = lay["preset"] == "sdar_tiny"  # the rehearsal: no width holds
    cfg = program.build_config(
        {k: v for k, v in conf.items() if k not in program._WIDTHS}
        if tiny else conf
    )
    gen = {k: lay[k] for k in ("denoising_steps", "remasking") if k in lay}
    cfg = dataclasses.replace(
        cfg, generation=dataclasses.replace(cfg.generation, **gen)
    )
    if not tiny:
        for key in _EXPERT_KEYS:
            if key in conf and conf[key] != getattr(cfg.llm, key):
                raise SystemExit(
                    f"config {conf.get('name')}: {key} {conf[key]} in the "
                    f"file, {getattr(cfg.llm, key)} in the program"
                )
    if lay.get("block_length", cfg.llm.block_length) != cfg.llm.block_length:
        raise SystemExit(
            f"config {conf.get('name')}: block_length "
            f"{lay['block_length']} in the file, {cfg.llm.block_length} "
            "in the program"
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

    from benchmark import correctness_sdar

    lay = conf["layout"]
    t0 = time.monotonic()
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    say(event="init", seconds=time.monotonic() - t0,
        params=int(sum(x.size for x in jax.tree.leaves(params))))

    t0 = time.monotonic()
    check = correctness_sdar.block_logit_check(
        params["llm"], cfg, args.seed, page_size=lay["page_size"],
        prefill_chunk=lay["prefill_chunk"], **conf.get("logit_check", {}),
    )
    say(event="logit_check", seconds=time.monotonic() - t0, **check)

    hi = min(cfg.llm.vocab_size, cfg.llm.mask_token_id)
    # No chat template: a prompt is the user's text and the "plain"
    # template's newline (its stop string never shows in `<id>` text).
    # The "qwen" template would open every prompt with the same 77
    # characters; see SpreadTokenizer.
    pipe = OryxInference(SpreadTokenizer(hi), params, cfg, template="plain")
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=lay["num_slots"],
        page_size=lay["page_size"], max_ctx=lay["max_ctx"],
        prefill_chunk=lay["prefill_chunk"],
        kv_dtype=lay.get("kv_dtype", "bf16"),
        prefix_cache=bool(lay.get("prefix_cache", True)),
        max_tokens_limit=lay["max_ctx"], max_queue=lay.get("max_queue", 256),
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    say(event="ready", port=srv.server_address[1],
        t=time.monotonic() - T_START)

    if not lifeline.serve_until_stopped(srv, args.trace_dir):
        return lifeline.ORPHANED
    say(event="stopped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
