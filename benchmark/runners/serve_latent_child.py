"""The process that holds the chip in a latent-attention serve cell.

    configuration -> seeded weights (no vision tower) -> the logit check
    against the plain reference (correctness_longcat.py) ->
    OryxInference -> api_server.build_server(engine="continuous") ->
    serve_forever

and then the same one-line commands on stdin and JSON events on stdout
as runners/serve_child.py (`arm`, `trace_start`, `trace_stop`,
`disarm`, `stop`). A program that lacks the configuration's preset (the
parent commit) leaves at once, before it touches the device.
"""

from __future__ import annotations

import argparse
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

# Configuration-file key (the source's own name) -> the program's, for
# what program.check_widths does not know: a file whose latent, expert
# or router geometry the program would not run is refused.
_KEYS = {
    "ffn_hidden_size": "intermediate_size",
    "expert_ffn_hidden_size": "moe_intermediate_size",
    "num_layers": "num_layers",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "mla_scale_q_lora": "mla_scale_q_lora",
    "mla_scale_kv_lora": "mla_scale_kv_lora",
    "routed_scaling_factor": "routed_scaling_factor",
    "n_routed_experts": "num_experts",
    "zero_expert_num": "zero_experts",
    "moe_topk": "num_experts_per_tok",
    "attention_bias": "attention_bias",
    "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
    "max_position_embeddings": "max_position_embeddings",
}


class PrefixTokenizer:
    """Tokenizer stand-in for a cell of sessions that re-send their
    history to a model whose layers depend on WHICH tokens arrive. One
    id per character in (a prompt of N characters is N tokens) and
    `<id>` per token out, as program.IdTokenizer; ids spread over
    3..hi-1 like uniformly random ones, as the logit check's prompts
    are (see serve_blockdiff_child.SpreadTokenizer for why that matters
    under seeded random weights: behind a shared head every slot routes
    alike).

    The hash starts from the text's first HEAD characters (the traffic
    opens every session with a seed-made tag of that length) and is
    then rolled one character at a time: an id depends on the head and
    on the text UP TO its character, on nothing after it. So a history
    that is re-sent with more text behind it has the same ids, and the
    prefix cache finds its pages; two sessions differ from their first
    id on."""

    HEAD = 16

    def __init__(self, hi: int):
        self.span = hi - 3

    def encode(self, text, add_special_tokens=False):
        import hashlib

        m = (1 << 64) - 1
        h = int.from_bytes(hashlib.blake2b(
            text[:self.HEAD].encode(), digest_size=8).digest(), "little")
        out = []
        for ch in text:
            h = (h * 1_000_003 + ord(ch) + 1) & m
            out.append(3 + ((h * 0x9E3779B97F4A7C15 & m) >> 24) % self.span)
        return out

    def decode(self, ids, skip_special_tokens=True):
        return "".join(f"<{int(i)}>" for i in ids)


class NoStopPrefixTokenizer(PrefixTokenizer):
    """PrefixTokenizer for a cell whose every request has to run to its
    `max_tokens` at every seed. The program turns the chat template's
    stop string ("\n" under `plain`) into token ids ONCE, with this
    tokenizer (`generate.make_stop_sequences`), and ends a lane on the
    device when the ids it emitted match them. PrefixTokenizer gives
    that one character one id below `hi`, the same at every seed, and
    seeded random weights emit every id about once in `hi` greedy
    tokens: about one request in twenty of glm-5.long-sessions ended
    early with `finish_reason` "stop", at another place at every seed,
    its client went on to its next prompt, and `serve_tok_s` read up to
    3 % apart between seeds whose runs repeat to five digits (my chip
    runs, PR 55; PERF.md section 6). Here a text that IS a stop string
    gets ids `hi + 1`, which the head has no row for (and which is not
    the preset's EOS id, `hi`): the stop can never match. Every other
    text, so every prompt, gets PrefixTokenizer's ids."""

    def __init__(self, hi: int, stop_strs=("\n",)):
        super().__init__(hi)
        self._never = hi + 1
        self._stop_strs = frozenset(stop_strs)

    def encode(self, text, add_special_tokens=False):
        if text in self._stop_strs:
            return [self._never] * len(text)
        return super().encode(text, add_special_tokens)


def build_config(conf: dict):
    """The named preset with the file's layout, through program.py's own
    builder; then the file's latent, expert and router keys against
    what the program will run."""
    from oryx_tpu import config as cfg_lib

    from benchmark import program

    lay = conf["layout"]
    if not hasattr(cfg_lib, lay["preset"]):
        raise SystemExit(
            f"config {conf.get('name')}: this program has no preset "
            f"{lay['preset']!r}"
        )
    tiny = lay["preset"] == "longcat_tiny"  # the rehearsal: no width holds
    cfg = program.build_config(
        {k: v for k, v in conf.items() if k not in program._WIDTHS}
        if tiny else conf
    )
    if not tiny:
        have = {key: getattr(cfg.llm, attr) for key, attr in _KEYS.items()}
        have["experts_held"] = cfg.llm.held[1]
        for key, got in have.items():
            if key in conf and conf[key] != got:
                raise SystemExit(
                    f"config {conf.get('name')}: {key} {conf[key]} in the "
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

    from benchmark import correctness_longcat

    lay = conf["layout"]
    t0 = time.monotonic()
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    say(event="init", seconds=time.monotonic() - t0,
        params=int(sum(x.size for x in jax.tree.leaves(params))))

    t0 = time.monotonic()
    check = correctness_longcat.logit_check(
        params["llm"], cfg, args.seed, page_size=lay["page_size"],
        prefill_chunk=lay["prefill_chunk"], decode_chunk=lay["decode_chunk"],
        **conf.get("logit_check", {}),
    )
    say(event="logit_check", seconds=time.monotonic() - t0, **check)

    pipe = OryxInference(PrefixTokenizer(cfg.llm.vocab_size), params, cfg,
                         template="plain")
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=lay["num_slots"],
        page_size=lay["page_size"], decode_chunk=lay["decode_chunk"],
        max_ctx=lay["max_ctx"], prefill_chunk=lay["prefill_chunk"],
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
