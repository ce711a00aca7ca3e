"""The process that holds the chip in the long-document cell of a
single-latent-block model (Mistral-Small-4).

    configuration -> seeded weights (no vision tower) -> OryxInference
    -> api_server.build_server(engine="continuous") -> serve_forever

and then the same one-line commands on stdin and JSON events on stdout
as runners/serve_latent_child.py, whose tokenizer and command loop it
uses as they are. What differs is WHEN and ON WHAT `correct` is
decided: between `arm` and `disarm` every request handed to the engine
is kept with its handle (`Served`); on `stop` the server is closed and
its pool given back, a sample of the requests the window FINISHED is
taken (`sample_served`) and the tokens the engine streamed for them go
to correctness_mistral4.logit_check with their prompts; the
`logit_check` event follows `stop`, before `stopped`. So the
reference's seconds and the twin's compiles are no part of `setup_s`,
and what is compared came out of the window's own programs. A program
that lacks the configuration's preset (the parent commit) leaves at
once, before it touches the device.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import re
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark.runners import lifeline  # noqa: E402
from benchmark.runners.lifeline import say  # noqa: E402
from benchmark.runners.serve_latent_child import (  # noqa: E402
    PrefixTokenizer,
)

T_START = time.monotonic()
TINY = "mistral4_tiny"  # the rehearsal: no width holds

# Configuration-file key (the source's own name) -> the program's, for
# what program.check_widths does not know: a file whose latent, expert,
# router or RoPE geometry the program would not run is refused.
_KEYS = {
    "num_hidden_layers": "num_layers",
    "moe_intermediate_size": "moe_intermediate_size",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "routed_scaling_factor": "routed_scaling_factor",
    "n_routed_experts": "num_experts",
    "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "rope_interleave": "rope_interleaved",
    "attention_bias": "attention_bias",
    "tie_word_embeddings": "tie_word_embeddings",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_position_embeddings",
}
_ROPE_KEYS = {
    "rope_theta": "rope_theta",
    "factor": "rope_scaling_factor",
    "original_max_position_embeddings": "rope_original_max_position",
    "beta_fast": "rope_beta_fast",
    "beta_slow": "rope_beta_slow",
    "mscale": "rope_mscale",
    "mscale_all_dim": "rope_mscale_all_dim",
    "llama_4_scaling_beta": "llama4_scaling_beta",
}


def build_config(conf: dict):
    """The named preset with the file's layout, through program.py's own
    builder; then the file's latent, expert, router and RoPE keys
    against what the program will run."""
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
        want = dict(conf)
        for key, attr in _ROPE_KEYS.items():
            have["rope_parameters." + key] = getattr(cfg.llm, attr)
            want["rope_parameters." + key] = conf["rope_parameters"][key]
        for key, got in have.items():
            if key in want and want[key] != got:
                raise SystemExit(
                    f"config {conf.get('name')}: {key} {want[key]} in the "
                    f"file, {got} in the program"
                )
    return cfg


class Served:
    """The requests handed to the engine between `arm` and `disarm`,
    each with the handle its reply lands in. `scheduler.submit` is
    wrapped (the HTTP handlers look it up at every call), and the
    command lines are seen on their way to `serve_commands`, which
    obeys them."""

    def __init__(self, scheduler, lines):
        self.items: list[tuple[dict, int, object]] = []
        self.armed = False
        self.window_closed = False
        self._lines = lines
        submit = scheduler.submit

        def recording(request, max_new, *a, **kw):
            handle = submit(request, max_new, *a, **kw)
            if self.armed:
                self.items.append((request, max_new, handle))
            return handle

        scheduler.submit = recording

    def __iter__(self):
        for line in self._lines:
            cmd = line.strip()
            if cmd in ("arm", "disarm"):
                self.armed = cmd == "arm"
                self.window_closed = cmd == "disarm"
            yield line


_TOKEN = re.compile(r"<(\d+)>")


def sample_served(served: Served, pipe, *, long_prompt: int,
                  max_positions: int, min_tokens: int):
    """(prompts, streams, what each is): of the requests the window
    finished in full, the shortest of each kind, while their positions
    fit `max_positions` (the reference's two forwards cost a second a
    thousand positions on the chip): a cold document over `long_prompt`
    positions (its chunks pass through every block-table width), a
    cached follow-up over it (a suffix prefilled at the widest table
    against a spliced prefix), a cached follow-up under it, a cold
    document under it. The lengths are the same at every seed, so the
    sample is too, as far as the window gets."""
    kinds: dict[str, list] = {}
    for request, max_new, h in served.items:
        if not h.done.is_set() or h.error is not None or h.cancelled \
                or h.finish_reason != "length":
            continue
        stream = [int(t) for t in _TOKEN.findall(h.reply or "")]
        if len(stream) != max_new or len(stream) < min_tokens:
            continue
        ids = pipe._prepare_request(request)[0]
        kind = ("cached" if request.get("history") else "cold") + (
            "_long" if len(ids) > long_prompt else "")
        kinds.setdefault(kind, []).append((len(ids), ids, stream))
    prompts, streams, what, left = [], [], [], max_positions
    for kind in ("cold_long", "cached_long", "cached", "cold"):
        for n, ids, stream in sorted(kinds.get(kind, []), key=lambda x: x[0]):
            if n + len(stream) <= left:
                prompts.append(ids)
                streams.append(stream)
                what.append({"kind": kind, "prompt_tokens": n,
                             "served_tokens": len(stream)})
                left -= n + len(stream)
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

    from benchmark import correctness_mistral4

    lay = conf["layout"]
    t0 = time.monotonic()
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    say(event="init", seconds=time.monotonic() - t0,
        params=int(sum(x.size for x in jax.tree.leaves(params))))

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
            check = correctness_mistral4.logit_check(
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
