"""The process that holds the chip in the mixed-queue cell of a window /
global hybrid with routed experts (SmallThinker-21BA3B-Instruct).

    configuration -> seeded weights (no vision tower) -> OryxInference
    -> api_server.build_server(engine="continuous") -> serve_forever

and then the same one-line commands on stdin and JSON events on stdout
as runners/serve_reasoning_child.py, whose command loop
(serve_latent_child's `serve_commands`), `Served` record and tokenizer
it uses as they are. What is this cell's own: the
configuration keys it holds the program to (`build_config`), the sizes
the plain reference reads (`ref_sizes`), the KINDS of finished request
it samples for the comparison (`sample_served`: a long document, a
short request, one whose answer crosses the window) and the comparison
(correctness_smallthinker.logit_check), which follows `stop`, before
`stopped`. Nothing of the comparison is inside `setup_s`. A program
that lacks the configuration's preset (the parent commit) leaves at
once, before it touches the device.
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
    PrefixTokenizer,
)

T_START = time.monotonic()
TINY = "smallthinker_tiny"  # the rehearsal: no width holds

# Configuration-file key (the source's own name) -> the program's, for
# what program.check_widths does not know.
_KEYS = {
    "num_hidden_layers": "num_layers",
    "moe_ffn_hidden_size": "moe_intermediate_size",
    "moe_num_primary_experts": "num_experts",
    "moe_num_active_primary_experts": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "sliding_window_size": "sliding_window",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_word_embeddings",
    "rms_norm_eps": "rms_norm_eps",
    "max_position_embeddings": "max_position_embeddings",
}


def layouts_of(llm) -> list[int]:
    """The source's `sliding_window_layout` (= `rope_layout`) as the
    program would run it: 0 on a global layer, 1 on a window layer."""
    per, off = llm.global_layer_period, llm.global_layer_offset
    return [0 if i % per == off else 1 for i in range(llm.num_layers)]


def build_config(conf: dict):
    """The named preset with the file's layout, through program.py's own
    builder; then the file's expert, window and layer-kind keys against
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
        got = {key: getattr(llm, attr) for key, attr in _KEYS.items()}
        n = llm.num_layers
        got["sliding_window_layout"] = got["rope_layout"] = layouts_of(llm)
        for key, have in got.items():
            want = conf.get(key)
            if isinstance(want, list):
                want = want[:n]
            if want is not None and want != have:
                raise SystemExit(
                    f"config {conf.get('name')}: {key} {want} in the "
                    f"file, {have} in the program"
                )
        if llm.rope_window_only is not True or llm.moe_activation != "relu" \
                or llm.router_input != "layer_input":
            raise SystemExit(
                f"config {conf.get('name')}: the program's preset does not "
                "run the file's `assumed` conventions")
    return cfg


def ref_sizes(conf: dict, cfg) -> dict:
    """What the plain reference reads: the configuration file's
    published keys (the tiny preset's own in the rehearsal, where the
    file's widths do not hold)."""
    from benchmark.reference import smallthinker_ref

    if conf["layout"]["preset"] != TINY:
        return smallthinker_ref.sizes_from_keys(
            dict(conf, num_hidden_layers=conf["layout"]["num_layers"]))
    llm = cfg.llm
    return smallthinker_ref.sizes_from_keys({
        "num_hidden_layers": llm.num_layers,
        "num_attention_heads": llm.num_heads,
        "num_key_value_heads": llm.num_kv_heads, "head_dim": llm.head_dim,
        "rms_norm_eps": llm.rms_norm_eps, "rope_theta": llm.rope_theta,
        "sliding_window_size": llm.sliding_window,
        "sliding_window_layout": layouts_of(llm),
        "rope_layout": layouts_of(llm),
        "moe_num_primary_experts": llm.num_experts,
        "moe_num_active_primary_experts": llm.num_experts_per_tok,
        "norm_topk_prob": llm.norm_topk_prob,
    })


def sample_served(served: Served, pipe, *, window: int, page_size: int,
                  long_prompt: int, max_positions: int):
    """(prompts, streams, what each is): of the requests the window
    finished in full, the one with the fewest positions of each KIND
    (correctness_smallthinker.kind_of: long_doc, short, crossing),
    while their positions fit `max_positions`. The lists are the same
    at every seed, so the sample is too, as far as the window gets."""
    from benchmark import correctness_smallthinker as check

    kinds: dict[str, list] = {}
    for request, max_new, h in served.items:
        if not h.done.is_set() or h.error is not None or h.cancelled \
                or h.finish_reason != "length":
            continue
        stream = [int(t) for t in _TOKEN.findall(h.reply or "")]
        if len(stream) != max_new:
            continue
        ids = [int(t) for t in pipe._prepare_request(request)[0]]
        kind = check.kind_of(len(ids), len(ids) + len(stream), window,
                             long_prompt, page_size)
        if kind:
            kinds.setdefault(kind, []).append((ids, stream))
    size = lambda r: len(r[0]) + len(r[1])  # noqa: E731
    prompts, streams, what, left = [], [], [], max_positions
    for kind in check.KINDS:
        for ids, stream in sorted(kinds.get(kind, []), key=size):
            if size((ids, stream)) <= left:
                prompts.append(ids)
                streams.append(stream)
                what.append({"kind": kind, "prompt_tokens": len(ids),
                             "served_tokens": len(stream)})
                left -= size((ids, stream))
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

    from benchmark import correctness_smallthinker

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
        prefix_cache=bool(lay.get("prefix_cache", False)),
        max_tokens_limit=lay["max_ctx"], max_queue=lay.get("max_queue", 256),
    )
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    served = Served(srv.scheduler, sys.stdin)
    sys.stdin = served  # serve_commands reads its lines through it
    say(event="ready", port=srv.server_address[1],
        t=time.monotonic() - T_START,
        window_pages=int(srv.scheduler.num_window_pages),
        window_table_pages=int(srv.scheduler.wplane.tables.shape[1]))
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
            served, pipe, window=cfg.llm.sliding_window,
            page_size=lay["page_size"], **about["sample"])
        if prompts:
            check = correctness_smallthinker.logit_check(
                params["llm"], cfg, args.seed, sizes=ref_sizes(conf, cfg),
                page_size=lay["page_size"],
                prefill_chunk=lay["prefill_chunk"],
                decode_chunk=lay["decode_chunk"], max_ctx=lay["max_ctx"],
                head=about["head"], tail=about["tail"],
                long_prompt=about["sample"]["long_prompt"],
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
