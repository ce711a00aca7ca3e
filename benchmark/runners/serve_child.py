"""The process that holds the chip in a serve cell.

    seeded weights -> logit check against the plain reference ->
    OryxInference -> api_server.build_server(engine="continuous") ->
    serve_forever on a thread

and then obeys one-line commands on stdin from the load-generating
parent (which never touches jax), answering each with one JSON line on
stdout: `arm` (recompile watchdog on), `trace_start` / `trace_stop`
(jax.profiler around a slice of the window), `disarm` (compile count,
peak memory, reduced trace), `stop`.
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

    cache_dir = program.configure_cache()
    device = program.device_record(args.chips, rehearse=bool(args.rehearse))
    from oryx_tpu.ops import packing

    say(event="device", device=device, cache_dir=cache_dir,
        embed_buckets=list(packing.DEFAULT_BUCKETS),
        t=time.monotonic() - T_START)

    import jax

    from oryx_tpu.serve import api_server
    from oryx_tpu.serve.pipeline import OryxInference

    from benchmark import correctness

    cfg = program.build_config(conf)
    lay = conf["layout"]
    t0 = time.monotonic()
    params = program.seeded_params(cfg, args.seed, lay["dtype"])
    say(event="init", seconds=time.monotonic() - t0,
        params=int(sum(x.size for x in jax.tree.leaves(params))))

    t0 = time.monotonic()
    check = correctness.serve_logit_check(
        params, cfg, args.seed, page_size=lay["page_size"],
        **conf.get("logit_check", {}),
    )
    say(event="logit_check", seconds=time.monotonic() - t0, **check)

    pipe = OryxInference(program.IdTokenizer(), params, cfg)
    srv = api_server.build_server(
        pipe, port=0, engine="continuous", num_slots=lay["num_slots"],
        page_size=lay["page_size"], decode_chunk=lay["decode_chunk"],
        max_ctx=lay["max_ctx"], prefill_chunk=lay["prefill_chunk"],
        ragged=bool(lay["ragged"]), kv_dtype=lay.get("kv_dtype", "bf16"),
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
