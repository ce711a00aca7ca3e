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
import contextlib
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

T_START = time.monotonic()


def say(**kw) -> None:
    sys.stdout.write(json.dumps(kw) + "\n")
    sys.stdout.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)  # resolved json, inline
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--trace-dir", default="")
    args = ap.parse_args(argv)
    conf = json.loads(args.config)

    from benchmark import program

    cache_dir = program.configure_cache()
    device = program.device_record(args.chips, rehearse=bool(args.rehearse))
    from oryx_tpu.ops import packing

    say(event="device", device=device, cache_dir=cache_dir,
        embed_buckets=list(packing.DEFAULT_BUCKETS),
        t=time.monotonic() - T_START)

    import jax

    from oryx_tpu.analysis.sanitizers import recompile_watchdog
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

    stack = contextlib.ExitStack()
    wd = None
    trace_t = {}
    try:
        for line in sys.stdin:
            cmd = line.strip()
            if cmd == "arm":
                wd = stack.enter_context(
                    recompile_watchdog(budget=10**9, action="record")
                )
                say(event="armed")
            elif cmd == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # host spans, no py stacks
                jax.profiler.start_trace(
                    args.trace_dir, profiler_options=opts
                )
                trace_t["start"] = time.monotonic()
                say(event="trace_started")
            elif cmd == "trace_stop":
                trace_t["stop"] = time.monotonic()
                jax.profiler.stop_trace()
                say(event="trace_stopped",
                    seconds=trace_t["stop"] - trace_t["start"])
            elif cmd == "disarm":
                stack.close()
                out = {
                    "event": "disarmed",
                    "compiles": int(wd.total) if wd else None,
                    "compile_counts": dict(wd.counts) if wd else {},
                    "memory_peak_bytes": program.memory_peak_bytes(),
                }
                if trace_t:
                    from benchmark import trace as trace_lib

                    out["trace"] = trace_lib.reduce_dir(
                        args.trace_dir,
                        window_s=trace_t["stop"] - trace_t["start"],
                    )
                say(**out)
            elif cmd == "stop":
                break
    finally:
        if srv.supervisor is not None:
            srv.supervisor.stop()
        srv.scheduler.close()
        srv.shutdown()
        srv.server_close()
    say(event="stopped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
