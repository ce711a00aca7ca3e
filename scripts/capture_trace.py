"""Capture an XLA profiler trace of the bench train step and print an
op-level summary — the "profile, iterate" loop for MFU work.

Runs the same geometry/config selection as bench.py (same env knobs:
BENCH_REMAT_POLICY, BENCH_LOSS_CHUNK, BENCH_MOMENT_DTYPE, BENCH_BATCH,
BENCH_SEQ), warms up, then traces TRACE_STEPS steps with
jax.profiler.trace and decodes the written xplane.pb with the
dependency-free reader in oryx_tpu/utils/xplane.py (the TF/tensorboard
profile tooling on this box is version-broken). Prints one JSON line:
top ops by total device time (TPU plane when present, host plane as
fallback on CPU smoke runs).

Each traced step also records a host-side span (utils/trace.py, the
same machinery behind the serving flight recorder), and the written
xplane is joined back against those windows — per-step device time
attributed to host spans ("span_device_ms"), closing the loop between
live tracing and on-chip profiles. To join a LIVE recording instead —
e.g. decode-chunk spans exported from a serving run's flight recorder
(Tracer.write_jsonl / GET /debug/trace) — point TRACE_SPANS at the
JSONL and TRACE_SPAN_NAME at the span to attribute (default
decode_chunk); the windows then come from that file rather than the
steps traced here.

    TRACE_DIR=/tmp/oryx_trace python scripts/capture_trace.py
    TRACE_SPANS=flight.jsonl python scripts/capture_trace.py
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TRACE_STEPS = int(os.environ.get("TRACE_STEPS", "3"))
TOP_N = int(os.environ.get("TRACE_TOP_N", "30"))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from bench import _bench_cfg, _make_batch, chip_info
    from oryx_tpu.models import oryx
    from oryx_tpu.train import step as step_lib
    from oryx_tpu.train.optimizer import make_optimizer
    from oryx_tpu.utils import profiling
    from oryx_tpu.utils import trace as trace_lib
    from oryx_tpu.utils import xplane

    trace_dir = os.environ.get("TRACE_DIR", "/tmp/oryx_trace")
    backend = jax.default_backend()
    _, hbm, _ = chip_info(jax)
    geo_name, cfg, batch_size, seq_bucket, img_side = _bench_cfg(backend, hbm)
    host = _make_batch(cfg, batch_size, seq_bucket, img_side)
    batch = {k: jnp.asarray(v)[None] for k, v in host.items()}

    params = oryx.init_params(cfg, jax.random.key(0))
    tx = make_optimizer(cfg.train, params)
    state = step_lib.TrainState(
        step=jnp.zeros((), jnp.int32), params=params, opt_state=tx.init(params)
    )

    # Warmup outside the trace: compile noise would dominate the profile.
    # The carry threads through so every traced step is a REAL step (a
    # repeated identical step could be elided by donation aliasing).
    holder = {"state": state}

    def one_step():
        holder["state"], metrics = step_lib.train_step(
            holder["state"], batch, cfg, tx
        )
        return metrics["loss"]

    for _ in range(2):
        loss = one_step()
    jax.device_get(loss)

    # Host-side step spans for the post-hoc span<->xplane join. The
    # per-step device_get sync pins each window around its step's real
    # device execution (async dispatch would otherwise close the window
    # before the device ran) — attribution mode trades a little overlap
    # for attributable windows.
    tracer = trace_lib.Tracer(max(TRACE_STEPS, 4))
    steps_trace = tracer.start_trace("profile", label="capture_trace")

    def traced_step():
        with steps_trace.span("train_step"):
            out = jax.block_until_ready(one_step())
        return out

    try:
        prof = profiling.op_profile(
            traced_step, trace_dir=trace_dir, steps=TRACE_STEPS,
            top_n=TOP_N,
        )
    except RuntimeError as e:  # no xplane written (e.g. trace aborted)
        print(json.dumps({"error": "no_xplane_written", "detail": str(e)}))
        raise SystemExit(1)
    except ValueError as e:  # truncated xplane (profiler killed mid-write)
        print(json.dumps({"error": "corrupt_xplane", "detail": str(e)}))
        raise SystemExit(1)
    steps_trace.finish()

    # Join device time back onto host spans: the traced steps above, or
    # — with TRACE_SPANS — an exported flight recorder from a live run
    # (e.g. the serving scheduler's decode-chunk spans).
    if spans_path := os.environ.get("TRACE_SPANS"):
        windows = trace_lib.windows_from_jsonl(
            spans_path, os.environ.get("TRACE_SPAN_NAME", "decode_chunk")
        )
    else:
        windows = trace_lib.windows_from_traces(
            [steps_trace.to_dict()], "train_step"
        )
    planes = xplane.parse_xspace(prof.xplane_path)
    filters = (
        {"plane_filter": "TPU", "line_filter": "Ops"}
        if prof.source == "tpu_xla_ops" else {}
    )
    attributed = xplane.attribute_device_time(
        planes, windows, session_end_ns=prof.trace_end_ns, **filters
    )
    print(json.dumps({
        "metric": "trace_top_ops",
        "geometry": geo_name,
        "steps": TRACE_STEPS,
        "backend": backend,
        # source=host_fallback on a TPU run means the device plane was
        # NOT found — host dispatch noise, not device op time.
        "source": prof.source,
        "planes": prof.plane_names,
        "xplane": prof.xplane_path,
        "top_ops_ms": [
            {"op": name, "ms": round(ms, 3)} for name, ms in prof.top
        ],
        # Device time attributed per host span window (the join); a
        # dominant _unattributed bucket means the clocks didn't line up
        # or the windows came from a different run than the xplane.
        "span_device_ms": {
            label: round(ps / 1e9, 3)
            for label, ps in sorted(attributed.items())
        },
    }))


if __name__ == "__main__":
    main()
