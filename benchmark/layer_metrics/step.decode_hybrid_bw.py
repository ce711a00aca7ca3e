"""Model step: the decode step's share of the HBM roofline in a
gated-short-convolution hybrid with experts, %: bytes the decode steps
of the traced slice had to move (costs_lfm2.decode_bytes: every operator,
the dense FFNs, norms, routers and the head once a step; gate, up and
down of every expert a decode step's lanes hit, `moe_experts_hit_total`
less the prefill chunks' `moe_prefill_held_experts_hit_total`; the live
lanes' K/V in the attention layers, `decode_kv_tokens_total`; twice the
conv rows a live lane-step, `conv_decode_lane_steps_total`) / device
seconds of `paged_decode_chunk` / the chip's peak bytes/s. A lower bound
of what moved (activations, the sampler, the page-edge writes and
padding are left out), so it cannot pass 100.

None where the slice has no such counter (a program without a conv
state) or the trace no decode dispatch."""
LAYER = "model step"
from benchmark import costs_lfm2, program, trace

PROGRAMS = ("paged_decode_chunk",)


def read(run):
    tr = run.get("trace") or {}
    sec, n = trace.match_seconds(tr.get("modules", {}), PROGRAMS)
    sc = tr.get("slice_counters", {})
    lane_steps = sc.get("conv_decode_lane_steps_total")
    if not sec or not n or lane_steps is None:
        return None
    c = run["config"]
    hit = sc.get("moe_experts_hit_total", 0.0) - sc.get(
        "moe_prefill_held_experts_hit_total", 0.0)
    need = costs_lfm2.decode_bytes(
        c, steps=n * c["layout"]["decode_chunk"], experts_hit=max(hit, 0.0),
        kv_tokens=sc.get("decode_kv_tokens_total", 0.0),
        lane_steps=lane_steps)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
