"""Model step: the decode step's share of the HBM roofline by weights,
experts hit and both planes' K/V, %: bytes the decode steps of the
traced slice had to move (costs_smallthinker.decode_bytes: attention,
norms, router and head once a step; gate, up and down of every expert a
decode step's lanes hit, `moe_experts_hit_total` less the prefill
chunks' `moe_prefill_held_experts_hit_total`; a global layer's
`decode_kv_tokens_total` and a window layer's
`decode_window_kv_tokens_total` cached tokens) / device seconds of
`paged_decode_chunk` / the chip's peak bytes/s. A lower bound of what
moved (activations, the sampler and padding are left out), so it cannot
pass 100.

None where the slice has no such counter (a program without window
layers) or the trace no decode dispatch."""
LAYER = "model step"
from benchmark import costs_smallthinker, program, trace

PROGRAMS = ("paged_decode_chunk",)


def read(run):
    tr = run.get("trace") or {}
    sec, n = trace.match_seconds(tr.get("modules", {}), PROGRAMS)
    sc = tr.get("slice_counters", {})
    window = sc.get("decode_window_kv_tokens_total")
    if not sec or not n or window is None:
        return None
    c = run["config"]
    hit = sc.get("moe_experts_hit_total", 0.0) - sc.get(
        "moe_prefill_held_experts_hit_total", 0.0)
    need = costs_smallthinker.decode_bytes(
        c, steps=n * c["layout"]["decode_chunk"], experts_hit=max(hit, 0.0),
        kv_tokens=sc.get("decode_kv_tokens_total", 0.0),
        window_kv_tokens=window)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
