"""Model step: the decode step's share of the HBM roofline in a Mamba-2
hybrid with latent experts, %: bytes the decode steps of the traced
slice had to move (costs_nemotron.decode_bytes: every mixer, the
attention layer, the routers, latent projections, shared experts, norms
and the head once a step; both kernels of every held expert a decode
step's lanes hit, `moe_experts_hit_total` less the prefill chunks'
`moe_prefill_held_experts_hit_total`; the live lanes' K/V in the
attention layer, `decode_kv_tokens_total`; twice the state and the conv
rows of every mixer a live lane-step, `ssm_decode_lane_steps_total`) /
device seconds of `paged_decode_chunk` / the chip's peak bytes/s. A
lower bound of what moved (activations, the sampler and padding are left
out), so it cannot pass 100.

None where the slice has no such counter (a program without a Mamba
state), the configuration no latent, or the trace no decode dispatch."""
LAYER = "model step"
from benchmark import costs_nemotron, program, trace

PROGRAMS = ("paged_decode_chunk",)


def read(run):
    tr = run.get("trace") or {}
    sec, n = trace.match_seconds(tr.get("modules", {}), PROGRAMS)
    sc = tr.get("slice_counters", {})
    lane_steps = sc.get("ssm_decode_lane_steps_total")
    c = run["config"]
    if not sec or not n or lane_steps is None or "moe_latent_size" not in c:
        return None
    hit = sc.get("moe_experts_hit_total", 0.0) - sc.get(
        "moe_prefill_held_experts_hit_total", 0.0)
    need = costs_nemotron.decode_bytes(
        c, steps=n * c["layout"]["decode_chunk"], experts_hit=max(hit, 0.0),
        kv_tokens=sc.get("decode_kv_tokens_total", 0.0),
        lane_steps=lane_steps)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
