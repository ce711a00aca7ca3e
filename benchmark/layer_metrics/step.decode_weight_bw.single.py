"""Model step: the decode step's share of the HBM roofline by weights
alone, in a model of single latent blocks, %: weight bytes the decode
steps of the traced slice had to read
(costs_mla_single.decode_weight_bytes: attention, the shared expert,
the router and the head's slice once a step, and every HELD expert that
took a row once a layer-forward, from `moe_held_experts_hit_total`) /
device seconds of `paged_decode_chunk` / the chip's peak bytes/s. The
latent cache's bytes are left out (they are
`kernel.latent_paged_bw.single`'s), so the share reads low by their
part, never high.

None where the counters have no held experts or the trace no decode
dispatch."""
LAYER = "model step"
from benchmark import costs_mla_single, program, trace

PROGRAMS = ("paged_decode_chunk",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"].get("modules", {}), PROGRAMS)
    sc = run["trace"].get("slice_counters", {})
    hit, slots = (sc.get("moe_held_experts_hit_total"),
                  sc.get("moe_held_expert_slots_total"))
    if not sec or hit is None or not slots:
        return None
    c = run["config"]
    steps = slots / (c["num_hidden_layers"] * c["experts_held"])
    need = costs_mla_single.decode_weight_bytes(c, steps=steps, held_hit=hit)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
