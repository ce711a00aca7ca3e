"""Model step: the decode step's share of the HBM roofline under learned
sparse attention, %: bytes the decode steps of the traced slice had to
read (costs_dsa.decode_bytes: a step's weights once whatever the lanes,
every HELD expert that took a row once a layer-forward, the index keys
scored and the latent rows selected) / device seconds of
`paged_decode_chunk` / the chip's peak bytes/s. The sort's passes over
the scores, the scores themselves and the gathered copy of the rows are
traffic the program has and the algorithm does not need: they read as a
lower share.

None where the slice's counters have no selected rows or held experts
(a program without an indexer) or the trace no decode dispatch."""
LAYER = "model step"
from benchmark import program, trace

PROGRAMS = ("paged_decode_chunk",)


def read(run):
    from benchmark import costs_dsa

    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("modules", {}), PROGRAMS)
    sc = tr.get("slice_counters", {})
    rows, keys = (sc.get("decode_selected_tokens_total"),
                  sc.get("decode_kv_tokens_total"))
    hit, slots = (sc.get("moe_held_experts_hit_total"),
                  sc.get("moe_held_expert_slots_total"))
    if not sec or not rows or not keys or hit is None or not slots:
        return None
    c = run["config"]
    need = costs_dsa.decode_bytes(
        c, steps=costs_dsa.decode_steps(c, held_slots=slots), held_hit=hit,
        kv_tokens=keys, selected_tokens=rows)
    peak = program.load_peaks()[run["device"]["kind"]]["hbm_bytes_per_s"]
    return 100.0 * need / sec / peak
