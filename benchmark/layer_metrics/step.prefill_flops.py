"""Model step: the prefill programs' share of the chip's peak
operations, %: what the chunks of the traced slice NEEDED
(costs_mla_single.prefill_flops: the matmuls of the chunks' real
tokens, the expanded attention over `prefill_attn_pairs_total`, the
up-projection of each chunk's live prefix from
`prefill_live_positions_total`) / device seconds of every
`paged_prefill` program (one a block-table width) / the chip's peak
bf16 operations/s. The held experts' part is what the chunks' routing
gave (`moe_prefill_held_rows_total / moe_prefill_pairs_total`). It counts what the algorithm needs, so a chunk's
padding, a table wider than the live prefix and the masked half of a
diagonal tile read as a lower share, never over 100 %.

None where the program has no such counters (before PR 33) or the
trace no prefill dispatch."""
LAYER = "model step"
from benchmark import costs_mla_single, program, trace

PROGRAMS = ("paged_prefill",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"].get("modules", {}), PROGRAMS)
    sc = run["trace"].get("slice_counters", {})
    tokens, pairs, live = (sc.get("prefill_tokens_total"),
                           sc.get("prefill_attn_pairs_total"),
                           sc.get("prefill_live_positions_total"))
    if not sec or not tokens or pairs is None or live is None:
        return None
    c = run["config"]
    # The share of the chunks' picks that landed on a held expert, as
    # counted (`moe_prefill_*`, summed over layers); uniform routing's
    # on a program that has no such counters.
    picks, held = (sc.get("moe_prefill_pairs_total"),
                   sc.get("moe_prefill_held_rows_total"))
    need = costs_mla_single.prefill_flops(
        c, tokens=tokens, attn_pairs=pairs, live_positions=live,
        held_share=held / picks if picks and held is not None
        else c["experts_held"] / c["n_routed_experts"])
    peak = program.load_peaks()[run["device"]["kind"]]["bf16_flops_per_s"]
    return 100.0 * need / sec / peak
