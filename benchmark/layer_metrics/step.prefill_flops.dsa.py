"""Model step: the prefill program's share of the FLOP peak under
learned sparse attention, %: operations the prefill chunks of the traced
slice needed (costs_dsa.prefill_flops: the real tokens through every
matmul of their layer, the held share of their picks, the pairs the
indexer had to score, `prefill_index_pairs_total`, and the pairs
attention had to read, `prefill_selected_pairs_total`) / device seconds
of `paged_prefill` / the chip's peak bf16 FLOP/s. The masked prefill
this program runs computes every causal pair of a chunk and drops what
was not selected, pads a chunk to 1,024 rows and a table to its bucket:
all of that reads as a lower share.

None where the slice prefilled nothing, has no such counters (a program
without an indexer) or the trace no prefill dispatch."""
LAYER = "model step"
from benchmark import program, trace

PROGRAMS = ("paged_prefill",)


def read(run):
    from benchmark import costs_dsa

    tr = run.get("trace") or {}
    sec, _ = trace.match_seconds(tr.get("modules", {}), PROGRAMS)
    sc = tr.get("slice_counters", {})
    tokens = sc.get("prefill_tokens_total")
    sel = sc.get("prefill_selected_pairs_total")
    if not sec or not tokens or not sel:
        return None
    pairs = sc.get("moe_prefill_pairs_total")
    held = sc.get("moe_prefill_held_rows_total", 0.0) / pairs if pairs else 0.0
    need = costs_dsa.prefill_flops(
        run["config"], tokens=tokens,
        index_pairs=sc.get("prefill_index_pairs_total", 0.0),
        selected_pairs=sel, held_share=held)
    peak = program.load_peaks()[run["device"]["kind"]]["bf16_flops_per_s"]
    return 100.0 * need / sec / peak
