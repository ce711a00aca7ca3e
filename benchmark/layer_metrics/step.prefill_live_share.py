"""Model step: what a prefill chunk's attention needs of what it is
handed, %: `prefill_attn_pairs_total` (the causal query-key pairs of
the chunks' real tokens) / `prefill_table_positions_total` (dispatched
rows x the positions of the block table the program ran with), over the
window. The causal half of a full table reads 50 %; a short suffix
against a long cached prefix whose table is cut to its bucket reads
prefix / bucket; the same suffix against the whole max_ctx table reads
prefix / max_ctx.

None where the program has no such counters (before PR 33)."""
LAYER = "model step"


def read(run):
    c = run["counters"]
    pairs, table = (c.get("prefill_attn_pairs_total"),
                    c.get("prefill_table_positions_total"))
    if pairs is None or not table:
        return None
    return 100.0 * pairs / table
