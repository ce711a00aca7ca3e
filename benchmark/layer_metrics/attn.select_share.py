"""Model step: the selection mechanism's share of the decode program's
device time, %: summed self time of the index scores (`_dsa_index`), the
top k (XLA's `sort` ops: `jax.lax.top_k` of a [lanes, table] block of
scores lowers to ONE stable sort of it and a second of the k indices;
the router's and the pair sort's [lanes, 256] sorts are in the sum and
are thousandths of it), the row read (XLA's gather) and the attention
over the selected rows (`_latent_paged`) / the time of
`paged_decode_chunk` in the traced slice. What is left is the weights:
projections, the dense layer, the shared and held experts, the head.

None where the trace has no index-score kernel (a program without an
indexer) or no decode dispatch."""
LAYER = "model step"
from benchmark import trace

STAGES = ("_dsa_index", "sort", "gather", "_latent_paged")
PROGRAMS = ("paged_decode_chunk",)


def read(run):
    tr = run.get("trace") or {}
    ops = tr.get("ops", {})
    if not trace.match_seconds(ops, STAGES[:1])[0]:
        return None
    sec, _ = trace.match_seconds(tr.get("modules", {}), PROGRAMS)
    return 100.0 * trace.match_seconds(ops, STAGES)[0] / sec if sec else None
