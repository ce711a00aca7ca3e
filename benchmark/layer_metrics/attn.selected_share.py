"""Model step: how sparse the window's decode reads were, %: latent rows
the decode rows read / index keys they scored, a cache layer
(`decode_selected_tokens_total / decode_kv_tokens_total`): index_topk
over the live lanes' mean length where every lane is past it, 100 where
none is. Dense latent attention reads the denominator.

Reads run["counters"]. None where the program has no such counter (no
indexer)."""
LAYER = "model step"


def read(run):
    c = run["counters"]
    rows, keys = (c.get("decode_selected_tokens_total"),
                  c.get("decode_kv_tokens_total"))
    return 100.0 * rows / keys if keys and rows is not None else None
