"""Cache manager: share of prompt tokens served from the prefix cache
over the window, hit / (hit + prefilled), %. 0 means nothing hit."""
LAYER = "cache manager"


def read(run):
    c = run["counters"]
    hit, pre = c["prefix_cache_hit_tokens_total"], c["prefill_tokens_total"]
    return 100.0 * hit / (hit + pre) if hit + pre else None
