"""Model step: device time of the prefill program per 1000 prefilled
tokens (trace slice; tokens from the counters scraped at its ends)."""
LAYER = "model step"
from benchmark import trace

PROGRAMS = ("paged_prefill",)


def read(run):
    sec, _ = trace.match_seconds(run["trace"]["modules"], PROGRAMS)
    toks = run["trace"]["slice_counters"].get("prefill_tokens_total")
    return 1e3 * sec / (toks / 1e3) if sec and toks else None
