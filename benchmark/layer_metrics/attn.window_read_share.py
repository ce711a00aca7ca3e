"""Model step: cached tokens a window layer's decode rows read as a
share of what a global layer's read, % (`decode_window_kv_tokens_total
/ decode_kv_tokens_total`, the window's sum of min(length, window) over
the sum of lengths). 100 while no lane has passed the window, ~43 for a
lane at 9.5k positions; LOWER is the mechanism more engaged.

Reads run["counters"]. None where the program has no such counter (no
window layers) or no lane decoded."""
LAYER = "model step"


def read(run):
    c = run["counters"]
    window = c.get("decode_window_kv_tokens_total")
    whole = c.get("decode_kv_tokens_total")
    return 100.0 * window / whole if whole and window is not None else None
