"""Model step: share of the decode steps' (token, expert) pairs that
went to a zero-compute expert, over the window, %
(`moe_zero_pairs_total / moe_pairs_total`): work the expert layer does
without a product. 256 of 768 router outputs are zero-compute, a third
when routing is even.

None where the program has no such counter."""
LAYER = "model step"


def read(run):
    c = run["counters"]
    zero, pairs = c.get("moe_zero_pairs_total"), c.get("moe_pairs_total")
    return 100.0 * zero / pairs if pairs and zero is not None else None
