"""Model step: share of the held experts that took at least one row, a
layer-forward of a decode step, over the window, %
(`moe_held_experts_hit_total / moe_held_expert_slots_total`). It sizes
the expert bytes a step reads: with 64 lanes x 12 pairs over 768 router
outputs a held expert is hit with probability 1 - (1 - 1/768)^768 = 63
% when routing is even.

None where the program has no such counter."""
LAYER = "model step"


def read(run):
    c = run["counters"]
    hit = c.get("moe_held_experts_hit_total")
    slots = c.get("moe_held_expert_slots_total")
    return 100.0 * hit / slots if slots and hit is not None else None
