"""Cache manager: share of the window's streams that began from a
page-edge snapshot of the conv state and not from zeros,
`conv_state_handovers_total / (conv_state_handovers_total +
conv_state_resets_total)`, %. A stream begins at every admission (a
replay after an eviction is one more). 0 means no hit handed a state
over.

Reads run["counters"]. None where the program has no such counter (no
conv state) or no stream began."""
LAYER = "cache manager"


def read(run):
    c = run["counters"]
    handed = c.get("conv_state_handovers_total")
    fresh = c.get("conv_state_resets_total")
    if handed is None or fresh is None or not handed + fresh:
        return None
    return 100.0 * handed / (handed + fresh)
