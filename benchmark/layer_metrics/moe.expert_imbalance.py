"""Model step: how unevenly the router loads the experts: rows of the
fullest expert / mean rows an expert, summed over the window's
layer-forwards (`moe_expert_rows_max_total /
moe_expert_rows_mean_total`). 1 is perfectly even; the grouped
product's time follows the rows, its bytes the experts hit.

Reads run["counters"]. None where the program has no such counter or no
row was routed."""
LAYER = "model step"


def read(run):
    c = run["counters"]
    mean = c.get("moe_expert_rows_mean_total")
    top = c.get("moe_expert_rows_max_total")
    return top / mean if mean and top is not None else None
