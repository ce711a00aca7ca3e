"""Model step: the expert layers' share of the step program's device
time, %: self seconds under the `moe` scope (norm, router, the routed
experts' grouped products `moe/moe_routed`, the shared expert
`moe/moe_shared`, the combine) / the summed self seconds of the step
program.

None where the step program has no expert layer, or the capture names
no scopes (`scope_table.table`)."""
LAYER = "model step"
from benchmark import scope_table


def read(run):
    return scope_table.share(run, ("moe",))
