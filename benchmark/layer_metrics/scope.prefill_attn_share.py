"""Model step: the attention sublayers' share of the PREFILL program's
device time, %: self seconds under the `attn` scope / the summed self
seconds of `paged_prefill` in the traced slice. Beside
`scope.attn_share*` (the decode or block step's) it says which program
a long context costs.

None where the slice holds no prefill chunk, or the capture names no
scopes (`scope_table.table`)."""
LAYER = "model step"
from benchmark import scope_table


def read(run):
    return scope_table.share(run, ("attn",), scope_table.PREFILL)
