"""Model step: learned sparse attention's share of the decode program's
device time, %: self seconds under `attn/dsa_index` (the index scores),
`attn/dsa_select` (the exact top k) and `attn/dsa_attend` (the row
gather and the attention over the selected rows) / the summed self
seconds of `paged_decode_chunk`. By scope, so it holds whatever ops a
stage is made of this compile: the gathers, the counting loops, a
kernel that replaced a sort.

None where the decode program has no indexer, or the capture names no
scopes (`scope_table.table`)."""
LAYER = "model step"
from benchmark import scope_table

STAGES = ("attn/dsa_index", "attn/dsa_select", "attn/dsa_attend")


def read(run):
    return scope_table.share(run, STAGES, ("paged_decode_chunk",))
