"""Model step: the attention sublayers' share of the step program's
device time, %: self seconds of the ops traced under the `attn` scope
(the sublayer whole: its norm, the q/k/v, latent and index projections,
rope, the cache write, the kernel or XLA attention, `o_proj`; a backward
op under its forward's scope) / the summed self seconds of the step
program (`scope_table.STEP_PROGRAMS`: the block step, the decode chunk
or the train step).

None where the capture names no scopes (`scope_table.table`)."""
LAYER = "model step"
from benchmark import scope_table


def read(run):
    return scope_table.share(run, ("attn",))
