"""Model step: what the step program spends around its layers, %: self
seconds under `embed` (the token embedding, the splice), `head` (final
norm, the vocabulary product, the logits' slice), `sample` (sampler,
stop matching, the counters, seating the token) and `loss` (the train
step's) / the summed self seconds of the step program.

None where the capture names no scopes (`scope_table.table`)."""
LAYER = "model step"
from benchmark import scope_table


def read(run):
    return scope_table.share(run, ("embed", "head", "sample", "loss"))
