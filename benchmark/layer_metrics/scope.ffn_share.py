"""Model step: the dense MLP sublayers' share of the step program's
device time, %: self seconds under the `ffn` scope (a SwiGLU with its
norm and residual; a leading dense layer's shows as `ffn/dense_ffn`) /
the summed self seconds of the step program.

None where the step program runs no dense MLP, or the capture names no
scopes (`scope_table.table`)."""
LAYER = "model step"
from benchmark import scope_table


def read(run):
    return scope_table.share(run, ("ffn",))
