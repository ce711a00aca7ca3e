"""Model step: the state layers' share of the step program's device
time, %: self seconds under the `mixer` scope (a state-space or
short-convolution sublayer with its norm, the read of its state and
the write-back: `mixer/mamba`, `mixer/ssm_step`, `mixer/short_conv`) /
the summed self seconds of the step program.

None where the step program has no state layer, or the capture names
no scopes (`scope_table.table`)."""
LAYER = "model step"
from benchmark import scope_table


def read(run):
    return scope_table.share(run, ("mixer",))
