"""Device: the instrument's own alarm, %: self seconds of the ops of ALL
programs that lie under no scope of the vocabulary (`unscoped`: no
op_name, or none of its components a layer's name) / the slice's
`busy_s`. Near 100: the executables came out of a compile cache filled
before the scopes were written (`scope_table`'s note), and no `scope.*`
share of this run means anything.

None where there is no table to read (`scope_table.table`: no capture,
no device plane, a program older than the vocabulary) or the device
was never busy."""
LAYER = "device"
from benchmark import scope_table


def read(run):
    tab = scope_table.table(run)
    busy = (run.get("trace") or {}).get("busy_s")
    if not tab or not busy:
        return None
    return 100.0 * sum(
        paths.get(scope_table.UNSCOPED, (0.0, 0))[0]
        for paths in tab.values()) / busy
