"""Device: share of the listed idle time that carries a name, %: 1 -
seconds labelled "unattributed" / seconds of all listed idle gaps. A
gap is named by the shortest host event of the capture that covers
half of it (trace._covering_span): the program's phases
(`oryx.engine.*`, `oryx.train.*`) or PJRT's own events.

Reads run["trace"]["idle_gaps"] ([[label, seconds]], the ten labels
with most time). None where there is no device plane (the CPU
rehearsal) or no gap."""
LAYER = "device"


def read(run):
    gaps = run["trace"].get("idle_gaps")
    total = sum(s for _, s in gaps or ())
    if not total:
        return None
    unnamed = sum(s for label, s in gaps if label == "unattributed")
    return 100.0 * (1.0 - unnamed / total)
