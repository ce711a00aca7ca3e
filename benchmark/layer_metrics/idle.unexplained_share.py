"""Device: the part of the traced slice on which the profiler and the
engine disagree about whether the device had work, % of the slice:
abs(the device's idle share by the trace, 1 - busy_s / window_s, minus
the engine's own account of it, (starved + `idle` seconds) / the sum of
the phase seconds). The instrument checking itself: 0 when every idle
second is one the engine counted as starved or request-less; it grows
where the engine believes the device busy while it is not (a short
program that ends before the next enqueue) or the other way round.

The second share comes from run["trace"]["slice_counters"] alone (the
/metrics deltas between the two scrapes beside the capture's start and
stop; they sit a few ms inside the capture, so shares are compared and
not seconds; a phase open at a scrape is billed at its end). None
where there is no device plane (the CPU rehearsal), where the program
has no starved counter (a parent before PR 35) or where no phase second
passed."""
LAYER = "device"


def read(run):
    t = run["trace"]
    c = t.get("slice_counters") or {}
    if not t.get("busy_s") or "engine_starved_seconds_total" not in c:
        return None
    window = c["engine_phase_seconds_total"]
    if not window:
        return None
    counted = c["engine_starved_seconds_total"] + c.get(
        'engine_phase_seconds_total{phase="idle"}', 0.0)
    return 100.0 * abs((1.0 - t["busy_s"] / t["window_s"]) - counted / window)
