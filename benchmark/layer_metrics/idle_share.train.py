"""Device: 1 - (union of device-op intervals / traced slice), %."""
LAYER = "device"


def read(run):
    t = run["trace"]
    if not t.get("busy_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
