"""Device: share of the traced interval in which no operation ran on the
chip (1 - union of device-op intervals over the traced window)."""
from benchmark.harness import facts


def read(run):
    tr = facts.traced(run)
    if tr is None:
        return None
    s = tr[0]
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
