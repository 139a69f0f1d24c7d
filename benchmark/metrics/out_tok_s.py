"""Output tokens released inside the window over the window's seconds: all
the work and all the time of the window (host clock)."""
from benchmark.harness.stats import rate


def read(run):
    t0, s = run["window"]["t0"], run["window"]["seconds"]
    n = sum(1 for r in run["rows"] for t in r["stamps"] if t0 <= t <= t0 + s)
    return rate(n, s)
