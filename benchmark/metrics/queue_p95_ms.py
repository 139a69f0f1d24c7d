"""Scheduler admission: 95th percentile of `t_admitted` (the program's
stamp) minus due time, over the requests that were admitted."""
from benchmark.harness.stats import percentile


def read(run):
    return percentile([(r["admitted"] - r["due"]) * 1e3 for r in run["rows"]
                       if r["admitted"] is not None], 95)
