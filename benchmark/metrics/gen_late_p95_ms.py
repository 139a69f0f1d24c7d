"""Load generator: 95th percentile of send time minus due time. A starved
generator must not read as a fast server."""
from benchmark.harness.stats import percentile


def read(run):
    return percentile([(r["sent"] - r["due"]) * 1e3 for r in run["rows"]
                       if r["sent"] is not None], 95)
