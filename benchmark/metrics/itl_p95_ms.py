"""95th percentile over all gaps between consecutive output tokens of all
requests, stamped by the sink passed as `submit(stream=...)` at the moment
the scheduler releases each token (host clock)."""
from benchmark.harness.stats import gaps, percentile


def read(run):
    return percentile([g * 1e3 for r in run["rows"]
                       for g in gaps(r["stamps"])], 95)
