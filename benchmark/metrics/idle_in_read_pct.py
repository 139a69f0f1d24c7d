"""Scheduler loop: the share of device 0's idle seconds in which the
scheduler's thread was in a `*_read` phase or in `accept`: the crossing of a
result to the host and the sampling of its probabilities in NumPy (ROADMAP
Speed item 2's target). The arithmetic, and how the ring is aligned to the
trace, are `idle_in_launch_pct.py`'s, beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

_split = module_at("_metric_idle_in_launch_pct",
                   Path(__file__).with_name("idle_in_launch_pct.py"))


def read(run):
    return _split.share(run, "read")
