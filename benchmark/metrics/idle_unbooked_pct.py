"""Scheduler loop: the share of device 0's idle seconds that no booked phase
of the scheduler's thread covers: between iterations, the idle wait for a
request, and loop code outside the profiler. 100 less the three shares is the
other booked phases (`admit`, `pool`, `roll`, the `*_wait`s, `flush`). The
arithmetic, and how the ring is aligned to the trace, are
`idle_in_launch_pct.py`'s, beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

_split = module_at("_metric_idle_in_launch_pct",
                   Path(__file__).with_name("idle_in_launch_pct.py"))


def read(run):
    return _split.share(run, "unbooked")
