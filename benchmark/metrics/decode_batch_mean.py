"""Scheduler loop: live slots per decode step = decode-step tokens released
in the window over the decode dispatches the profiler counted in it."""
from benchmark.harness.facts import decode_depths


def read(run):
    w = run["window"]
    steps = w["dispatches"].get("decode", 0)
    if not steps:
        return None
    return len(decode_depths(run["rows"], w["t0"], w["t0"] + w["seconds"])) \
        / steps
