"""Scheduler loop: time per iteration in which the scheduler's thread is
blocked until the device is done: the `StepPhaseProfiler` phases whose name
ends in `_wait`, over the window's iterations. Set it beside the trace's busy
seconds per iteration. Nothing to read where the program has no such phase."""


def read(run):
    w = run["window"]
    waits = [s for k, s in w["phase_seconds"].items() if k.endswith("_wait")]
    if not waits or not w["iterations"]:
        return None
    return sum(waits) / w["iterations"] * 1e3
