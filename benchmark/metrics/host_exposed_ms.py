"""Scheduler loop: time per iteration in which the scheduler's thread is NOT
waiting on the device: every `StepPhaseProfiler` phase whose name does not
end in `_wait`, over the window's iterations. An upper estimate of the
device's idle time per iteration (a prefill chunk in flight overlaps the host
work that follows its launch); with `device_wait_ms` it adds up to
`sched_iter_ms`. Nothing to read where the program does not tell its waits
apart (no phase ends in `_wait`)."""


def read(run):
    w = run["window"]
    ph = w["phase_seconds"]
    if not any(k.endswith("_wait") for k in ph) or not w["iterations"]:
        return None
    return sum(s for k, s in ph.items() if not k.endswith("_wait")) \
        / w["iterations"] * 1e3
