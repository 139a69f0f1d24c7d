"""Scheduler loop: time per iteration spent copying results device -> host
and converting them (the `[slots, vocab]` probabilities of a decode step, a
prompt's last chunk): the `StepPhaseProfiler` phases whose name ends in
`_read`, over the window's iterations. Nothing to read where the program does
not tell the copy from the wait (no phase ends in `_wait`)."""


def read(run):
    w = run["window"]
    ph = w["phase_seconds"]
    if not any(k.endswith("_wait") for k in ph) or not w["iterations"]:
        return None
    return sum(s for k, s in ph.items() if k.endswith("_read")) \
        / w["iterations"] * 1e3
