"""Scheduler loop: the longest booked iteration whose begin lies in the
window, from the program's ring: one `sched_iter` record per iteration
(PR 38), written at its close, whose `end` is its length (it began at the
record's time less `end`). The record also holds the iteration's phases as
offsets from its begin (`phases`) and the thread's CPU seconds (`cpu_s`, in
the host's 10 ms ticks on the v5e), so the phase a stall fell in, and
whether the thread burned it or was held off the CPU, are there for whoever
looks. Nothing to read where the program keeps no such record."""


def read(run):
    w = run["window"]
    t_hi = w["t0"] + w["seconds"]
    ends = [s["end"] for s in w["spans"]
            if s["name"] == "sched_iter" and "end" in s
            and w["t0"] <= s["t"] - s["end"] <= t_hi]
    return 1e3 * max(ends) if ends else None
