"""Scheduler loop: all `StepPhaseProfiler` phase seconds inside the window
over its iterations (host clock on the scheduler thread, the blocking
readback included). The per-phase split goes into the breakdown."""


def read(run):
    w = run["window"]
    if not w["iterations"]:
        return None
    return sum(w["phase_seconds"].values()) / w["iterations"] * 1e3
