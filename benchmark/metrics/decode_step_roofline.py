"""Decode program's share of its roofline, over the traced interval: the
least time the chip could take for the decode steps' work (weights once a
step, each live slot's cache at its real depth; the `work.py` of the
configuration's family) over the device time of the decode module's
executions in the trace."""
from benchmark.harness import facts, peaks, reducer

# XLA module name of the decode program today (jit of
# DecodeScheduler._step_paged_fn); a rename is repaired here.
MODULE = r"^jit__step_paged_fn$"


def read(run):
    tr = facts.traced(run)
    if tr is None or run["peaks"] is None:
        return None
    summary, t_on, t_off = tr
    n, seconds = reducer.module_seconds(summary, MODULE)
    depths = facts.decode_depths(run["rows"], t_on, t_off)
    if not n or not depths or seconds <= 0:
        return None
    f, b = facts.decode_work(run, depths, n, t_on, t_off)
    return 100.0 * peaks.least_seconds(f, b, run["peaks"]) / seconds
