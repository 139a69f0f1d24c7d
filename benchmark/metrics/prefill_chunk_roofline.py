"""Prefill program's share of its roofline, over the traced interval: the
least time for the chunks the program's `prefill_chunk` spans report there
(real tokens, real depth, head only on a prompt's last chunk) over the
device time of the prefill module's executions in the trace."""
from benchmark.harness import facts, peaks, reducer

# jit of DecodeScheduler._prefill_paged_fn
MODULE = r"^jit__prefill_paged_fn$"


def read(run):
    tr = facts.traced(run)
    if tr is None or run["peaks"] is None:
        return None
    summary, t_on, t_off = tr
    n, seconds = reducer.module_seconds(summary, MODULE)
    chs = facts.chunks(run, t_on, t_off)
    if not n or not chs or seconds <= 0:
        return None
    f, b = facts.prefill_work(run, chs)
    return 100.0 * peaks.least_seconds(f, b, run["peaks"]) / seconds
