"""Kernels, decode: how many of the per-slot state rows its decode dispatches
name a step really reads and writes. A state-space layer keeps one row of
fixed size a slot (`ssm` and `conv`), and a decode program is compiled for
every slot. The program counts the rows in every decode dispatch
(`ssm_rows_bucket_total`: `n_slots` a dispatch) and the rows whose state the
dispatch read and wrote (`ssm_rows_stepped_total`, by the layer's one static
rule: every slot's while the step computes all lanes and selects, the fed
slots' once it steps those alone); stepped over named, inside the window.
100 means every slot's state is read and written whatever is live. A program
without the counters reads nothing."""


def read(run):
    c = run["window"]["counters"]
    named, got = c.get("ssm_rows_bucket_total"), \
        c.get("ssm_rows_stepped_total")
    if named is None or got is None or named <= 0:
        return None
    return 100.0 * got / named
