"""Kernels, decode: how much of what decode tokens attend over is the
compressed part. The program counts, from host-side depths, the exact rows of
the open window (`eva_rows_exact_total`) and the chunk-summary rows of closed
windows (`eva_rows_summary_total`) each decode token attends over; the
summaries' share of both, inside the window. A program without the counters
reads nothing."""


def read(run):
    c = run["window"]["counters"]
    summary, exact = c.get("eva_rows_summary_total"), \
        c.get("eva_rows_exact_total")
    if summary is None or exact is None or summary + exact <= 0:
        return None
    return 100.0 * summary / (summary + exact)
