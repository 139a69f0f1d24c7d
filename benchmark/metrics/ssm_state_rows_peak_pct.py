"""Cache manager: the most state rows that held a request at once inside the
window, over the engine's `n_slots`. A net with state-space layers keeps a
row of fixed size a slot beside the paged pool, so what fills first is one
of two things: the pool's blocks (`nemo.kv_pool_peak_pct`) or these rows,
one to each slot that holds a request (the program's gauge
`decode_active_slots`). The harness hands a reader the window's counters
and no gauge, and a gauge's own high-water mark is its process's, warm-up
included; so the window's maximum is rebuilt from the program's stamps that
the gauge moves by, a request's admission into a slot (`t_admitted`) and
its end, as `kv_pool_peak_pct` rebuilds the pool's, and read only where
the program counts state rows at all (`ssm_rows_bucket_total`). A program
without state rows reads nothing."""


def read(run):
    w = run["window"]
    if not w["counters"].get("ssm_rows_bucket_total"):
        return None
    t0, t1 = w["t0"], w["t0"] + w["seconds"]
    events = []
    for r in run["rows"]:
        if r["admitted"] is None or r["admitted"] > t1:
            continue
        events.append((max(r["admitted"], t0), 1))
        if r["done"] is not None and r["done"] <= t1:
            events.append((r["done"], -1))
    peak = cur = 0
    for _, d in sorted(events):
        cur += d
        peak = max(peak, cur)
    slots = run["geometry"]["n_slots"]
    return 100.0 * peak / slots if events and slots else None
