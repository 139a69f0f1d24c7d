"""Cache manager: host milliseconds per window roll. The scheduler books the
return of a closed window's exact pages to the pool under the profiler phase
`roll` and marks each with a `window_roll` span on its track (request, window,
blocks freed); the phase's seconds inside the window over the spans begun in
it. A program without the phase or the span reads nothing."""


def read(run):
    w = run["window"]
    seconds = w["phase_seconds"].get("roll")
    rolls = sum(1 for s in w["spans"] if s["name"] == "window_roll")
    if seconds is None or not rolls:
        return None
    return 1e3 * seconds / rolls
