"""Scheduler loop: the share of device 0's idle seconds in which the
scheduler's thread was launching a program (`decode_launch`,
`prefill_launch`): the jit call, its uploads and what the host does before
it.

The one implementation of the three idle readers (`idle_in_read_pct.py` and
`idle_unbooked_pct.py` load this file). Idle seconds are the gaps between
device 0's program executions (`summary["modules"]`, the reducer's own
definition of a gap), counted from the first booked iteration that lies in
the traced interval to the last. What the thread was doing comes from the
program's ring: one `sched_iter` record per booked iteration (PR 38),
written at the iteration's close: its `end` is the iteration's length, so
the iteration began at the record's time less `end`, and its phases and
dispatches are offsets from that begin.

The ring's clock (`time.monotonic`) and the trace's share no origin. The
first guess is that trace time 0 is `t_on`, the instant `start_trace`
returned (on the v5e the trace's clock already reads 40-50 ms there);
causality corrects it. A program starts after its dispatch was stamped
(`count`, after the launch's uploads and before its jit call), and ends
before the end of the first `*_wait` of its iteration that follows the
dispatch (the
device runs one program at a time, in order). A family's k-th execution in
the trace is its (k + shift)-th dispatch on the ring: for each shift the
bounds leave an interval for the correction, and a shift whose interval is
empty is not the one. The families' intervals are intersected and the
widest is kept (a wrong shift fits only where iterations are as regular as
the interval is wide, and fits narrower). The correction is the interval's
low end. That end is set by the one iteration, of hundreds, whose thread
woke soonest after its program ended, so it lies within the shortest wake
(microseconds) of the truth; the high end is set by the shortest jit call,
which always stands between the stamp and the program's start (1.2-1.7 ms
a launch on the v5e, PERF.md section 5). The shares are therefore exact to the wake, and
the interval's width (`slack_us`, PERF.md section 5) bounds how far any
other choice could move them. Modules that no dispatch stamps
(`jit__zero_fn`, `jit__sumtab_fn`) are split but do not bound. Nothing to
read without a trace, without a booked iteration in it, or when the bounds
cross or leave the low end open."""
import bisect
import re

from benchmark.harness import facts

# the family a dispatch is stamped under -> the program it launches
PROGRAMS = {"decode": re.compile(r"^jit__step(_paged)?_fn$"),
            "prefill": re.compile(r"^jit__prefill(_paged)?_fn$")}
LAUNCH = ("decode_launch", "prefill_launch")


def kind(phase):
    """Which of the three shares a booked phase's idle seconds go to."""
    if phase in LAUNCH:
        return "launch"
    if phase.endswith("_read") or phase == "accept":
        return "read"
    return "other"


def iterations(spans):
    """The ring's `sched_iter` records: (begin, end, phases [(start, end,
    name)], dispatches [(family, instant, end of the wait after it or
    None)]), on the ring's clock, in order."""
    out = []
    for s in spans:
        if s["name"] != "sched_iter" or "phases" not in s:
            continue
        t = s["t"] - s["end"]
        marks = [(name, t + off) for name, off in s["phases"]]
        ends = [b for _, b in marks[1:]] + [t + s["end"]]
        phases = [(a, b, name) for (name, a), b in zip(marks, ends)]
        waits = [b for a, b, name in phases if name.endswith("_wait")]
        starts = [a for a, b, name in phases if name.endswith("_wait")]
        disp = []
        for family, _, off in s["dispatches"]:
            d = t + off
            i = bisect.bisect_left(starts, d)
            disp.append((family, d, waits[i] if i < len(waits) else None))
        out.append((t, t + s["end"], phases, disp))
    return out


def shifts(disp, progs, t_on):
    """[(low, high)] of the correction c (trace s = ring s - t_on + c) for
    each shift under which program k is dispatch k + shift and the bounds
    leave an interval."""
    out = []
    for k in range(len(disp) - len(progs) + 1):
        low, high = -float("inf"), float("inf")
        for (start, end), (d, w) in zip(progs, disp[k:]):
            high = min(high, start - (d - t_on))
            if w is not None:
                low = max(low, end - (w - t_on))
            if low > high:
                break
        if low <= high:
            out.append((low, high))
    return out


def align(iters, modules, t_on):
    """(low, high) of the correction, or None."""
    options = []
    for family, rx in PROGRAMS.items():
        progs = [(m["start_ns"] / 1e9, m["start_ns"] / 1e9 + m["seconds"])
                 for m in modules if rx.search(m["name"])]
        if progs:
            disp = [(d, w) for _, _, _, ds in iters for f, d, w in ds
                    if f == family]
            options.append(shifts(disp, sorted(progs), t_on))
    if not options:
        return None
    fits = options[0]
    for other in options[1:]:
        fits = [(max(a, c), min(b, d)) for a, b in fits for c, d in other
                if max(a, c) <= min(b, d)]
    fits = [f for f in fits if f[0] != -float("inf")]
    return max(fits, key=lambda f: f[1] - f[0], default=None)


def gaps(modules):
    """[(start, end)] s between program executions, in the trace's seconds."""
    out, edge = [], None
    for s, e in sorted((m["start_ns"] / 1e9,
                        m["start_ns"] / 1e9 + m["seconds"])
                       for m in modules):
        if edge is not None and s > edge:
            out.append((edge, s))
        edge = e if edge is None else max(edge, e)
    return out


def split(run):
    """Idle seconds of the traced iterations by `kind` of the booked phase
    that covers them, `unbooked` where none does; with `total` and the
    alignment's `slack_us` (the width of the causal interval). None where
    there is nothing to read."""
    tr = facts.traced(run)
    if tr is None:
        return None
    summary, t_on, _ = tr
    modules = summary.get("modules") or []
    iters = iterations(run["window"]["spans"])
    if not modules or not iters:
        return None
    bounds = align(iters, modules, t_on)
    if bounds is None:
        return None
    c = bounds[0]
    lo = min(m["start_ns"] for m in modules) / 1e9
    hi = max(m["start_ns"] / 1e9 + m["seconds"] for m in modules)
    shift = c - t_on                  # ring s + shift = trace s
    inside = [it for it in iters
              if it[0] + shift >= lo and it[1] + shift <= hi]
    if not inside:
        return None
    t0, t1 = inside[0][0] + shift, inside[-1][1] + shift
    spans = [(a + shift, b + shift, name)
             for it in inside for a, b, name in it[2]]
    starts = [a for a, _, _ in spans]
    out = {"launch": 0.0, "read": 0.0, "other": 0.0, "unbooked": 0.0}
    total = 0.0
    for g0, g1 in gaps(modules):
        g0, g1 = max(g0, t0), min(g1, t1)
        if g1 <= g0:
            continue
        total += g1 - g0
        left = g1 - g0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(spans) and spans[i][0] < g1:
            a, b, name = spans[i]
            cut = min(b, g1) - max(a, g0)
            if cut > 0:
                out[kind(name)] += cut
                left -= cut
            i += 1
        out["unbooked"] += max(0.0, left)
    return {**out, "total": total,
            "slack_us": (bounds[1] - bounds[0]) * 1e6,
            "correction_ms": c * 1e3}


def share(run, key):
    got = split(run)
    if got is None:
        return None
    return 100.0 * got[key] / got["total"] if got["total"] > 0 else 0.0


def read(run):
    return share(run, "launch")
