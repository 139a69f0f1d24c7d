"""Device idle time by what the scheduler's thread was doing meanwhile.

The reducer names an idle gap by the program that ended it. This tool reads
the same gaps (device 0, between executions on the `XLA Modules` line) and
splits each over the `sched/<phase>` annotations that
`StepPhaseProfiler` writes on the scheduler thread's host line
(`/host:CPU`, the line that holds the `sched_iter` steps), remainder
`unannotated`. Not run by the driver: a `benchmark` issue moves this into
`harness/reducer.py`, which deletes the trace before a reader could see it.

    python3 benchmark/tools/idle_by_phase.py --workload <cell> --seconds 45 --keep chiprun_out/trace
    python3 benchmark/tools/idle_by_phase.py --xplane <file.xplane.pb>

The two planes of one trace do not share an origin to the microsecond: on
the v5e every program starts about 0.8 ms BEFORE the runtime issues it, by
the trace's own stamps. So the host's spans are shifted first, by what
causality allows: a program starts after the runtime issues it
(`tpu::System::Execute=>IssueSequencedEvent`) and after the call that
launched it begins (`PjitFunction(_step_paged_fn)`); it ends before the
runtime hears of it (`tpu::System::Execute=>Done`) and before the wait for
it returns (`sched/decode_wait`). The shift is the middle of the interval
those leave, and the interval is printed. Gaps are counted from the first
recorded `sched_iter` to the last: an iteration that began before the trace
did has no annotation."""
import argparse
import bisect
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import start  # noqa: E402

HOST_PLANE = "/host:CPU"
STEP_LAUNCH = "PjitFunction(_step_paged_fn)"
STEP_MODULE = "jit__step_paged_fn"
STEP_WAIT = "sched/decode_wait"
ISSUED = "tpu::System::Execute=>IssueSequencedEvent"
DONE = "tpu::System::Execute=>Done"
REST = "unannotated"


def split_gaps(gaps, spans):
    """Seconds of `gaps` [(start, end)] under each name of `spans`
    [(start, end, name)], which do not overlap one another; what no span
    covers goes to `unannotated`. All times in one unit; returns that unit."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    out = {}
    for g0, g1 in gaps:
        left = g1 - g0
        i = max(0, bisect.bisect_right(starts, g0) - 1)
        while i < len(spans) and spans[i][0] < g1:
            s, e, name = spans[i]
            cut = min(e, g1) - max(s, g0)
            if cut > 0:
                out[name] = out.get(name, 0) + cut
                left -= cut
            i += 1
        if left > 0:
            out[REST] = out.get(REST, 0) + left
    return out


def idle_gaps(modules):
    """[(start, end)] between program executions [(start, end, name)]: the
    reducer's definition of a gap."""
    gaps, edge = [], None
    for s, e, _ in sorted(modules):
        if edge is not None and s > edge:
            gaps.append((edge, s))
        edge = e if edge is None else max(edge, e)
    return gaps


def causal_bounds(before, after, programs):
    """(low, high) of what may be added to the host's times, from program
    executions [(start, end)] on the device's clock and two lists of host
    instants in the programs' order: `before[k]` precedes the start of its
    program, `after[k]` follows the end of its program. Either list may lack
    entries at the trace's edges, so an instant is matched to the first
    program that starts (the last that ends) within a slack of it: 5 ms, or
    half the instants' median spacing where that is less. A match that misses
    falls on a later program (an earlier one), which widens the interval and
    never makes it wrong."""
    def slack(ts):
        steps = sorted(b - a for a, b in zip(ts, ts[1:]))
        return min(steps[len(steps) // 2] / 2, 5e6) if steps else 5e6

    starts = [p[0] for p in programs]
    ends = [p[1] for p in programs]
    low, high = -float("inf"), float("inf")
    k, sl = 0, slack(before)
    for t in before:
        k = max(k, bisect.bisect_left(starts, t - sl))
        if k == len(programs):
            break
        high = min(high, starts[k] - t)
        k += 1
    k, sl = len(programs) - 1, slack(after)
    for t in reversed(after):
        k = min(k, bisect.bisect_right(ends, t + sl) - 1)
        if k < 0:
            break
        low = max(low, ends[k] - t)
        k -= 1
    return low, high


def read_planes(path):
    """Device 0's program executions; from the scheduler's host line the
    `sched/*` spans, the `sched_iter` steps and the decode launches; from the
    runtime's lines the instants a program is issued and reported done."""
    from jax.profiler import ProfileData
    from benchmark.harness import reducer

    data = ProfileData.from_file(path)
    dev = next((p for p in data.planes
                if reducer.DEVICE_PLANE.match(p.name)), None)
    modules = []
    if dev is not None:
        modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                          reducer.module_name(e.name))
                         for line in dev.lines
                         if line.name == reducer.MODULES_LINE
                         for e in line.events)
    host = {"spans": [], "iters": [], "launches": [], "issued": [],
            "done": []}
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            evs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events)
            host["issued"] += [s for s, _, n in evs if n == ISSUED]
            host["done"] += [s for s, _, n in evs if n == DONE]
            if not any(n == "sched_iter" for _, _, n in evs):
                continue
            host["iters"] += [(s, e) for s, e, n in evs if n == "sched_iter"]
            host["spans"] += [ev for ev in evs if ev[2].startswith("sched/")]
            end = 0           # the call is recorded twice, nested
            for s, e, n in evs:
                if n == STEP_LAUNCH and s >= end:
                    host["launches"].append(s)
                    end = e
    for key in ("issued", "done"):
        host[key].sort()
    return modules, host


def report(path, longest=5):
    modules, host = read_planes(path)
    if not modules:
        return None
    steps = [(s, e) for s, e, n in modules if n == STEP_MODULE]
    bounds = [
        causal_bounds(host["issued"], host["done"],
                      [(s, e) for s, e, _ in modules]),
        causal_bounds(host["launches"],
                      [e for _, e, n in host["spans"] if n == STEP_WAIT],
                      steps)]
    low = max(b[0] for b in bounds)
    high = min(b[1] for b in bounds)
    finite = [b for b in (low, high) if abs(b) != float("inf")]
    shift = sum(finite) / len(finite) if finite else 0.0
    spans = [(s + shift, e + shift, n) for s, e, n in host["spans"]]
    gaps = idle_gaps(modules)
    if host["iters"]:
        t0 = host["iters"][0][0] + shift
        t1 = host["iters"][-1][1] + shift
        inside = [(max(s, t0), min(e, t1)) for s, e in gaps
                  if min(e, t1) > max(s, t0)]
    else:
        inside = gaps
    by = split_gaps(inside, spans)
    total = sum(e - s for s, e in inside)
    window = modules[-1][1] - modules[0][0]
    top = sorted(inside, key=lambda g: g[0] - g[1])[:longest]
    iters = len(host["iters"])
    return {
        "window_s": window / 1e9,
        "idle_s": sum(e - s for s, e in gaps) / 1e9, "gaps": len(gaps),
        "iterations": iters, "idle_in_iterations_s": total / 1e9,
        "iteration_ms": sum(e - s for s, e in host["iters"]) / 1e6 / iters
        if iters else None,
        "idle_ms_per_iteration": total / 1e6 / iters if iters else None,
        "host_shift_ms": shift / 1e6,
        "host_shift_allowed_ms": [low / 1e6, high / 1e6],
        "bounds_ms": {"runtime_issue_done": [b / 1e6 for b in bounds[0]],
                      "step_launch_wait": [b / 1e6 for b in bounds[1]]},
        "by_phase": sorted(([k, v / 1e9, v / total if total else 0.0]
                            for k, v in by.items()), key=lambda r: -r[1]),
        "named_share": 1 - by.get(REST, 0) / total if total else None,
        "longest": [{"seconds": (e - s) / 1e9,
                     "phases": sorted(([k, v / 1e9] for k, v in
                                       split_gaps([(s, e)], spans).items()),
                                      key=lambda r: -r[1])}
                    for s, e in top],
    }


def print_report(r):
    print(f"device 0: window {r['window_s']:.3f} s, idle {r['idle_s']:.3f} s "
          f"in {r['gaps']} gaps; {r['iterations']} scheduler iterations "
          f"recorded, idle inside them {r['idle_in_iterations_s']:.3f} s"
          + (f", {r['idle_ms_per_iteration']:.2f} ms of an iteration's "
             f"{r['iteration_ms']:.2f}" if r["iterations"] else ""))
    print(f"host spans shifted by {r['host_shift_ms']:.3f} ms (allowed "
          f"{r['host_shift_allowed_ms']}; {r['bounds_ms']})")
    for name, sec, share in r["by_phase"]:
        print(f"  {name:24s} {sec:9.4f} s  {100 * share:5.1f} %")
    print(f"under a named phase: {100 * (r['named_share'] or 0):.1f} %")
    for g in r["longest"]:
        print(f"  gap {g['seconds'] * 1e3:8.3f} ms: " + ", ".join(
            f"{k} {v * 1e3:.3f}" for k, v in g["phases"]))
    print(json.dumps(r))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--xplane", default=None,
                    help="reduce a kept trace and run nothing")
    ap.add_argument("--workload")
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--keep", default=None,
                    help="directory to keep the .xplane.pb in")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--bench-root", default=None)
    args = ap.parse_args()
    root, runner = start(args.bench_root,
                         args.rehearse_cpu or bool(args.xplane))
    from benchmark.harness import reducer

    if args.xplane:
        path = args.xplane
    else:
        if not args.workload:
            ap.error("--workload or --xplane")
        ctx = runner.load_cell(root, args.workload)
        device = runner.find_device(ctx["cell"]["chips"], args.rehearse_cpu)
        if device is None:
            return 3
        st = runner.setup(ctx, args.seed)
        m = runner.measure(ctx, st, args.seed, args.seconds, True)
        runner.free_engine(st)
        path = reducer.find_xplane(m["trace"]["dir"])
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            kept = os.path.join(args.keep, f"{args.workload}.xplane.pb")
            shutil.copy(path, kept)
            print("kept", kept, os.path.getsize(kept), "bytes")
    r = report(path)
    if not args.xplane:
        shutil.rmtree(m["trace"]["dir"], ignore_errors=True)
    if r is None:
        print("the trace has no device plane: nothing to attribute",
              file=sys.stderr)
        return 1
    print_report(r)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
