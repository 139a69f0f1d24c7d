"""Look at one trace by hand: run a cell briefly with the profiler on, keep
the `.xplane.pb` (under chiprun_out/ so that it comes back from the chip)
and print its planes, lines and a few event names. How the recorded trace
under benchmark/tests/data/ was made. Not run by the driver.

    python3 benchmark/tools/probe_trace.py --workload <cell> --seconds 4 --keep chiprun_out/trace
"""
import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import start  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--keep", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--bench-root", default=None)
    args = ap.parse_args()
    root, runner = start(args.bench_root, args.rehearse_cpu)
    from jax.profiler import ProfileData
    from benchmark.harness import reducer

    ctx = runner.load_cell(root, args.workload)
    device = runner.find_device(ctx["cell"]["chips"], args.rehearse_cpu)
    if device is None:
        return 3
    st = runner.setup(ctx, args.seed)
    m = runner.measure(ctx, st, args.seed, args.seconds, True)
    runner.free_engine(st)
    path = reducer.find_xplane(m["trace"]["dir"])
    os.makedirs(args.keep, exist_ok=True)
    kept = os.path.join(args.keep, f"{args.workload}.xplane.pb")
    shutil.copy(path, kept)
    print("kept", kept, os.path.getsize(kept), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})
            print("  LINE", repr(line.name), len(evs), names[:6])
    s = reducer.summarize(path)
    if s:
        s["modules"] = s["modules"][:5]
    print(json.dumps(s)[:3000])
    shutil.rmtree(m["trace"]["dir"], ignore_errors=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
