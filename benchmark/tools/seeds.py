"""Many seeds of one cell in one process: the readings the limits of
`correct` are set from. One set-up; for each seed: new weights into the
same engine, a short window at the cell's own load, the drain, then the
reference (beside the idle engine, whose pool stays); for the first
`--controls` seeds also the control (the reference in fp8). Not run by the
driver. One JSON line per seed.

    python3 benchmark/tools/seeds.py --workload <cell> --seeds 11,12,13 --seconds 15 --controls 3
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import start  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--controls", type=int, default=0)
    ap.add_argument("--control", default="fp8",
                    help="comma list of lower precisions, e.g. fp8,int8")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--bench-root", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    root, runner = start(args.bench_root, args.rehearse_cpu)
    ctx = runner.load_cell(root, args.workload)
    device = runner.find_device(ctx["cell"]["chips"], args.rehearse_cpu)
    if device is None:
        return 3
    lines = []
    seeds = [int(s) for s in args.seeds.split(",")]
    t = time.time()
    st = runner.setup(ctx, seeds[0])
    setup_s = time.time() - t
    for i, seed in enumerate(seeds):
        if i:
            runner.reseed(ctx, st, seed)
        m = runner.measure(ctx, st, seed, args.seconds, False)
        v = runner.compare(ctx, st, m, seed,
                           controls=tuple(args.control.split(","))
                           if i < args.controls else ())
        out = runner.result(ctx, st, m, v, device, setup_s, False)
        line = {"seed": seed, "correct": out["correct"],
                "checks": out["checks"], "gap": v["gap"],
                "control": v["control"], "reference_s": v["reference_s"],
                "metrics": {k: mv["value"] for k, mv in out["metrics"].items()},
                "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        lines.append(line)
        print(json.dumps(line), flush=True)
        del m
    runner.free_engine(st)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
