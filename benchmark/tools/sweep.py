"""The knee sweep of one cell: one set-up, several fixed rates of a short
window each, drained between. Not run by the driver; its table is in
PERF.md and the rate it led to is written into the traffic file.

    python3 benchmark/tools/sweep.py --workload <cell> --rates 1,2,3,4,5 --seconds 20
"""
import argparse
import copy
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import start  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--bench-root", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    root, runner = start(args.bench_root, args.rehearse_cpu)
    from benchmark.harness import check, stats

    ctx = runner.load_cell(root, args.workload)
    device = runner.find_device(ctx["cell"]["chips"], args.rehearse_cpu)
    if device is None:
        return 3
    t = time.time()
    st = runner.setup(ctx, args.seed)
    setup_s = time.time() - t
    print(f"set-up {setup_s:.1f} s, warmed {st['warmed']}", file=sys.stderr)
    table = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(ctx["mix"])
        mix["arrival"]["rate_per_s"] = rate
        m = runner.measure(ctx, st, args.seed + i, args.seconds, False, mix)
        run = runner.facts(ctx, m, device, setup_s)
        e2e = runner.read_metrics(ctx, run, False)
        v = check.verdict(m["rows"], m["window"], None, {"max_gap": 0})
        row = {"rate_per_s": rate, "attempted": len(m["rows"]),
               "unfinished": v["failed"],
               **{k: mv["value"] for k, mv in e2e.items()},
               "drained_s": m["window"]["drained_s"],
               "queue_p95_ms": runner.reader(root, "queue_p95_ms")(run),
               "ttft_p50_ms": stats.percentile(
                   [(r["first"] - r["due"]) * 1e3 for r in m["rows"]
                    if r["first"] is not None], 50),
               "batch_mean": runner.reader(root, "decode_batch_mean")(run),
               "preempted": m["window"]["preempted"],
               "compiles": sum(m["window"]["compiles"].values())}
        table.append(row)
        print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(table, f, indent=1)
    runner.free_engine(st)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
