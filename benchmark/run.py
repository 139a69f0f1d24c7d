"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process per run. It refuses to run without the TPU the cell asks
for (exit 3, no result line), builds the cell's configuration and engine,
warms the shapes the cell's traffic can reach (set-up), drives the window
open loop through `DecodeScheduler.submit`, drains, frees the engine, checks
the served tokens against the plain reference, and prints one JSON line.
Everything about a cell is data: `BENCHMARK.json` names the configuration,
the traffic mix and the metrics; each is a file found by that name under
`benchmark/{configs,traffic,workloads,metrics}/`. Everything about an
architecture is files too: the configuration's `model_type` names a
directory `benchmark/families/<model_type>/` (weights, graph, reference,
work; `harness/family.py`). So a cell of an architecture the benchmark has
is new data files plus one entry, and a new architecture is its family
directory besides; neither edits a file that is there. (Before PR 28 that
held for a third StarCoder2-shaped configuration and for nothing else: the
block was written into four modules of the harness.) A family without its
directory is exit 2, before any device is asked for.

`--rehearse-cpu` (the benchmark's own flag, never given by the driver) lets
the same path run on the CPU at a toy size for the tests; its result names
the platform `cpu`, and the readers of device metrics find nothing there."""
import time
_T_START = time.time()

import argparse
import os
import sys
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--bench-root", default=None,
                    help="directory that holds BENCHMARK.json (tests)")
    args = ap.parse_args(argv)

    root = Path(args.bench_root or Path(__file__).resolve().parents[1])
    sys.path.insert(0, str(root))
    import importlib.util
    if importlib.util.find_spec("deeplearning4j_tpu") is None:
        print("the system under test (deeplearning4j_tpu) is not beside the "
              "benchmark: nothing to measure", file=sys.stderr)
        return 4
    from benchmark.harness import runner

    runner.prepare(root, args.rehearse_cpu)
    try:
        ctx = runner.load_cell(root, args.workload)
    except (KeyError, OSError) as e:
        print(f"cannot load the cell: {e}", file=sys.stderr)
        return 2
    device = runner.find_device(ctx["cell"]["chips"], args.rehearse_cpu)
    if device is None:
        return 3
    st = runner.setup(ctx, args.seed)
    setup_s = time.time() - _T_START
    m = runner.measure(ctx, st, args.seed, args.seconds, bool(args.trace))
    runner.free_engine(st)
    v = runner.compare(ctx, st, m, args.seed)
    runner.print_result(runner.result(ctx, st, m, v, device, setup_s,
                                      bool(args.trace)))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
