"""Builds a scratch benchmark root for the CPU tests: a copy of the
benchmark's code beside a BENCHMARK.json that names a toy cell. The same
recipe a later PR follows to add a cell: new files, one new entry each."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
TINY = Path(__file__).resolve().parent / "data" / "tiny"


def make_root(tmp: Path, config="tiny", traffic="tiny-mix", cell="tiny.cell",
              extra_metrics=()) -> Path:
    root = tmp / "root"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "tests"))
    b = root / "benchmark"
    shutil.copy(TINY / "config.json", b / "configs" / f"{config}.json")
    shutil.copy(TINY / "traffic.json", b / "traffic" / f"{traffic}.json")
    shutil.copy(TINY / "workload.json", b / "workloads" / f"{cell}.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "toy",
                             "file": f"benchmark/configs/{config}.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "CPU test"})
    for name, src in extra_metrics:
        (b / "metrics" / f"{name}.py").write_text(src)
        bench["per_layer"].append({"name": name, "unit": "count",
                                   "better": "higher",
                                   "source": "program_counter",
                                   "layer": "test", "moves": "out_tok_s",
                                   "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the program itself is found where it is installed: the repo
    return root
