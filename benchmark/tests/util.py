"""Builds a scratch benchmark root for the CPU tests: a copy of the
benchmark's code beside a BENCHMARK.json that names a toy cell. The same
recipe a later PR follows to add a cell, and with `families` an
architecture: new files, one new entry each, no copied file edited."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
TINY = Path(__file__).resolve().parent / "data" / "tiny"


def make_root(tmp: Path, config="tiny", traffic="tiny-mix", cell="tiny.cell",
              extra_metrics=(), config_keys=None, families=None,
              like="sc2-7b-d16.repo-complete") -> Path:
    """`config_keys` are laid over the toy configuration's (a `model_type`
    of its own, say); `families` maps a family's name to its files' sources,
    `{"weights": ..., "graph": ..., "reference": ..., "work": ...}`. A metric
    that lists its cells (`workloads`) is reported by the new cell where it
    is reported by the cell `like`: the new cell's name joins that list."""
    root = tmp / "root"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", ".trace", "tests"))
    b = root / "benchmark"
    cfg = {**json.loads((TINY / "config.json").read_text()),
           **(config_keys or {})}
    (b / "configs" / f"{config}.json").write_text(json.dumps(cfg, indent=1))
    for fam, files in (families or {}).items():
        (b / "families" / fam).mkdir()
        for part, src in files.items():
            (b / "families" / fam / f"{part}.py").write_text(src)
    shutil.copy(TINY / "traffic.json", b / "traffic" / f"{traffic}.json")
    shutil.copy(TINY / "workload.json", b / "workloads" / f"{cell}.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config, "source": "toy",
                             "file": f"benchmark/configs/{config}.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell)
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
