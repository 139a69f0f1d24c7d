"""Shared start-up of the tools: the same environment as `run.py`."""
from __future__ import annotations

import sys
from pathlib import Path


def start(bench_root, rehearse: bool):
    root = Path(bench_root or Path(__file__).resolve().parents[2])
    sys.path.insert(0, str(root))
    from benchmark.harness import runner
    runner.prepare(root, rehearse)
    return root, runner
