"""`ttft_p95_ms` as the cell `sc2-3b.chat` reports it: per layer, moving
`itl_p95_ms`. Over the cell's 99 requests the 95th percentile is one request's
reading, the sixth largest, and swings by a scheduler iteration from run to
run: too wide for any bound an end-to-end metric may have (PERF.md, PR 28).
The arithmetic is the one reader's, `ttft_p95_ms.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_ttft_p95_ms", Path(__file__).with_name("ttft_p95_ms.py")).read
