"""`ttft_p95_ms` as the cell `evabyte-d16.longdoc-complete` reports it: per layer,
moving `itl_p95_ms`. Over the cell's 22 requests the 95th percentile lies between the second largest
reading and the largest, two long prompts whose prefills take turns with the
others', and its sets of six runs spread 2.9 % where an end-to-end metric over
so few requests may spread a fifth of its bound, 2 % (PERF.md, PR 30).
The arithmetic is the one reader's, `ttft_p95_ms.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_ttft_p95_ms", Path(__file__).with_name("ttft_p95_ms.py")).read
