"""`ttft_p95_ms` as the cell `sc2-3b.repo-complete` reports it: per layer, moving
`itl_p95_ms`. Over the cell's 58 requests the 95th percentile lies between the fourth largest
reading and the third, and six runs of one program spread 5.4-9 % (943-1,159 ms)
where a new cell's end-to-end metric may spread half its bound, 5 % (PERF.md, PR 30).
The arithmetic is the one reader's, `ttft_p95_ms.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_ttft_p95_ms", Path(__file__).with_name("ttft_p95_ms.py")).read
