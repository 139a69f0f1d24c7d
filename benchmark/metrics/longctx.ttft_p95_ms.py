"""`ttft_p95_ms` as the cell `axk1-ep16-d6.longctx-chat` reports it: per layer,
moving `itl_p95_ms`. Over the cell's few dozen requests the 95th percentile lies among the two or three longest
prompts, whose prefills take turns with the others' 512-token chunks: too few for an end-to-end tail.
The arithmetic is the one reader's, `ttft_p95_ms.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_ttft_p95_ms", Path(__file__).with_name("ttft_p95_ms.py")).read
