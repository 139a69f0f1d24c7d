"""`prefill_chunk_roofline` as the cell `nemotron3-nano-ep8.chat-burst` reports it: per layer,
moving `itl_p95_ms`. The cell has no end-to-end `ttft_p95_ms` for it to move; the 95th percentile of its gaps between tokens is an iteration with a chunk in it.
The arithmetic is the one reader's, `prefill_chunk_roofline.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_prefill_chunk_roofline", Path(__file__).with_name("prefill_chunk_roofline.py")).read
