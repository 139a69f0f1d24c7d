"""`queue_p95_ms` as the cell `nemotron3-nano-ep8.chat-burst` reports it: per layer, moving
`out_tok_s`. The cell has no end-to-end `ttft_p95_ms` for it to move; a burst's requests wait for admission and release their tokens that much later.
The arithmetic is the one reader's, `queue_p95_ms.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_queue_p95_ms", Path(__file__).with_name("queue_p95_ms.py")).read
