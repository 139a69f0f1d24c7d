"""`gen_late_p95_ms` as the cell `nemotron3-nano-ep8.chat-burst` reports it: per layer,
moving `out_tok_s`. The cell has no end-to-end `ttft_p95_ms` for it to move; a late generator offers less load inside the window.
The arithmetic is the one reader's, `gen_late_p95_ms.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_gen_late_p95_ms", Path(__file__).with_name("gen_late_p95_ms.py")).read
