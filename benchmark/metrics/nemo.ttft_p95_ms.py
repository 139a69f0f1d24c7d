"""`ttft_p95_ms` as the cell `nemotron3-nano-ep8.chat-burst` reports it: per layer,
moving `itl_p95_ms`. Arrivals come in bursts (gamma, cv 2), so the 95th percentile is the queue behind a burst's prefills.
The arithmetic is the one reader's, `ttft_p95_ms.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_ttft_p95_ms", Path(__file__).with_name("ttft_p95_ms.py")).read
