"""`kv_pool_peak_pct` as the cell `nemotron3-nano-ep8.chat-burst` reports it: blocks of 393,216 B over the six attention blocks, beside
the per-slot state rows (`ssm_state_rows_peak_pct`): of the two, which fills first is what bounds the requests held at once.
The arithmetic is the one reader's, `kv_pool_peak_pct.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_kv_pool_peak_pct", Path(__file__).with_name("kv_pool_peak_pct.py")).read
