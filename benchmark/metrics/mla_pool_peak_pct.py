"""`eva_pool_peak_pct`'s reading as the cell `axk1-ep16-d6.longctx-chat` reports it: the
pool's own high-water mark (`kv_pool_blocks_live`) over `capacity_blocks`, here of blocks of
latent rows, 442,368 B each. The trie adopts finished prompts' pages, so the gauge counts
those it still keeps beside the resident requests' (`kv_pool_peak_pct` counts requests alone,
from the rows, and lists the StarCoder2 cells).
The arithmetic is the one reader's, `eva_pool_peak_pct.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_eva_pool_peak_pct", Path(__file__).with_name("eva_pool_peak_pct.py")).read
