"""`kv_pages_read_share` as the cell `nemotron3-nano-ep8.chat-burst` reports it: the six attention blocks' block tables (2 kv heads
of 128, a page of 64 KB); beside the state rows the pages are a small part of a step's bytes.
The arithmetic is the one reader's, `kv_pages_read_share.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_kv_pages_read_share", Path(__file__).with_name("kv_pages_read_share.py")).read
