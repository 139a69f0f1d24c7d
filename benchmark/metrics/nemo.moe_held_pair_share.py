"""`moe_held_pair_share` as the cell `nemotron3-nano-ep8.chat-burst` reports it: the share of the routers' pairs on the 16 held
experts of 128 (12.5 % when routing is even), over 23 routed blocks; what the family's `work.py` charges expert operations by.
The arithmetic is the one reader's, `moe_held_pair_share.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_moe_held_pair_share", Path(__file__).with_name("moe_held_pair_share.py")).read
