"""`moe_experts_hit_share` as the cell `nemotron3-nano-ep8.chat-burst` reports it: of the 16 x 23 held experts, the share a decode
dispatch hit (6 of 128 a token: 64 live slots give 3 pairs an expert); what `work.py` charges a step's expert bytes by.
The arithmetic is the one reader's, `moe_experts_hit_share.py` beside this file."""
from pathlib import Path

from benchmark.harness.family import module_at

read = module_at("_metric_moe_experts_hit_share", Path(__file__).with_name("moe_experts_hit_share.py")).read
