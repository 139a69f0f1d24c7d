"""A toy second family for the CPU tests (`test_family.py`): a post-LN block
with a ReLU feed-forward part that has no biases and no final LayerNorm.
Its tree shares no block leaf's name with StarCoder2's."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import draw


def shapes(cfg: dict) -> dict:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    layer = {"q": (d, d), "k": (d, kv), "v": (d, kv), "o": (d, d),
             "o_b": (d,), "n1_g": (d,), "n1_b": (d,),
             "up": (d, ff), "down": (ff, d), "n2_g": (d,), "n2_b": (d,)}
    return {"ends": {"tok_w": (v, d), "tok_b": (d,),
                     "head_w": (d, v), "head_b": (v,)},
            "layers": [dict(layer) for _ in range(cfg["num_hidden_layers"])]}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    tree = shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    lo, hi = draw.split_seed(seed)
    one = jax.jit(lambda a, b, i: draw.part(tree["layers"][0], a, b, i, std,
                                            dtype))
    ends = jax.jit(lambda a, b: draw.part(tree["ends"], a, b,
                                          jnp.uint32(draw.ENDS), std, dtype))
    return {"ends": ends(lo, hi),
            "layers": [one(lo, hi, jnp.uint32(i))
                       for i in range(len(tree["layers"]))]}
