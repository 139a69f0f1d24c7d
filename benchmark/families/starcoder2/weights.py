"""StarCoder2's weights from `--seed`, made on the device by jitted calls,
bfloat16.

The tree is the harness's own (the reference's naming); `graph.py` beside
this file maps it onto the program's layer names. The program's `net.init()`
is not used: the reference may take nothing the program has made, and
`init()` draws leaf by leaf. Scales follow StarCoder2's `initializer_range`;
the LayerNorm gains and all biases are drawn too (`harness/draw.py`: the
rule by a leaf's name and rank, and the fold-in of seed, part and index,
shared by every family)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import draw


def shapes(cfg: dict) -> dict:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    dh = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * dh
    block = {"ln1_g": (d,), "ln1_b": (d,), "wq": (d, d), "wk": (d, kv),
             "wv": (d, kv), "wo": (d, d), "bo": (d,),
             "ln2_g": (d,), "ln2_b": (d,), "w_up": (d, ff), "b_up": (ff,),
             "w_down": (ff, d), "b_down": (d,)}
    return {"embed_w": (v, d), "embed_b": (d,),
            "blocks": [dict(block) for _ in range(cfg["num_hidden_layers"])],
            "lnf_g": (d,), "lnf_b": (d,), "head_w": (d, v), "head_b": (v,)}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """One small jitted program per kind of part (a block; the two ends),
    with the seed and the block's index as traced arguments: the same two
    compiled programs for every seed, every depth and every block, and no
    more than one block's float32 draws alive at a time (one program for
    the whole tree let the compiler hold most of them at once: 15.7 GiB
    peak at the 3B, my chip run, PR 25)."""
    tree = shapes(cfg)
    std = float(cfg.get("initializer_range", 0.02))
    block_shapes = tree["blocks"][0]
    ends = {k: v for k, v in tree.items() if k != "blocks"}

    lo, hi = draw.split_seed(seed)
    gen_block = jax.jit(lambda a, b, i: draw.part(block_shapes, a, b, i, std,
                                                  dtype))
    out = jax.jit(lambda a, b: draw.part(ends, a, b, jnp.uint32(draw.ENDS),
                                         std, dtype))(lo, hi)
    out["blocks"] = [gen_block(lo, hi, jnp.uint32(i))
                     for i in range(len(tree["blocks"]))]
    return out
