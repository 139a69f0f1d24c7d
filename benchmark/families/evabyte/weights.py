"""EvaByte's weights from `--seed`, made on the device by jitted calls,
bfloat16.

The tree is the harness's own (the reference's naming); `graph.py` beside
this file maps it onto the program's layer names. The draw is
`harness/draw.py`'s (gains 1 + 0.1 N, other vectors 0.002 N, matrices
`init_std` N), with two rules of this family's own, both applied
inside the jitted part:

  - a `*_g` leaf is stored as the OFFSET of the gain from one
    (`norm_add_unit_offset`: the norm multiplies by 1 + g), so the stored
    value is the draw less one, and a norm that forgot the offset would
    multiply by about 0.1 N;
  - the pooling vectors `mu` and `phi` (`adaptive_mu_k`, `adaptive_phi`:
    [heads, head]) are drawn at unit scale, not at `init_std`: at
    0.02 both poolings would be uniform over a chunk to within rounding,
    and a dropped or swapped vector would not show in the comparison.

No q/k/v/o biases are drawn: the attention layer has none. The biases of the
embedding, the three FFN matrices and the head are the graph's
(`departures`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import draw


def shapes(cfg: dict) -> dict:
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    block = {"ln1_g": (d,), "wq": (d, d), "wk": (d, d), "wv": (d, d),
             "wo": (d, d), "mu": (h, d // h), "phi": (h, d // h),
             "ln2_g": (d,), "w_gate": (d, ff), "b_gate": (ff,),
             "w_up": (d, ff), "b_up": (ff,), "w_down": (ff, d),
             "b_down": (d,)}
    return {"embed_w": (v, d), "embed_b": (d,),
            "blocks": [dict(block) for _ in range(cfg["num_hidden_layers"])],
            "lnf_g": (d,), "head_w": (d, v), "head_b": (v,)}


def _part(shapes_: dict, lo, hi, index, std: float, dtype) -> dict:
    out = draw.part(shapes_, lo, hi, index, std, dtype)
    for name, leaf in out.items():
        if name.endswith("_g"):
            out[name] = leaf - jnp.asarray(1, dtype)
        elif name in ("mu", "phi"):
            out[name] = (leaf.astype(jnp.float32) / std).astype(dtype)
    return out


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """One small jitted program per kind of part (a block; the two ends),
    with the seed and the block's index as traced arguments, as
    StarCoder2's: the same two programs for every seed and every block."""
    tree = shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    block_shapes = tree["blocks"][0]
    ends = {k: v for k, v in tree.items() if k != "blocks"}

    lo, hi = draw.split_seed(seed)
    gen_block = jax.jit(lambda a, b, i: _part(block_shapes, a, b, i, std,
                                              dtype))
    out = jax.jit(lambda a, b: _part(ends, a, b, jnp.uint32(draw.ENDS),
                                     std, dtype))(lo, hi)
    out["blocks"] = [gen_block(lo, hi, jnp.uint32(i))
                     for i in range(len(tree["blocks"]))]
    return out
