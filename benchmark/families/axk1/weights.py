"""A.X-K1's weights from `--seed`, made on the device by jitted calls,
bfloat16: the share one chip of the stated deployment holds (the
configuration's `n_routed_experts` experts of each routed layer, its
`vocab_size` rows of the embedding and the head), every width as published.

The tree is the harness's own (the reference's naming); `graph.py` beside
this file maps it onto the program's layer names. The draw is
`harness/draw.py`'s (gains 1 + 0.1 N, other vectors 0.002 N, matrices
`init_std` N; an expert stack [held, in, out] is drawn as a matrix). A block
is either dense (`first_k_dense_replace` leading layers) or routed, so there
are two block programs beside the ends'.

Column layout of the two up-projections, as the DeepSeek-V2/V3 family's
public modeling code splits them: `wuq` [q_lora_rank, heads x (nope | rope)],
`wukv` [kv_lora_rank, heads x (nope | value)]; `wdkv` [hidden, kv_lora_rank |
rope]. The rope parts are rotated in the rotate-half pairing (a column
permutation of these random matrices away from the release's interleaved
pairing; `departures`). No attention or expert bias is drawn: the layers have
none. The biases of the embedding, the dense and shared FFN matrices and the
head are the graph's (`departures`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import draw


def dims(cfg: dict) -> dict:
    return {"d": cfg["hidden_size"], "L": cfg["num_hidden_layers"],
            "H": cfg["num_attention_heads"], "Q": cfg["q_lora_rank"],
            "C": cfg["kv_lora_rank"], "dn": cfg["qk_nope_head_dim"],
            "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "ff": cfg["intermediate_size"], "f": cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"],
            "experts": cfg.get("router_outputs", cfg["n_routed_experts"]),
            "first": cfg.get("experts_held_first", 0),
            "k": cfg["num_experts_per_tok"], "shared": cfg["n_shared_experts"],
            "dense": cfg["first_k_dense_replace"], "v": cfg["vocab_size"]}


def shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d, H = m["d"], m["H"]
    attn = {"ln1_g": (d,), "wdq": (d, m["Q"]), "qn_g": (m["Q"],),
            "wuq": (m["Q"], H * (m["dn"] + m["dr"])),
            "wdkv": (d, m["C"] + m["dr"]), "kvn_g": (m["C"],),
            "wukv": (m["C"], H * (m["dn"] + m["dv"])),
            "wo": (H * m["dv"], d), "ln2_g": (d,)}

    def ffn(prefix, width):
        return {f"{prefix}_gate": (d, width), f"b{prefix[1:]}_gate": (width,),
                f"{prefix}_up": (d, width), f"b{prefix[1:]}_up": (width,),
                f"{prefix}_down": (width, d), f"b{prefix[1:]}_down": (d,)}

    dense = {**attn, **ffn("w", m["ff"])}
    fs = m["f"] * m["shared"]
    routed = {**attn, "w_router": (d, m["experts"]),
              "we_gate": (m["held"], d, m["f"]),
              "we_up": (m["held"], d, m["f"]),
              "we_down": (m["held"], m["f"], d), **ffn("ws", fs)}
    return {"embed_w": (m["v"], d), "embed_b": (d,),
            "blocks": [dict(dense if i < m["dense"] else routed)
                       for i in range(m["L"])],
            "lnf_g": (d,), "head_w": (d, m["v"]), "head_b": (m["v"],)}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """One small jitted program per kind of part (a dense block, a routed
    block, the two ends), with the seed and the block's index as traced
    arguments: the same programs for every seed and every block."""
    tree = shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    ends = {k: v for k, v in tree.items() if k != "blocks"}
    lo, hi = draw.split_seed(seed)
    programs = {}

    def block(i, shapes_):
        kind = tuple(sorted(shapes_))
        if kind not in programs:
            programs[kind] = jax.jit(
                lambda a, b, n: draw.part(shapes_, a, b, n, std, dtype))
        return programs[kind](lo, hi, jnp.uint32(i))

    out = jax.jit(lambda a, b: draw.part(ends, a, b, jnp.uint32(draw.ENDS),
                                         std, dtype))(lo, hi)
    out["blocks"] = [block(i, s) for i, s in enumerate(tree["blocks"])]
    return out
