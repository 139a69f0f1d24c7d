"""Operations and bytes the A.X-K1 share *needs*, from shapes and from what
was routed.

The same count whatever implements the step: an embedding is a row gather
(not a one-hot matmul), the head is needed only where a token is sampled,
the weights outside the routed experts are read once per program execution,
a held expert's weights once where a token chose it, and the cache is read
once at its real depth. A latent row serves all heads, so a decode query
costs `2 H ((C + dr) + C)` operations a row a layer (the absorbed form: the
score over the whole row, the weighted sum over the latent) and a chunk's
`2 H (dn + dr + dv)` a query-key pair (the expanded form; rebuilding the
keys and values of rows that are already cached is not work, nor are
padding, gathered copies or the table bucket's width).

What was routed is read from the run (`run["window"]["counters"]`, the
window's difference of the engine's `moe_*` counters): the share of the
routers' token-expert pairs that fell on held experts, and the share of held
experts a decode dispatch hit. Without a run (the tests, a hand count) the
routing is even (`held / router outputs` of the pairs) and every held expert
is hit. A prefill chunk's counts are not read back unless it is the
prompt's last, so a chunk is charged the window's share of its own pairs,
and the held experts at least one of those pairs reaches.

A configuration is the dict of `configs/axk1-ep16-d6.json`; weights and
cache are `bytes_per_el` wide (2 = bfloat16)."""
from __future__ import annotations

from typing import Iterable, Optional, Tuple


def dims(cfg: dict) -> dict:
    L, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "L": L, "dense": dense,
            "routed": L - dense, "H": cfg["num_attention_heads"],
            "Q": cfg["q_lora_rank"], "C": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "ff": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"],
            "experts": cfg.get("router_outputs", cfg["n_routed_experts"]),
            "k": cfg["num_experts_per_tok"],
            "shared": cfg["n_shared_experts"], "v": cfg["vocab_size"]}


def attn_params(cfg: dict) -> int:
    """Wdq, Wuq, Wdkv, Wukv, Wo: a token is multiplied with each once,
    expanded or absorbed (absorbed, Wukv meets the query and the weighted
    sum in place of the row)."""
    m = dims(cfg)
    return (m["d"] * m["Q"] + m["Q"] * m["H"] * (m["dn"] + m["dr"])
            + m["d"] * (m["C"] + m["dr"])
            + m["C"] * m["H"] * (m["dn"] + m["dv"])
            + m["H"] * m["dv"] * m["d"])


def expert_params(cfg: dict) -> int:
    m = dims(cfg)
    return 3 * m["d"] * m["f"]


def matmul_params_outside_experts(cfg: dict) -> int:
    """Every layer's matrices a token always meets: attention, then the
    dense FFN or the router and the shared expert(s)."""
    m = dims(cfg)
    return (m["L"] * attn_params(cfg) + m["dense"] * 3 * m["d"] * m["ff"]
            + m["routed"] * (m["d"] * m["experts"]
                             + m["shared"] * expert_params(cfg)))


def vector_params(cfg: dict) -> int:
    """Gains and biases as the graph holds them: four RMSNorm gains a layer
    (two of the block, two inside the attention) and the three biases of a
    dense or shared FFN."""
    m = dims(cfg)
    norms = m["L"] * (2 * m["d"] + m["Q"] + m["C"])
    return (norms + m["dense"] * (2 * m["ff"] + m["d"])
            + m["routed"] * (2 * m["f"] * m["shared"] + m["d"]))


def head_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * m["v"] + m["v"] + m["d"]     # head + bias + final norm


def kv_bytes_per_position(cfg: dict, bytes_per_el: int = 2) -> int:
    """One cached position: a latent row (the normed latent and the one
    rotated key) in every layer held."""
    m = dims(cfg)
    return m["L"] * (m["C"] + m["dr"]) * bytes_per_el


def routing(cfg: dict, run: Optional[dict]) -> Tuple[float, float]:
    """(share of the routers' pairs on held experts, share of held experts a
    decode dispatch hit), from the run's counters; even routing and every
    expert hit where there is no run or it counted nothing."""
    m = dims(cfg)
    c = ((run or {}).get("window") or {}).get("counters") or {}
    routed, slots = c.get("moe_pairs_routed_total"), \
        c.get("moe_expert_slots_total")
    return (c.get("moe_pairs_held_total", 0) / routed if routed
            else m["held"] / m["experts"],
            c.get("moe_experts_hit_total", 0) / slots if slots else 1.0)


def _weights_once(cfg: dict, head: bool) -> int:
    return matmul_params_outside_experts(cfg) + vector_params(cfg) \
        + (head_params(cfg) if head else 0)


def decode_step(cfg: dict, depths: Iterable[int], bytes_per_el: int = 2,
                run: Optional[dict] = None, t_lo: Optional[float] = None,
                t_hi: Optional[float] = None) -> Tuple[float, float]:
    """One decode step over live slots; `depths[i]` = rows slot i attends
    over with the new one. The run's counters are the whole window's: the
    interval is not read."""
    depths = list(depths)
    n, rows = len(depths), sum(depths)
    m = dims(cfg)
    held_share, hit_share = routing(cfg, run)
    pairs = n * m["k"] * m["routed"] * held_share
    flops = (n * 2 * (matmul_params_outside_experts(cfg) + m["d"] * m["v"])
             + pairs * 2 * expert_params(cfg)
             + rows * m["L"] * 2 * m["H"] * (2 * m["C"] + m["dr"]))
    kvb = kv_bytes_per_position(cfg, bytes_per_el)
    byts = ((_weights_once(cfg, True)
             + hit_share * m["routed"] * m["held"] * expert_params(cfg))
            * bytes_per_el
            + n * m["d"] * bytes_per_el           # embedding rows
            + rows * kvb + n * kvb)               # cache read, row written
    return float(flops), float(byts)


def prefill_chunk(cfg: dict, n_tokens: int, depth0: int, final: bool,
                  bytes_per_el: int = 2, run: Optional[dict] = None,
                  span: Optional[dict] = None) -> Tuple[float, float]:
    """One prefill chunk of `n_tokens` real tokens after `depth0` cached
    positions; `final` chunks also sample the first output token (head).
    The cache is read once a chunk, at its last query's depth."""
    m = dims(cfg)
    held_share, _ = routing(cfg, run)
    chosen = n_tokens * m["k"]                    # pairs a routed layer
    reached = 1.0 - (1.0 - held_share / m["held"]) ** chosen
    pairs_qk = n_tokens * depth0 + n_tokens * (n_tokens + 1) // 2
    flops = (n_tokens * 2 * matmul_params_outside_experts(cfg)
             + chosen * m["routed"] * held_share * 2 * expert_params(cfg)
             + pairs_qk * m["L"] * 2 * m["H"] * (m["dn"] + m["dr"] + m["dv"]))
    if final:
        flops += 2 * m["d"] * m["v"]
    kvb = kv_bytes_per_position(cfg, bytes_per_el)
    byts = ((_weights_once(cfg, final)
             + reached * m["routed"] * m["held"] * expert_params(cfg))
            * bytes_per_el
            + n_tokens * m["d"] * bytes_per_el
            + (depth0 + n_tokens) * kvb + n_tokens * kvb)
    return float(flops), float(byts)


def param_count(cfg: dict) -> int:
    """Parameters as the graph holds them: the held experts, the held rows
    of the vocabulary (embedding + bias, head + bias), every gain."""
    m = dims(cfg)
    return (matmul_params_outside_experts(cfg) + vector_params(cfg)
            + m["routed"] * m["held"] * expert_params(cfg)
            + m["v"] * m["d"] + m["d"] + head_params(cfg))
