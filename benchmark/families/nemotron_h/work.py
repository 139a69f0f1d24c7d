"""Operations and bytes the Nemotron-H share *needs*, from shapes, from the
live slots and from what was routed.

The same count whatever implements the step: an embedding is a row gather
(not a one-hot matmul), the head is needed only where a token is sampled,
the weights outside the routed experts are read once per program execution,
a held expert's weights once where a token chose it, the K/V cache of the
attention blocks once at its real depth. A Mamba block's state is read and
written ONCE for each live slot and for no other (`state_bytes`: ssm
float32 + the conv's window): a slot that holds no token in the step is not
work, nor are padding, selects and copies. A token's scan is the recurrence
itself, `5 H P N` operations a block (decay, outer product, add; product and
sum of the read-out) and `2 K` a conv channel, by its real tokens; what the
chunked form multiplies beyond that is not charged.

What was routed is read from the run (`run["window"]["counters"]`, the
window's difference of the engine's `moe_*` counters): the share of the
routers' token-expert pairs that fell on held experts, and the share of held
experts a decode dispatch hit. Without a run (the tests, a hand count) the
routing is even (`held / router outputs` of the pairs) and every held expert
is hit. A prefill chunk's counts are not read back unless it is the
prompt's last, so a chunk is charged the window's share of its own pairs,
and the held experts at least one of those pairs reaches.

A configuration is the dict of `configs/nemotron3-nano-ep8.json`; weights
and cache are `bytes_per_el` wide (2 = bfloat16), the ssm state 4."""
from __future__ import annotations

from typing import Iterable, Optional, Tuple


def dims(cfg: dict) -> dict:
    pat = cfg["hybrid_override_pattern"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {"d": cfg["hidden_size"], "M": pat.count("M"),
            "E": pat.count("E"), "A": pat.count("*"), "L": len(pat),
            "mH": H, "mP": P, "mN": N, "mK": cfg["conv_kernel"],
            "inner": H * P, "conv": H * P + 2 * G * N,
            "H": cfg["num_attention_heads"],
            "Hkv": cfg["num_key_value_heads"], "Dh": cfg["head_dim"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_shared_expert_intermediate_size"]
            * cfg["n_shared_experts"],
            "held": cfg["n_routed_experts"],
            "experts": cfg.get("router_outputs", cfg["n_routed_experts"]),
            "k": cfg["num_experts_per_tok"], "v": cfg["vocab_size"]}


def mamba_matmul_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * (m["inner"] + m["conv"] + m["mH"]) + m["inner"] * m["d"]


def mamba_vector_params(cfg: dict) -> int:
    """The conv (weights and bias), A_log, D, dt_bias, the gated norm's
    gain."""
    m = dims(cfg)
    return m["conv"] * (m["mK"] + 1) + 3 * m["mH"] + m["inner"]


def attn_params(cfg: dict) -> int:
    m = dims(cfg)
    return (m["d"] * m["H"] * m["Dh"] + 2 * m["d"] * m["Hkv"] * m["Dh"]
            + m["H"] * m["Dh"] * m["d"])


def expert_params(cfg: dict) -> int:
    m = dims(cfg)
    return 2 * m["d"] * m["f"]


def matmul_params_outside_experts(cfg: dict) -> int:
    """Every block's matrices a token always meets: the Mamba projections,
    the attention projections, the router and the shared expert."""
    m = dims(cfg)
    return (m["M"] * mamba_matmul_params(cfg) + m["A"] * attn_params(cfg)
            + m["E"] * (m["d"] * m["experts"] + 2 * m["d"] * m["fs"]))


def vector_params(cfg: dict) -> int:
    """Gains, and the Mamba blocks' vectors, as published; then what the
    graph holds beside them: the selection bias, the attention's output
    bias, the shared expert's two biases."""
    m = dims(cfg)
    return (m["L"] * m["d"] + m["M"] * mamba_vector_params(cfg)
            + m["A"] * m["d"] + m["E"] * (m["experts"] + m["fs"] + m["d"]))


def head_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * m["v"] + m["v"] + m["d"]     # head + bias + final norm


def published_params(cfg: dict) -> int:
    """The parameters of the published model this share holds (the router
    with its selection bias; no bias the graph adds): 5,258,420,544 for the
    configuration."""
    m = dims(cfg)
    return (m["M"] * (mamba_matmul_params(cfg) + mamba_vector_params(cfg))
            + m["A"] * attn_params(cfg)
            + m["E"] * ((m["d"] + 1) * m["experts"] + 2 * m["d"] * m["fs"]
                        + m["held"] * expert_params(cfg))
            + 2 * m["v"] * m["d"] + (m["L"] + 1) * m["d"])


def kv_bytes_per_position(cfg: dict, bytes_per_el: int = 2) -> int:
    """One cached position: a key row and a value row of the compact K/V
    heads in every attention block. The Mamba blocks keep nothing a
    position."""
    m = dims(cfg)
    return m["A"] * 2 * m["Hkv"] * m["Dh"] * bytes_per_el


def state_bytes(cfg: dict, bytes_per_el: int = 2) -> int:
    """What a slot holds whatever its length: in every Mamba block the
    float32 state [H, P, N] and the conv's window of K - 1 input rows."""
    m = dims(cfg)
    return m["M"] * (m["inner"] * m["mN"] * 4
                     + (m["mK"] - 1) * m["conv"] * bytes_per_el)


def scan_flops_per_token(cfg: dict) -> int:
    """The recurrence and the conv of every Mamba block, one token."""
    m = dims(cfg)
    return m["M"] * (5 * m["inner"] * m["mN"] + 2 * m["mK"] * m["conv"])


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
    over with the new one, so `len(depths)` slots are live: each one's state
    is read and written once. The run's counters are the whole window's:
    the interval is not read."""
    depths = list(depths)
    n, rows = len(depths), sum(depths)
    m = dims(cfg)
    held_share, hit_share = routing(cfg, run)
    pairs = n * m["k"] * m["E"] * held_share
    flops = (n * 2 * (matmul_params_outside_experts(cfg) + m["d"] * m["v"])
             + pairs * 2 * expert_params(cfg)
             + n * scan_flops_per_token(cfg)
             + rows * m["A"] * 4 * m["H"] * m["Dh"])
    kvb = kv_bytes_per_position(cfg, bytes_per_el)
    byts = ((_weights_once(cfg, True)
             + hit_share * m["E"] * m["held"] * expert_params(cfg))
            * bytes_per_el
            + n * m["d"] * bytes_per_el           # embedding rows
            + n * 2 * state_bytes(cfg, bytes_per_el)  # read and written
            + rows * kvb + n * kvb)               # cache read, row written
    return float(flops), float(byts)


def prefill_chunk(cfg: dict, n_tokens: int, depth0: int, final: bool,
                  bytes_per_el: int = 2, run: Optional[dict] = None,
                  span: Optional[dict] = None) -> Tuple[float, float]:
    """One prefill chunk of `n_tokens` real tokens after `depth0` cached
    positions; `final` chunks also sample the first output token (head).
    The cache is read once a chunk, at its last query's depth; the slot's
    state is read and written once."""
    m = dims(cfg)
    held_share, _ = routing(cfg, run)
    chosen = n_tokens * m["k"]                    # pairs a routed block
    reached = 1.0 - (1.0 - held_share / m["held"]) ** chosen
    pairs_qk = n_tokens * depth0 + n_tokens * (n_tokens + 1) // 2
    flops = (n_tokens * 2 * matmul_params_outside_experts(cfg)
             + chosen * m["E"] * held_share * 2 * expert_params(cfg)
             + n_tokens * scan_flops_per_token(cfg)
             + pairs_qk * m["A"] * 4 * m["H"] * m["Dh"])
    if final:
        flops += 2 * m["d"] * m["v"]
    kvb = kv_bytes_per_position(cfg, bytes_per_el)
    byts = ((_weights_once(cfg, final)
             + reached * m["E"] * m["held"] * expert_params(cfg))
            * bytes_per_el
            + n_tokens * m["d"] * bytes_per_el
            + 2 * state_bytes(cfg, bytes_per_el)
            + (depth0 + n_tokens) * kvb + n_tokens * kvb)
    return float(flops), float(byts)


def param_count(cfg: dict) -> int:
    """Parameters as the graph holds them: the held experts, the held rows
    of the vocabulary (embedding + bias, head + bias), every gain and
    bias."""
    m = dims(cfg)
    return (matmul_params_outside_experts(cfg) + vector_params(cfg)
            + m["E"] * m["held"] * expert_params(cfg)
            + m["v"] * m["d"] + m["d"] + head_params(cfg))
