"""Operations and bytes the StarCoder2 block *needs*, from shapes alone.

The same count whatever implements the step: an embedding is a row gather
(not a one-hot matmul), the head is needed only where a token is sampled,
the cache is read once at its real depth and the weights once per program
execution. Recomputation, padding and copies a program adds are not work.

A configuration is the published dict (`hidden_size`, `num_hidden_layers`,
`num_attention_heads`, `num_key_value_heads`, `intermediate_size`,
`vocab_size`); weights and cache are `bytes_per_el` wide (2 = bfloat16)."""
from __future__ import annotations

from typing import Iterable, Optional, Tuple


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = d // h
    return {"d": d, "L": cfg["num_hidden_layers"], "h": h, "dh": dh,
            "hkv": cfg["num_key_value_heads"], "ff": cfg["intermediate_size"],
            "v": cfg["vocab_size"]}


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied with in one block: q, k, v, o, up, down."""
    m = dims(cfg)
    kv = m["hkv"] * m["dh"]
    return m["d"] * m["d"] * 2 + m["d"] * kv * 2 + 2 * m["d"] * m["ff"]


def layer_vector_params(cfg: dict) -> int:
    """Biases and LayerNorm vectors of one block as the zoo graph holds them:
    two LayerNorms (gain, beta), the attention output bias, two FFN biases."""
    m = dims(cfg)
    return 4 * m["d"] + m["d"] + m["ff"] + m["d"]


def head_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * m["v"] + m["v"] + 2 * m["d"]  # head + bias + final LN


def kv_bytes_per_position(cfg: dict, bytes_per_el: int = 2) -> int:
    m = dims(cfg)
    return m["L"] * 2 * m["hkv"] * m["dh"] * bytes_per_el


def _attn_flops(cfg: dict, keys_seen: int) -> int:
    """QK^T and PV for one query token over `keys_seen` keys, all layers."""
    m = dims(cfg)
    return m["L"] * 4 * m["h"] * m["dh"] * keys_seen


def decode_step(cfg: dict, depths: Iterable[int], bytes_per_el: int = 2,
                run: Optional[dict] = None, t_lo: Optional[float] = None,
                t_hi: Optional[float] = None) -> Tuple[float, float]:
    """One decode step over live slots; `depths[i]` = keys slot i attends
    over (its prompt and generated tokens so far, the new one included).
    The run and the interval the steps lie in (`harness/facts.decode_work`)
    are not read: a dense block's work follows from shapes and depths
    alone."""
    depths = list(depths)
    n = len(depths)
    m = dims(cfg)
    per_tok = 2 * (m["L"] * layer_matmul_params(cfg) + m["d"] * m["v"])
    flops = n * per_tok + sum(_attn_flops(cfg, k) for k in depths)
    weights = (m["L"] * (layer_matmul_params(cfg) + layer_vector_params(cfg))
               + head_params(cfg)) * bytes_per_el
    kvb = kv_bytes_per_position(cfg, bytes_per_el)
    byts = (weights + n * m["d"] * bytes_per_el       # embedding rows
            + sum(depths) * kvb                        # cache read (new row too)
            + n * kvb)                                 # cache write
    return float(flops), float(byts)


def prefill_chunk(cfg: dict, n_tokens: int, depth0: int, final: bool,
                  bytes_per_el: int = 2, run: Optional[dict] = None,
                  span: Optional[dict] = None) -> Tuple[float, float]:
    """One prefill chunk of `n_tokens` real tokens after `depth0` cached
    positions; `final` chunks also sample the first output token (head).
    The run and the chunk's own span are not read (see `decode_step`)."""
    m = dims(cfg)
    flops = 2 * m["L"] * layer_matmul_params(cfg) * n_tokens
    # causal: token i (0-based) sees depth0 + i + 1 keys
    keys = n_tokens * depth0 + n_tokens * (n_tokens + 1) // 2
    flops += _attn_flops(cfg, 1) * keys
    weights = m["L"] * (layer_matmul_params(cfg) + layer_vector_params(cfg))
    if final:
        flops += 2 * m["d"] * m["v"]
        weights += head_params(cfg)
    kvb = kv_bytes_per_position(cfg, bytes_per_el)
    byts = (weights * bytes_per_el + n_tokens * m["d"] * bytes_per_el
            + (depth0 + n_tokens) * kvb      # cache read once per chunk
            + n_tokens * kvb)                # cache write
    return float(flops), float(byts)


def param_count(cfg: dict) -> int:
    """Parameters as the zoo graph holds them (embedding + bias, untied head)."""
    m = dims(cfg)
    return (m["L"] * (layer_matmul_params(cfg) + layer_vector_params(cfg))
            + m["v"] * m["d"] + m["d"] + head_params(cfg))
