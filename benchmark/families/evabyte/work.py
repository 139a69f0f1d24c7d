"""Operations and bytes the EvaByte block *needs*, from shapes alone.

The same count whatever implements the step: an embedding is a row gather
(not a one-hot matmul), the head is needed only where a byte is sampled, the
weights are read once per program execution, and the cache is read once at
what a query really attends over. That is NOT one row per position: the
query at position `t`, with `W = t // window_size` and `w0 = W * window_size`,
attends over

    (t - w0 + 1)                      exact rows of its own window, and
    (window_size / chunk_size) * W    summary rows, one per chunk of every
                                      earlier window,

and each new row is pooled again into its chunk's summary (the chunk's own
rows, at most `chunk_size`, read once more; one summary row written).
Recomputation, padding, gathered copies and a whole window read where a part
is attended are not work.

A configuration is the published dict (`hidden_size`, `num_hidden_layers`,
`num_attention_heads`, `intermediate_size`, `vocab_size`, `window_size`,
`chunk_size`); weights and cache are `bytes_per_el` wide (2 = bfloat16)."""
from __future__ import annotations

from typing import Iterable, Optional, Tuple


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "L": cfg["num_hidden_layers"], "h": h, "dh": d // h,
            "ff": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "window": cfg["window_size"], "chunk": cfg["chunk_size"]}


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token is multiplied with in one block: q, k, v, o and the
    gated FFN's gate, up, down."""
    m = dims(cfg)
    return 4 * m["d"] * m["d"] + 3 * m["d"] * m["ff"]


def layer_vector_params(cfg: dict) -> int:
    """Vectors of one block as the graph holds them: two RMSNorm gains, the
    two pooling vectors per head, and the three FFN biases."""
    m = dims(cfg)
    return 2 * m["d"] + 2 * m["h"] * m["dh"] + 2 * m["ff"] + m["d"]


def head_params(cfg: dict) -> int:
    m = dims(cfg)
    return m["d"] * m["v"] + m["v"] + m["d"]   # head 0 + bias + final norm


def kv_bytes_per_position(cfg: dict, bytes_per_el: int = 2) -> int:
    """Bytes of one EXACT cached position (a key row and a value row in every
    layer held): what a position of the open window costs. A position of a
    closed window costs a sixteenth of this (`1 / chunk_size`): its chunk's
    one summary row, of the same shape, stands for `chunk_size` positions."""
    m = dims(cfg)
    return m["L"] * 2 * m["h"] * m["dh"] * bytes_per_el


def rows_attended(cfg: dict, t: int) -> Tuple[int, int]:
    """(exact rows, summary rows) the query at position `t` attends over."""
    m = dims(cfg)
    W = t // m["window"]
    return t - W * m["window"] + 1, (m["window"] // m["chunk"]) * W


def _attn_flops(cfg: dict, rows: int) -> int:
    """QK^T and PV for one query over `rows` rows, all layers."""
    m = dims(cfg)
    return m["L"] * 4 * m["h"] * m["dh"] * rows


def _pool_flops(cfg: dict, rows: int) -> int:
    """The two poolings of a chunk summary over `rows` of its rows, all
    layers: two logits and two weighted sums, 2 d operations a row each."""
    m = dims(cfg)
    return m["L"] * 8 * m["h"] * m["dh"] * rows


def decode_step(cfg: dict, depths: Iterable[int], bytes_per_el: int = 2,
                run: Optional[dict] = None, t_lo: Optional[float] = None,
                t_hi: Optional[float] = None) -> Tuple[float, float]:
    """One decode step over live slots; `depths[i]` = positions slot i holds
    with the new one (so its query stands at `depths[i] - 1`). The run and
    the interval are not read: the work follows from shapes and depths."""
    depths = list(depths)
    n = len(depths)
    m = dims(cfg)
    flops = n * 2 * (m["L"] * layer_matmul_params(cfg) + m["d"] * m["v"])
    rows_read = 0
    for depth in depths:
        exact, summary = rows_attended(cfg, depth - 1)
        own = (depth - 1) % m["chunk"] + 1      # its chunk's rows so far
        flops += _attn_flops(cfg, exact + summary) + _pool_flops(cfg, own)
        rows_read += exact + summary + own
    weights = (m["L"] * (layer_matmul_params(cfg) + layer_vector_params(cfg))
               + head_params(cfg)) * bytes_per_el
    kvb = kv_bytes_per_position(cfg, bytes_per_el)
    byts = (weights + n * m["d"] * bytes_per_el       # embedding rows
            + rows_read * kvb                          # cache read
            + 2 * n * kvb)                             # exact + summary row
    return float(flops), float(byts)


def prefill_chunk(cfg: dict, n_tokens: int, depth0: int, final: bool,
                  bytes_per_el: int = 2, run: Optional[dict] = None,
                  span: Optional[dict] = None) -> Tuple[float, float]:
    """One prefill chunk of `n_tokens` real tokens after `depth0` cached
    positions; `final` chunks also sample the first output byte (head). The
    cache is read once a chunk: what its last query attends over."""
    m = dims(cfg)
    flops = 2 * m["L"] * layer_matmul_params(cfg) * n_tokens
    for t in range(depth0, depth0 + n_tokens):
        flops += _attn_flops(cfg, sum(rows_attended(cfg, t)))
    flops += _pool_flops(cfg, n_tokens)
    weights = m["L"] * (layer_matmul_params(cfg) + layer_vector_params(cfg))
    if final:
        flops += 2 * m["d"] * m["v"]
        weights += head_params(cfg)
    kvb = kv_bytes_per_position(cfg, bytes_per_el)
    summaries = -(-n_tokens // m["chunk"])
    byts = (weights * bytes_per_el + n_tokens * m["d"] * bytes_per_el
            + sum(rows_attended(cfg, depth0 + n_tokens - 1)) * kvb
            + (n_tokens + summaries) * kvb)            # cache write
    return float(flops), float(byts)


def param_count(cfg: dict) -> int:
    """Parameters as the graph holds them (embedding + bias, one head)."""
    m = dims(cfg)
    return (m["L"] * (layer_matmul_params(cfg) + layer_vector_params(cfg))
            + m["v"] * m["d"] + m["d"] + head_params(cfg))
