"""The toy family's count of work. Unlike StarCoder2's it reads the run it
is handed: the step's weights are counted once for every `decode_step` span
the program recorded in the interval, where there is a run."""
from __future__ import annotations


def _m(cfg):
    d = cfg["hidden_size"]
    dh = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * dh
    return d, cfg["num_hidden_layers"], cfg["intermediate_size"], \
        cfg["vocab_size"], kv


def _layer_params(cfg):
    d, _, ff, _, kv = _m(cfg)
    return 2 * d * d + 2 * d * kv + 2 * d * ff + 5 * d   # no FFN biases


def param_count(cfg):
    d, L, _, v, _ = _m(cfg)
    return L * _layer_params(cfg) + 2 * v * d + d + v    # no final LayerNorm


def kv_bytes_per_position(cfg, bytes_per_el=2):
    _, L, _, _, kv = _m(cfg)
    return L * 2 * kv * bytes_per_el


def decode_step(cfg, depths, bytes_per_el=2, run=None, t_lo=None, t_hi=None):
    d, L, _, v, _ = _m(cfg)
    depths = list(depths)
    steps = 1
    if run is not None and depths:
        steps = max(1, sum(1 for s in run["window"]["spans"]
                           if s["name"] == "decode_step"
                           and t_lo <= s["t"] <= t_hi))
    flops = len(depths) * 2 * (L * (_layer_params(cfg) - 5 * d) + d * v) \
        + sum(L * 4 * d * k for k in depths)
    byts = steps * (L * _layer_params(cfg) + d * v + v) * bytes_per_el \
        + (sum(depths) + len(depths)) * kv_bytes_per_position(
            cfg, bytes_per_el)
    return float(flops), float(byts)


def prefill_chunk(cfg, n_tokens, depth0, final, bytes_per_el=2, run=None,
                  span=None):
    d, L, _, v, _ = _m(cfg)
    keys = n_tokens * depth0 + n_tokens * (n_tokens + 1) // 2
    flops = 2 * L * (_layer_params(cfg) - 5 * d) * n_tokens + L * 4 * d * keys
    byts = L * _layer_params(cfg) * bytes_per_el \
        + (depth0 + 2 * n_tokens) * kv_bytes_per_position(cfg, bytes_per_el)
    if final:
        flops += 2 * d * v
        byts += (d * v + v) * bytes_per_el
    return float(flops), float(byts)
