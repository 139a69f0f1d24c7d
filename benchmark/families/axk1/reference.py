"""The plain reference: the A.X-K1 block as `configs/axk1-ep16-d6.json`
states it, in float32 `jax.numpy` at `highest` matmul precision. No cache,
no paging, no absorbed form, nothing imported from the program. `x` is a row
of the residual stream; every norm is RMSNorm at eps with a gain.

    h = x + MLA(RMS1(x));   y = h + FFN(RMS2(h));   logits = RMSf(y) Wh + bh

    MLA   c_q = RMSq(x Wdq);  q = c_q Wuq -> heads of [q_n (nope) | q_r (rope)]
          [c_kv | k_r] = x Wdkv;  c = RMSkv(c_kv)
          q_r, k_r <- RoPE (k_r: ONE rotated key shared by all heads)
          k_h = [c Wuk_h | k_r],  v_h = c Wuv_h      ([Wuk_h | Wuv_h] = Wukv's
          p = softmax_causal(s (q_n.k_n + q_r.k_r))    columns of head h)
          MLA = concat_h(p v_h) Wo
    s     = (nope + rope)^-1/2 m^2,  m = 0.1 mscale_all_dim ln(factor) + 1
    RoPE  rotate-half over the rope dims at YaRN's frequencies (`_inv_freq`):
          theta^(-2i/rope) blended with the same divided by `factor`, by a
          linear ramp between the correction dims of beta_fast and beta_slow
          over original_max_position_embeddings; cos/sin times
          mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    FFN   the `first_k_dense_replace` leading layers:
          (silu(x Wg + bg) * (x Wu + bu)) Wd + bd at intermediate_size
          the others: sigma = sigmoid(x Wr) over ALL router outputs;
          T = the num_experts_per_tok largest (`_route`: `topk_method` none
          is read as no groups and no selection bias; listed under
          `assumed`); g_e = routed_scaling_factor sigma_e / sum_T sigma;
          FFN = sum_{e in T, e held} g_e E_e(x) + S(x), E_e and S gated FFNs
          of moe_intermediate_size (E_e without biases, S with the graph's)

The reference is given the same share as the program: the held experts
(`experts_held_first`, `n_routed_experts` of `router_outputs`) and the held
rows of the vocabulary. The normaliser runs over all chosen experts, held or
not; what the absent experts would add is left out, and that partial sum
goes on to the next layer.

Departures of the program's graph that the reference follows (listed in the
configuration file): an embedding bias, biases on the dense and shared FFN
matrices and on the head.

It runs after the window has closed and the engine's state is freed, one
block at a time with the weights upcast inside the jitted block (the held
experts one at a time, in a scan), queries in blocks, the FFN in row blocks:
float32 copies of the 8.33 GB are never alive together.

`quant` is the control precision (`harness/precision.py`): every matmul with
a weight matrix, the router's included, goes through its `mm`."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.harness.precision import mm as _mm

_QBLOCK = 256
_FBLOCK = 2048


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(dim, theta, yarn):
    """Inverse frequencies of the dim/2 rotated pairs; `yarn` is the
    configuration's `rope_scaling` as a sorted tuple of items, or ()."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    extra = theta ** (-2.0 * i / dim)
    y = dict(yarn)
    if not y or y["factor"] <= 1:
        return extra

    def correction_dim(rotations):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return extra / y["factor"] * (1.0 - keep) + extra * keep


def _rope(a, theta, yarn):
    """a: [R, T, H, D]; rotate-half pairing (dim i with i + D/2)."""
    half = a.shape[-1] // 2
    y = dict(yarn)
    amp = _mscale(y["factor"], y["mscale"]) \
        / _mscale(y["factor"], y["mscale_all_dim"]) if y else 1.0
    ang = jnp.arange(a.shape[1], dtype=jnp.float32)[:, None] \
        * _inv_freq(a.shape[-1], theta, yarn)[None]
    cos = (jnp.cos(ang) * amp)[None, :, None]
    sin = (jnp.sin(ang) * amp)[None, :, None]
    a1, a2 = a[..., :half], a[..., half:]
    return jnp.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos], -1)


def _attention(q, k, v, s):
    """Causal softmax attention, `_QBLOCK` queries at a time (one compiled
    block, mapped). q, k: [R, T, H, Dqk]; v: [R, T, H, Dv]."""
    R, T = q.shape[:2]
    n = -(-T // _QBLOCK)
    qb = jnp.pad(q, ((0, 0), (0, n * _QBLOCK - T), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qb.reshape((R, n, _QBLOCK) + q.shape[2:]), 1, 0)

    def block(args):
        qi, a = args
        sc = s * jnp.einsum("rqhd,rthd->rhqt", qi, k)
        ok = jnp.arange(T)[None, :] <= (a + jnp.arange(_QBLOCK))[:, None]
        pr = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("rhqt,rthd->rqhd", pr, v)

    out = jax.lax.map(block, (qb, jnp.arange(n) * _QBLOCK))
    return jnp.moveaxis(out, 0, 1).reshape((R, n * _QBLOCK) + v.shape[2:])[:, :T]


def _mla(x, p, m, quant):
    R, T, _ = x.shape
    H, dn, dr, dv, C = m["H"], m["dn"], m["dr"], m["dv"], m["C"]
    yarn = m["yarn"]
    cq = _rms(_mm(x, p["wdq"], quant), p["qn_g"], m["eps"])
    q = _mm(cq, p["wuq"], quant).reshape(R, T, H, dn + dr)
    ckv = _mm(x, p["wdkv"], quant)
    c = _rms(ckv[..., :C], p["kvn_g"], m["eps"])
    k_r = _rope(ckv[..., None, C:], m["theta"], yarn)           # one head
    q_r = _rope(q[..., dn:], m["theta"], yarn)
    kv = _mm(c, p["wukv"], quant).reshape(R, T, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r, (R, T, H, dr))], -1)
    y = dict(yarn)
    mall = _mscale(y["factor"], y["mscale_all_dim"]) if y else 1.0
    s = (dn + dr) ** -0.5 * mall * mall
    o = _attention(jnp.concatenate([q[..., :dn], q_r], -1), k, kv[..., dn:],
                   s)
    return _mm(o.reshape(R, T, H * dv), p["wo"], quant)


def _gated(n, p, w, b, quant):
    g = jax.nn.silu(_mm(n, p[f"{w}_gate"], quant)
                    + p[f"{b}_gate"].astype(jnp.float32))
    u = _mm(n, p[f"{w}_up"], quant) + p[f"{b}_up"].astype(jnp.float32)
    return _mm(g * u, p[f"{w}_down"], quant) \
        + p[f"{b}_down"].astype(jnp.float32)


def _route(n, w_router, m, quant, chosen=None):
    """Gates [.., held] of the held experts: the score of each chosen one
    over the sum of ALL the chosen, times the scaling factor; 0 where the
    token did not choose the expert. Also the chosen experts [.., k]: the k
    largest scores, or `chosen` where a caller fixes the choice
    (`tools/route_flips.py`: the program's own)."""
    if m["scoring"] != "sigmoid":
        raise ValueError(f"scoring_func {m['scoring']!r} is not written "
                         "down here: 'sigmoid' is")
    sigma = jax.nn.sigmoid(_mm(n, w_router, quant))
    if chosen is None:
        top, chosen = jax.lax.top_k(sigma, m["k"])
    else:
        top = jnp.take_along_axis(sigma, chosen, -1)
    if m["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    held = m["first"] + jnp.arange(m["held"])
    return m["scale"] * jnp.sum(
        jnp.where(chosen[..., None] == held, top[..., None], 0.0),
        axis=-2), chosen


def _routed(n, p, m, quant, chosen=None):
    gates, chosen = _route(n, p["w_router"], m, quant, chosen)

    def one(y, expert):
        w_gate, w_up, w_down, gate = expert
        h = jax.nn.silu(_mm(n, w_gate, quant)) * _mm(n, w_up, quant)
        return y + gate[..., None] * _mm(h, w_down, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n),
                        (p["we_gate"], p["we_up"], p["we_down"],
                         jnp.moveaxis(gates, -1, 0)))
    return y + _gated(n, p, "ws", "bs", quant), chosen


@partial(jax.jit, static_argnames=("m", "quant"))
def _block(x, p, chosen=None, *, m, quant):
    """-> (the block's output, the experts its router chose [R, T, k], or
    None for a dense block)."""
    m = dict(m)
    x = x + _mla(_rms(x, p["ln1_g"], m["eps"]), p, m, quant)
    outs, picks = [], []
    for a in range(0, x.shape[1], _FBLOCK):
        n = _rms(x[:, a:a + _FBLOCK], p["ln2_g"], m["eps"])
        if "w_router" in p:
            y, pick = _routed(n, p, m, quant, None if chosen is None
                              else chosen[:, a:a + _FBLOCK])
            picks.append(pick)
        else:
            y = _gated(n, p, "w", "b", quant)
        outs.append(y)
    return x + jnp.concatenate(outs, 1), \
        jnp.concatenate(picks, 1) if picks else None


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, pos, g, w, bw, *, eps, quant):
    """Logits at positions `pos` [R, P] only: where a token was sampled."""
    xs = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    return _mm(_rms(xs, g, eps), w, quant) + bw.astype(jnp.float32)


def _stream(params, cfg, ids, quant, routes):
    """The residual stream after the last block, and the experts each
    routed block's router chose ([R, T, k] a block). `routes`, such a list,
    fixes the choices instead."""
    yarn = cfg.get("rope_scaling") or {}
    m = {"H": cfg["num_attention_heads"], "dn": cfg["qk_nope_head_dim"],
         "dr": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
         "C": cfg["kv_lora_rank"], "eps": float(cfg["rms_norm_eps"]),
         "theta": float(cfg["rope_theta"]),
         "yarn": tuple(sorted((k, v) for k, v in yarn.items()
                              if k != "type")),
         "k": cfg["num_experts_per_tok"], "held": cfg["n_routed_experts"],
         "first": cfg.get("experts_held_first", 0),
         "scoring": cfg["scoring_func"],
         "norm_topk": bool(cfg["norm_topk_prob"]),
         "scale": float(cfg["routed_scaling_factor"])}
    m = tuple(sorted(m.items()))
    routes, chose = iter(routes or ()), []
    x = params["embed_w"][ids].astype(jnp.float32) \
        + params["embed_b"].astype(jnp.float32)
    for p in params["blocks"]:
        x, pick = _block(x, p, next(routes, None) if "w_router" in p
                         else None, m=m, quant=quant)
        if pick is not None:
            chose.append(pick)
    return x, chose


def logits_at(params: dict, cfg: dict, ids, pos, quant=None, routes=None):
    """ids [R, T] int32 (padded at the end; causal, so padding is inert),
    pos [R, P] int32 -> float32 logits [R, P, vocab] for the *next* token
    after each position, over the held rows of the vocabulary. `routes`
    (`tools/route_flips.py` alone passes it) fixes every router's choice:
    one [R, T, k] array of experts a routed block."""
    with jax.default_matmul_precision("highest"):
        x, _ = _stream(params, cfg, ids, quant, routes)
        return _head(x, pos, params["lnf_g"], params["head_w"],
                     params["head_b"], eps=float(cfg["rms_norm_eps"]),
                     quant=quant)


def routing_at(params: dict, cfg: dict, ids, quant=None) -> list:
    """The experts the reference's own routers choose at every position:
    one int32 [R, T, k] array a routed block."""
    with jax.default_matmul_precision("highest"):
        return _stream(params, cfg, ids, quant, None)[1]
