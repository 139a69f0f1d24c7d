"""The plain reference: the Nemotron-H block as
`configs/nemotron3-nano-ep8.json` states it, in float32 `jax.numpy` at
`highest` matmul precision. No cache, no paging, no chunked form, nothing
imported from the program. `x` is a row of the residual stream; every norm
is RMSNorm at eps with a plain gain; block i has ONE mixer, by the i-th
letter of `hybrid_override_pattern`:

    x <- x + mixer_i(RMS_i(x));      logits = RMSf(x) Wh + bh

    M  Mamba-2 (`_mamba`), n = RMS_i(x):
       [z | u | dt] = n W_in          widths H P | H P + 2 G N | H
                                      (`_inner`: mamba_num_heads x
                                      mamba_head_dim, NOT expand x hidden)
       u_t <- silu(b + sum_{j<K} w_j * u_{t-K+1+j})   zeros before t = 0
       u = [a | B | C],  a: H heads of P;  B, C: G groups of N (head h reads
                                      group h // (H / G); `n_groups`)
       D_t = softplus(dt_t + dt_bias) (no clamp: time_step_limit (0, inf))
       S_t = exp(D_t A) S_{t-1} + D_t a_t (x) B_t,  A = -exp(A_log),  S_0 = 0
       y_t = S_t C_t + D a_t          one token after another (`lax.scan`)
       out = RMS_G(y * silu(z)) W_out     gate before norm, the norm over
                                      each of G groups of H P / G
    *  attention (`_gqa`): q = n Wq (heads of head_dim), k = n Wk, v = n Wv
       (num_key_value_heads of head_dim), NO positional embedding
       (`assumed`), softmax_causal(q.k / sqrt(head_dim)), query head h reads
       kv head h // (heads / kv heads), out = concat(heads) Wo + bo
    E  experts (`_routed`): sigma = sigmoid(n Wr) over ALL router outputs;
       T = the num_experts_per_tok largest of sigma + b_sel (`_route`: the
       bias chooses and does not weigh; n_group 1 / topk_group 1 = no group
       limit); g_e = routed_scaling_factor sigma_e / sum_T sigma;
       out = sum_{e in T, e held} g_e E_e(n) + S(n),
       E_e(n) = relu(n Wu_e)^2 Wd_e at moe_intermediate_size (no biases),
       S(n) = relu(n Wsu + bsu)^2 Wsd + bsd at
       moe_shared_expert_intermediate_size

The reference is given the same share as the program: the held experts
(`experts_held_first`, `n_routed_experts` of `router_outputs`) and the held
rows of the vocabulary. The normaliser runs over all chosen experts, held or
not; what the absent experts would add is left out, and that partial sum
goes on to the next block.

Departures of the program's graph that the reference follows (listed in the
configuration file): an embedding bias, biases on the shared expert's two
matrices, on the attention's output projection and on the head.

It runs after the window has closed and the engine's state is freed, one
block at a time with the weights upcast inside the jitted block (the held
experts one at a time, in a scan), queries in blocks.

`quant` is the control precision (`harness/precision.py`): every matmul with
a weight matrix, the router's included, goes through its `mm`. The conv, the
recurrence and the norms are not matmuls with a weight and stay float32."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.harness.precision import mm as _mm

_QBLOCK = 256


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _inner(m):
    """Mamba's inner width: heads x head width (4,096 here), not
    `expand` x hidden_size (5,376), which the family's code does not read."""
    return m["mH"] * m["mP"]


def _mamba(n, p, m, quant, drop=()):
    """n [R, T, d] -> [R, T, d]. `drop` (the tests alone pass it) leaves a
    named part out: "state", "D", "conv_bias", "gate"."""
    f32 = jnp.float32
    H, P, N, G, K = m["mH"], m["mP"], m["mN"], m["mG"], m["mK"]
    inner, R, T = _inner(m), n.shape[0], n.shape[1]
    C = inner + 2 * G * N
    zud = _mm(n, p["w_in"], quant)
    z, u, dt = zud[..., :inner], zud[..., inner:inner + C], \
        zud[..., inner + C:]
    w = p["conv_w"].astype(f32)
    up = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    acc = jnp.zeros_like(u) if "conv_bias" in drop \
        else jnp.broadcast_to(p["conv_b"].astype(f32), u.shape)
    for j in range(K):
        acc = acc + w[j] * up[:, j:j + T]
    u = jax.nn.silu(acc)
    a = u[..., :inner].reshape(R, T, H, P)
    Bm = jnp.repeat(u[..., inner:inner + G * N].reshape(R, T, G, N),
                    H // G, axis=2)
    Cm = jnp.repeat(u[..., inner + G * N:].reshape(R, T, G, N),
                    H // G, axis=2)
    step = jax.nn.softplus(dt + p["dt_bias"].astype(f32))       # [R, T, H]
    A = -jnp.exp(p["a_log"].astype(f32))

    def one(S, inp):
        a_t, b_t, c_t, d_t = inp
        S = jnp.exp(d_t * A)[..., None, None] * S \
            + (d_t[..., None] * a_t)[..., None] * b_t[..., None, :]
        if "state" in drop:
            S = (d_t[..., None] * a_t)[..., None] * b_t[..., None, :]
        return S, jnp.sum(S * c_t[..., None, :], -1)

    _, y = jax.lax.scan(one, jnp.zeros((R, H, P, N), f32),
                        tuple(jnp.moveaxis(v, 1, 0)
                              for v in (a, Bm, Cm, step)))
    y = jnp.moveaxis(y, 0, 1)                                   # [R,T,H,P]
    if "D" not in drop:
        y = y + p["d_skip"].astype(f32)[:, None] * a
    g = y.reshape(R, T, inner)
    if "gate" not in drop:
        g = g * jax.nn.silu(z)
    g = g.reshape(R, T, G, inner // G)
    g = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + m["eps"])
    return _mm(g.reshape(R, T, inner) * p["norm_g"].astype(f32),
               p["w_out"], quant)


def _gqa(n, p, m, quant):
    """No positional embedding: the family's modeling code reads neither
    rope_theta nor partial_rotary_factor (`assumed`)."""
    R, T, _ = n.shape
    H, Hkv, Dh = m["H"], m["Hkv"], m["Dh"]
    q = _mm(n, p["wq"], quant).reshape(R, T, Hkv, H // Hkv, Dh)
    k = _mm(n, p["wk"], quant).reshape(R, T, Hkv, Dh)
    v = _mm(n, p["wv"], quant).reshape(R, T, Hkv, Dh)
    nq = -(-T // _QBLOCK)
    qb = jnp.pad(q, ((0, 0), (0, nq * _QBLOCK - T)) + ((0, 0),) * 3)
    qb = jnp.moveaxis(qb.reshape((R, nq, _QBLOCK) + q.shape[2:]), 1, 0)

    def block(args):
        qi, a0 = args
        sc = jnp.einsum("rqhgd,rthd->rhgqt", qi, k) * Dh ** -0.5
        ok = jnp.arange(T)[None, :] <= (a0 + jnp.arange(_QBLOCK))[:, None]
        pr = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
        return jnp.einsum("rhgqt,rthd->rqhgd", pr, v)

    o = jax.lax.map(block, (qb, jnp.arange(nq) * _QBLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(R, nq * _QBLOCK, H * Dh)[:, :T]
    return _mm(o, p["wo"], quant) + p["bo"].astype(jnp.float32)


def _relu2(x):
    r = jax.nn.relu(x)
    return r * r


def _route(n, p, m, quant, chosen=None):
    """Gates [.., held] of the held experts: the score of each chosen one
    over the sum of ALL the chosen, times the scaling factor; 0 where the
    token did not choose the expert. Also the chosen experts [.., k]: the k
    largest of score + selection bias (n_group 1, topk_group 1: one group,
    so no group limit), or `chosen` where a caller fixes the choice
    (`tools/route_flips.py`: the program's own)."""
    sigma = jax.nn.sigmoid(_mm(n, p["w_router"], quant))
    if chosen is None:
        _, chosen = jax.lax.top_k(sigma + p["b_sel"].astype(jnp.float32),
                                  m["k"])
    top = jnp.take_along_axis(sigma, chosen, -1)
    if m["norm_topk"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    held = m["first"] + jnp.arange(m["held"])
    return m["scale"] * jnp.sum(
        jnp.where(chosen[..., None] == held, top[..., None], 0.0),
        axis=-2), chosen


def _routed(n, p, m, quant, chosen=None):
    gates, chosen = _route(n, p, m, quant, chosen)

    def one(y, expert):
        w_up, w_down, gate = expert
        return y + gate[..., None] * _mm(_relu2(_mm(n, w_up, quant)),
                                         w_down, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(n),
                        (p["we_up"], p["we_down"],
                         jnp.moveaxis(gates, -1, 0)))
    shared = _mm(_relu2(_mm(n, p["ws_up"], quant)
                        + p["bs_up"].astype(jnp.float32)),
                 p["ws_down"], quant) + p["bs_down"].astype(jnp.float32)
    return y + shared, chosen


@partial(jax.jit, static_argnames=("m", "quant"))
def _block(x, p, chosen=None, *, m, quant):
    """-> (the block's output, the experts its router chose [R, T, k], or
    None for a block that routes nothing). The block's kind is what its
    weights are."""
    m = dict(m)
    n = _rms(x, p["ln_g"], m["eps"])
    pick = None
    if "w_in" in p:
        y = _mamba(n, p, m, quant)
    elif "wq" in p:
        y = _gqa(n, p, m, quant)
    else:
        y, pick = _routed(n, p, m, quant, chosen)
    return x + y, pick


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, pos, g, w, bw, *, eps, quant):
    """Logits at positions `pos` [R, P] only: where a token was sampled."""
    xs = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    return _mm(_rms(xs, g, eps), w, quant) + bw.astype(jnp.float32)


def dims(cfg: dict) -> tuple:
    m = {"mH": cfg["mamba_num_heads"], "mP": cfg["mamba_head_dim"],
         "mN": cfg["ssm_state_size"], "mG": cfg["n_groups"],
         "mK": cfg["conv_kernel"], "eps": float(cfg["layer_norm_epsilon"]),
         "H": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
         "Dh": cfg["head_dim"], "k": cfg["num_experts_per_tok"],
         "held": cfg["n_routed_experts"],
         "first": cfg.get("experts_held_first", 0),
         "norm_topk": bool(cfg["norm_topk_prob"]),
         "scale": float(cfg["routed_scaling_factor"])}
    return tuple(sorted(m.items()))


def _stream(params, cfg, ids, quant, routes):
    """The residual stream after the last block, and the experts each
    routed block's router chose ([R, T, k] a block). `routes`, such a list,
    fixes the choices instead."""
    m = dims(cfg)
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
                     params["head_b"],
                     eps=float(cfg["layer_norm_epsilon"]), quant=quant)


def routing_at(params: dict, cfg: dict, ids, quant=None) -> list:
    """The experts the reference's own routers choose at every position:
    one int32 [R, T, k] array a routed block."""
    with jax.default_matmul_precision("highest"):
        return _stream(params, cfg, ids, quant, None)[1]
