"""The plain reference: the StarCoder2 block as the configuration states it,
in float32 `jax.numpy` at `highest` matmul precision. No cache, no paging, no
batching tricks, nothing imported from the program.

    x = embed[ids] + b_e
    x = x + (attn(LN1(x)) @ Wo + bo)       causal, RoPE (rotate-half), GQA
    x = x + (gelu_tanh(LN2(x) @ Wu + bu) @ Wd + bd)
    logits = LNf(x) @ Wh + bh

Departures of the zoo graph from the published model that the reference
follows (listed in the configuration files): no q/k/v biases, an embedding
bias, an untied head, no sliding window (requests stay <= 4,096 positions).

It runs after the window has closed and the engine's state is freed, one
block at a time with the weights upcast inside the jitted block, attention
over query blocks, so it fits beside the bfloat16 weights.

`quant` is the control precision (`harness/precision.py`, shared by every
family): every matmul with a weight goes through its `mm`, which rounds both
sides to fp8 (the control) or int8 (kept for the record)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.harness.precision import mm as _mm

_QBLOCK = 512


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rope(a, theta):
    """a: [R, T, H, Dh]; rotate-half pairing (dim i with i + Dh/2)."""
    half = a.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(a.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a1, a2 = a[..., :half], a[..., half:]
    return jnp.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos], -1)


@partial(jax.jit, static_argnames=("h", "hkv", "eps", "theta", "quant"))
def _block(x, p, *, h, hkv, eps, theta, quant):
    R, T, d = x.shape
    dh = d // h
    y = _ln(x, p["ln1_g"], p["ln1_b"], eps)
    q = _rope(_mm(y, p["wq"], quant).reshape(R, T, h, dh), theta)
    k = _rope(_mm(y, p["wk"], quant).reshape(R, T, hkv, dh), theta)
    v = _mm(y, p["wv"], quant).reshape(R, T, hkv, dh)
    qg = q.reshape(R, T, hkv, h // hkv, dh)
    outs = []
    for s in range(0, T, _QBLOCK):          # query blocks, so scores fit
        e = min(s + _QBLOCK, T)
        sc = jnp.einsum("rqkgd,rtkd->rkgqt", qg[:, s:e], k[:, :e]) \
            / jnp.sqrt(jnp.float32(dh))
        ok = jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None]
        sc = jnp.where(ok[None, None, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("rkgqt,rtkd->rqkgd", pr, v[:, :e]))
    o = jnp.concatenate(outs, 1).reshape(R, T, d)
    x = x + _mm(o, p["wo"], quant) + p["bo"].astype(jnp.float32)
    y = _ln(x, p["ln2_g"], p["ln2_b"], eps)
    u = jax.nn.gelu(_mm(y, p["w_up"], quant) + p["b_up"].astype(jnp.float32),
                    approximate=True)
    return x + _mm(u, p["w_down"], quant) + p["b_down"].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, pos, g, b, w, bw, *, eps, quant):
    """Logits at positions `pos` [R, P] only: where a token was sampled."""
    xs = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    return _mm(_ln(xs, g, b, eps), w, quant) + bw.astype(jnp.float32)


def logits_at(params: dict, cfg: dict, ids, pos, quant=None):
    """ids [R, T] int32 (padded at the end; causal, so padding is inert),
    pos [R, P] int32 -> float32 logits [R, P, vocab] for the *next* token
    after each position."""
    kw = dict(h=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
              eps=float(cfg["norm_epsilon"]), theta=float(cfg["rope_theta"]),
              quant=quant)
    with jax.default_matmul_precision("highest"):
        x = params["embed_w"][ids].astype(jnp.float32) \
            + params["embed_b"].astype(jnp.float32)
        for p in params["blocks"]:
            x = _block(x, p, **kw)
        return _head(x, pos, params["lnf_g"], params["lnf_b"],
                     params["head_w"], params["head_b"],
                     eps=kw["eps"], quant=quant)
