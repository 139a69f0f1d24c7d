"""The toy family's plain reference, float32 at `highest`:

    x = tok[ids] + b
    x = LN1(x + attn(x) @ Wo + bo)          causal, RoPE (rotate-half), GQA
    x = LN2(x + relu(x @ Wu) @ Wd)
    logits = x @ Wh + bh
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.precision import mm


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _rope(a, theta):
    half = a.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(a.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a1, a2 = a[..., :half], a[..., half:]
    return jnp.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos], -1)


def _layer(x, p, h, hkv, eps, theta, quant):
    R, T, d = x.shape
    dh = d // h
    q = _rope(mm(x, p["q"], quant).reshape(R, T, h, dh), theta)
    k = _rope(mm(x, p["k"], quant).reshape(R, T, hkv, dh), theta)
    v = mm(x, p["v"], quant).reshape(R, T, hkv, dh)
    sc = jnp.einsum("rqkgd,rtkd->rkgqt", q.reshape(R, T, hkv, h // hkv, dh),
                    k) / jnp.sqrt(jnp.float32(dh))
    ok = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    pr = jax.nn.softmax(jnp.where(ok[None, None, None], sc, -jnp.inf), -1)
    o = jnp.einsum("rkgqt,rtkd->rqkgd", pr, v).reshape(R, T, d)
    x = _ln(x + mm(o, p["o"], quant) + p["o_b"].astype(jnp.float32),
            p["n1_g"], p["n1_b"], eps)
    u = jax.nn.relu(mm(x, p["up"], quant))
    return _ln(x + mm(u, p["down"], quant), p["n2_g"], p["n2_b"], eps)


def logits_at(params: dict, cfg: dict, ids, pos, quant=None):
    e = params["ends"]
    with jax.default_matmul_precision("highest"):
        x = e["tok_w"][ids].astype(jnp.float32) + e["tok_b"].astype(
            jnp.float32)
        for p in params["layers"]:
            x = _layer(x, p, cfg["num_attention_heads"],
                       cfg["num_key_value_heads"], float(cfg["norm_epsilon"]),
                       float(cfg["rope_theta"]), quant)
        xs = jnp.take_along_axis(x, pos[:, :, None], axis=1)
        return mm(xs, e["head_w"], quant) + e["head_b"].astype(jnp.float32)
