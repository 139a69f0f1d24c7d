"""The plain reference: the EvaByte block as `configs/evabyte-d16.json`
states it, in float32 `jax.numpy` at `highest` matmul precision. No cache,
no paging, nothing imported from the program.

    x = embed[ids] + b_e
    h = x + Attn(RMS1(x)) @ Wo
    y = h + (silu(n @ Wg + bg) * (n @ Wu + bu)) @ Wd + bd,   n = RMS2(h)
    logits = RMSf(y) @ Wh + bh
    RMS(x) = x / sqrt(mean(x^2) + eps) * (1 + g)

Attn is EVA's (Zheng et al. 2023, arXiv:2302.04542) as EvaByte runs it.
With `s = 1/sqrt(head)`, `W(t) = t // window_size`, chunk `c` the positions
`[c*chunk_size, (c+1)*chunk_size)` and rotate-half RoPE at `rope_theta` on q
and k, the query at `t` attends in ONE softmax over

    the exact keys of its own window,  window_size*W(t) <= n <= t,  and
    one summary (k~_c, v~_c) per chunk of every earlier window,
    c < (window_size/chunk_size) * W(t),

    k~_c = sum_{n in c} softmax_n(s mu_h.k_n) k_n
    v~_c = sum_{n in c} softmax_n(s phi_h.k_n) v_n        (`_summaries`)

per head h, on the rotated keys. The two pooling logits are as recalled from
the release's `eva.py` (`adaptive_mu_k`, `adaptive_phi`) and are listed under
the configuration's `assumed`; they live in `_summaries` alone.

Departures of the program's graph that the reference follows (listed in the
configuration file): an embedding bias, biases on the three FFN matrices and
on the head. The head is head 0 of the published eight.

It runs after the window has closed and the engine's state is freed, one
block at a time with the weights upcast inside the jitted block, the
attention one window and one block of queries at a time and the FFN in row
blocks, so 16 K positions fit beside the bfloat16 weights.

`quant` is the control precision (`harness/precision.py`): every matmul with
a weight matrix goes through its `mm`."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark.harness.precision import mm as _mm

_QBLOCK = 512
_FBLOCK = 2048


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g.astype(jnp.float32))


def _rope(a, theta):
    """a: [R, T, H, Dh]; rotate-half pairing (dim i with i + Dh/2)."""
    half = a.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(a.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a1, a2 = a[..., :half], a[..., half:]
    return jnp.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos], -1)


def _summaries(k, v, mu, phi, chunk):
    """k, v: [R, T, H, Dh] (k rotated) -> k~, v~: [R, ceil(T/chunk), H, Dh].
    Rows past T (a last partial chunk) are left out of the pooling."""
    R, T, H, Dh = k.shape
    n = -(-T // chunk)
    s = 1.0 / jnp.sqrt(jnp.float32(Dh))

    def chunks(a):
        a = jnp.pad(a, ((0, 0), (0, n * chunk - T), (0, 0), (0, 0)))
        return a.reshape(R, n, chunk, H, Dh)

    kc, vc = chunks(k), chunks(v)
    real = (jnp.arange(n * chunk) < T).reshape(1, n, chunk, 1)

    def pooled(vec, rows):
        lg = s * jnp.einsum("rcnhd,hd->rcnh", kc, vec.astype(jnp.float32))
        pr = jax.nn.softmax(jnp.where(real, lg, -jnp.inf), axis=2)
        return jnp.einsum("rcnh,rcnhd->rchd", pr, rows)

    return pooled(mu, kc), pooled(phi, vc)


def _attention(q, k, v, ks, vs, window, chunk):
    """One softmax over the window's exact rows and the earlier windows'
    chunk summaries; a window, and `_QBLOCK` queries of it, at a time."""
    R, T, H, Dh = q.shape
    s = 1.0 / jnp.sqrt(jnp.float32(Dh))
    per_window = window // chunk
    outs = []
    for w0 in range(0, T, window):
        w1 = min(w0 + window, T)
        seen = (w0 // window) * per_window      # chunks of earlier windows
        kw = jnp.concatenate([k[:, w0:w1], ks[:, :seen]], 1)
        vw = jnp.concatenate([v[:, w0:w1], vs[:, :seen]], 1)
        for a in range(w0, w1, _QBLOCK):
            b = min(a + _QBLOCK, w1)
            sc = s * jnp.einsum("rqhd,rthd->rhqt", q[:, a:b], kw)
            ok = jnp.concatenate(
                [jnp.arange(w0, w1)[None, :] <= jnp.arange(a, b)[:, None],
                 jnp.ones((b - a, seen), bool)], 1)
            pr = jax.nn.softmax(jnp.where(ok[None, None], sc, -jnp.inf), -1)
            outs.append(jnp.einsum("rhqt,rthd->rqhd", pr, vw))
    return jnp.concatenate(outs, 1)


@partial(jax.jit, static_argnames=("h", "eps", "theta", "window", "chunk",
                                   "quant"))
def _block(x, p, *, h, eps, theta, window, chunk, quant):
    R, T, d = x.shape
    dh = d // h
    y = _rms(x, p["ln1_g"], eps)
    q = _rope(_mm(y, p["wq"], quant).reshape(R, T, h, dh), theta)
    k = _rope(_mm(y, p["wk"], quant).reshape(R, T, h, dh), theta)
    v = _mm(y, p["wv"], quant).reshape(R, T, h, dh)
    ks, vs = _summaries(k, v, p["mu"], p["phi"], chunk)
    o = _attention(q, k, v, ks, vs, window, chunk).reshape(R, T, d)
    x = x + _mm(o, p["wo"], quant)
    outs = []
    for a in range(0, T, _FBLOCK):
        n = _rms(x[:, a:a + _FBLOCK], p["ln2_g"], eps)
        g = jax.nn.silu(_mm(n, p["w_gate"], quant)
                        + p["b_gate"].astype(jnp.float32))
        u = _mm(n, p["w_up"], quant) + p["b_up"].astype(jnp.float32)
        outs.append(_mm(g * u, p["w_down"], quant)
                    + p["b_down"].astype(jnp.float32))
    return x + jnp.concatenate(outs, 1)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, pos, g, w, bw, *, eps, quant):
    """Logits at positions `pos` [R, P] only: where a token was sampled."""
    xs = jnp.take_along_axis(x, pos[:, :, None], axis=1)
    return _mm(_rms(xs, g, eps), w, quant) + bw.astype(jnp.float32)


def logits_at(params: dict, cfg: dict, ids, pos, quant=None):
    """ids [R, T] int32 (padded at the end; causal, so padding is inert),
    pos [R, P] int32 -> float32 logits [R, P, vocab] for the *next* byte
    after each position (prediction head 0)."""
    kw = dict(h=cfg["num_attention_heads"], eps=float(cfg["rms_norm_eps"]),
              theta=float(cfg["rope_theta"]), window=int(cfg["window_size"]),
              chunk=int(cfg["chunk_size"]), quant=quant)
    with jax.default_matmul_precision("highest"):
        x = params["embed_w"][ids].astype(jnp.float32) \
            + params["embed_b"].astype(jnp.float32)
        for p in params["blocks"]:
            x = _block(x, p, **kw)
        return _head(x, pos, params["lnf_g"], params["head_w"],
                     params["head_b"], eps=kw["eps"], quant=quant)
