"""Mamba-2 selective state-space mixer (Dao & Gu 2024).

No reference counterpart. A stateful layer (BaseRecurrentImpl) whose state
is a FIXED size whatever the sequence length: per batch row ``ssm``
``[H, P, N]`` float32 (H heads of P channels, N state dims) and ``conv``
``[K - 1, C]`` (the last K - 1 rows of the conv's input, C = H P + 2 G N
channels). With n the layer's input row,

    [z | u | dt] = n W_in                       widths H P | C | H
    u_t   <- silu(b + sum_{j<K} w_j * u_{t-K+1+j})   depthwise, causal, zeros
                                                     (or ``conv``) before t=0
    u     = [a | B | C]     a: H heads of P;  B, C: G groups of N
                            (head h reads group h // (H / G))
    D_t   = softplus(dt_t + dt_bias)            [H], float32, not clamped
    A     = -exp(A_log)                         [H], float32
    S_t   = exp(D_t A) S_{t-1} + D_t a_t (x) B_t      per head, [P, N] float32
    y_t   = S_t C_t + D a_t
    out   = RMSNorm_G(y * silu(z)) W_out        gate BEFORE norm; the norm
                                                over each of G groups of
                                                H P / G, gain H P wide

A sequence is computed in the chunked (SSD) form, ``chunk_size`` tokens a
chunk: inside a chunk the quadratic form ``(C B^T * L) X`` with the decay
matrix ``L``, between chunks the recurrence on S — the same function as the
step-by-step recurrence (tests/test_mamba2_layer.py). One token (T = 1, the
serving engine's decode step) takes the recurrence directly, elementwise in
float32.

The mask. A step may come with a write mask ([B, T] bool: the serving
engine injects ``wmask`` into the state, `inference/engine.py:_inject_paged`;
a feature ``mask`` is read the same way). A token outside it does not
exist: its D_t is 0 and it does not enter the conv's window, so a lane with
no real token leaves ``ssm`` and ``conv`` EXACTLY as they were (selected,
not multiplied by one), and a chunk padded past its real tokens ends in the
state of its real tokens. The real tokens of a row must come first (the
engine's masks are a whole lane at T = 1 and ``arange < n_real`` in a
chunk). ``ssm`` stays float32 whatever the compute dtype."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import register_impl
from .recurrent import BaseRecurrentImpl
from .. import weights as winit

Array = jax.Array


@register_impl("Mamba2Layer")
class Mamba2LayerImpl(BaseRecurrentImpl):
    WEIGHT_KEYS = ("W_in", "W_out")

    # -- what the serving engine asks of a stateful layer ---------------------
    def takes_chunk(self) -> bool:
        return True

    def masks_own_lanes(self) -> bool:
        return True

    def _dims(self):
        c = self.conf
        H, P, N, G = (int(c.n_heads), int(c.head_dim), int(c.state_size),
                      int(c.n_groups))
        return H, P, N, G, H * P, H * P + 2 * G * N, int(c.conv_kernel)

    def init_params(self, key, dtype=jnp.float32):
        """Matrices by the conf's initialiser; the recurrence's own vectors
        by the Mamba-2 paper's draws: A uniform on [1, 16], D = 1, dt_bias
        the inverse softplus of a step log-uniform on [1e-3, 1e-1]."""
        conf = self.conf
        H, P, N, G, inner, C, K = self._dims()
        dist = conf.dist.spec() if getattr(conf, "dist", None) is not None \
            else None
        init = conf.weight_init or "xavier"
        ki, ko, kc, ka, kd = jax.random.split(key, 5)
        step = jnp.exp(jax.random.uniform(kd, (H,), jnp.float32,
                                          jnp.log(1e-3), jnp.log(1e-1)))
        step = jnp.maximum(step, 1e-4)
        return {
            "W_in": winit.init_weights(ki, (conf.n_in, inner + C + H), init,
                                       dist, dtype),
            "conv_w": (jax.random.uniform(kc, (K, C), jnp.float32, -1.0, 1.0)
                       * K ** -0.5).astype(dtype),
            "conv_b": jnp.zeros((C,), dtype),
            "A_log": jnp.log(jax.random.uniform(ka, (H,), jnp.float32,
                                                1.0, 16.0)).astype(dtype),
            "D": jnp.ones((H,), dtype),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
            "norm_g": jnp.ones((inner,), dtype),
            "W_out": winit.init_weights(ko, (inner, conf.n_out), init, dist,
                                        dtype),
        }

    def init_state(self, batch: int, dtype=jnp.float32):
        H, P, N, _, _, C, K = self._dims()
        return {"ssm": jnp.zeros((batch, H, P, N), jnp.float32),
                "conv": jnp.zeros((batch, K - 1, C), dtype)}

    # -- the two forms of the recurrence --------------------------------------
    @staticmethod
    def _recur(S, a, Bm, Cm, dt, A):
        """One token. S [B,H,P,N] f32; a [B,H,P]; Bm, Cm [B,H,N] (already
        per head); dt [B,H] f32; A [H] f32 -> (y [B,H,P] f32, S')."""
        f32 = jnp.float32
        decay = jnp.exp(dt * A)[..., None, None]
        S = S * decay + (dt[..., None] * a.astype(f32))[..., None] \
            * Bm.astype(f32)[..., None, :]
        return jnp.sum(S * Cm.astype(f32)[..., None, :], axis=-1), S

    def _chunked(self, S0, a, Bm, Cm, dt, A):
        """The SSD form over T tokens, ``chunk_size`` a chunk. S0
        [B,H,P,N] f32; a [B,T,H,P]; Bm, Cm [B,T,G,N]; dt [B,T,H] f32 (0 at
        a token that does not exist) -> (y [B,T,H,P] f32, S_T)."""
        f32 = jnp.float32
        # the state is float32 by contract: its products are too (on a TPU
        # a float32 matmul at the default precision rounds its inputs to
        # bfloat16; these are a few percent of a chunk's operations)
        hi = jax.lax.Precision.HIGHEST
        H, P, N, G, _, _, _ = self._dims()
        B_, T = a.shape[:2]
        Q = min(int(self.conf.chunk_size), T)
        n = -(-T // Q)
        pad = n * Q - T
        if pad:     # tokens that do not exist: no step, no input
            a, Bm, Cm, dt = (jnp.pad(v, ((0, 0), (0, pad))
                                     + ((0, 0),) * (v.ndim - 2))
                             for v in (a, Bm, Cm, dt))
        r = H // G
        # [B, n, Q, G, r, ...]: a head is (its group, its place in it)
        x = (a.astype(f32) * dt[..., None]).reshape(B_, n, Q, G, r, P)
        Bc = Bm.reshape(B_, n, Q, G, N)
        Cc = Cm.reshape(B_, n, Q, G, N)
        la = (dt * A).reshape(B_, n, Q, G, r)           # log decay a token
        cum = jnp.cumsum(la, axis=2)                    # through token l
        # inside a chunk: y_l += sum_{s<=l} exp(cum_l - cum_s) (C_l.B_s) x_s
        scores = jnp.einsum("bnlgk,bnsgk->bngls", Cc, Bc, precision=hi,
                            preferred_element_type=f32)
        seg = cum[:, :, :, None] - cum[:, :, None, :]   # [B,n,l,s,G,r]
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None, None]
        L = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
        y = jnp.einsum("bngls,bnlsgr,bnsgrp->bnlgrp", scores, L, x,
                       precision=hi)
        # what a chunk adds to the state by its end, and its whole decay
        to_end = jnp.exp(cum[:, :, -1:] - cum)          # [B,n,Q,G,r]
        add = jnp.einsum("bnsgk,bnsgr,bnsgrp->bngrpk", Bc.astype(f32),
                         to_end, x, precision=hi)
        total = jnp.exp(cum[:, :, -1])                  # [B,n,G,r]

        def carry(S, inp):
            add_c, total_c = inp
            return S * total_c[..., None, None] + add_c, S

        S_T, starts = jax.lax.scan(
            carry, S0.reshape(B_, G, r, P, N),
            (jnp.moveaxis(add, 1, 0), jnp.moveaxis(total, 1, 0)))
        starts = jnp.moveaxis(starts, 0, 1)             # [B,n,G,r,P,N]
        # the state a chunk started from, decayed to each of its tokens
        y = y + jnp.einsum("bnlgk,bngrpk,bnlgr->bnlgrp", Cc.astype(f32),
                           starts, jnp.exp(cum), precision=hi)
        y = y.reshape(B_, n * Q, H, P)[:, :T]
        return y, S_T.reshape(B_, H, P, N)

    # -- forward --------------------------------------------------------------
    def forward_with_state(self, params, x, state0, *, train=False, rng=None,
                           mask=None):
        """x [B, T, n_in] -> (out [B, T, n_out], state). ``state0`` None is
        a sequence from zeros (training, `output`); a state dict steps on
        from it, T = 1 or a chunk, under its ``wmask`` where the engine
        injected one (else under ``mask``)."""
        f32 = jnp.float32
        conf = self.conf
        H, P, N, G, inner, C, K = self._dims()
        x = self._dropout(x, train, rng)
        B_, T, _ = x.shape
        st = state0 if state0 is not None else self.init_state(B_, x.dtype)
        m = st.get("wmask", mask)
        m = None if m is None else (m.reshape(B_, T) > 0)
        with jax.named_scope("mamba2"):
            zxd = jnp.einsum("btf,fo->bto", x, params["W_in"])
            z, u, dt = (zxd[..., :inner], zxd[..., inner:inner + C],
                        zxd[..., inner + C:])
            if m is not None:
                # a token that does not exist brings nothing into the
                # window (and nothing that is not finite into the products)
                u = jnp.where(m[..., None], u, 0)
            ext = jnp.concatenate([st["conv"].astype(u.dtype), u], axis=1)
            w = params["conv_w"].astype(f32)
            acc = params["conv_b"].astype(f32)
            for j in range(K):
                acc = acc + w[j] * ext[:, j:j + T].astype(f32)
            xbc = jax.nn.silu(acc).astype(x.dtype)
            # the window's last K - 1 rows of REAL input: no shift for a
            # token that does not exist
            if m is None:
                conv = ext[:, T:]
            else:
                cnt = jnp.sum(m, axis=1).astype(jnp.int32)
                conv = jax.vmap(lambda e, c: jax.lax.dynamic_slice_in_dim(
                    e, c, K - 1, axis=0))(ext, cnt)
            a = xbc[..., :inner].reshape(B_, T, H, P)
            Bm = xbc[..., inner:inner + G * N].reshape(B_, T, G, N)
            Cm = xbc[..., inner + G * N:].reshape(B_, T, G, N)
            step = jax.nn.softplus(dt.astype(f32)
                                   + params["dt_bias"].astype(f32))
            if m is not None:
                step = jnp.where(m[..., None], step, 0.0)
            A = -jnp.exp(params["A_log"].astype(f32))
            S0 = st["ssm"]
            if T == 1:
                r = H // G
                y, S = self._recur(S0, a[:, 0], jnp.repeat(Bm[:, 0], r, 1),
                                   jnp.repeat(Cm[:, 0], r, 1), step[:, 0], A)
                y = y[:, None]
            else:
                y, S = self._chunked(S0, a, Bm, Cm, step, A)
            if m is not None:
                # a lane with no real token: exactly what it held (a
                # product with one would turn a -0.0 into 0.0)
                S = jnp.where(jnp.any(m, axis=1)[:, None, None, None], S, S0)
            y = y + params["D"].astype(f32)[:, None] * a.astype(f32)
            g = y.reshape(B_, T, inner) * jax.nn.silu(z.astype(f32))
            gg = g.reshape(B_, T, G, inner // G)
            gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, -1, keepdims=True)
                                    + float(conf.eps))
            g = (gg.reshape(B_, T, inner)
                 * params["norm_g"].astype(f32)).astype(x.dtype)
            out = jnp.einsum("btf,fo->bto", g, params["W_out"])
        return self.activation_fn()(out), \
            {"ssm": S, "conv": conv.astype(st["conv"].dtype)}

    def step(self, params, x_t, state):
        """One timestep for stateful inference: x_t [B, n_in]."""
        y, st = self.forward_with_state(params, x_t[:, None], state)
        return y[:, 0], st
