"""One device's share of a routed mixture of experts.

`RoutedExpertsLayer` (conf) is what expert parallelism asks of a layer
whatever the number of devices: it routes over ALL ``n_experts``, is told
which experts it holds (``held = (first, count)``), and returns the part of
the result its own experts give,

    sigma = sigmoid(x Wr)                     all n_experts outputs, float32
    T     = the top_k largest of sigma (+ b_sel where ``selection_bias``:
            the bias chooses and does not weigh)
    g_e   = scale * sigma_e / sum_T sigma     (norm_topk; over ALL chosen)
    y     = sum_{e in T, e held} g_e E_e(x),  E_e(x) = (act(x Wg_e) * x Wu_e) Wd_e
            (``gated``, act = silu by default), or E_e(x) = act(x Wu_e) Wd_e
            (``gated=False``: the plain two-matrix expert, e.g. act = relu2)

What the absent experts would add is left out; summing the shares of every
device gives the whole layer (tests/test_routed_experts.py). There is no
capacity factor and no token is dropped. On one device the layer runs
without its exchange; nothing here stands in for the absent devices
(`parallel/moe.py` is another thing: top-1, capacity-dropping, `shard_map`).

The grouped path (`_grouped`, inference) sorts the ``tokens * top_k``
token-expert pairs by held expert (pairs not ours last), gathers their rows
once, and multiplies each matrix in one grouped-matmul kernel call over the
sorted rows (`ops/grouped_matmul.py`): the kernel walks the (expert, row
tile) visits of the experts that hold a pair, streaming each visited
expert's weights while the previous block is multiplied, so an expert no
token chose is never read and the cost follows the routing, at static
shapes. The arithmetic is `_expert`'s: operands promoted as its `jnp.dot`
promotes them, float32 accumulation, ``h`` rounded to the activations' dtype before the
down matrix; the down matrix's call adds each row, times its float32 gate,
into its token's row, so no scatter and no loop over ``[tokens, hidden]``
is left to XLA. A stack whose width is not a multiple of 128 (Nemotron's
up matrix, 1,856) is laid out by the TPU with its depth minor, and the
kernel reads it transposed, as it lies: a bitcast, not a copy. Tiles follow
the shapes (`row_tile`, `_depth_block`); nothing chooses the path but
``train``. The dense path (`_dense`, training) runs every held expert over
every token under a mask, and is what the grouped path is tested against.

At inference the layer hands back, as its ``routing_counts`` variable, the
pairs that fell on each held expert (int32 [count]); the serving engine
reads them with the step's probabilities (`inference/engine.py`) and counts,
besides, the kernel's visits from them by the function its grid uses
(`tile_visits`: ``moe_weight_passes_total``, one a hit expert when its pairs
fit one row tile). A feature ``mask`` ([B, T]; the engine's live lanes)
keeps padded lanes out of the routing altogether."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .base import LayerImpl, register_impl
from .. import weights as winit
from ...ops import activations
from ...ops.grouped_matmul import (grouped_matmul, grouped_matmul_sum,
                                   row_tile)


@register_impl("RoutedExpertsLayer")
class RoutedExpertsLayerImpl(LayerImpl):
    WEIGHT_KEYS = ("Wr", "Wg", "Wu", "Wd")

    def _held(self):
        conf = self.conf
        first, count = conf.held if conf.held is not None \
            else (0, conf.n_experts)
        if not (0 <= first and count >= 1
                and first + count <= conf.n_experts):
            raise ValueError(f"held={conf.held} is not a range of the "
                             f"{conf.n_experts} experts")
        if not 1 <= conf.top_k <= conf.n_experts:
            raise ValueError(f"top_k={conf.top_k} of {conf.n_experts}")
        return int(first), int(count)

    def init_params(self, key, dtype=jnp.float32):
        conf = self.conf
        _, G = self._held()
        d, f = conf.n_in, int(conf.width)
        dist = conf.dist.spec() if getattr(conf, "dist", None) is not None \
            else None
        init = conf.weight_init or "xavier"
        kr, kg, ku, kd = jax.random.split(key, 4)

        def stack(k, i, o):
            return jnp.stack([winit.init_weights(kk, (i, o), init, dist, dtype)
                              for kk in jax.random.split(k, G)])

        params = {"Wr": winit.init_weights(kr, (d, conf.n_experts), init,
                                           dist, dtype),
                  "Wu": stack(ku, d, f), "Wd": stack(kd, f, d)}
        if conf.gated:
            params["Wg"] = stack(kg, d, f)
        if conf.selection_bias:
            params["b_sel"] = jnp.zeros((conf.n_experts,), dtype)
        return params

    # -- routing: one function, shared by both paths ----------------------------
    def route(self, params, x):
        """x [N, d] -> (experts [N, top_k] int32 over all n_experts, gates
        [N, top_k] float32): no groups, the ``top_k`` largest of all the
        scores, or of the scores plus the selection bias where the layer
        has one (the gates are of the scores alone)."""
        conf = self.conf
        logits = jnp.einsum("nd,de->ne", x, params["Wr"],
                            preferred_element_type=jnp.float32)
        if conf.scoring != "sigmoid":
            raise ValueError(f"scoring {conf.scoring!r} is not built: "
                             "'sigmoid' is")
        sigma = jax.nn.sigmoid(logits)
        if conf.selection_bias:
            _, idx = jax.lax.top_k(
                sigma + params["b_sel"].astype(jnp.float32),
                int(conf.top_k))
            top = jnp.take_along_axis(sigma, idx, axis=-1)
        else:
            top, idx = jax.lax.top_k(sigma, int(conf.top_k))
        if conf.norm_topk:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        return idx.astype(jnp.int32), top * float(conf.scale)

    def _expert(self, params, e, xs):
        """Expert ``e`` (held index, may be traced) on rows xs [m, d]."""
        f32 = jnp.float32
        act = activations.get(self.conf.expert_activation)
        if self.conf.gated:
            g = jnp.dot(xs, params["Wg"][e], preferred_element_type=f32)
            u = jnp.dot(xs, params["Wu"][e], preferred_element_type=f32)
            h = act(g) * u
        else:
            h = act(jnp.dot(xs, params["Wu"][e], preferred_element_type=f32))
        return jnp.dot(h.astype(xs.dtype), params["Wd"][e],
                       preferred_element_type=f32)

    def _dense(self, params, x, local, gates):
        """Every held expert over every token, weighted by its gate where
        the token chose it (0 elsewhere). local [N, k]: held index, or
        ``count`` for a pair that is not ours."""
        _, G = self._held()
        w = jnp.sum(jnp.where(local[..., None] == jnp.arange(G), gates[..., None],
                              0.0), axis=1)                           # [N, G]

        def one(y, e):
            return y + w[:, e, None] * self._expert(params, e, x), None

        y, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                            jnp.arange(G))
        return y

    def _grouped(self, params, x, local, gates, counts):
        """The pairs sorted by expert (those not ours last), each matrix one
        grouped-matmul kernel call over the sorted rows; the down matrix's
        call adds each row, under its gate, into its token's row."""
        N, k = local.shape
        act = activations.get(self.conf.expert_activation)
        order = jnp.argsort(local.reshape(-1), stable=True)   # ours first
        tok = order // k
        xs = x[tok]
        kw = dict(tm=row_tile(N * k), interpret=jax.default_backend() != "tpu")
        mm = partial(grouped_matmul, sizes=counts, **kw)
        if self.conf.gated:
            h = act(mm(xs, params["Wg"])) * mm(xs, params["Wu"])
        else:
            h = act(mm(xs, params["Wu"]))
        return grouped_matmul_sum(h.astype(xs.dtype), params["Wd"], counts,
                                  tok, gates.reshape(-1)[order], n=N, **kw)

    def forward(self, params, x, *, train=False, rng=None, variables=None,
                mask=None):
        x = self._dropout(x, train, rng)
        first, G = self._held()
        shape = x.shape
        xf = x.reshape(-1, shape[-1])
        with jax.named_scope("routed_experts"):
            idx, gates = self.route(params, xf)
            local = idx - first
            ours = (local >= 0) & (local < G)
            if mask is not None:
                ours &= (mask.reshape(-1) > 0)[:, None]
            local = jnp.where(ours, local, G)
            counts = jnp.sum(local.reshape(-1)[:, None] == jnp.arange(G),
                             axis=0, dtype=jnp.int32)
            if train:
                y = self._dense(params, xf, local, gates)
            else:
                y = self._grouped(params, xf, local, gates, counts)
        y = self.activation_fn()(y.astype(x.dtype).reshape(shape))
        if train:
            return y, variables or {}
        return y, {**(variables or {}), "routing_counts": counts}
