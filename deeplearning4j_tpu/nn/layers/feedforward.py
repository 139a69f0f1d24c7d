"""Dense / output / activation / dropout / embedding layer impls.

Parity: reference nn/layers/DenseLayer, BaseOutputLayer/OutputLayer,
ActivationLayer, DropoutLayer, feedforward/embedding/EmbeddingLayer
(deeplearning4j-core/.../nn/layers/; preOutput = x·W + b per
BaseLayer.preOutput).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .base import LayerImpl, register_impl
from .. import weights as winit

Array = jax.Array


class _LinearLayer(LayerImpl):
    def init_params(self, key, dtype=jnp.float32):
        conf = self.conf
        kw, _ = jax.random.split(key)
        dist = conf.dist.spec() if getattr(conf, "dist", None) is not None else None
        W = winit.init_weights(kw, (conf.n_in, conf.n_out), conf.weight_init or "xavier",
                               dist, dtype)
        b = jnp.full((conf.n_out,), float(conf.bias_init or 0.0), dtype)
        return {"W": W, "b": b}

    def _pre_output(self, params, x):
        return x @ params["W"] + params["b"]

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        y, _, v = self.forward_with_preout(params, x, train=train, rng=rng,
                                           variables=variables, mask=mask)
        return y, v

    def forward_with_preout(self, params, x, *, train=False, rng=None,
                            variables=None, mask=None):
        """forward() that additionally returns the PRE-activation output, so
        the loss path can use the stable from-logits losses
        (ops/losses.fused_from_logits) — reproducing the reference's analytic
        output-layer delta (BaseOutputLayer.java getGradientsAndDelta).
        forward() delegates here: one definition of the layer math."""
        x = self._dropout(x, train, rng)
        z = self._pre_output(params, x)
        return self.activation_fn()(z), z, variables or {}


@register_impl("DenseLayer")
class DenseLayerImpl(_LinearLayer):
    pass


@register_impl("OutputLayer")
class OutputLayerImpl(_LinearLayer):
    """Output layer; the network computes the loss from conf.loss
    (reference BaseOutputLayer computes score via LossCalculation)."""


@register_impl("RnnOutputLayer")
class RnnOutputLayerImpl(_LinearLayer):
    """Per-timestep output: [B, T, F] -> [B, T, n_out]
    (reference nn/layers/recurrent/RnnOutputLayer.java reshapes 3d<->2d)."""

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        y, _, v = self.forward_with_preout(params, x, train=train, rng=rng,
                                           variables=variables, mask=mask)
        return y, v

    def forward_with_preout(self, params, x, *, train=False, rng=None,
                            variables=None, mask=None):
        x = self._dropout(x, train, rng)
        dt = getattr(self.conf, "logits_dtype", None)
        z = jnp.einsum("btf,fo->bto", x, params["W"],
                       preferred_element_type=dt and jnp.dtype(dt))
        z = z + params["b"].astype(z.dtype)
        y = self.activation_fn()(z)
        if mask is not None:
            y = y * mask[..., None].astype(y.dtype)
        return y, z, variables or {}


@register_impl("LossLayer")
class LossLayerImpl(LayerImpl):
    def has_params(self):
        return False

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        y, _, v = self.forward_with_preout(params, x, train=train, rng=rng,
                                           variables=variables, mask=mask)
        return y, v

    def forward_with_preout(self, params, x, *, train=False, rng=None,
                            variables=None, mask=None):
        """LossLayer's pre-activation IS its input — exposing it keeps the
        stable from-logits loss path (the saturated-softmax wedge fix)
        working for nets that end in LossLayer(softmax, mcxent)."""
        return self.activation_fn()(x), x, variables or {}


@register_impl("ActivationLayer")
class ActivationLayerImpl(LayerImpl):
    def has_params(self):
        return False

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        x = self._dropout(x, train, rng)
        return self.activation_fn()(x), variables or {}


@register_impl("DropoutLayer")
class DropoutLayerImpl(LayerImpl):
    def has_params(self):
        return False

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        return self._dropout(x, train, rng), variables or {}


@register_impl("GlobalPoolingLayer")
class GlobalPoolingLayerImpl(LayerImpl):
    """Pool over time ([B,T,F] -> [B,F]) or space ([B,H,W,C] -> [B,C])."""

    def has_params(self):
        return False

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        pool = self.conf.pooling_type.lower()
        axes = (1,) if x.ndim == 3 else (1, 2)
        if pool == "max":
            if mask is not None and x.ndim == 3:
                neg = jnp.finfo(x.dtype).min
                x = jnp.where(mask[..., None] > 0, x, neg)
            return jnp.max(x, axis=axes), variables or {}
        if pool in ("avg", "mean"):
            if mask is not None and x.ndim == 3:
                m = mask[..., None].astype(x.dtype)
                s = jnp.sum(x * m, axis=axes)
                return s / jnp.maximum(jnp.sum(m, axis=axes), 1.0), variables or {}
            return jnp.mean(x, axis=axes), variables or {}
        if pool == "sum":
            if mask is not None and x.ndim == 3:
                x = x * mask[..., None].astype(x.dtype)
            return jnp.sum(x, axis=axes), variables or {}
        if pool == "pnorm":
            p = float(getattr(self.conf, "pnorm", 2))
            return jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axes), 1.0 / p), variables or {}
        raise ValueError(f"Unknown pooling type {pool}")


@register_impl("EmbeddingLayer")
class EmbeddingLayerImpl(LayerImpl):
    """Row lookup (reference nn/layers/feedforward/embedding/EmbeddingLayer.java).
    Accepts integer indices [B] / [B,1] or one-hot [B, n_in]; the lookup is a
    gather, which XLA lowers to a dynamic-slice — no one-hot matmul needed."""

    def init_params(self, key, dtype=jnp.float32):
        conf = self.conf
        dist = conf.dist.spec() if getattr(conf, "dist", None) is not None else None
        W = winit.init_weights(key, (conf.n_in, conf.n_out), conf.weight_init or "xavier",
                               dist, dtype)
        params = {"W": W}
        if getattr(conf, "has_bias", True):
            params["b"] = jnp.full((conf.n_out,), float(conf.bias_init or 0.0), dtype)
        return params

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim == 2 and x.shape[-1] == self.conf.n_in:
            out = x @ params["W"]  # one-hot path
        else:
            idx = x.astype(jnp.int32).reshape(x.shape[0], -1)[:, 0] if x.ndim > 1 else x.astype(jnp.int32)
            out = jnp.take(params["W"], idx, axis=0)
        if "b" in params:
            out = out + params["b"]
        return self.activation_fn()(out), variables or {}
