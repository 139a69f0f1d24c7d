"""BatchNormalization + LocalResponseNormalization impls.

Parity: reference nn/layers/normalization/BatchNormalization.java (train vs
global stats preOutput:200, gamma/beta :103,227-231) and
LocalResponseNormalization.java; accelerated via the helper seam
(reference CudnnBatchNormalizationHelper / CudnnLocalResponseNormalizationHelper).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import LayerImpl, register_impl
from ...ops import helpers as ophelpers

Array = jax.Array


@register_impl("BatchNormalization")
class BatchNormalizationImpl(LayerImpl):
    WEIGHT_KEYS = ()  # gamma/beta not regularized (matches reference)

    def init_params(self, key, dtype=jnp.float32):
        conf = self.conf
        n = conf.n_out
        if conf.lock_gamma_beta:
            return {}
        return {
            "gamma": jnp.full((n,), float(conf.gamma), dtype),
            "beta": jnp.full((n,), float(conf.beta), dtype),
        }

    def init_variables(self, dtype=jnp.float32):
        n = self.conf.n_out
        return {"mean": jnp.zeros((n,), dtype), "var": jnp.ones((n,), dtype)}

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        conf = self.conf
        variables = variables or self.init_variables(x.dtype)
        axes = tuple(range(x.ndim - 1))  # all but channel/feature
        if conf.lock_gamma_beta:
            gamma = jnp.asarray(conf.gamma, x.dtype)
            beta = jnp.asarray(conf.beta, x.dtype)
        else:
            gamma, beta = params["gamma"], params["beta"]

        if train and not conf.use_global_stats:
            mean32, var32 = ophelpers.bn_batch_stats(x)
            mean = mean32.astype(x.dtype)
            var = var32.astype(x.dtype)
            vdt = variables["mean"].dtype
            d = jnp.asarray(conf.decay, vdt)
            new_vars = {
                "mean": d * variables["mean"] + (1.0 - d) * mean32.astype(vdt),
                "var": d * variables["var"] + (1.0 - d) * var32.astype(vdt),
            }
        else:
            mean, var = variables["mean"], variables["var"]
            new_vars = variables

        y = ophelpers.batch_norm(x, gamma, beta, mean, var, eps=conf.eps)
        return self.activation_fn()(y) if conf.activation not in (None, "identity", "linear") else y, new_vars


    def forward_fused_pool(self, params, x, *, variables=None):
        """Train-mode BN + activation + the FOLLOWING 2x2/s2 max-pool layer
        as one composite op (ops/helpers.bn_act_pool). Engaged by the
        facades when the layer pair matches (nn/multilayer._forward_impl);
        the Pallas plugin overrides the composite's backward with a 2-pass
        fused kernel (ops/pallas_kernels.py). Semantics are identical to
        running the two layers separately."""
        conf = self.conf
        variables = variables or self.init_variables(x.dtype)
        if conf.lock_gamma_beta:
            gamma = jnp.full((conf.n_out,), float(conf.gamma), x.dtype)
            beta = jnp.full((conf.n_out,), float(conf.beta), x.dtype)
        else:
            gamma, beta = params["gamma"], params["beta"]
        y, mean32, var32 = ophelpers.bn_act_pool(
            x, gamma, beta, eps=conf.eps,
            activation=conf.activation or "identity")
        vdt = variables["mean"].dtype
        d = jnp.asarray(conf.decay, vdt)
        new_vars = {
            "mean": d * variables["mean"] + (1.0 - d) * mean32.astype(vdt),
            "var": d * variables["var"] + (1.0 - d) * var32.astype(vdt),
        }
        return y, new_vars

    @staticmethod
    def can_fuse_pool(bn_conf, pool_conf, x) -> bool:
        """True when [this BN layer -> pool_conf] matches the fused
        composite: train batch stats, 2x2/s2 max pool with no effective
        padding, even spatial dims."""
        return (x.ndim == 4
                and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0
                and not bn_conf.use_global_stats
                and pool_conf.pooling_type == "max"
                and tuple(pool_conf.kernel_size) == (2, 2)
                and tuple(pool_conf.stride) == (2, 2)
                and (pool_conf.convolution_mode == "same"
                     or tuple(pool_conf.padding) == (0, 0)))


@register_impl("LocalResponseNormalization")
class LocalResponseNormalizationImpl(LayerImpl):
    def has_params(self):
        return False

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        c = self.conf
        return ophelpers.lrn(x, k=c.k, n=c.n, alpha=c.alpha, beta=c.beta), variables or {}


@register_impl("LayerNormalization")
class LayerNormalizationImpl(LayerImpl):
    """Per-example normalization over the trailing feature axis with learned
    gain/bias (transformer building block — see conf LayerNormalization)."""

    def init_params(self, key, dtype=jnp.float32):
        conf = self.conf
        n = conf.n_out or conf.n_in
        gain = (jnp.zeros if getattr(conf, "unit_offset", False)
                else jnp.ones)((n,), dtype)
        if getattr(conf, "rms", False):
            return {"gain": gain}
        return {"gain": gain, "beta": jnp.zeros((n,), dtype)}

    def forward(self, params, x, *, train=False, rng=None, variables=None,
                mask=None):
        conf = self.conf
        x = self._dropout(x, train, rng)
        eps = jnp.asarray(conf.eps, x.dtype)
        gain = params["gain"]
        if getattr(conf, "unit_offset", False):
            gain = 1 + gain
        if getattr(conf, "rms", False):
            y = x * jax.lax.rsqrt(
                jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain
        else:
            mean = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.var(x, axis=-1, keepdims=True)
            y = (x - mean) * jax.lax.rsqrt(var + eps) * gain + params["beta"]
        if conf.activation not in (None, "identity", "linear"):
            y = self.activation_fn()(y)
        return y, variables or {}
