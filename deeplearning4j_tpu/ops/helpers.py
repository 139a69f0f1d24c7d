"""Accelerated-op helper seam.

Parity with the reference's per-layer `*Helper` plugin seam
(nn/layers/convolution/ConvolutionHelper.java:29 + the cuDNN plugin module
deeplearning4j-cuda-7.5, loaded reflectively at ConvolutionLayer.java:64-70
with silent fallback). TPU redesign: the seam lives at the *op* level — a
registry of implementations for conv2d / pool2d / batch_norm / lrn. The
default impls are XLA-lowered lax ops (already MXU-tiled and fused); Pallas
kernels register overrides via `register_helper` (see ops/pallas_kernels.py),
and callers never change. `use_helper(name, None)` restores the default —
the same silent-fallback semantics as the reference.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

_HELPERS: Dict[str, Callable] = {}


def register_helper(name: str, fn: Optional[Callable]) -> None:
    """Override the implementation of an op; None restores the default."""
    if fn is None:
        _HELPERS.pop(name, None)
    else:
        _HELPERS[name] = fn


def get_helper(name: str) -> Optional[Callable]:
    return _HELPERS.get(name)


# -- conv2d --------------------------------------------------------------------

_DIMNUMS = ("NHWC", "HWIO", "NHWC")


def _conv2d_default(x: Array, w: Array, *, stride, padding, dilation=(1, 1)) -> Array:
    # bf16 inputs: the TPU MXU accumulates partial sums in f32 internally;
    # forcing preferred_element_type=f32 here breaks the autodiff transpose
    # (mixed-dtype conv in the backward pass), so dtypes are left as-is.
    return lax.conv_general_dilated(
        x, w,
        window_strides=tuple(stride),
        padding=padding,
        rhs_dilation=tuple(dilation),
        dimension_numbers=_DIMNUMS,
    )


def conv2d(x: Array, w: Array, *, stride=(1, 1), padding="SAME", dilation=(1, 1)) -> Array:
    """NHWC x HWIO -> NHWC convolution."""
    impl = _HELPERS.get("conv2d", _conv2d_default)
    return impl(x, w, stride=stride, padding=padding, dilation=dilation)


# -- fused conv2d + bias + activation -----------------------------------------

def _conv2d_bias_act_default(x, w, b, *, stride, padding, dilation, activation):
    from . import activations
    # route through the public conv2d seam so a 'conv2d' override still
    # applies when no fused-op override is registered
    y = conv2d(x, w, stride=stride, padding=padding, dilation=dilation)
    return activations.get(activation)(y + b)


def conv2d_bias_act(x: Array, w: Array, b: Array, *, stride=(1, 1),
                    padding="SAME", dilation=(1, 1),
                    activation="identity") -> Array:
    """Fused NHWC conv + bias + activation — the cuDNN-helper hot path
    (CudnnConvolutionHelper.java:48). Default: XLA fuses the epilogue into
    the conv; Pallas override in ops/pallas_kernels.py."""
    impl = _HELPERS.get("conv2d_bias_act", _conv2d_bias_act_default)
    return impl(x, w, b, stride=stride, padding=padding, dilation=dilation,
                activation=activation)


# -- fused LSTM sequence -------------------------------------------------------

def lstm_cell(z, c_prev, peep, act_fn):
    """One LSTM cell step from pre-activations z = x·W + b + h·RW.
    Gate packing [i, f, o, g]; peep = (pI, pF, pO) peephole weights (zeros/
    scalars for a plain LSTM). THE single definition of the cell math —
    shared by the scan default below and _LSTMCore._gates (masked path /
    rnnTimeStep); the Pallas kernel mirrors it on padded shapes."""
    H = c_prev.shape[-1]
    i = jax.nn.sigmoid(z[..., :H] + c_prev * peep[0])
    f = jax.nn.sigmoid(z[..., H:2 * H] + c_prev * peep[1])
    g = act_fn(z[..., 3 * H:])
    c = f * c_prev + i * g
    o = jax.nn.sigmoid(z[..., 2 * H:3 * H] + c * peep[2])
    h = o * act_fn(c)
    return h, c


def _lstm_sequence_default(xproj_t, rw, peep, h0, c0, *, activation, reverse):
    from . import activations
    act_fn = activations.get(activation)

    def body(state, xp):
        h_prev, c_prev = state
        h, c = lstm_cell(xp + h_prev @ rw, c_prev, peep, act_fn)
        return (h, c), h

    (ht, ct), ys = lax.scan(body, (h0, c0), xproj_t, reverse=reverse)
    return ys, ht, ct


def lstm_sequence(xproj_t: Array, rw: Array, peep: Array, h0: Array, c0: Array,
                  *, activation="tanh", reverse=False):
    """Fused LSTM over a pre-projected sequence (the LSTMHelpers.java:132
    hot loop). xproj_t: [T, B, 4H] = x·W + b for all timesteps; gate packing
    [i, f, o, g]; peep: [3, H] peephole weights (zeros => plain LSTM).
    Returns (ys [T, B, H], h_T, c_T)."""
    impl = _HELPERS.get("lstm_sequence", _lstm_sequence_default)
    return impl(xproj_t, rw, peep, h0, c0, activation=activation,
                reverse=reverse)


# -- pool2d --------------------------------------------------------------------

def _pool2d_default(x: Array, *, kind, kernel, stride, padding, pnorm=2) -> Array:
    # NOTE (r4 device-trace study, tools/trace_alexnet.py, last in commit
    # dd74740): reduce_window is the RIGHT lowering here. Alternatives
    # tried and measured worse on the full AlexNet step: rank-6
    # reshape+max (its gradient materializes
    # [B,H/2,2,W/2,2,C] broadcasts) and strided-slice pairwise max (layout
    # copies around every strided read). select-and-scatter for the 2x2/s2
    # backward runs at ~memory roofline for the large shapes; the remaining
    # win is cross-op fusion of the BN+act+pool epilogue, not the pool alone.
    kh, kw = kernel
    window = (1, kh, kw, 1)
    strides = (1, stride[0], stride[1], 1)
    if padding == "SAME":
        pad = "SAME"
    else:
        (ph0, ph1), (pw0, pw1) = padding
        pad = ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0))
    kind = kind.lower()
    if kind == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, pad)
    if kind in ("avg", "mean"):
        s = lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
        ones = jnp.ones_like(x)
        count = lax.reduce_window(ones, 0.0, lax.add, window, strides, pad)
        return s / count
    if kind == "sum":
        return lax.reduce_window(x, 0.0, lax.add, window, strides, pad)
    if kind == "pnorm":
        p = float(pnorm)
        s = lax.reduce_window(jnp.power(jnp.abs(x), p), 0.0, lax.add, window, strides, pad)
        return jnp.power(s, 1.0 / p)
    raise ValueError(f"Unknown pooling kind '{kind}'")


def pool2d(x: Array, *, kind="max", kernel=(2, 2), stride=(2, 2), padding="SAME", pnorm=2) -> Array:
    impl = _HELPERS.get("pool2d", _pool2d_default)
    return impl(x, kind=kind, kernel=kernel, stride=stride, padding=padding, pnorm=pnorm)


# -- batch norm ----------------------------------------------------------------

def _batch_norm_default(x, gamma, beta, mean, var, *, eps) -> Array:
    inv = lax.rsqrt(var + eps)
    return (x - mean) * inv * gamma + beta


def batch_norm(x, gamma, beta, mean, var, *, eps=1e-5) -> Array:
    impl = _HELPERS.get("batch_norm", _batch_norm_default)
    return impl(x, gamma, beta, mean, var, eps=eps)


# -- fused train-mode BatchNorm + activation + 2x2/s2 max-pool ----------------

def bn_batch_stats(x) -> Tuple[Array, Array]:
    """Per-channel batch (mean, var) over all-but-last axes — THE single
    definition of the BN stats math. For sub-f32 inputs: one-pass
    E[x^2]-E[x]^2 with f32 accumulation (one fused multi-output reduction,
    fusable into the producer conv's epilogue; f32 has ~16 guard bits over
    bf16/f16 significands so the cancellation is safe). For f32/f64 the
    cancellation would destroy precision, so two-pass jnp.var is kept.
    Callers: BatchNormalizationImpl.forward, _bn_act_pool_default, and the
    Pallas bn_act_pool override."""
    axes = tuple(range(x.ndim - 1))
    if x.dtype in (jnp.bfloat16, jnp.float16):
        xf = x.astype(jnp.float32)
        mean32 = jnp.mean(xf, axis=axes)
        var32 = jnp.maximum(
            jnp.mean(xf * xf, axis=axes) - mean32 * mean32, 0.0)
    else:
        mean32 = jnp.mean(x, axis=axes)
        var32 = jnp.var(x, axis=axes)
    return mean32, var32


def _bn_act_pool_default(x, gamma, beta, *, eps, activation):
    from . import activations
    mean32, var32 = bn_batch_stats(x)
    y = batch_norm(x, gamma, beta, mean32.astype(x.dtype),
                   var32.astype(x.dtype), eps=eps)
    y = activations.get(activation)(y)
    y = pool2d(y, kind="max", kernel=(2, 2), stride=(2, 2), padding="SAME")
    return y, mean32, var32


def bn_act_pool(x, gamma, beta, *, eps=1e-5, activation="relu"):
    """Train-mode batch norm (batch stats) + activation + 2x2/s2 max-pool as
    ONE composite op, returning (pooled, batch_mean32, batch_var32).

    Why a composite exists at the seam: the device trace of the AlexNet
    train step (tools/trace_alexnet.py, last in commit dd74740) shows
    XLA's BACKWARD for this layer-pair costs ~4 HBM passes over the
    largest activations
    (select-and-scatter pool grad + act/BN-dx passes + two stat-grad
    reductions); a fused custom-VJP kernel does it in two
    (ops/pallas_kernels.py). Reference analog: the cuDNN BN helper fuses
    normalize+activation the same way (CudnnBatchNormalizationHelper).
    Requires x [B,H,W,C] with even H and W."""
    impl = _HELPERS.get("bn_act_pool", _bn_act_pool_default)
    return impl(x, gamma, beta, eps=eps, activation=activation)


# -- local response normalization ---------------------------------------------

def _lrn_default(x: Array, *, k, n, alpha, beta) -> Array:
    # cross-channel sliding-window sum of squares; NHWC channels-last
    half = int(n) // 2
    sq = x * x
    window = (1, 1, 1, 2 * half + 1)
    s = lax.reduce_window(sq, 0.0, lax.add, window, (1, 1, 1, 1),
                          ((0, 0), (0, 0), (0, 0), (half, half)))
    return x / jnp.power(k + alpha * s, beta)


def lrn(x: Array, *, k=2.0, n=5.0, alpha=1e-4, beta=0.75) -> Array:
    impl = _HELPERS.get("lrn", _lrn_default)
    return impl(x, k=k, n=n, alpha=alpha, beta=beta)


# -- multi-head attention -----------------------------------------------------

def _attention_default(q: Array, k: Array, v: Array, *, causal=False,
                       scale=None) -> Array:
    """Dense attention via XLA einsums (parallel/ring.full_attention)."""
    from ..parallel.ring import full_attention
    return full_attention(q, k, v, causal=causal, scale=scale)


def attention(q: Array, k: Array, v: Array, *, causal: bool = False,
              scale=None) -> Array:
    """Multi-head attention helper seam. q,k,v: [B, L, H, D] -> [B, L, H, D].
    The accelerated plugin may register a flash-attention kernel here
    (ops/pallas_kernels.py), same silent-fallback semantics as the conv/
    LSTM helpers."""
    impl = _HELPERS.get("attention", _attention_default)
    return impl(q, k, v, causal=causal, scale=scale)


# -- fused paged-attention decode ----------------------------------------------

def paged_decode_attention(q: Array, k_pages: Array, v_pages: Array,
                           table: Array, pos: Array, *,
                           k_scales=None, v_scales=None,
                           mode: str = "auto", mesh=None):
    """Fused paged-KV decode attention seam (ISSUE 15).

    ``q``: [B, 1, H, Dh] single-token queries (RoPE already applied);
    ``k_pages``/``v_pages``: [pages, block, Hkv, Dh] pool-wide page
    arrays AFTER this step's write (page 0 = scratch); ``table``:
    [B, nb] int32 block tables (scratch-padded); ``pos``: [B] int32
    decode depths — row b attends causally over absolute positions
    [0, pos[b]]. ``k_scales``/``v_scales``: [pages, block, Hkv] f32
    dequant scales when the pages are int8 (ops/kvquant.py contract).
    ``mode``: "auto" (per-shape autotune vs the XLA gather path) /
    "on" (force the kernel) / "off". ``mesh``: the engine's tp mesh —
    the registered kernel grids over the LOCAL Hkv shard via shard_map
    so head-sharded serving never reshards (inference/sharding.py).

    Returns [B, 1, H, Dh], or **None** — the contract's silent-fallback
    arm: no kernel registered, mode "off", an unsupported shape, or a
    per-shape autotune decision for XLA. The caller (the layer's
    ``_paged_step``) then runs its own gather/einsum body, which stays
    the token-identity reference. The decision is made at TRACE time
    (shapes and mode are static), so a None costs nothing compiled.
    """
    impl = _HELPERS.get("paged_decode_attention")
    if impl is None or mode == "off":
        return None
    return impl(q, k_pages, v_pages, table, pos, k_scales=k_scales,
                v_scales=v_scales, mode=mode, mesh=mesh)
