"""Pallas TPU kernels behind the accelerated-helper seam (ops/helpers.py).

The TPU analog of the reference's cuDNN helper plugin
(deeplearning4j-cuda-7.5/.../nn/layers/convolution/CudnnConvolutionHelper.java:48
plus the subsampling/BN/LRN helpers, loaded reflectively with silent fallback
at ConvolutionLayer.java:64-70). Kernel families behind the seam:

  - ``conv2d_bias_act``: per-(batch-tile, output-row, kernel-row) grid; each
    step runs ONE MXU matmul [bt*ow, kw*c]x[kw*c, oc] with the bias-add +
    activation fused into the last accumulation — the cuDNN "conv+bias+act"
    fused path. Measured 0.66-0.90x of XLA's native conv on v5e (XLA's
    emitter avoids even the kw-fold row expansion), so enable() registers it
    opt-in only; it stands as the seam's working reference kernel.
  - ``attention``: per-shape autotuned choice among XLA einsum attention,
    the TPU flash-attention kernel under several block configs, and splash
    attention — the long-context winner (2.5-3x XLA at L=8192; sole
    survivor past L~16k where dense cannot compile).
  - ``bn_act_pool``: composite BN+activation+2x2-maxpool with a fused
    2-pass Pallas BACKWARD in two layout-matched variants, autotuned.
  - ``paged_decode_attention``: FlashDecoding-style fused paged-KV
    decode (ISSUE 15) — one pass per (batch row, kv-head) walks the
    slot's scalar-prefetched block table and runs QK^T + online softmax
    + V accumulation page by page, int8 dequant fused in-loop; the
    [B, nb*block, Hkv, Dh] gathered cache is never materialized. Per-
    shape autotuned against the XLA gather path; under a tp mesh it
    grids over the LOCAL Hkv shard (shard_map) so the serving
    collective audit is unchanged.
  - ``lstm_sequence``: RETIRED round 4 (XLA's scan won every probed
    regime — see the tombstone note at the section below); the seam and
    the autotune machinery remain.

Training works unchanged: custom kernels are wrapped in ``jax.custom_vjp``
(either with a hand-written fused backward validated against autodiff, or
re-running the XLA default), so numerics match the unfused path.

Selection discipline: decisions are EMPIRICAL per shape (the cuDNN
find-algorithm analog) and measured with scan-timed probes — K chained
applications inside one jitted scan, so a sub-millisecond op is not
drowned by per-dispatch overhead. A candidate that RAISED (the TPU
compiler refused it) loses like one that was slow, but its reason is kept
(:func:`autotune_refusals`) so "XLA won" and "the kernel never compiled"
stay distinguishable.

``enable()`` registers the kernels via ``register_helper``; ``disable()``
restores the XLA defaults — the same silent-fallback seam semantics as the
reference. On non-TPU backends ``enable()`` uses the Pallas interpreter
(slow; for tests only); :func:`kernel_execution` says which of the two a
process is running, and the serving banner and ``/debug/engine`` repeat it.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import activations
from . import helpers
from . import kvquant

Array = jax.Array

_INTERPRET = False


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# =============================================================================
# fused conv2d + bias + activation
# =============================================================================

def _conv_geometry(h: int, w: int, kh: int, kw: int, stride, padding):
    sh, sw = stride
    if padding == "SAME":
        oh = -(-h // sh)
        ow = -(-w // sw)
        pad_h = max((oh - 1) * sh + kh - h, 0)
        pad_w = max((ow - 1) * sw + kw - w, 0)
        pads = ((pad_h // 2, pad_h - pad_h // 2),
                (pad_w // 2, pad_w - pad_w // 2))
    elif padding == "VALID":
        pads = ((0, 0), (0, 0))
        oh = (h - kh) // sh + 1
        ow = (w - kw) // sw + 1
    else:
        pads = tuple(tuple(p) for p in padding)
        oh = (h + pads[0][0] + pads[0][1] - kh) // sh + 1
        ow = (w + pads[1][0] + pads[1][1] - kw) // sw + 1
    return oh, ow, pads


def _conv_kernel(xs_ref, w_ref, b_ref, o_ref, *, kh, act_fn):
    """One grid step handles (batch-tile bt, output row oh, kernel row ki):
    the pre-shifted patch row for input row oh*sh+ki sits in VMEM and feeds
    ONE MXU matmul [bt*ow, kw*c]x[kw*c, oc] against kernel row ki's weights,
    accumulated into the VMEM-resident output block; bias+activation fuse
    into the last accumulation step. The full kh*kw*c im2col matrix is never
    materialized in HBM — only a kw-fold row expansion is."""
    ki = pl.program_id(2)
    a = xs_ref[:, 0]  # [bt, ow, kw*c]
    bt, ow, kwc = a.shape
    partial_sum = jnp.dot(a.reshape(bt * ow, kwc), w_ref[0],
                          preferred_element_type=jnp.float32)
    partial_sum = partial_sum.reshape(bt, 1, ow, -1)

    @pl.when(ki == 0)
    def _():
        o_ref[:] = partial_sum

    @pl.when(ki > 0)
    def _():
        o_ref[:] = o_ref[:] + partial_sum

    @pl.when(ki == kh - 1)
    def _():
        o_ref[:] = act_fn(o_ref[:] + b_ref[0, 0].astype(jnp.float32))


def _conv2d_bias_act_forward(x, w, b, stride, padding, dilation, activation):
    act_fn = activations.get(activation)
    kh, kw, _, oc = w.shape
    b_, h, wdt, c = x.shape
    sh, sw = stride
    oh, ow, pads = _conv_geometry(h, wdt, kh, kw, stride, padding)
    hp = (oh - 1) * sh + kh  # rows addressed by oi*sh + ki
    xp = jnp.pad(x, ((0, 0),
                     (pads[0][0], max(hp - h - pads[0][0], 0)),
                     pads[1], (0, 0)))[:, :hp]
    # kj-shifts hoisted to XLA (a kw-fold expansion, cheap vs full im2col);
    # feature order (kj, c) matches w.reshape(kh, kw*c, oc)
    xs = jnp.concatenate(
        [xp[:, :, kj:kj + sw * (ow - 1) + 1:sw, :] for kj in range(kw)],
        axis=-1)  # [B, hp, ow, kw*c]
    wk = w.reshape(kh, kw * c, oc)
    bk = b.reshape(1, 1, oc)
    # batch tile: keep patch-row + out blocks within the VMEM budget
    bt = b_
    while bt > 1 and (2 * bt * ow * kw * c + 2 * bt * ow * oc) * 4 \
            > 8 * 1024 * 1024:
        bt //= 2
    bp = _round_up(b_, bt)
    if bp != b_:
        xs = jnp.pad(xs, ((0, bp - b_), (0, 0), (0, 0), (0, 0)))
    out = pl.pallas_call(
        partial(_conv_kernel, kh=kh, act_fn=act_fn),
        out_shape=jax.ShapeDtypeStruct((bp, oh, ow, oc), jnp.float32),
        grid=(bp // bt, oh, kh),
        in_specs=[
            pl.BlockSpec((bt, 1, ow, kw * c),
                         lambda bi, oi, ki, sh=sh: (bi, oi * sh + ki, 0, 0)),
            pl.BlockSpec((1, kw * c, oc), lambda bi, oi, ki: (ki, 0, 0)),
            pl.BlockSpec((1, 1, oc), lambda bi, oi, ki: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bt, 1, ow, oc),
                               lambda bi, oi, ki: (bi, oi, 0, 0)),
        interpret=_INTERPRET,
    )(xs, wk, bk)
    return out[:b_].astype(x.dtype)


_conv_vjp_cache: Dict = {}


def _get_conv_fn(stride, padding, dilation, activation):
    key = (stride, padding, dilation, activation)
    if key in _conv_vjp_cache:
        return _conv_vjp_cache[key]

    def ref_fn(x, w, b):
        return helpers._conv2d_bias_act_default(
            x, w, b, stride=stride, padding=padding, dilation=dilation,
            activation=activation)

    @jax.custom_vjp
    def fn(x, w, b):
        return _conv2d_bias_act_forward(x, w, b, stride, padding, dilation,
                                        activation)

    def fn_fwd(x, w, b):
        return fn(x, w, b), (x, w, b)

    def fn_bwd(res, g):
        _, vjp = jax.vjp(ref_fn, *res)
        return vjp(g)

    fn.defvjp(fn_fwd, fn_bwd)
    _conv_vjp_cache[key] = fn
    return fn


def conv2d_bias_act_pallas(x, w, b, *, stride, padding, dilation, activation):
    """Measured on v5e (f32, AlexNet shapes): this kernel reaches 0.66-0.90x
    of XLA's native conv — XLA's internal conv emitter wins by avoiding even
    the kw-fold row expansion. Kept as the working reference implementation
    of the helper seam (and the template for fusions XLA can't do); enable()
    therefore registers it only when ``use_conv=True``."""
    # fall back to XLA for dilated convs and for tiny contraction dims
    # (kw*c << MXU lane width starves the systolic array, e.g. 1-channel
    # LeNet conv1 — the same algorithm-applicability choice cuDNN makes)
    if tuple(dilation) != (1, 1) or w.shape[1] * w.shape[2] < 8:
        return helpers._conv2d_bias_act_default(
            x, w, b, stride=stride, padding=padding, dilation=dilation,
            activation=activation)
    pad_key = padding if isinstance(padding, str) \
        else tuple(tuple(p) for p in padding)
    return _get_conv_fn(tuple(stride), pad_key, tuple(dilation), activation)(
        x, w, b)


# =============================================================================
# fused LSTM sequence — RETIRED (round 4)
# =============================================================================
# A full-sequence Pallas LSTM kernel (grid over timesteps, f32 VMEM-resident
# h/c state, one MXU matmul per step) lived here for rounds 2-3 behind a
# per-shape autotune. Round 4's scan-timed measurements (see _measure_scan)
# showed the XLA lax.scan default beating it at EVERY probed regime,
# including the large-state shapes the kernel was built for:
#
#   train (fwd+bwd), bf16, xla/pallas ratio — >1 would mean the kernel wins:
#     T=50  B=128 H=256  -> 0.70      T=50 B=256 H=512 -> 0.69
#     T=50  B=256 H=1024 -> 0.74      T=50 B=512 H=512 -> 0.98
#     T=100 B=256 H=512  -> 0.75
#   forward-only: 0.65-1.00 across the same grid.
#
# XLA pipelines the per-step [B,4H] matmul chain as well as the hand-written
# grid while fusing the gate math; the kernel's only structural edge
# (HBM-resident h/c avoided) does not bind at these sizes. Per the
# win-or-delete rule the kernel is deleted; the `lstm_sequence` HELPER SEAM
# stays (ops/helpers.py, reference LSTMHelpers.java:132 analog) so a future
# kernel can register against the same contract, and the empirical autotune
# machinery lives on in the attention/bn_act_pool seams below.

# =============================================================================
# fused BN+act+pool backward (bn_act_pool composite seam)
# =============================================================================

# activation + derivative pairs the fused backward can recompute in-kernel
_BNAP_ACTS = {
    "relu": (lambda z: jnp.maximum(z, 0.0),
             lambda z: (z > 0).astype(jnp.float32)),
    "identity": (lambda z: z, lambda z: jnp.ones_like(z)),
    "linear": (lambda z: z, lambda z: jnp.ones_like(z)),
    "tanh": (jnp.tanh, lambda z: 1.0 - jnp.tanh(z) ** 2),
    "sigmoid": (jax.nn.sigmoid,
                lambda z: jax.nn.sigmoid(z) * (1.0 - jax.nn.sigmoid(z))),
}


def _bnap_recompute(x_ref, g_ref, p_ref, act_fn, dact_fn, ch_last):
    """Shared recompute for both backward passes. The block is a 5D view
    (2 pool-rows, W/2, 2 pool-cols, D1, D2) where (D1, D2) is (C, bb) for
    the channels-sublane variant or (bb, C) for the channels-lane variant —
    the two physical layouts XLA actually assigns to NHWC activations
    ({0,3,2,1} batch-minor and {3,0,2,1}); feeding the matching transposed
    VIEW makes the transpose a free bitcast instead of a real copy (the
    row-major kernel measured 0.46 ms/step of pure layout copies around the
    pallas calls). From x it rebuilds x_hat, z, the activation, the 2x2
    argmax routing, and the routed gradient g_z — x and g are read from HBM
    exactly once per pass."""
    x = x_ref[...].astype(jnp.float32)        # (2, W2, 2, D1, D2)
    expand = (lambda v: v[None, :]) if ch_last else (lambda v: v[:, None])
    mean = expand(p_ref[0])
    inv = expand(p_ref[1])
    gam = expand(p_ref[2])
    bet = expand(p_ref[3])
    g = g_ref[...].astype(jnp.float32)        # (1, W2, 1, D1, D2)
    xh = (x - mean) * inv
    z = xh * gam + bet
    a = act_fn(z)
    # argmax routing must match the FORWARD's pool, which compared the
    # x.dtype-cast activations (fwd_chain: act(z).astype(x.dtype)) — for
    # bf16, f32 values that tie after rounding would otherwise route the
    # whole gradient to one element instead of splitting it (advisor r4)
    a_c = a.astype(x_ref.dtype).astype(jnp.float32)
    m = jnp.max(a_c, axis=(0, 2), keepdims=True)  # (1, W2, 1, D1, D2)
    eq = (a_c == m).astype(jnp.float32)
    cnt = jnp.sum(eq, axis=(0, 2), keepdims=True)  # ties per 2x2 window
    ga = eq * (g / cnt)  # even split among tied maxima — jnp.max's own
    # gradient convention (select-and-scatter routes to one element; the
    # difference exists only at exact ties, measure-zero for continuous
    # data, and preserves total gradient mass)
    return xh, ga * dact_fn(z)


def _bnap_sums_kernel(x_ref, g_ref, p_ref, dg_ref, db_ref, *, act_fn,
                      dact_fn, ch_last):
    first = jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0)

    @pl.when(first)
    def _():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    xh, gz = _bnap_recompute(x_ref, g_ref, p_ref, act_fn, dact_fn, ch_last)
    axes = (0, 1, 2, 3) if ch_last else (0, 1, 2, 4)
    db_ref[:] += jnp.sum(gz, axes)
    dg_ref[:] += jnp.sum(gz * xh, axes)


def _bnap_dx_kernel(x_ref, g_ref, p_ref, s_ref, dx_ref, *, act_fn, dact_fn,
                    ch_last, n):
    xh, gz = _bnap_recompute(x_ref, g_ref, p_ref, act_fn, dact_fn, ch_last)
    expand = (lambda v: v[None, :]) if ch_last else (lambda v: v[:, None])
    inv = expand(p_ref[1])
    gam = expand(p_ref[2])
    s_b = expand(s_ref[0]) / n
    s_g = expand(s_ref[1]) / n
    dx_ref[...] = (inv * gam * (gz - s_b - xh * s_g)).astype(dx_ref.dtype)


def _bnap_batch_stats(x):
    # shared dtype-guarded definition (one-pass only for sub-f32 inputs)
    return helpers.bn_batch_stats(x)


_bnap_vjp_cache: Dict = {}


def _get_bnap_fn(eps, activation, variant="hwcb"):
    """variant: which physical layout the backward kernels assume.
    'hwcb' = batch on lanes (matches XLA's batch-minor {0,3,2,1}, the
    layout picked for C < 128 activations); 'hwbc' = channels on lanes
    (matches {3,0,2,1}, picked for C >= 128). The matching transposed view
    turns the layout adaptation into a bitcast instead of a real copy."""
    key = (float(eps), activation, variant)
    if key in _bnap_vjp_cache:
        return _bnap_vjp_cache[key]
    act_fn, dact_fn = _BNAP_ACTS[activation]
    ch_last = variant == "hwbc"

    def fwd_chain(x, gamma, beta):
        mean32, var32 = _bnap_batch_stats(x)
        inv = jax.lax.rsqrt(var32 + eps)
        z = (x.astype(jnp.float32) - mean32) * inv * gamma.astype(
            jnp.float32) + beta.astype(jnp.float32)
        a = act_fn(z).astype(x.dtype)
        B, H, W, C = x.shape
        p = jnp.max(a.reshape(B, H // 2, 2, W // 2, 2, C), axis=(2, 4))
        return p, (mean32, var32)

    @jax.custom_vjp
    def fn(x, gamma, beta):
        p, (mean32, var32) = fwd_chain(x, gamma, beta)
        # the stats outputs are EMA-only by contract: bn_act_pool_pallas
        # stop-gradients them at the seam, so fn_bwd may ignore their
        # cotangents. Returning them here (instead of recomputing outside
        # the opaque custom_vjp call) keeps the production program
        # identical to what the autotune probe measured.
        return p, mean32, var32

    def fn_fwd(x, gamma, beta):
        p, (mean32, var32) = fwd_chain(x, gamma, beta)
        return (p, mean32, var32), (x, gamma, beta, mean32, var32)

    def fn_bwd(res, g):
        x, gamma, beta, mean32, var32 = res
        g = g[0]  # pooled-output cotangent; stat cotangents are zero by
        # the stop-gradient contract at the seam
        B, H, W, C = x.shape
        W2 = W // 2
        n = B * H * W
        inv32 = jax.lax.rsqrt(var32 + eps)
        p = jnp.stack([mean32, inv32, gamma.astype(jnp.float32),
                       beta.astype(jnp.float32)])          # (4, C)
        bb = 64 if ch_last else 128  # lanes need 128; sublane tiles 8x
        Bp = _round_up(B, bb)
        if Bp != B:
            x = jnp.pad(x, ((0, Bp - B), (0, 0), (0, 0), (0, 0)))
            g = jnp.pad(g, ((0, Bp - B), (0, 0), (0, 0), (0, 0)))
        if ch_last:  # [H, W2, 2, B, C]
            xv = x.transpose(1, 2, 0, 3).reshape(H, W2, 2, Bp, C)
            gv = g.transpose(1, 2, 0, 3).reshape(H // 2, W2, 1, Bp, C)
            xspec = pl.BlockSpec((2, W2, 2, bb, C),
                                 lambda hi, bi: (hi, 0, 0, bi, 0))
            gspec = pl.BlockSpec((1, W2, 1, bb, C),
                                 lambda hi, bi: (hi, 0, 0, bi, 0))
        else:        # [H, W2, 2, C, B]
            xv = x.transpose(1, 2, 3, 0).reshape(H, W2, 2, C, Bp)
            gv = g.transpose(1, 2, 3, 0).reshape(H // 2, W2, 1, C, Bp)
            xspec = pl.BlockSpec((2, W2, 2, C, bb),
                                 lambda hi, bi: (hi, 0, 0, 0, bi))
            gspec = pl.BlockSpec((1, W2, 1, C, bb),
                                 lambda hi, bi: (hi, 0, 0, 0, bi))
        grid = (H // 2, Bp // bb)
        common_in = [xspec, gspec,
                     pl.BlockSpec((4, C), lambda hi, bi: (0, 0))]
        dg, db = pl.pallas_call(
            partial(_bnap_sums_kernel, act_fn=act_fn, dact_fn=dact_fn,
                    ch_last=ch_last),
            out_shape=(jax.ShapeDtypeStruct((C,), jnp.float32),
                       jax.ShapeDtypeStruct((C,), jnp.float32)),
            grid=grid,
            in_specs=common_in,
            out_specs=(pl.BlockSpec((C,), lambda hi, bi: (0,)),
                       pl.BlockSpec((C,), lambda hi, bi: (0,))),
            interpret=_INTERPRET,
        )(xv, gv, p)
        s = jnp.stack([db, dg])                             # (2, C)
        dxv = pl.pallas_call(
            partial(_bnap_dx_kernel, act_fn=act_fn, dact_fn=dact_fn,
                    ch_last=ch_last, n=float(n)),
            out_shape=jax.ShapeDtypeStruct(xv.shape, x.dtype),
            grid=grid,
            in_specs=common_in + [pl.BlockSpec((2, C),
                                               lambda hi, bi: (0, 0))],
            out_specs=xspec,
            interpret=_INTERPRET,
        )(xv, gv, p, s)
        if ch_last:
            dx = dxv.reshape(H, W, Bp, C).transpose(2, 0, 1, 3)
        else:
            dx = dxv.reshape(H, W, C, Bp).transpose(3, 0, 1, 2)
        return (dx[:B], dg.astype(gamma.dtype), db.astype(beta.dtype))

    fn.defvjp(fn_fwd, fn_bwd)
    _bnap_vjp_cache[key] = fn
    return fn


_BNAP_AUTOTUNE_CACHE: Dict = {}


def autotune_decisions() -> Dict:
    """Snapshot of ALL per-shape kernel-vs-XLA decisions made so far,
    keyed ("attention", ...shape key...) / ("bn_act_pool", ...) /
    ("paged_decode", ...)."""
    out = {("attention",) + k: v
           for k, v in _ATTN_AUTOTUNE_CACHE.items()}
    out.update({("bn_act_pool",) + k: v
                for k, v in _BNAP_AUTOTUNE_CACHE.items()})
    out.update({("paged_decode",) + k: v
                for k, v in _PAGED_AUTOTUNE_CACHE.items()})
    return out


# (family, *shape key) -> {candidate: "ExcType: first line"} for every
# autotune candidate that raised instead of being timed
_AUTOTUNE_REFUSED: Dict = {}


def autotune_refusals() -> Dict:
    """Candidates that RAISED during an autotune probe, keyed like
    :func:`autotune_decisions`: {key: {candidate: reason}}. A falsy
    verdict whose kernel candidates are all listed here means the
    compiler refused the kernel, not that XLA won the race."""
    return {k: dict(v) for k, v in _AUTOTUNE_REFUSED.items()}


def _note_refusal(key: tuple, candidate, exc: BaseException) -> None:
    lines = str(exc).strip().splitlines()
    _AUTOTUNE_REFUSED.setdefault(key, {})[str(candidate)] = (
        f"{type(exc).__name__}: {lines[0] if lines else ''}"[:300])


def kernel_execution() -> str:
    """"compiled" (Mosaic, on the TPU) or "interpreted" (the Pallas
    interpreter, every other backend) — as chosen by the last
    :func:`enable` / :func:`enable_paged_decode`."""
    return "interpreted" if _INTERPRET else "compiled"


def clear_autotune_cache() -> None:
    _ATTN_AUTOTUNE_CACHE.clear()
    _BNAP_AUTOTUNE_CACHE.clear()
    _PAGED_AUTOTUNE_CACHE.clear()
    _PAGED_ENGAGED.clear()
    _AUTOTUNE_REFUSED.clear()


def _eagerly(fn):
    """Run an autotune probe OUTSIDE any ambient trace. The helpers are
    normally first called while a train step is being jit-traced; without
    this escape every probe's `float()` fetch hits ConcretizationTypeError
    (inner jit calls inline into the outer trace), the except-clause eats
    it, and the seam silently falls back to XLA forever. jax.core's
    eval_context restores top-level eager semantics for the probe, so the
    measurement is real and the cached decision is shape-true."""
    import functools

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.core.eval_context():
            return fn(*args, **kwargs)
    return wrapped


def _measure_scan(step_fn, x0, K=32, repeats=3) -> float:
    """Per-iteration device time of ``step_fn`` measured as ONE jitted
    lax.scan of K carry-chained applications + one host fetch, so the
    dispatch and fetch overheads are paid once per K iterations instead of
    swamping a sub-ms op. The carry feeds back into the input so XLA
    cannot hoist the body out of the loop."""
    import time

    def body(c, _):
        return step_fn(c), None

    run = jax.jit(lambda c: jax.lax.scan(body, c, None, length=K)[0])
    out = run(x0)
    _ = float(jnp.sum(jax.tree_util.tree_leaves(out)[0].astype(jnp.float32)))
    best = float("inf")
    for _rep in range(repeats):
        t0 = time.perf_counter()
        out = run(x0)
        _ = float(jnp.sum(
            jax.tree_util.tree_leaves(out)[0].astype(jnp.float32)))
        best = min(best, time.perf_counter() - t0)
    return best / K


@_eagerly
def _autotune_bnap(B, H, W, C, dtype, eps, activation) -> bool:
    """Measure the fused-backward composite against the XLA default IN
    CONTEXT: sandwiched between a producer conv (whose input/weight grads
    XLA fuses the BN-backward into) and the train-step chain — the r4
    ISOLATED probe selected the kernel at 8x8x256 where the full model then
    measured a 0.5% LOSS, because the custom-call boundary breaks exactly
    those fusions (VERDICT r4 weak #3 / item 5; docs/ROOFLINE_CNN.md §3).
    Selection rule: the kernel must win the in-context composite by >=5%
    (the find-algorithm discipline of CudnnConvolutionHelper.java:48, with
    the margin covering probe noise), else XLA fallback."""
    import numpy as np
    rng = np.random.default_rng(0)
    # producer conv: same-C 3x3 SAME, the AlexNet-shaped adjacency whose
    # backward XLA fuses the composite's dx into
    xin = jnp.asarray(rng.normal(size=(B, H, W, C)), dtype)
    wc = jnp.asarray(rng.normal(size=(3, 3, C, C)) * 0.05, dtype)
    gamma = jnp.ones((C,), dtype)
    beta = jnp.zeros((C,), dtype)
    dn = ("NHWC", "HWIO", "NHWC")

    def ref(y, gamma, beta):
        return helpers._bn_act_pool_default(
            y, gamma, beta, eps=eps, activation=activation)[0]

    def train_step(comp):
        def loss(xc):
            y = jax.lax.conv_general_dilated(
                xc, wc, (1, 1), "SAME", dimension_numbers=dn)
            return jnp.sum(comp(y, gamma, beta).astype(jnp.float32) ** 2)
        g = jax.grad(loss)
        return lambda xc: xc + 1e-6 * g(xc).astype(xc.dtype)

    fam_key = ("bn_act_pool", B, H, W, C, jnp.dtype(dtype).name,
               float(eps), activation)
    best = None  # (time, variant)
    for variant in ("hwcb", "hwbc"):
        fused = _get_bnap_fn(eps, activation, variant)

        def pooled_only(y, g_, b_, fused=fused):
            return fused(y, g_, b_)[0]

        try:
            t = _measure_scan(train_step(pooled_only), xin)
        except Exception as e:
            _note_refusal(fam_key, variant, e)
            continue
        if best is None or t < best[0]:
            best = (t, variant)
    if best is None:
        return False
    try:
        t_r = _measure_scan(train_step(ref), xin)
    except Exception as e:
        _note_refusal(fam_key, "xla", e)
        # reference measurement failed transiently: no walkover for a
        # net-negative-prone kernel — fall back to XLA (advisor r4; the
        # attention seam walks over instead because dense XLA genuinely
        # cannot compile at its failing shapes)
        return False
    return best[1] if best[0] * 1.05 < t_r else False


def bn_act_pool_pallas(x, gamma, beta, *, eps=1e-5, activation="relu"):
    """bn_act_pool seam override: identical XLA forward, fused 2-pass Pallas
    BACKWARD (pool-argmax routing + act' + BN stat-grads recomputed
    in-kernel from x — select-and-scatter and the separate reduction passes
    disappear). Per-shape autotuned with silent XLA fallback."""
    B, H, W, C = x.shape
    supported = (activation in _BNAP_ACTS and H % 2 == 0 and W % 2 == 0
                 and C % 8 == 0 and W >= 4)
    if not supported:
        return helpers._bn_act_pool_default(x, gamma, beta, eps=eps,
                                            activation=activation)
    variant = "hwbc"  # interpreter/test default
    if not _INTERPRET:
        key = (B, H, W, C, jnp.dtype(x.dtype).name, float(eps), activation)
        if key not in _BNAP_AUTOTUNE_CACHE:
            _BNAP_AUTOTUNE_CACHE[key] = _autotune_bnap(
                B, H, W, C, x.dtype, float(eps), activation)
        variant = _BNAP_AUTOTUNE_CACHE[key]
        if not variant:
            return helpers._bn_act_pool_default(x, gamma, beta, eps=eps,
                                                activation=activation)
    pooled, mean32, var32 = _get_bnap_fn(float(eps), activation, variant)(
        x, gamma, beta)
    return (pooled, jax.lax.stop_gradient(mean32),
            jax.lax.stop_gradient(var32))


# =============================================================================
# flash attention (library Pallas kernel behind the helper seam)
# =============================================================================

_ATTN_AUTOTUNE_CACHE: Dict = {}


def _flash_block_sizes(block: int):
    """Square BlockSizes config for fwd AND both backward kernels."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes
    b = block
    return BlockSizes(block_q=b, block_k_major=b, block_k=b, block_b=1,
                      block_q_major_dkv=b, block_k_major_dkv=b,
                      block_k_dkv=b, block_q_dkv=b,
                      block_k_major_dq=b, block_k_dq=b, block_q_dq=b)


def _flash_call(q, k, v, causal, scale, block: int = 0):
    """q,k,v: [B, L, H, D] (the framework layout) -> [B, L, H, D] via the
    TPU flash-attention Pallas kernel (jax.experimental.pallas.ops.tpu),
    which ships its own backward pass. block=0 uses the library default
    BlockSizes; nonzero uses a square config (the autotuner probes these —
    measured on v5e the defaults are badly mistuned: L=8192 bf16 runs
    11.4 ms default vs 2.95 ms at block 1024 vs 5.9 ms XLA)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import \
        flash_attention
    D = q.shape[-1]
    sm_scale = float(scale) if scale is not None else float(1.0 / (D ** 0.5))
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, L, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    bs = _flash_block_sizes(block) if block else None
    out = flash_attention(qt, kt, vt, causal=causal, sm_scale=sm_scale,
                          block_sizes=bs)
    return jnp.swapaxes(out, 1, 2)


def _splash_call(q, k, v, causal, scale):
    """q,k,v: [B, L, H, D] -> [B, L, H, D] via the splash-attention Pallas
    kernel (jax.experimental.pallas.ops.tpu.splash_attention) — never
    materializes the [L, L] score matrix, so it trains sequence lengths the
    dense path cannot compile at all (measured v5e, H=8 D=128: dense OOMs at
    L=32k while splash runs 563 ms/step; at 64k splash runs 2.27 s).
    The kernel has no sm_scale parameter, so the scale folds into q."""
    from jax.experimental.pallas.ops.tpu.splash_attention import \
        splash_attention_kernel as sak
    from jax.experimental.pallas.ops.tpu.splash_attention import \
        splash_attention_mask as sam
    B, L, H, D = q.shape
    s = float(scale) if scale is not None else float(1.0 / (D ** 0.5))
    mk = sam.CausalMask((L, L)) if causal else sam.FullMask((L, L))
    kernel = sak.make_splash_mha(mask=sam.MultiHeadMask([mk] * H),
                                 head_shards=1, q_seq_shards=1,
                                 interpret=_INTERPRET)
    qt = jnp.swapaxes(q * jnp.asarray(s, q.dtype), 1, 2)  # [B, H, L, D]
    out = jax.vmap(kernel)(qt, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
    return jnp.swapaxes(out, 1, 2)


@_eagerly
def _autotune_attention(B, L, H, D, dtype, causal):
    """Probe the flash kernel (library-default blocks plus square block
    candidates that divide L) and the splash kernel against the XLA einsum
    attention on this exact shape — forward AND fwd+bwd. Returns the
    winning config: an int flash block (0 = library default), the string
    "splash", or False for the XLA path. When the dense XLA path cannot
    even compile (its [L, L] scores blow HBM at very long L), the best
    kernel wins by walkover."""
    import numpy as np
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, L, H, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, L, H, D)), dtype)

    def train_step(fn):
        # carry-chained fwd+bwd step for _measure_scan (q feeds back so
        # XLA cannot hoist the body); K/V captured
        g = jax.grad(lambda qc: jnp.sum(fn(qc, k, v).astype(jnp.float32)))
        return lambda qc: qc + jnp.asarray(1e-6, qc.dtype) * g(qc).astype(
            qc.dtype)

    def ref(q, k, v):
        return helpers._attention_default(q, k, v, causal=causal, scale=None)

    # time K chained applications inside ONE jitted scan (_measure_scan).
    # Probes are TRAIN-only (fwd+bwd through jax.grad — the cost that
    # decides the selection; measured fwd-only rankings track it) and the
    # candidate list shrinks with L so the probe's compile budget stays
    # bounded: every (candidate, K) pair is its own compile.
    fam_key = ("attention", B, L, H, D, jnp.dtype(dtype).name, bool(causal))
    K = 16 if L <= 2048 else (8 if L <= 8192 else 4)
    if L >= 4096:
        candidates = [b for b in (512, 1024) if L % b == 0] + ["splash"]
    else:
        candidates = [0] + [b for b in (256, 512, 1024) if L % b == 0] \
            + ["splash"]
    best = None  # (train_time, config)
    for block in candidates:
        if block == "splash":
            def fla(q, k, v):
                return _splash_call(q, k, v, causal, None)
        else:
            def fla(q, k, v, block=block):
                return _flash_call(q, k, v, causal, None, block=block)
        try:
            t_t = _measure_scan(train_step(fla), q, K=K, repeats=2)
        except Exception as e:  # unsupported config for this shape
            _note_refusal(fam_key, block, e)
            continue
        if best is None or t_t < best[0]:
            best = (t_t, block)
    if best is None:
        return False
    try:
        t_r_t = _measure_scan(train_step(ref), q, K=K, repeats=2)
    except Exception as e:
        _note_refusal(fam_key, "xla", e)
        # Walkover. The dominant case is a permanent compile failure — the
        # dense [L, L] scores exceed HBM at very long L — but even for a
        # transient error the kernel just measured HEALTHY on this shape
        # while the dense path errored, so the kernel is the safe cached
        # choice.
        return best[1]
    return best[1] if best[0] < t_r_t * 0.95 else False


def attention_pallas(q, k, v, *, causal=False, scale=None):
    """Helper-seam attention: per-shape autotuned choice among the XLA
    einsum path, the flash-attention Pallas kernel under several block
    configurations, and the splash-attention kernel (cuDNN find-algorithm
    semantics).

    Measured on v5e (H=8, D=128, bf16, causal, through the seam inside a
    jitted step): at L=8192 flash with square 1024 blocks trains at
    ~18 ms/step vs ~20 ms XLA; at L=32768 the dense path cannot compile at
    all (34 GB of [L, L] scores vs 15.75 GB HBM) and the kernel wins by
    walkover — 94 ms/step, with splash (563 ms) as the backstop when flash
    blocks don't fit. Short sequences keep the XLA path."""
    if _INTERPRET:  # CPU/test runs: the flash kernel is TPU-only
        return helpers._attention_default(q, k, v, causal=causal,
                                          scale=scale)
    B, L, H, D = q.shape
    key = (B, L, H, D, jnp.dtype(q.dtype).name, bool(causal))
    if key not in _ATTN_AUTOTUNE_CACHE:
        _ATTN_AUTOTUNE_CACHE[key] = _autotune_attention(
            B, L, H, D, q.dtype, bool(causal))
    decision = _ATTN_AUTOTUNE_CACHE[key]
    if decision is False:
        return helpers._attention_default(q, k, v, causal=causal,
                                          scale=scale)
    if decision == "splash":
        return _splash_call(q, k, v, causal, scale)
    return _flash_call(q, k, v, causal, scale, block=int(decision))


# =============================================================================
# fused paged-attention decode (ISSUE 15 tentpole)
# =============================================================================
# The paged decode hot path gathered a slot's ENTIRE logical cache
# [B, nb*block, Hkv, Dh] out of the page arrays every step (attention.py
# `_paged_step`), so decode bandwidth scaled with pool capacity instead of
# live tokens — and the int8 path additionally materialized a full
# dequantized fp copy of that gather. This kernel is the FlashDecoding
# treatment: one grid pass per (batch row, kv-head) walks the row's int32
# block table (scalar-prefetched, so each page's HBM->VMEM stream is
# issued straight off the table entry), computes QK^T + online softmax
# (running max / sum-exp in VMEM scratch) + V accumulation page by page,
# and dequantizes int8 rows in-loop via the shared ops/kvquant.py helpers.
# The gathered cache never exists; HBM traffic is one pass over the rows
# the table actually references.
#
# Seam contract (ops/helpers.py `paged_decode_attention`): the layer's
# gather/einsum body STAYS as the token-identity reference and the
# fallback — prefill chunks (T > 1), shapes the kernel does not support,
# mode "off", and every shape where the per-shape autotune picks XLA all
# return None here and run the reference. K/V WRITES (including the wmask
# scratch-page redirect and int8 quantization) also stay in the XLA
# prologue: the kernel fuses only the read side, so host-side table
# surgery, COW, and masked-lane semantics are untouched.

_PAGED_AUTOTUNE_CACHE: Dict = {}
# every trace-time engagement decision (forced AND autotuned), keyed like
# the autotune cache — the observability feed for the engine's
# `paged_kernel_engaged` gauge and the /debug/engine cost table
_PAGED_ENGAGED: Dict = {}
_PAGED_DEFAULT_VARIANT = "bh"


def paged_decode_decisions() -> Dict:
    """Trace-time kernel-vs-XLA engagements for the paged-decode family
    (includes forced ``mode="on"`` traces, unlike the autotune cache):
    {(B, nb, block, Hkv, H, Dh, dtype, quantized, mode): variant | False}.
    The MODE is part of the key — co-resident engines over the same
    shapes but different ``paged_kernel`` modes (the bench's A/B
    topology) must not overwrite each other's verdicts."""
    return dict(_PAGED_ENGAGED)


def enable_paged_decode(interpret=None) -> None:
    """Register ONLY the paged-decode seam (the serve CLI's arming
    path). Unlike :func:`enable`, this leaves every other helper —
    attention, conv, bn_act_pool — at its XLA default: a serving
    process that opted into ``--paged-kernel`` must not have its
    /predict forwards or GQA contraction silently rerouted through the
    rest of the plugin."""
    global _INTERPRET
    _INTERPRET = (jax.default_backend() != "tpu") if interpret is None \
        else bool(interpret)
    helpers.register_helper("paged_decode_attention",
                            paged_decode_attention_pallas)


def _paged_decode_body(table_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, acc_ref, m_ref, l_ref, *, block,
                       batch_major):
    """One grid step = one page of one (batch row, kv-head) pair.

    Grid (b, h, j) ("bh" variant; "hb" swaps the outer two), j the
    LOGICAL block index — sequential on TPU, so the f32 VMEM scratch
    (acc [G, Dh], running max m and sum-exp l) carries the online
    softmax across the row's pages. The page itself arrives via the
    BlockSpec index map reading the scalar-prefetched table
    (``table_ref[b, j]``), i.e. the gather IS the block fetch. Blocks
    past the row's decode depth are skipped whole; inside a live block,
    positions beyond ``pos`` mask to -inf (same coverage as the
    reference's ``arange(L) <= pos``). int8 pages dequantize per row
    inside the loop (the cast-then-multiply of
    ``kvquant.dequantize_kv_rows``, which the XLA gather uses), so no fp
    copy of the table ever exists.

    The contraction runs on the VPU, one query head at a time with the
    page's rows kept on sublanes ([block, 1] scores, [1, Dh] output
    row): G = H/Hkv is 1-8, so an MXU matmul would be a [G, Dh] sliver,
    and Mosaic multiplies f32 operands in one bf16 pass — measured on a
    v5e that put the kernel 5e-3 from the XLA gather path, whose G=1
    decode contraction is exact f32, where this form agrees to 1e-6."""
    del table_ref  # consumed by the index maps
    b = pl.program_id(0 if batch_major else 1)
    h = pl.program_id(1 if batch_major else 0)
    j = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]

    # skip pages wholly beyond this row's depth: the guard also keeps the
    # running max finite (a processed block always has a valid position,
    # since block j's first position j*block <= pos)
    @pl.when(j * block <= pos)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)          # [G, Dh]
        k = k_ref[0]                                 # [block, Dh]
        v = v_ref[0]
        if ks_ref is not None:
            # the scale block carries every kv-head's column (a one-head
            # block would put size 1 on the minor axis, which the TPU
            # tiling refuses); pick this head's by lane mask
            lane = jax.lax.broadcasted_iota(jnp.int32, ks_ref.shape[1:], 1)
            k_scale = jnp.sum(jnp.where(lane == h, ks_ref[0], 0.0),
                              axis=-1, keepdims=True)  # [block, 1]
            v_scale = jnp.sum(jnp.where(lane == h, vs_ref[0], 0.0),
                              axis=-1, keepdims=True)
            # kvquant.dequantize_kv_rows' cast-then-multiply, on the
            # column the mask already produced
            k = k.astype(jnp.float32) * k_scale
            v = v.astype(jnp.float32) * v_scale
        else:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        offs = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, 1), 0)
        for g in range(q.shape[0]):
            s = jnp.sum(k * q[g:g + 1, :], axis=-1, keepdims=True)
            s = s / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
            s = jnp.where(offs <= pos, s, -jnp.inf)  # [block, 1]
            m_prev = m_ref[g:g + 1, 0:1]             # [1, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_ref[g:g + 1, 0:1] * alpha \
                + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[g:g + 1, :] = acc_ref[g:g + 1, :] * alpha \
                + jnp.sum(p * v, axis=0, keepdims=True)
            m_ref[g:g + 1, :] = jnp.broadcast_to(m_new, (1, m_ref.shape[1]))
            l_ref[g:g + 1, :] = jnp.broadcast_to(l_new, (1, l_ref.shape[1]))

    @pl.when(j == nb - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)


def _paged_fp_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                     acc_ref, m_ref, l_ref, *, block, batch_major):
    _paged_decode_body(table_ref, pos_ref, q_ref, k_ref, v_ref, None,
                       None, o_ref, acc_ref, m_ref, l_ref, block=block,
                       batch_major=batch_major)


def _paged_int8_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, acc_ref, m_ref, l_ref, *, block,
                       batch_major):
    _paged_decode_body(table_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, o_ref, acc_ref, m_ref, l_ref, block=block,
                       batch_major=batch_major)


def _paged_decode_call(q, k_pages, v_pages, table, pos, k_scales=None,
                       v_scales=None, *, variant=_PAGED_DEFAULT_VARIANT):
    """The pallas_call over LOCAL (per-shard) shapes. q: [B, 1, H, Dh];
    k/v_pages: [pages, block, Hkv, Dh]; table: [B, nb] int32; pos: [B]
    int32 -> [B, 1, H, Dh]. ``variant``: grid-major-order config probed
    by the autotuner — "bh" walks all of a row's heads back-to-back
    (q block reuse), "hb" streams one head's pages across the batch
    (page-fetch pipeline depth B per head)."""
    B, _, H, Dh = q.shape
    pages, block, Hkv = k_pages.shape[:3]
    G = H // Hkv
    nb = table.shape[1]
    qr = q.reshape(B, Hkv, G, Dh)  # head h*G+g, the _grouped_attention order
    # a free view with the heads folded into the minor axis: head h's
    # rows are then a (block, Dh) tile at column block h, which the TPU
    # tiling accepts ((1, block, 1, Dh) over [.., Hkv, Dh] it refuses:
    # size 1 on the second-minor axis). KVPool's layout is unchanged.
    kv = k_pages.reshape(pages, block, Hkv * Dh)
    vv = v_pages.reshape(pages, block, Hkv * Dh)
    batch_major = variant != "hb"
    if batch_major:
        def bh(i0, i1):
            return i0, i1
        grid = (B, Hkv, nb)
    else:
        def bh(i0, i1):
            return i1, i0
        grid = (Hkv, B, nb)

    def qmap(i0, i1, j, tref, pref):
        b, h = bh(i0, i1)
        return (b, h, 0, 0)

    def kmap(i0, i1, j, tref, pref):
        b, h = bh(i0, i1)
        return (tref[b, j], 0, h)

    def smap(i0, i1, j, tref, pref):
        b, h = bh(i0, i1)
        return (tref[b, j], 0, 0)

    in_specs = [pl.BlockSpec((1, 1, G, Dh), qmap),
                pl.BlockSpec((1, block, Dh), kmap),
                pl.BlockSpec((1, block, Dh), kmap)]
    args = [qr, kv, vv]
    kern = _paged_fp_kernel
    if k_scales is not None:
        in_specs += [pl.BlockSpec((1, block, Hkv), smap),
                     pl.BlockSpec((1, block, Hkv), smap)]
        args += [k_scales, v_scales]
        kern = _paged_int8_kernel
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, Dh), qmap),
        scratch_shapes=[pltpu.VMEM((G, Dh), jnp.float32),
                        pltpu.VMEM((G, 128), jnp.float32),
                        pltpu.VMEM((G, 128), jnp.float32)])
    out = pl.pallas_call(
        partial(kern, block=block, batch_major=batch_major),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, Dh), q.dtype),
        grid_spec=grid_spec,
        interpret=_INTERPRET,
    )(table.astype(jnp.int32), pos.astype(jnp.int32), *args)
    return out.reshape(B, 1, H, Dh)


def _xla_paged_reference(q, k_pages, v_pages, table, pos, k_scales=None,
                         v_scales=None):
    """The current XLA gather path as a standalone function — the
    autotune probe's baseline and the tests' bit-level oracle. Mirrors
    attention.py `_paged_step`'s read side exactly: gather the whole
    logical cache through the table (dequantizing the int8 pool to the
    query dtype first), then the grouped contraction + f32 softmax of
    `_grouped_attention` with per-row causal depths."""
    B, T, H, Dh = q.shape
    block, Hkv = k_pages.shape[1], k_pages.shape[2]
    L = table.shape[1] * block
    dt = q.dtype
    if k_scales is not None:
        kc = kvquant.dequantize_kv_rows(
            k_pages[table], k_scales[table], dt).reshape(B, L, Hkv, Dh)
        vc = kvquant.dequantize_kv_rows(
            v_pages[table], v_scales[table], dt).reshape(B, L, Hkv, Dh)
    else:
        kc = k_pages[table].reshape(B, L, Hkv, Dh)
        vc = v_pages[table].reshape(B, L, Hkv, Dh)
    qg = q.reshape(B, T, Hkv, H // Hkv, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kc) / jnp.sqrt(
        jnp.asarray(Dh, dt))
    valid = (jnp.arange(L)[None, None, :]
             <= pos[:, None, None] + jnp.arange(T)[None, :, None])
    s = jnp.where(valid[:, None, None], s.astype(jnp.float32),
                  jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(dt)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, vc).reshape(B, T, H, Dh)


@_eagerly
def _autotune_paged_decode(B, nb, block, Hkv, H, Dh, dtype, quantized):
    """Probe the fused decode kernel's grid configs against the XLA
    gather path on this exact LOCAL shape (one decode step, carry-
    chained through _measure_scan). Returns the winning variant string
    or False for XLA. Rows are probed at FULL table depth — the
    regime the bucket was compiled for; shallower rows only shrink the
    kernel's walk. Selection needs a >= 5% win (find-algorithm margin
    over probe noise); a reference that cannot even run while the
    kernel measured healthy is a walkover, like the attention seam."""
    if _INTERPRET:
        # interpreter probes measure the interpreter, not the op: the
        # seam silently keeps XLA (tests force the kernel with "on")
        return False
    import numpy as np
    rng = np.random.default_rng(0)
    pages = B * nb + 1
    kp = jnp.asarray(rng.normal(size=(pages, block, Hkv, Dh)), dtype)
    vp = jnp.asarray(rng.normal(size=(pages, block, Hkv, Dh)), dtype)
    ks = vs = None
    if quantized:
        kp, ks = kvquant.quantize_kv_rows(kp)
        vp, vs = kvquant.quantize_kv_rows(vp)
    table = jnp.asarray(
        1 + np.arange(B * nb, dtype=np.int32).reshape(B, nb))
    pos = jnp.full((B,), nb * block - 1, jnp.int32)
    q0 = jnp.asarray(rng.normal(size=(B, 1, H, Dh)), dtype)

    def step(fn):
        def s(qc):
            out = fn(qc, kp, vp, table, pos, ks, vs)
            return qc + jnp.asarray(1e-6, qc.dtype) * out.astype(qc.dtype)
        return s

    fam_key = ("paged_decode", B, nb, block, Hkv, H, Dh,
               jnp.dtype(dtype).name, quantized)
    K = 16 if nb * block <= 4096 else 8
    best = None  # (time, variant)
    for variant in ("bh", "hb"):
        def fn(qc, kp, vp, tb, ps, ks, vs, variant=variant):
            return _paged_decode_call(qc, kp, vp, tb, ps, ks, vs,
                                      variant=variant)
        try:
            t = _measure_scan(step(fn), q0, K=K, repeats=2)
        except Exception as e:
            _note_refusal(fam_key, variant, e)
            continue
        if best is None or t < best[0]:
            best = (t, variant)
    if best is None:
        return False
    try:
        t_r = _measure_scan(step(_xla_paged_reference), q0, K=K, repeats=2)
    except Exception as e:
        _note_refusal(fam_key, "xla", e)
        # walkover: the gather path blew up (at large pools its
        # materialized [B, nb*block, Hkv, Dh] cache can exceed HBM)
        # while the kernel just measured healthy on this shape
        return best[1]
    return best[1] if best[0] * 1.05 < t_r else False


def paged_decode_attention_pallas(q, k_pages, v_pages, table, pos, *,
                                  k_scales=None, v_scales=None,
                                  mode="auto", mesh=None):
    """Seam override for `ops.helpers.paged_decode_attention`: per-shape
    autotuned choice between the fused page-walk kernel and the XLA
    gather path (returns None = caller runs its reference body — the
    silent-fallback contract). Under a tp mesh the kernel runs inside
    shard_map over the LOCAL Hkv shard (q/pages head-split, table/pos
    replicated — the layout the engine already carries), so the
    compiled program keeps the Megatron all-reduce-only collective
    budget: the kernel itself never communicates."""
    B, T, H, Dh = q.shape
    block, Hkv = k_pages.shape[1], k_pages.shape[2]
    # f32 only: the kernel accumulates QK^T/softmax/PV in f32, which
    # matches the XLA reference's arithmetic for f32 engines but NOT a
    # bf16 engine's (the reference contracts in the model dtype) — a
    # sub-f32 compute dtype falls back so the token-identity contract
    # holds; a dtype-disciplined bf16 variant is future headroom
    if T != 1 or H % Hkv or mode == "off" or q.dtype != jnp.float32:
        return None
    quantized = k_scales is not None
    tp = 1
    if mesh is not None:
        from ..inference.sharding import TP_AXIS as axis
        tp = int(dict(mesh.shape).get(axis, 1))
        if tp > 1 and (Hkv % tp or H % tp):
            return None
    key = (B, int(table.shape[1]), block, Hkv // tp, H // tp, Dh,
           jnp.dtype(q.dtype).name, quantized)
    if mode == "on":
        variant = _PAGED_DEFAULT_VARIANT
    else:
        if key not in _PAGED_AUTOTUNE_CACHE:
            _PAGED_AUTOTUNE_CACHE[key] = _autotune_paged_decode(
                *key[:6], q.dtype, quantized)
        variant = _PAGED_AUTOTUNE_CACHE[key]
    _PAGED_ENGAGED[key + (mode,)] = variant
    if not variant:
        return None
    if tp > 1:
        from jax.sharding import NamedSharding
        from ..inference.sharding import paged_kernel_shard_specs
        sp = paged_kernel_shard_specs(axis)
        hs4, hs3, rep = sp["rows"], sp["scales"], sp["host"]
        # anchor q's propagated placement to the head split the
        # column-parallel Wq already implies — a no-op when GSPMD
        # agrees, and it keeps the audit at zero resharding when it
        # would otherwise hedge
        q = jax.lax.with_sharding_constraint(
            q, NamedSharding(mesh, hs4))
        args = (q, k_pages, v_pages, table, pos)
        in_specs = (hs4, hs4, hs4, rep, rep)
        if quantized:
            args += (k_scales, v_scales)
            in_specs += (hs3, hs3)
        fn = jax.shard_map(
            partial(_paged_decode_call, variant=variant),
            mesh=mesh, in_specs=in_specs, out_specs=hs4, check_vma=False)
        return fn(*args)
    return _paged_decode_call(q, k_pages, v_pages, table, pos, k_scales,
                              v_scales, variant=variant)


# =============================================================================
# registration
# =============================================================================

def enable(interpret=None, use_conv=None, use_bn_act_pool=None) -> None:
    """Register the Pallas kernels behind the helper seam.

    interpret=None auto-detects: compiled on TPU, interpreter elsewhere
    (tests). The interpreter is orders of magnitude slower than XLA — only
    enable on CPU to validate numerics. :func:`kernel_execution` reports
    the outcome.

    use_conv=None registers the conv kernel only in interpreter (test) runs:
    on real TPU it measures slower than XLA's native conv (see
    conv2d_bias_act_pallas).

    use_bn_act_pool=None likewise registers the fused BN+act+pool backward
    only in interpreter (test) runs — PRODUCTION-RETIRED r5 by the same
    win-or-delete rule that retired the LSTM kernel. Measured history on
    the AlexNet-CIFAR10 flagship (v5e, bf16, B=512): the r4 ISOLATED
    scan-probe win (1.10-1.13x at C>=128) was already known not to
    survive in context (full-model 0.995, VERDICT r4 weak #3); the r5
    IN-CONTEXT probe (composite sandwiched in a producer conv, >=5%
    required margin) still selected it, but three independent full-model
    A/Bs measured helper_delta_vs_xla = 1.024 / 0.975 / 0.976 — parity
    within run-to-run noise, median slightly NEGATIVE, below the >=1.05
    full-model bar (VERDICT r4 item 5). The custom-call boundary forfeits
    XLA's fusion of BN-dx into the adjacent conv gradients and the 2-pass
    HBM saving does not cover that loss at these shapes. Kernel, VJP,
    autotuner, and interpret-mode numerics tests remain for
    experimentation (pass use_bn_act_pool=True).
    """
    global _INTERPRET
    _INTERPRET = (jax.default_backend() != "tpu") if interpret is None \
        else bool(interpret)
    if use_conv is None:
        use_conv = _INTERPRET
    if use_bn_act_pool is None:
        use_bn_act_pool = _INTERPRET
    if use_conv:
        helpers.register_helper("conv2d_bias_act", conv2d_bias_act_pallas)
    helpers.register_helper("attention", attention_pallas)
    # paged-decode is registered unconditionally like attention: its own
    # per-shape autotune (and the engine's paged_kernel mode knob) keeps
    # XLA wherever the kernel does not win, and in interpreter runs the
    # "auto" decision is always XLA — tests force engagement with "on"
    helpers.register_helper("paged_decode_attention",
                            paged_decode_attention_pallas)
    if use_bn_act_pool:
        helpers.register_helper("bn_act_pool", bn_act_pool_pallas)


def disable() -> None:
    """Restore the XLA default implementations (silent-fallback seam)."""
    helpers.register_helper("conv2d_bias_act", None)
    helpers.register_helper("attention", None)
    helpers.register_helper("paged_decode_attention", None)
    helpers.register_helper("bn_act_pool", None)
