"""The control precisions, shared by every family's reference.

`quant="fp8"` is the control: the same forward with every weight matrix
rounded to fp8 (e4m3) per output channel and every matmul input rounded to
fp8 per token, the nearest precision below bfloat16. `quant="int8"` is the
same with int8 (W8A8, symmetric); it is kept for the record: its readings
lie too close to the bfloat16 engine's own to separate (PERF.md). A family's
reference sends every matmul with a weight through `mm`, and so gets both
controls with nothing of its own."""
from __future__ import annotations

import jax.numpy as jnp


def fq(x, axis):
    """Symmetric fake int8 quantisation along `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def fq8(x, axis):
    """Fake fp8 (e4m3) quantisation along `axis`, scaled to the format's
    largest finite value."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = fq(x, -1), fq(w, 0)
    elif quant == "fp8":
        x, w = fq8(x, -1), fq8(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w)
