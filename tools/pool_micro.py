"""Micro-benchmark max-pool 2x2/s2 fwd+bwd variants on AlexNet shapes,
measured INSIDE a lax.scan so the dispatch+fetch round trip amortizes away
(see tools/xplane_summary.py, last in commit dd74740).

Run on the chip.
"""
from __future__ import annotations

import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def rw_pool(x):
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")

    @jax.custom_vjp
    def cv_pool(x):
        return rw_pool(x)

    def cv_fwd(x):
        y = rw_pool(x)
        return y, (x, y)

    def cv_bwd(res, g):
        x, y = res
        up_y = jnp.repeat(jnp.repeat(y, 2, axis=1), 2, axis=2)
        up_g = jnp.repeat(jnp.repeat(g, 2, axis=1), 2, axis=2)
        return (jnp.where(x == up_y, up_g, jnp.zeros_like(up_g)),)

    cv_pool.defvjp(cv_fwd, cv_bwd)

    def ss_pool(x):
        a = jnp.maximum(x[:, 0::2], x[:, 1::2])
        return jnp.maximum(a[:, :, 0::2], a[:, :, 1::2])

    def rs_pool(x):
        B, H, W, C = x.shape
        return jnp.max(x.reshape(B, H // 2, 2, W // 2, 2, C), axis=(2, 4))

    import sys
    only = sys.argv[1] if len(sys.argv) > 1 else None
    K = 50
    rng = np.random.default_rng(0)
    for shape in [(512, 32, 32, 64), (512, 16, 16, 128), (512, 8, 8, 256)]:
        x = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
        nbytes = x.size * 2
        print(f"-- {shape}  ({nbytes/1e6:.1f} MB) --")
        for name, pool in [("reduce_window", rw_pool), ("custom_vjp", cv_pool),
                           ("strided", ss_pool), ("reshape6", rs_pool)]:
            if only and name != only:
                continue
            g = jax.grad(lambda x, p=pool: jnp.sum(
                p(x).astype(jnp.float32) ** 2))

            def body(c, _, g=g):
                return c + 1e-6 * g(x + 1e-6 * c), 0.0

            f = jax.jit(lambda c: lax.scan(body, c, None, length=K)[0])
            c0 = jnp.zeros_like(x)
            o = f(c0)
            _ = float(jnp.sum(o.astype(jnp.float32)))
            best = float("inf")
            for _i in range(3):
                t0 = time.perf_counter()
                o = f(c0)
                _ = float(jnp.sum(o.astype(jnp.float32)))
                best = min(best, (time.perf_counter() - t0 - 0.105) / K)
            print(f"  {name:14s} {best*1e3:7.3f} ms  "
                  f"({3*nbytes/best/1e9:6.1f} GB/s effective)")


if __name__ == "__main__":
    main()
