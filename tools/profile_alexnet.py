"""Ablation profiler for the AlexNet-CIFAR10 MFU gap (VERDICT r3 #1).

Times jitted train-step variants on the real chip with best-of-3 blocks and
host-fetch sync. Run on the chip: `python tools/profile_alexnet.py`.
"""
from __future__ import annotations

import time

import numpy as np


def timeit(fn, sync, iters, blocks=3):
    fn()
    sync()
    best = float("inf")
    for _ in range(blocks):
        t0 = time.perf_counter()
        for _ in range(iters):
            fn()
        sync()
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def flops_of(jitted, *args):
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        return float(cost.get("flops", 0.0)) or None
    except Exception:
        return None


def main():
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (
        BatchNormalization, ConvolutionLayer, DenseLayer, OutputLayer,
        SubsamplingLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater.updaters import Adam
    from deeplearning4j_tpu.models.zoo import alexnet_cifar10

    PEAK = 197e12
    rng = np.random.default_rng(0)
    B = 512
    x = jnp.asarray(rng.normal(size=(B, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, B)])

    def bench_conf(name, conf, scan_k=16):
        net = MultiLayerNetwork(conf).init()
        sf = net._get_train_step((False, False, False))
        fl = flops_of(sf, net.params, net.variables, net.updater_state,
                      jnp.asarray(0), jax.random.PRNGKey(0), x, y,
                      None, None, None)
        xs = jnp.tile(x[None], (scan_k,) + (1,) * x.ndim)
        ys = jnp.tile(y[None], (scan_k,) + (1,) * y.ndim)
        losses = [net.fit_scan(xs, ys)]

        def step():
            losses[0] = net.fit_scan(xs, ys)

        dt = timeit(step, lambda: float(losses[0][-1]), iters=12) / scan_k
        mfu = fl / dt / PEAK if fl else None
        print(f"{name:34s} {dt*1e3:8.3f} ms  flops={fl and fl/1e9:.1f}G"
              f"  mfu={mfu and round(mfu,3)}")
        return dt, fl

    def conv_block(n_out, bn=True):
        layers = [ConvolutionLayer(n_out=n_out, kernel_size=(3, 3),
                                   stride=(1, 1), padding=(1, 1),
                                   activation="identity" if bn else "relu")]
        if bn:
            layers.append(BatchNormalization(activation="relu"))
        layers.append(SubsamplingLayer(pooling_type="max",
                                       kernel_size=(2, 2), stride=(2, 2)))
        return layers

    def variant(bn=True, dropout=0.5, dense=True):
        b = (NeuralNetConfiguration.builder()
             .seed(42).learning_rate(1e-3).updater(Adam())
             .regularization(True).l2(1e-4).dtype("bfloat16").list())
        for n_out in (64, 128, 256):
            for l in conv_block(n_out, bn=bn):
                b.layer(l)
        if dense:
            b.layer(DenseLayer(n_out=512, activation="relu", dropout=dropout))
        b.layer(OutputLayer(n_out=10, activation="softmax",
                            loss="negativeloglikelihood"))
        return b.build_with_input(InputType.convolutional(32, 32, 3)) \
            if hasattr(b, "build_with_input") else \
            b.set_input_type(InputType.convolutional(32, 32, 3)).build()

    # calibration: big bf16 matmul MFU through the same timing path
    a = jnp.asarray(rng.normal(size=(4096, 4096)), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    out = [mm(a)]

    def mstep():
        out[0] = mm(out[0])

    dt = timeit(mstep, lambda: float(jnp.sum(out[0].astype(jnp.float32))),
                iters=200)
    fl = 2 * 4096**3
    print(f"{'calib matmul 4096^3 bf16':34s} {dt*1e3:8.3f} ms  "
          f"flops={fl/1e9:.1f}G  mfu={fl/dt/PEAK:.3f}")

    bench_conf("alexnet full (zoo, bf16)", alexnet_cifar10(dtype="bfloat16"))
    bench_conf("no BN", variant(bn=False))
    bench_conf("no dropout", variant(dropout=None))
    bench_conf("no BN, no dropout", variant(bn=False, dropout=None))

    # forward-only cost of the full net
    net = MultiLayerNetwork(alexnet_cifar10(dtype="bfloat16")).init()
    import jax

    fwd = jax.jit(lambda p, v, x: net._forward_impl(
        p, v, x, train=False, rng=None)[0][-1])
    o = [fwd(net.params, net.variables, x)]

    def fstep():
        o[0] = fwd(net.params, net.variables, x)

    dt = timeit(fstep, lambda: float(jnp.sum(o[0].astype(jnp.float32))),
                iters=200)
    print(f"{'forward only (eval)':34s} {dt*1e3:8.3f} ms")


if __name__ == "__main__":
    main()
