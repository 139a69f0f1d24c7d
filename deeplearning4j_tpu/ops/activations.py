"""Activation functions, named to match the reference's string-keyed registry.

Capability parity with ND4J's transform ops consumed by BaseLayer
(reference: deeplearning4j-core/.../nn/layers/BaseLayer.java — `conf.getActivationFunction()`
string dispatch into org.nd4j.linalg.ops.transforms.Transforms). Here each activation is
a pure jax-traceable function; XLA fuses it into the preceding matmul/conv.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

Array = jax.Array


def identity(x: Array) -> Array:
    return x


def sigmoid(x: Array) -> Array:
    return jax.nn.sigmoid(x)


def tanh(x: Array) -> Array:
    return jnp.tanh(x)


def relu(x: Array) -> Array:
    return jax.nn.relu(x)


def leakyrelu(x: Array) -> Array:
    return jax.nn.leaky_relu(x, negative_slope=0.01)


def elu(x: Array) -> Array:
    return jax.nn.elu(x)


def selu(x: Array) -> Array:
    return jax.nn.selu(x)


def softplus(x: Array) -> Array:
    return jax.nn.softplus(x)


def softsign(x: Array) -> Array:
    return jax.nn.soft_sign(x)


def hardtanh(x: Array) -> Array:
    return jnp.clip(x, -1.0, 1.0)


def hardsigmoid(x: Array) -> Array:
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


def cube(x: Array) -> Array:
    return x * x * x


def rationaltanh(x: Array) -> Array:
    # 1.7159 * tanh(2x/3) approximation used by ND4J's RationalTanh
    ax = jnp.abs(2.0 * x / 3.0)
    approx = jnp.sign(x) * (1.0 - 1.0 / (1.0 + ax + ax * ax + 1.41645 * ax**4))
    return 1.7159 * approx


def rectifiedtanh(x: Array) -> Array:
    return jnp.maximum(0.0, jnp.tanh(x))


def softmax(x: Array) -> Array:
    return jax.nn.softmax(x, axis=-1)


def gelu(x: Array) -> Array:
    return jax.nn.gelu(x)


def swish(x: Array) -> Array:
    return jax.nn.silu(x)


def relu2(x: Array) -> Array:
    """Squared ReLU, ``relu(x)**2``."""
    r = jax.nn.relu(x)
    return r * r


ACTIVATIONS: dict[str, Callable[[Array], Array]] = {
    "identity": identity,
    "linear": identity,
    "sigmoid": sigmoid,
    "tanh": tanh,
    "relu": relu,
    "leakyrelu": leakyrelu,
    "elu": elu,
    "selu": selu,
    "softplus": softplus,
    "softsign": softsign,
    "hardtanh": hardtanh,
    "hardsigmoid": hardsigmoid,
    "cube": cube,
    "rationaltanh": rationaltanh,
    "rectifiedtanh": rectifiedtanh,
    "softmax": softmax,
    "gelu": gelu,
    "swish": swish,
    "relu2": relu2,
}


def get(name: str) -> Callable[[Array], Array]:
    try:
        return ACTIVATIONS[name.lower()]
    except KeyError:
        raise ValueError(
            f"Unknown activation '{name}'. Available: {sorted(ACTIVATIONS)}"
        ) from None
