"""The seeded draw of weights, shared by every family's `make_params`.

A family names its leaves and their shapes and says which leaves make one
part (a block, the two ends); this module turns `--seed`, the part's index
and a leaf's place in the part into the leaf, inside the family's jitted
program. The seed is any whole number up to a little over 2**31: it goes in
as two traced uint32 halves, so every seed runs the same compiled program.
The rule by a leaf's name and rank: `*_g` is a gain, 1 + 0.1 N; any other
vector is a bias, 0.002 N; a matrix is std N. Gains and biases are drawn
too, so that a dropped bias or gain shows in the comparison."""
from __future__ import annotations

import jax
import jax.numpy as jnp

ENDS = 1 << 20          # the part index of what is not a block


def split_seed(seed: int):
    seed = int(seed)
    return jnp.uint32(seed & 0x7FFFFFFF), jnp.uint32(seed >> 31)


def leaf(key, name: str, shape, std: float, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_g"):
        x = 1.0 + 0.1 * x
    elif len(shape) == 1:
        # small: 60 biases of 0.02 summed along the residual stream drowned
        # the tokens' own signal, and at some seeds one token then won every
        # position by a margin no precision could flip (my chip runs, PR 25)
        x = 0.002 * x
    else:
        x = std * x
    return x.astype(dtype)


def part(shapes: dict, lo, hi, index, std: float, dtype) -> dict:
    """The leaves of one part, `{name: shape}`, from the seed's halves and
    the part's index; a leaf's own key folds in its place among the part's
    sorted names."""
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(lo), hi), index)
    return {name: leaf(jax.random.fold_in(key, i), name, shape, std, dtype)
            for i, (name, shape) in enumerate(sorted(shapes.items()))}
