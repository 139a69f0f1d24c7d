"""Grouped matrix multiplication over rows sorted by group.

The rows of ``x`` [M, K] come in groups, group ``g`` the ``sizes[g]`` rows
after the groups before it, and each group is multiplied by its own matrix
``w[g]``:

- ``grouped_matmul(x, w, sizes)``: ``out[r] = x[r] @ w[g(r)]`` [M, N]
  float32. The rows past ``sum(sizes)`` belong to no group and are left as
  the kernel found them (uninitialised): a caller selects them away, it never
  multiplies them by zero (NaN x 0 is NaN).
- ``grouped_matmul_sum(x, w, sizes, to, scale, n)``: the same products, each
  row scaled by ``scale[r]`` and added into row ``to[r]`` of a float32
  ``[n, N]`` result, inside the kernel: a routed layer's down matrix and its
  combine in one pass, one float32 add a row as a scatter-add would make.

The kernel walks (group, row tile) visits, a scalar-prefetched list of the
groups that hold a row in row order (`_metadata`): a group of no row is
never visited, so its matrix is never read, and a row tile that two groups
share is visited once by each, every visit storing only its own group's
rows. The grid is (visit, depth block): a visit streams its group's matrix
in blocks of whole rows (contiguous in memory), the next block's copy in
flight while this one is multiplied, across visits too, so expert ``e + 1``'s
first block arrives while expert ``e``'s last is multiplied. A group's matrix
is read once a visit: ``tile_visits(sizes, tm).sum()`` visits a call, one a
group where its rows fit one tile (where the whole matrix is one block,
consecutive visits of a group read it once).

Tiles follow the shapes, which are static: the row tile from ``M``
(`row_tile`), the depth block from ``K``, ``N`` and the element size (the
deepest block of whole rows under `_BLOCK_BYTES`). A stack whose matrices
are ``[K, N]`` with ``N`` not a multiple of 128 and ``K`` one is laid out by
the TPU with ``K`` minor (entry layout ``{1,2,0}``), so the kernel reads
``swapaxes(w, 1, 2)``, a bitcast of what the device holds, and contracts it
transposed; reading ``w`` as ``[K, N]`` there would copy the whole stack at
every call."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 128                 # rows a visit multiplies: the MXU's depth
_BLOCK_BYTES = 8 << 20      # what one block of one matrix may take


def row_tile(rows: int) -> int:
    """The row tile of ``rows`` sorted rows: 128, or all of them rounded up
    to 16 (a bfloat16 tile's rows) when there are fewer."""
    return min(_ROWS, -(-int(rows) // 16) * 16)


def tile_visits(sizes, tm: int):
    """The (group, row tile) visits the kernel makes, per group, along the
    last axis of ``sizes``: the tiles a group's rows touch, 0 for a group of
    no row. Arithmetic and methods only, so that the host computes it from
    read-back counts (numpy) exactly as the kernel's grid does (traced)."""
    ends = sizes.cumsum(-1)
    starts = ends - sizes
    return (sizes > 0) * ((ends - 1) // tm - starts // tm + 1)


def _metadata(sizes, tm: int, tiles: int):
    """(offsets [G + 1], the group of each visit, its row tile, the number
    of visits): the visit lists padded to their most, ``tiles + G - 1``."""
    G = sizes.shape[0]
    visits = tile_visits(sizes, tm)
    vend = jnp.cumsum(visits)
    v = jnp.arange(tiles + G - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(vend[None, :] <= v[:, None], axis=1,
                                dtype=jnp.int32), G - 1)
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    tile = (ends - sizes)[group] // tm + v - (vend - visits)[group]
    return (offsets.astype(jnp.int32), group,
            jnp.clip(tile, 0, tiles - 1).astype(jnp.int32), vend[-1])


def transposed(k: int, n: int) -> bool:
    """Whether a ``[G, k, n]`` stack lies on the TPU with ``k`` minor."""
    return n % 128 != 0 and k % 128 == 0


def _depth_block(k: int, n: int, itemsize: int) -> int:
    """The deepest block of whole rows of a ``[k, n]`` matrix, all ``k`` or
    a multiple of 128 that divides it, that fits `_BLOCK_BYTES`; the
    shallowest of those where none fits."""
    fits = [k] + [t for t in range(k // 128 * 128, 0, -128)
                  if t != k and k % t == 0]
    return next((t for t in fits if t * n * itemsize <= _BLOCK_BYTES),
                fits[-1])


def _kernel(offsets_ref, group_ref, tile_ref, *refs, tm, transpose, summed):
    """One depth block of one visit; at the visit's last block its rows go
    out: stored under the group's row mask, or (``summed``) scaled and added
    into their target rows one at a time."""
    if summed:
        to_ref, x_ref, w_ref, scale_ref, out_ref, acc_ref, part_ref = refs
    else:
        x_ref, w_ref, out_ref, acc_ref = refs
    v, kb = pl.program_id(0), pl.program_id(1)
    g, t = group_ref[v], tile_ref[v]

    @pl.when(kb == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if summed:
        @pl.when((v == 0) & (kb == 0))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

    dt = jnp.promote_types(x_ref.dtype, w_ref.dtype)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(dt), w_ref[...].astype(dt),
        (((1,), (1 if transpose else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        if not summed:
            row = t * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                     acc_ref.shape, 0)
            mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
            out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])
            return
        part_ref[...] = acc_ref[...] * scale_ref[...]
        first = jnp.maximum(offsets_ref[g] - t * tm, 0)
        last = jnp.minimum(offsets_ref[g + 1] - t * tm, tm)

        def add(r, carry):      # only the group's own rows are read
            dst = to_ref[t * tm + r]
            out_ref[pl.ds(dst, 1), :] += part_ref[pl.ds(r, 1), :]
            return carry

        jax.lax.fori_loop(first, last, add, 0)


def _call(x, w, sizes, tm, interpret, to=None, scale=None, n=None):
    M, K = x.shape
    G, _, N = w.shape
    tiles = -(-M // tm)
    pad = tiles * tm - M
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    offsets, group, tile, n_visits = _metadata(sizes.astype(jnp.int32), tm,
                                               tiles)
    transpose = transposed(K, N)
    # index maps: grid (visit, depth block), then the prefetched offsets,
    # group and row tile of each visit (and the target rows)
    if transpose:
        # [G, N, K]: the stored order. A block is the whole matrix; a
        # block of some of its depth would be a strided read of short rows
        tk = K
        w = jnp.swapaxes(w, 1, 2)
        w_spec = pl.BlockSpec((None, N, K),
                              lambda v, kb, o, group, *_: (group[v], 0, 0))
    else:
        tk = _depth_block(K, N, w.dtype.itemsize)
        w_spec = pl.BlockSpec((None, tk, N),
                              lambda v, kb, o, group, *_: (group[v], kb, 0))
    summed = to is not None
    x_spec = pl.BlockSpec((tm, tk),
                          lambda v, kb, o, g, tile, *_: (tile[v], kb))
    prefetch = [offsets, group, tile]
    scratch = [pltpu.VMEM((tm, N), jnp.float32)]
    # both operands' blocks double-buffered, the accumulator, the output
    held = 2 * (tm * tk * x.dtype.itemsize + tk * N * w.dtype.itemsize) \
        + tm * N * 4
    if summed:
        rows = -(-n // 8) * 8
        prefetch.append(jnp.pad(to.astype(jnp.int32), (0, pad)))
        in_specs = [x_spec, w_spec, pl.BlockSpec(
            (tm, 1), lambda v, kb, o, g, tile, *_: (tile[v], 0))]
        operands = [x, w, jnp.pad(scale.astype(jnp.float32),
                                  (0, pad)).reshape(-1, 1)]
        out_shape = jax.ShapeDtypeStruct((rows, N), jnp.float32)
        out_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
        scratch.append(pltpu.VMEM((tm, N), jnp.float32))
        held += rows * N * 4 + tm * N * 4
    else:
        in_specs, operands = [x_spec, w_spec], [x, w]
        out_shape = jax.ShapeDtypeStruct((tiles * tm, N), jnp.float32)
        out_spec = pl.BlockSpec((tm, N),
                                lambda v, kb, o, g, tile, *_: (tile[v], 0))
        held += 2 * tm * N * 4
    out = pl.pallas_call(
        partial(_kernel, tm=tm, transpose=transpose, summed=summed),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(n_visits, K // tk),
            in_specs=in_specs, out_specs=out_spec, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(16 << 20, 5 * held // 4 + (4 << 20))),
        name="grouped_matmul_sum" if summed else "grouped_matmul",
        interpret=interpret,
    )(*prefetch, *operands)
    if summed:
        # no visit at all leaves the output as it found it
        return jnp.where(n_visits > 0, out[:n], 0.0)
    return out[:M]


@partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul(x, w, sizes, *, tm, interpret=False):
    """x [M, K] rows sorted by group; w [G, K, N]; sizes [G] int32 ->
    [M, N] float32 (module docstring), in row tiles of ``tm``
    (`row_tile(M)`). Jitted on its own, so that the layers of one step
    program trace and lower the kernel once a shape."""
    return _call(x, w, sizes, tm, interpret)


@partial(jax.jit, static_argnames=("n", "tm", "interpret"))
def grouped_matmul_sum(x, w, sizes, to, scale, *, n, tm, interpret=False):
    """As `grouped_matmul`, and row ``r``'s product times ``scale[r]``
    (float32) added into row ``to[r]`` of an ``[n, N]`` float32 zero: rows
    of no group add nothing."""
    return _call(x, w, sizes, tm, interpret, to, scale, n)
