"""Fused paged attention read for one query row a slot (ISSUE 31).

``paged_read_attention(q, k_pages, v_pages, pages, rows)``: slot ``b``
attends, in one softmax, over the first ``rows[b, j]`` rows of page
``pages[b, j]`` for every ``j``. A page whose count is 0 is neither fetched
nor computed; inside a fetched page the rows at or beyond the count are
masked. Nothing here knows what a page holds: a cache of one row per
position is the case ``rows = clip(pos + 1 - j * block, 0, block)`` over the
block table, and `EvaAttentionLayerImpl._paged_step` passes the open
window's pages and the summary pages with the counts of each.

The kernel is one program over a compacted work list, not a grid of
slots x pages: the XLA prologue moves the (slot, page, count) triples with a
count above 0 to the front, in order, and the kernel walks the first ``nv``
of them, so a step costs what it attends over and an idle slot costs
nothing. A work item is a WHOLE page of all heads, ``[block, Hkv, Dh]``
(EvaByte: 64 x 4,096 bfloat16 = 512 KB, one contiguous DMA each for K and
V), double-buffered by hand so that item i + 1 is in flight, across slots
too, while item i is computed.

The contraction is the MXU's, on the pages as the pool lays them out: a
page ``[block, Hkv, Dh]`` is read as the matrix ``[block * Hkv, Dh]`` (a
free view: no relayout of the pool), every (row, KV head) pair one key.
``scores = Q @ K^T`` is ``[H, block * Hkv]`` (products exact, float32
accumulation), a query head keeps the keys of its own KV head and below the
count (the mask), and ``P @ V`` is the ``[H, Dh]`` output itself. Scores,
running max, sum and accumulator are float32; the probabilities are rounded
to the pages' dtype for ``P @ V``, as
`SelfAttentionLayerImpl._grouped_attention` rounds them. A slot with no row
at all returns zeros, not 0/0."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # below any score, and finite: exp(_NEG - m) is 0, never NaN


def _kernel(slot_ref, page_ref, rows_ref, nv_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, acc_ref, m_ref, l_ref, *, scale, kv_heads):
    """Walk the first ``nv`` work items. ``slot/page/rows_ref``: SMEM [N];
    ``q_ref``: VMEM [B, H, Dh]; ``k/v_hbm``: [pages, block * Hkv, Dh] left
    in HBM; ``o_ref``: VMEM [B, H, Dh]."""
    n_items = slot_ref.shape[0]
    H = o_ref.shape[1]
    keys = kbuf.shape[1]
    group = H // kv_heads
    nv = nv_ref[0]
    o_ref[...] = jnp.zeros_like(o_ref)  # a slot with no item keeps zeros

    def copies(i, buf):
        page = page_ref[i]
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf],
                                      sem.at[1, buf]))

    @pl.when(nv > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def item(i, carry):
        buf = i % 2
        b = slot_ref[i]
        first = jnp.logical_or(i == 0,
                               slot_ref[jnp.maximum(i - 1, 0)] != b)
        last = jnp.logical_or(
            i == nv - 1, slot_ref[jnp.minimum(i + 1, n_items - 1)] != b)

        @pl.when(i + 1 < nv)
        def _():
            for c in copies(i + 1, 1 - buf):
                c.start()

        @pl.when(first)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        live = rows_ref[i] * kv_heads    # keys of this page below the count
        kc, vc = copies(i, buf)
        kc.wait()
        s = jax.lax.dot_general(
            q_ref[b], kbuf[buf], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, keys]
        key = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        s = jnp.where((key % kv_heads == head) & (key < live), s, _NEG)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)          # 0 where masked: _NEG - m_new
        l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        vc.wait()

        def accumulate(v):
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

        @pl.when(live >= keys)
        def _():
            accumulate(vbuf[buf])

        @pl.when(live < keys)
        def _():
            # 0 * NaN is NaN: what lies beyond the count is not read
            row = jax.lax.broadcasted_iota(jnp.int32, vbuf.shape[1:], 0)
            accumulate(jnp.where(row < live, vbuf[buf], 0))

        @pl.when(last)
        def _():
            o_ref[b] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, nv, item, 0)


@partial(jax.jit, static_argnames=("interpret",))
def paged_read_attention(q, k_pages, v_pages, pages, rows, *,
                         interpret=False):
    """q: [B, 1, H, Dh]; k/v_pages: [P, block, Hkv, Dh] (H a multiple of
    Hkv, query head h on KV head h // (H/Hkv), `_grouped_attention`'s
    order); pages, rows: [B, n] int32 -> [B, 1, H, Dh] in q's dtype.
    Jitted on its own so that the layers of one step program trace and
    lower the kernel once between them (set-up is tracing, PERF.md §5)."""
    B, T, H, Dh = q.shape
    P, block, Hkv, _ = k_pages.shape
    if T != 1 or H % Hkv:
        raise ValueError(f"one query row a slot and H % Hkv == 0, got "
                         f"T={T}, H={H}, Hkv={Hkv}")
    n = pages.shape[1]
    N = B * n
    # the work list: items with a row to read first, in (slot, j) order
    count = jnp.clip(rows.reshape(N).astype(jnp.int32), 0, block)
    order = jnp.argsort(count == 0, stable=True)
    page = jnp.clip(pages.reshape(N).astype(jnp.int32), 0, P - 1)[order]
    slot = (jnp.arange(N, dtype=jnp.int32) // n)[order]
    nv = jnp.sum(count > 0, dtype=jnp.int32).reshape(1)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        partial(_kernel, scale=float(Dh) ** -0.5, kv_heads=Hkv),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,),
            in_specs=[vmem, hbm, hbm], out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, block * Hkv, Dh), k_pages.dtype),
                pltpu.VMEM((2, block * Hkv, Dh), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, Dh), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32)]),
        name="paged_read_attention",
        interpret=interpret,
    )(slot, page, count[order], nv, q.reshape(B, H, Dh),
      k_pages.reshape(P, block * Hkv, Dh),
      v_pages.reshape(P, block * Hkv, Dh))
    return out.reshape(B, 1, H, Dh)
