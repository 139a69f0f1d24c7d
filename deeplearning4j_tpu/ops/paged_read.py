"""Fused paged attention read for one query row a slot (ISSUE 31, 33, 35).

``paged_read_attention(q, k_pages, v_pages, pages, rows)``: slot ``b``
attends, in one softmax, over the first ``rows[b, j]`` rows of page
``pages[b, j]`` for every ``j``. A page whose count is 0 is neither fetched
nor computed; inside a fetched page the rows at or beyond the count are
masked. Nothing here knows what a page holds: a cache of one row per
position is the case ``rows = clip(pos + 1 - j * block, 0, block)`` over the
block table (`SelfAttentionLayerImpl._paged_step`),
`EvaAttentionLayerImpl._paged_step` passes the open window's pages and the
summary pages with the counts of each, and
`LatentAttentionLayerImpl._paged_step` passes the block table with no value
pages at all (the one-buffer form, below).

The kernel is one program over a compacted work list, not a grid of
slots x pages. A work item is up to ``G`` consecutive entries of one slot's
page list, and ``G`` follows from the page's bytes as the kernel sees them
(`pages_per_item`: about half a megabyte an item for K and again for V, so
16 pages of StarCoder2-3B's ``[64, 2, 128]`` bfloat16, 8 of the 7B's, ONE
of EvaByte's 512 KB, 7 of A.X-K1's 72 KB latent page): what an item costs
beyond its bytes (DMA issue and wait, the mask, one online-softmax update)
is paid once for ``G`` pages. The XLA prologue (`_work_list`) sorts the
groups that hold a row to the front, in (slot, j) order; the kernel walks
the first ``nv`` of them (`_walk`), so a step costs what it attends over and
an idle slot costs nothing. An item's pages are not neighbours in the pool:
``G`` DMAs (fewer where a page of the group has no row) land side by side in
one ``[G * block * Hkv, Dh]`` buffer for K and one for V, double-buffered by
hand so that item i + 1 is in flight, across slots too, while item i is
computed.

The contraction is the MXU's, on the pages as the pool lays them out: a
page ``[block, Hkv, Dh]`` is read as the matrix ``[block * Hkv, Dh]`` (a
free view: no relayout of the pool), every (row, KV head) pair one key.
``scores = Q @ K^T`` is ``[H, G * block * Hkv]`` (products exact, float32
accumulation), a query head keeps the keys of its own KV head and below
their page's count (the mask), and ``P @ V`` is the ``[H, Dh]`` output
itself. Scores, running max, sum and accumulator are float32; the
probabilities are rounded to the pages' dtype for ``P @ V``, as
`SelfAttentionLayerImpl._grouped_attention` rounds them. A slot with no row
at all returns zeros, not 0/0.

**The one-buffer form** (``v_pages`` None; `_rows_kernel`, an item body of
its own beside the same list, sort, walk and double buffer): a cached row is
key and value at once and every query head sees every key, a latent cache's
``[c | k_r]`` row under the absorbed query. The pool packs ``k`` positions
side by side into one page row (``k_pages``: ``[pages, block / k, k Dh]``,
position ``k r + j`` in lanes ``[j Dh, (j + 1) Dh)`` of row ``r``), and the
kernel DMAs a page as that matrix, ONCE; both products read the buffer, as
``k`` products over its lane windows (one softmax over them; the count mask
in that order); the output is ``sum p row`` over the whole ``Dh``-wide row
and the caller keeps the columns that are value. The softmax's ``scale`` is
the caller's. The other contraction of the packed row, a block-diagonal query
``[k H, k Dh]`` against whole buffer rows with the ``k`` partial softmaxes
merged at a slot's end, read 212 against 172 microseconds a layer at A.X-K1's
cell on a v5e (PERF.md section 6, PR 35)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30  # below any score, and finite: exp(_NEG - m) is 0, never NaN
# what one work item aims to bring in for K (and again for V): the pages an
# item takes follow from the page's bytes as the kernel sees them
_ITEM_BYTES = 512 * 1024
# the work list lives in SMEM (two int32 a page-list entry and one a group);
# a list padded beyond this many entries is not the kernel's to take
# (`list_fits`, asked by `SelfAttentionLayerImpl.fused_read_engages`)
MAX_ENTRIES = 64 * 1024


def pages_per_item(page_bytes: int) -> int:
    """``G``: 16 at StarCoder2-3B's 32 KB page, 8 at the 7B's 64 KB, 7 at
    A.X-K1's 72 KB latent page, 1 at EvaByte's 512 KB."""
    return max(1, _ITEM_BYTES // int(page_bytes))


def _items(n: int, page_bytes: int):
    """(``G``, work items a slot) of a page list of ``n`` entries a slot:
    the list is padded to whole items."""
    G = max(1, min(n, pages_per_item(page_bytes)))
    return G, -(-n // G)


def list_fits(slots: int, n: int, page_bytes: int) -> bool:
    """Whether the page lists of ``slots`` x ``n`` entries, padded as
    `paged_read_attention` pads them, fit the kernel's SMEM."""
    G, per_slot = _items(n, page_bytes)
    return slots * per_slot * G <= MAX_ENTRIES


def _walk(order_ref, page_ref, rows_ref, sem, *, group, per_slot, page_keys,
          row_keys=1):
    """What both item bodies walk the list by: ``slot_of(i)``, the slot of
    item ``i``; ``pages_of(i, body, init)``, ``body(live, copy, dst,
    carry)`` over the item's pages (the keys below the page's count, at
    ``row_keys`` keys a counted row; ``copy(hbm, vmem, j)``, the page's DMA
    into the rows ``dst`` of the buffer ``i`` takes of the two, on semaphore
    ``j``); ``edges(i, b, nv)``, whether ``i`` is its slot ``b``'s first
    item and its last."""
    n_items = order_ref.shape[0]

    def slot_of(i):
        return order_ref[i] // per_slot

    # the G pages of an item are walked by loops, not unrolled: what a step
    # program pays to trace and lower the kernel does not grow with G
    def pages_of(i, body, init=0):
        base, buf = order_ref[i] * group, i % 2

        def page(g, carry):
            dst = pl.ds(pl.multiple_of(g * page_keys, page_keys), page_keys)

            def copy(hbm, vmem, j):
                return pltpu.make_async_copy(hbm.at[page_ref[base + g]],
                                             vmem.at[buf, dst],
                                             sem.at[j, buf])

            return body(rows_ref[base + g] * row_keys, copy, dst, carry)

        return jax.lax.fori_loop(0, group, page, init)

    def edges(i, b, nv):
        return (jnp.logical_or(i == 0, slot_of(jnp.maximum(i - 1, 0)) != b),
                jnp.logical_or(
                    i == nv - 1, slot_of(jnp.minimum(i + 1, n_items - 1)) != b))

    return slot_of, pages_of, edges


def _kernel(order_ref, page_ref, rows_ref, nv_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, acc_ref, m_ref, l_ref, *, scale, kv_heads,
            group, per_slot):
    """Walk the first ``nv`` work items. ``order_ref``: SMEM [B * per_slot],
    the groups with a row first; ``page/rows_ref``: SMEM [B * per_slot *
    group], every slot's padded list; ``q_ref``: VMEM [B, H, Dh];
    ``k/v_hbm``: [pages, block * Hkv, Dh] left in HBM; ``o_ref``: VMEM
    [B, H, Dh]."""
    H = o_ref.shape[1]
    keys = kbuf.shape[1]
    page_keys = keys // group
    nv = nv_ref[0]
    slot_of, pages_of, edges = _walk(order_ref, page_ref, rows_ref, sem,
                                     group=group, per_slot=per_slot,
                                     page_keys=page_keys, row_keys=kv_heads)
    o_ref[...] = jnp.zeros_like(o_ref)  # a slot with no item keeps zeros
    # which keys a query head may see at all, and where a key sits in its
    # page: the same for every item
    key = jax.lax.broadcasted_iota(jnp.int32, (H, keys), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (H, keys), 0) // (H // kv_heads)
    own = key % kv_heads == head
    at = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
    vrow = jax.lax.broadcasted_iota(jnp.int32, (page_keys, vbuf.shape[2]), 0)

    def fetch(i):
        def start(live, copy, dst, carry):
            @pl.when(live > 0)  # a page with no row is not fetched
            def _():
                copy(k_hbm, kbuf, 0).start()
                copy(v_hbm, vbuf, 1).start()
            return carry

        pages_of(i, start)

    @pl.when(nv > 0)
    def _():
        fetch(0)

    def item(i, carry):
        buf = i % 2
        b = slot_of(i)
        first, last = edges(i, b, nv)

        @pl.when(i + 1 < nv)
        def _():
            fetch(i + 1)

        @pl.when(first)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def keys_in(live, copy, dst, end):
            @pl.when(live > 0)
            def _():
                copy(k_hbm, kbuf, 0).wait()
            return jnp.where((at >= dst.start) & (at < dst.start + page_keys),
                             dst.start + live, end)

        # per key: where its page's count ends
        end = pages_of(i, keys_in, jnp.zeros((1, keys), jnp.int32))
        s = jax.lax.dot_general(
            q_ref[b], kbuf[buf], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [H, keys]
        s = jnp.where(own & (at < end), s, _NEG)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)          # 0 where masked: _NEG - m_new
        l_new = l_ref[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        def values_in(live, copy, dst, carry):
            @pl.when(live > 0)
            def _():
                copy(v_hbm, vbuf, 1).wait()
            return carry

        def short_pages(live, copy, dst, carry):
            @pl.when(live < page_keys)
            def _():
                # 0 * NaN is NaN: what lies beyond the count is not read
                # (a page not fetched leaves whatever the buffer held)
                vbuf[buf, dst] = jnp.where(vrow < live, vbuf[buf, dst], 0)
            return carry

        # the item's copies share a semaphore, and a wait counts bytes, not
        # copies: only after the last wait has every page landed, so the
        # short pages are zeroed in a walk of their own
        pages_of(i, values_in)
        pages_of(i, short_pages)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(vbuf.dtype), vbuf[buf],
            preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            o_ref[b] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, nv, item, 0)


def _rows_kernel(order_ref, page_ref, rows_ref, nv_ref, q_ref, c_hbm, o_ref,
                 cbuf, sem, acc_ref, m_ref, l_ref, *, scale, group, per_slot):
    """The item of the one-buffer form (the module docstring): ``q_ref``:
    VMEM [B, H, R]; ``c_hbm``: [pages, block / k, k R] left in HBM;
    ``o_ref``: VMEM [B, H, R]; ``cbuf``: [2, G block / k, k R]."""
    R = q_ref.shape[2]
    k = cbuf.shape[2] // R
    keys = cbuf.shape[1]            # buffer rows, k positions each
    page_keys = keys // group
    nv = nv_ref[0]
    slot_of, pages_of, edges = _walk(order_ref, page_ref, rows_ref, sem,
                                     group=group, per_slot=per_slot,
                                     page_keys=page_keys)
    o_ref[...] = jnp.zeros_like(o_ref)  # a slot with no item keeps zeros
    # position ``k r + j`` of a page sits in lanes [j R, (j + 1) R) of its
    # row ``r``: where a buffer row and a lane of a page row stand, in
    # positions. The same for every item
    at = jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (page_keys, k * R), 1)
    held = k * jax.lax.broadcasted_iota(jnp.int32, (page_keys, k * R), 0) \
        + sum((lane >= j * R).astype(jnp.int32) for j in range(1, k))

    def fetch(i):
        def start(live, copy, dst, carry):
            @pl.when(live > 0)  # a page with no row is not fetched
            def _():
                copy(c_hbm, cbuf, 0).start()    # once: key and value
            return carry

        pages_of(i, start)

    @pl.when(nv > 0)
    def _():
        fetch(0)

    def item(i, carry):
        buf = i % 2
        b = slot_of(i)
        first, last = edges(i, b, nv)

        @pl.when(i + 1 < nv)
        def _():
            fetch(i + 1)

        @pl.when(first)
        def _():
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def rows_in(live, copy, dst, end):
            @pl.when(live > 0)
            def _():
                copy(c_hbm, cbuf, 0).wait()
            return jnp.where((at >= dst.start) & (at < dst.start + page_keys),
                             k * dst.start + live, end)

        def short_pages(live, copy, dst, carry):
            @pl.when(live < k * page_keys)
            def _():
                # 0 * NaN is NaN: what lies beyond the count is not read
                # (a page not fetched leaves whatever the buffer held)
                cbuf[buf, dst] = jnp.where(held < live, cbuf[buf, dst], 0)
            return carry

        # per buffer row: where its page's count ends, in positions. The
        # copies share a semaphore and a wait counts bytes: every page has
        # landed only after the last wait, so a second walk zeroes
        end = pages_of(i, rows_in, jnp.zeros((1, keys), jnp.int32))
        pages_of(i, short_pages)
        # k products over the lane windows of the buffer, one softmax
        q = q_ref[b]
        cs = [cbuf[buf, :, j * R:(j + 1) * R] for j in range(k)]
        ss = [jnp.where(k * at + j < end, jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale, _NEG)
            for j, c in enumerate(cs)]                      # k of [H, keys]
        m_prev = m_ref[:, 0:1]
        m_new = m_prev
        for s in ss:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        ps = [jnp.exp(s - m_new) for s in ss]   # 0 where masked
        l_new = l_ref[:, 0:1] * alpha + sum(
            jnp.sum(p, axis=1, keepdims=True) for p in ps)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + sum(
            jnp.dot(p.astype(c.dtype), c, preferred_element_type=jnp.float32)
            for p, c in zip(ps, cs))

        @pl.when(last)
        def _():
            o_ref[b] = (acc_ref[...] / l_ref[:, 0:1]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, nv, item, 0)


def _work_list(pages, rows, P, block, page_bytes):
    """The padded lists and their order: (G, work items a slot, ``order``
    [B * per_slot] with the groups that hold a row first, in (slot, j)
    order, ``page`` and ``count`` [B * per_slot * G], ``nv`` [1])."""
    B, n = pages.shape
    G, per_slot = _items(n, page_bytes)
    pad = ((0, 0), (0, per_slot * G - n))
    count = jnp.pad(jnp.clip(rows.astype(jnp.int32), 0, block), pad)
    page = jnp.pad(jnp.clip(pages.astype(jnp.int32), 0, P - 1), pad)
    empty = jnp.all(count.reshape(B * per_slot, G) == 0, axis=1)
    order = jnp.argsort(empty, stable=True).astype(jnp.int32)
    nv = jnp.sum(~empty, dtype=jnp.int32).reshape(1)
    return G, per_slot, order, page.reshape(-1), count.reshape(-1), nv


@partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_read_attention(q, k_pages, v_pages, pages, rows, *, scale=None,
                         interpret=False):
    """q: [B, 1, H, Dh]; k/v_pages: [P, block, Hkv, Dh] (H a multiple of
    Hkv, query head h on KV head h // (H/Hkv), `_grouped_attention`'s
    order); pages, rows: [B, n] int32 -> [B, 1, H, Dh] in q's dtype.
    With ``v_pages`` None the one-buffer form: k_pages [P, block / k, k Dh],
    a row of it k positions' rows side by side, each key and value to every
    head. ``scale``: the softmax's, ``Dh ** -0.5`` where none is given.
    Jitted on its own so that the layers of one step program trace and
    lower the kernel once between them (set-up is tracing, PERF.md §5)."""
    B, T, H, Dh = q.shape
    scale = float(Dh) ** -0.5 if scale is None else float(scale)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    if v_pages is None:
        P, prow, wide = k_pages.shape
        if T != 1 or wide % Dh:
            raise ValueError(f"one query row a slot and page rows of whole "
                             f"{Dh}-wide rows, got T={T}, a page row of "
                             f"{wide}")
        G, per_slot, *lists = _work_list(
            pages, rows, P, prow * (wide // Dh),
            prow * wide * k_pages.dtype.itemsize)
        # q and o stay in VMEM whole, their rows padded to 128 lanes, beside
        # the two buffers: beyond the compiler's 16 MiB at float32 widths
        held = (2 * B * H * -(-Dh // 128) * 128 * q.dtype.itemsize
                + 2 * G * prow * wide * k_pages.dtype.itemsize)
        out = pl.pallas_call(
            partial(_rows_kernel, scale=scale, group=G, per_slot=per_slot),
            out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(1,),
                in_specs=[vmem, hbm], out_specs=vmem,
                scratch_shapes=[
                    pltpu.VMEM((2, G * prow, wide), k_pages.dtype),
                    pltpu.SemaphoreType.DMA((1, 2)),
                    pltpu.VMEM((H, Dh), jnp.float32),
                    pltpu.VMEM((H, 128), jnp.float32),
                    pltpu.VMEM((H, 128), jnp.float32)]),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=max(16 << 20, 3 * held // 2)),
            name="paged_read_rows",
            interpret=interpret,
        )(*lists, q.reshape(B, H, Dh), k_pages)
        return out.reshape(B, 1, H, Dh)
    P, block, Hkv, _ = k_pages.shape
    if T != 1 or H % Hkv:
        raise ValueError(f"one query row a slot and H % Hkv == 0, got "
                         f"T={T}, H={H}, Hkv={Hkv}")
    G, per_slot, *lists = _work_list(
        pages, rows, P, block, block * Hkv * Dh * k_pages.dtype.itemsize)
    out = pl.pallas_call(
        partial(_kernel, scale=scale, kv_heads=Hkv, group=G,
                per_slot=per_slot),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(1,),
            in_specs=[vmem, hbm, hbm], out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, G * block * Hkv, Dh), k_pages.dtype),
                pltpu.VMEM((2, G * block * Hkv, Dh), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, Dh), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32)]),
        name="paged_read_attention",
        interpret=interpret,
    )(*lists, q.reshape(B, H, Dh), k_pages.reshape(P, block * Hkv, Dh),
      v_pages.reshape(P, block * Hkv, Dh))
    return out.reshape(B, 1, H, Dh)
