"""Multi-head self-attention layer.

No reference counterpart (pre-transformer codebase — SURVEY.md §5); added as
the long-context-capable layer of this framework. Under a `pjit`/GSPMD mesh
the dense path shards automatically; for explicit sequence parallelism use
`parallel.ring.ring_attention` / `ulysses_attention` (same math, tested equal).

Streaming inference: the impl extends the recurrent-state protocol
(BaseRecurrentImpl), carrying a fixed-capacity KV cache as its state — so
`rnn_time_step` (reference rnnTimeStep:1460, O(1)-memory streaming) works
for transformers exactly like for LSTMs: O(L_max) per token instead of
re-forwarding the full context. Training always runs the full-sequence
path; the cache exists only on the inference step path.

The cached step is multi-token and per-slot: ``pos`` may be a [B] vector
(each batch row decoding at its own depth — the serving engine's slot
scheduling) and the incoming x may carry T > 1 timesteps (chunked
prefill, inference/engine.py): a chunk's K/V rows land at [pos, pos+T)
via per-row offset `dynamic_update_slice`, RoPE rotates at each row's
absolute positions, and the causal mask covers both the cache depth AND
query order within the chunk (`_grouped_attention` qpos0).

Two cache layouts share that step contract: the original contiguous
per-slot stripe ({"k", "v", "pos"}), and the paged layout
({"k_pages", "v_pages", "pos"} + an injected block ``table`` —
inference/kvpool.py, `_paged_step`) where K/V rows live in pool-wide
fixed-size pages and a slot's capacity is bounded by pool bytes instead
of ``max_cache_len``. Both run the same `_grouped_attention` math, so
they are token-identical.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .base import LayerImpl, register_impl
from .recurrent import BaseRecurrentImpl
from .. import weights as winit
from ...ops import helpers as ophelpers
from ...ops.kvquant import dequantize_kv_rows, quantize_kv_rows

Array = jax.Array


@register_impl("SelfAttentionLayer")
class SelfAttentionLayerImpl(BaseRecurrentImpl):
    WEIGHT_KEYS = ("Wq", "Wk", "Wv", "Wo")
    TBPTT_STATE = False  # the KV cache is inference-only state; training
    # always runs the full-sequence path (no cross-window carry)

    def _kv_heads(self) -> int:
        """K/V head count: n_kv_heads (grouped-query attention) or n_heads
        (plain multi-head). Must divide n_heads."""
        conf = self.conf
        kv = getattr(conf, "n_kv_heads", None)
        if kv is None:
            return conf.n_heads
        if kv <= 0 or conf.n_heads % kv:
            raise ValueError(f"n_kv_heads={kv} must be a positive divisor "
                             f"of n_heads={conf.n_heads}")
        return kv

    def _head_dim(self) -> int:
        """A head's width: the conf's ``head_dim``, or the heads' share of
        ``n_out`` where none is given."""
        conf = self.conf
        Dh = getattr(conf, "head_dim", None)
        return int(Dh) if Dh is not None else conf.n_out // conf.n_heads

    def init_params(self, key, dtype=jnp.float32):
        conf = self.conf
        dist = conf.dist.spec() if getattr(conf, "dist", None) is not None else None
        kq, kk, kv, ko = jax.random.split(key, 4)
        model = conf.n_out
        Dh = self._head_dim()
        kv_dim = self._kv_heads() * Dh
        mk = lambda k, i, o: winit.init_weights(k, (i, o), conf.weight_init or "xavier",
                                                dist, dtype)
        return {
            "Wq": mk(kq, conf.n_in, conf.n_heads * Dh),
            "Wk": mk(kk, conf.n_in, kv_dim),
            "Wv": mk(kv, conf.n_in, kv_dim),
            "Wo": mk(ko, conf.n_heads * Dh, model),
            "b": jnp.full((model,), float(conf.bias_init or 0.0), dtype),
        }

    # -- recurrent-state protocol (KV cache) ----------------------------------
    def init_state(self, batch: int, dtype=jnp.float32):
        conf = self.conf
        Dh = self._head_dim()
        Hkv = self._kv_heads()  # GQA: the cache shrinks with the KV heads
        L = int(getattr(conf, "max_cache_len", 1024))
        return {"k": jnp.zeros((batch, L, Hkv, Dh), dtype),
                "v": jnp.zeros((batch, L, Hkv, Dh), dtype),
                "pos": jnp.zeros((), jnp.int32)}

    def _qkv(self, params, x, pos0=0):
        """Projections as [B, T, heads, Dh]; K/V carry `n_kv_heads` heads
        (NOT yet broadcast to the query heads — the cache stores them
        compact; `_expand_kv` broadcasts at attention time)."""
        conf = self.conf
        B, T, _ = x.shape
        H = conf.n_heads
        Dh = self._head_dim()

        def proj(w, heads):
            return jnp.einsum("btf,fo->bto", x, params[w]).reshape(
                B, T, heads, Dh)

        Hkv = self._kv_heads()
        q = proj("Wq", H)
        k = proj("Wk", Hkv)
        v = proj("Wv", Hkv)
        if getattr(conf, "rope", False):
            q = self._rope(q, pos0)
            k = self._rope(k, pos0)
        return q, k, v

    def _expand_kv(self, a):
        """Broadcast [B, T, Hkv, Dh] K/V to the n_heads query heads."""
        H = self.conf.n_heads
        Hkv = a.shape[2]
        if Hkv == H:
            return a
        return jnp.repeat(a, H // Hkv, axis=2)

    def _rope(self, a, pos0):
        """Rotary position embedding on [B, T, H, Dh] (Dh even), half-split
        pairing (GPT-NeoX "rotate-half" convention: dim i pairs with
        i + Dh/2 — NOT the paper's interleaved (0,1),(2,3) pairing; weight
        converters must match). The rotation commutes with the KV cache —
        cached keys are stored pre-rotated at their absolute position.
        ``pos0`` may be a scalar (whole batch at one depth) or a [B] vector
        (slot-based decode: each row at its own depth)."""
        B, T, H, Dh = a.shape
        if Dh % 2:
            raise ValueError(f"rope requires an even head dim, got {Dh}")
        half = Dh // 2
        freq = jnp.asarray(self.conf.rope_base, jnp.float32) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
        pos = jnp.asarray(pos0)
        t = jnp.arange(T, dtype=jnp.float32)
        if pos.ndim:  # per-row positions -> per-row angles [B, T, half]
            ang = (pos.astype(jnp.float32)[:, None]
                   + t[None, :])[:, :, None] * freq[None, None]
            cos = jnp.cos(ang)[:, :, None, :].astype(a.dtype)
            sin = jnp.sin(ang)[:, :, None, :].astype(a.dtype)
            a1, a2 = a[..., :half], a[..., half:]
            return jnp.concatenate([a1 * cos - a2 * sin,
                                    a1 * sin + a2 * cos], axis=-1)
        ang = (pos + t)[:, None] * freq[None]
        cos = jnp.cos(ang)[None, :, None, :].astype(a.dtype)
        sin = jnp.sin(ang)[None, :, None, :].astype(a.dtype)
        a1, a2 = a[..., :half], a[..., half:]
        return jnp.concatenate([a1 * cos - a2 * sin,
                                a1 * sin + a2 * cos], axis=-1)

    def _out(self, params, o, B, T):
        out = jnp.einsum("btm,mn->btn", o.reshape(B, T, -1),
                         params["Wo"]) + params["b"]
        return self.activation_fn()(out)

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        conf = self.conf
        x = self._dropout(x, train, rng)
        B, T, _ = x.shape
        q, k, v = self._qkv(params, x)
        if k.shape[2] != q.shape[2] and ophelpers.get_helper("attention") is None:
            # GQA on the default XLA path: grouped contraction against the
            # compact K/V — no H-expanded copies. A registered kernel
            # (flash/splash) requires matching head counts, so the repeat
            # only happens when a kernel is worth it (long context).
            o = self._grouped_attention(q, k, v, causal=conf.causal)
        else:
            o = ophelpers.attention(q, self._expand_kv(k),
                                    self._expand_kv(v), causal=conf.causal)
        if mask is not None:
            o = o * mask[:, :, None, None].astype(o.dtype)
        return self._out(params, o, B, T), variables or {}

    def _grouped_attention(self, q, k, v, *, causal, qpos0=0, valid=None):
        """Dense attention with q grouped over compact KV heads — THE single
        contraction for both the full forward (qpos0=0, L==T) and the
        KV-cached decode step (qpos0=cache position, L=cache capacity).
        q: [B, T, H, Dh]; k, v: [B, L, Hkv, Dh] -> [B, T, H, Dh].
        ``qpos0`` scalar, or [B] for per-row decode depths (slot scheduling:
        each row's causal horizon is its own cache position). ``valid``
        ([B or 1, T, L] bool) replaces the causal rule with the caller's
        own (a layer whose rows are not one per position)."""
        B, T, H, Dh = q.shape
        L, Hkv = k.shape[1], k.shape[2]
        qg = q.reshape(B, T, Hkv, H // Hkv, Dh)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / jnp.sqrt(
            jnp.asarray(Dh, q.dtype))
        if valid is not None:
            valid = valid[:, None, None]
        elif causal:
            qp = jnp.asarray(qpos0)
            if qp.ndim:  # [B] -> valid [B, T, L] -> [B, 1, 1, T, L]
                valid = (jnp.arange(L)[None, None, :]
                         <= qp[:, None, None] + jnp.arange(T)[None, :, None])
                valid = valid[:, None, None]
            else:
                valid = (jnp.arange(L)[None, :]
                         <= qp + jnp.arange(T)[:, None])[None, None, None]
        if valid is not None:
            s = jnp.where(valid, s.astype(jnp.float32),
                          jnp.finfo(jnp.float32).min)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(B, T, H, Dh)

    def forward_with_state(self, params, x, state0, *, train=False, rng=None,
                           mask=None):
        """Full-sequence attention when training or uncached (state passes
        through untouched); KV-cached incremental attention when an
        inference step arrives with a cache state. The step takes any T
        (T=1 decode, T=C chunked prefill) at scalar or per-row [B]
        positions; positions beyond `max_cache_len` are unsupported
        (fixed-capacity cache — chunk callers must keep pos+T <= cap,
        padding included: the overflow guard sees the PADDED length)."""
        if train or state0 is None:
            y, _ = self.forward(params, x, train=train, rng=rng, mask=mask)
            return y, state0
        if not self.conf.causal:
            raise NotImplementedError(
                "KV-cached streaming decode requires causal=True: a "
                "non-causal layer's full forward attends to FUTURE "
                "positions the cache cannot know yet (same limitation as "
                "bidirectional LSTM rnnTimeStep)")
        if "k_pages" in state0:
            return self._paged_step(params, x, state0, mask=mask)
        B, T, _ = x.shape
        pos = state0["pos"]
        L_cap = state0["k"].shape[1]
        per_slot = jnp.ndim(pos) > 0  # [B] positions: slot-based decode
        del rng  # no dropout on the inference step path
        if not isinstance(pos, jax.core.Tracer) and \
                int(jnp.max(pos) if per_slot else pos) + T > L_cap:
            raise ValueError(
                f"KV cache overflow: position "
                f"{int(jnp.max(pos) if per_slot else pos)}+{T} exceeds "
                f"max_cache_len={L_cap}; raise SelfAttentionLayer."
                f"max_cache_len or rnn_clear_previous_state()")
        # under a trace pos is abstract and cannot raise; poison the output
        # with NaN instead of silently reading a clamp-corrupted cache
        overflow = (pos + T) > L_cap
        q, k_new, v_new = self._qkv(params, x, pos0=pos)
        if per_slot:
            # per-row write offsets: vmap the slice update over the batch
            upd = jax.vmap(lambda c, n, p: jax.lax.dynamic_update_slice(
                c, n, (p, 0, 0)))
            kc = upd(state0["k"], k_new, pos)
            vc = upd(state0["v"], v_new, pos)
        else:
            kc = jax.lax.dynamic_update_slice(state0["k"], k_new,
                                              (0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(state0["v"], v_new,
                                              (0, pos, 0, 0))
        # grouped contraction against the COMPACT cache: never materialize
        # the H-expanded K/V copies GQA exists to avoid
        o = self._grouped_attention(q, kc, vc, causal=True, qpos0=pos)
        if mask is not None:
            o = o * mask[:, :, None, None].astype(o.dtype)
        y = self._out(params, o, B, T)
        ovf = overflow[:, None, None] if per_slot else overflow
        y = jnp.where(ovf, jnp.asarray(jnp.nan, y.dtype), y)
        # freeze the state on overflow (ADVICE r3): pos sticks at the
        # L_cap+1 sentinel so every LATER step also sees overflow and keeps
        # poisoning its output — the clamp-corrupted cache can never be
        # silently extended or wrapped back into a valid-looking range.
        # Recovery is rnn_clear_previous_state(), as documented above.
        next_pos = jnp.where(overflow, jnp.asarray(L_cap + 1, jnp.int32),
                             pos + T)
        return y, {"k": kc, "v": vc, "pos": next_pos}

    # -- what the serving engine asks of a stateful layer ----------------------
    def takes_chunk(self) -> bool:
        """The cached step is multi-token: offset writes and an in-chunk
        causal mask."""
        return True

    def keeps_pages(self) -> bool:
        """One cached row a position (or what a subclass keeps of them)."""
        return True

    # -- and of one that keeps pages
    def blocks_needed(self, depth: int, block: int) -> int:
        """Pool blocks a request holds once ``depth`` positions are cached:
        one row a position, for as long as the request lives."""
        return -(-depth // block)

    def page_recycling(self):
        """None: every page a request is given it keeps to its end, so its
        prompt's pages may be shared through the prefix trie. A layer that
        hands pages back while the request lives (`EvaAttentionLayerImpl`)
        returns its geometry here, and the trie stands aside."""
        return None

    def paged_leaves(self, block, dtype, cache_dtype=None):
        """The pool-wide page arrays this layer keeps, ``{leaf: (page shape,
        dtype)}`` with a page what ``block`` positions hold: the pool prices
        a block from it and the engine allocates ``[pages] + page`` a leaf
        (`inference/kvpool.py`; every name is one of its ``PAGE_KEYS``).
        Here a key row and a value row of the compact K/V heads a position;
        with ``cache_dtype="int8"`` int8 values beside one float32
        dequantization scale per (position, head)."""
        page = (int(block), self._kv_heads(), self._head_dim())
        if cache_dtype == "int8":
            return {"k_pages": (page, jnp.int8), "v_pages": (page, jnp.int8),
                    "k_scales": (page[:2], jnp.float32),
                    "v_scales": (page[:2], jnp.float32)}
        return {"k_pages": (page, jnp.dtype(dtype)),
                "v_pages": (page, jnp.dtype(dtype))}

    def fused_read_engages(self, mode, T, dtype, mesh=None, *, slots=0,
                           pages=0, block=1) -> bool:
        """Whether a paged step of ``T`` tokens reads its pages through
        `ops.paged_read.paged_read_attention` and not through the gather at
        the bucket's width: one query row a slot, bfloat16 or float32, no
        ``tp`` mesh, ``paged_kernel`` not ``"off"``, page lists (``slots``
        of them, ``pages`` entries each, pages of ``block`` rows) that fit
        the kernel's SMEM, and a TPU to compile the kernel for (``"on"``
        takes it anywhere, interpreted off the TPU: the tests' way in).
        Asked by `_paged_step` when it is traced and by the engine for its
        `*_pages_read_total`."""
        if not (mode != "off" and T == 1 and mesh is None
                and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
                and (mode == "on" or jax.default_backend() == "tpu")):
            return False
        # imported where it is used: Pallas costs a second to import
        from ...ops.paged_read import list_fits
        return list_fits(slots, pages, block * self._position_values()
                         * jnp.dtype(dtype).itemsize)

    def _position_values(self) -> int:
        """Values a position keeps in one page leaf: what the kernel's DMA
        of a page brings in, over ``block``."""
        return self._kv_heads() * self._head_dim()

    @staticmethod
    def _page_of(table, p, Bk, wmask=None):
        """(page, offset) of the rows ``p`` ([B, T] row indices; positions
        here) through ``table`` ([B, nb]: logical block -> page) at ``Bk``
        rows a page. Lanes ``wmask`` holds off, and rows beyond the table,
        go to the scratch page."""
        nb = table.shape[1]
        blk = jnp.take_along_axis(table, jnp.minimum(p // Bk, nb - 1),
                                  axis=1)
        if wmask is not None:
            blk = jnp.where(wmask, blk, 0)
        return jnp.where(p // Bk < nb, blk, 0), p % Bk

    @staticmethod
    def _rows_held(pos, nb, Bk, wmask=None):
        """[B, nb]: the rows each page of a block table holds for the query
        at ``pos`` (positions up to it; nothing for a lane ``wmask`` holds
        off): the counts `ops.paged_read` reads a table by."""
        rows = jnp.clip(pos[:, None] + 1
                        - jnp.arange(nb, dtype=pos.dtype)[None, :] * Bk,
                        0, Bk)
        if wmask is not None:
            rows = jnp.where(wmask[:, :1], rows, 0)
        return rows

    def _paged_step(self, params, x, state0, *, mask=None):
        """Paged-KV inference step (inference/kvpool.py, the ISSUE 6
        layout): K/V rows live in pool-wide page arrays
        (``k_pages``/``v_pages``: [pages, block, Hkv, Dh], page 0 the
        scratch row) instead of a per-slot contiguous stripe, and each
        batch row reaches its rows through an int32 block ``table``
        ([B, nb]: logical block index -> page). The write at absolute
        position p lands in ``pages[table[b, p//block], p % block]``;
        the read gathers the row's whole table back into logical order —
        positions [0, nb*block) — and runs the SAME grouped attention as
        the contiguous step (identical math, so paged decode is
        token-identical to contiguous decode).

        ``wmask`` ([B, T] bool, optional): rows whose write must NOT
        land (decode-masked idle/mid-prefill slots, padded prefill-chunk
        lanes) are redirected to the scratch page — without this, a
        frozen slot's garbage write would corrupt a possibly SHARED
        block at its own frontier. The scheduler guarantees every block
        a *real* write touches is allocated and exclusively owned
        (copy-on-write happens host-side, before dispatch).

        ``table``/``wmask`` are injected per call by the engine and not
        returned (the table is host-authoritative; device state carries
        only pages + pos).

        The T=1 READ on a TPU is the fused paged read
        (`fused_read_engages`, `ops.paged_read.paged_read_attention`):
        the table is the page list and ``rows = clip(pos + 1 - j * block,
        0, block)`` the count of each page (0 for a lane ``wmask`` holds
        off), so a step reads the pages its fed slots hold rows in and no
        others, whatever the bucket's width. The engine threads its
        ``paged_kernel`` mode ("auto"/"on"/"off") and tp ``mesh`` in as
        injected trace-time constants next to the table. Where the rule
        does not hold at T=1 (int8 pages, a mesh) the older page-walk
        kernel behind the ``paged_decode_attention`` seam (ops/helpers.py,
        ops/pallas_kernels.py, ISSUE 15; float32 only) may still take the
        read. The gather/einsum body below STAYS the token-identity
        reference and the fallback: prefill chunks (T > 1), ``"off"``,
        ``"auto"`` off the TPU, a page list beyond the kernel's SMEM and
        a seam that declines all run it. K/V WRITES (wmask scratch
        redirect, int8 quantize) always run here in XLA; a kernel fuses
        only the read."""
        B, T, _ = x.shape
        pos = state0["pos"]          # [B] int32 (per-slot decode depths)
        table = state0["table"]      # [B, nb] int32, padded with page 0
        kp, vp = state0["k_pages"], state0["v_pages"]
        Bk = kp.shape[1]
        nb = table.shape[1]
        L = nb * Bk
        wmask = state0.get("wmask")
        # int8 KV pages (engine kv_dtype="int8"): values quantize on
        # write against a per-(position, head) max-abs scale stored in
        # parallel scale pages, and dequantize on the table gather —
        # under half the pool bytes per block, same step contract
        ks, vs = state0.get("k_scales"), state0.get("v_scales")
        quantized = ks is not None
        overflow = (pos + T) > L
        q, k_new, v_new = self._qkv(params, x, pos0=pos)
        p = pos[:, None] + jnp.arange(T, dtype=pos.dtype)[None, :]  # [B, T]
        blk, off = self._page_of(table, p, Bk, wmask)
        if wmask is not None:
            # ZERO the masked lanes' values: a masked row deeper than this
            # step's table bucket is output-poisoned (overflow NaN), and
            # the next layer's K/V projection of that NaN would land in
            # the scratch page — where `softmax_prob(0) * NaN = NaN`
            # leaks through every later reader's attention einsum even
            # on causally-masked lanes. Pages must only ever hold
            # finite rows.
            keep = wmask[..., None, None]
            k_new = jnp.where(keep, k_new, 0)
            v_new = jnp.where(keep, v_new, 0)
        ks2 = vs2 = None
        if quantized:
            kq, ksc = quantize_kv_rows(k_new)   # ops/kvquant.py — the
            vq, vsc = quantize_kv_rows(v_new)   # shared int8 contract
            kp2 = kp.at[blk, off].set(kq)
            vp2 = vp.at[blk, off].set(vq)
            ks2 = ks.at[blk, off].set(ksc)
            vs2 = vs.at[blk, off].set(vsc)
        else:
            kp2 = kp.at[blk, off].set(k_new)
            vp2 = vp.at[blk, off].set(v_new)
        o = None
        mode = state0.get("paged_kernel", "auto")
        if not quantized and self.fused_read_engages(
                mode, T, q.dtype, state0.get("mesh"), slots=B, pages=nb,
                block=Bk):
            from ...ops.paged_read import paged_read_attention
            with jax.named_scope("paged_attention"):
                o = paged_read_attention(
                    q, kp2, vp2, table, self._rows_held(pos, nb, Bk, wmask),
                    interpret=jax.default_backend() != "tpu")
        elif T == 1:
            # the older page-walk kernel behind the seam, or None = run
            # the XLA reference below (trace-time decision)
            o = ophelpers.paged_decode_attention(
                q, kp2, vp2, table, pos, k_scales=ks2, v_scales=vs2,
                mode=mode, mesh=state0.get("mesh"))
        if o is None:
            dt = q.dtype
            if quantized:
                kc = dequantize_kv_rows(kp2[table], ks2[table],
                                        dt).reshape(
                    B, L, kp.shape[2], kp.shape[3])
                vc = dequantize_kv_rows(vp2[table], vs2[table],
                                        dt).reshape(
                    B, L, vp.shape[2], vp.shape[3])
            else:
                kc = kp2[table].reshape(B, L, kp.shape[2], kp.shape[3])
                vc = vp2[table].reshape(B, L, vp.shape[2], vp.shape[3])
            o = self._grouped_attention(q, kc, vc, causal=True, qpos0=pos)
        if mask is not None:
            o = o * mask[:, :, None, None].astype(o.dtype)
        y = self._out(params, o, B, T)
        y = jnp.where(overflow[:, None, None],
                      jnp.asarray(jnp.nan, y.dtype), y)
        # the overflow sentinel must out-range EVERY table bucket the
        # scheduler may present later (bucket widths vary per step), so
        # it is an absolute huge position, not this bucket's cap+1
        next_pos = jnp.where(overflow, jnp.asarray(1 << 30, jnp.int32),
                             pos + T)
        out_state = {"k_pages": kp2, "v_pages": vp2, "pos": next_pos}
        if quantized:
            out_state["k_scales"] = ks2
            out_state["v_scales"] = vs2
        return y, out_state


@register_impl("EvaAttentionLayer")
class EvaAttentionLayerImpl(SelfAttentionLayerImpl):
    """EVA chunked linearized attention (Zheng et al. 2023): the cache is
    NOT one row per position. With ``W(t) = t // window`` and chunk ``c``
    the positions ``[c*chunk, (c+1)*chunk)``, the query at ``t`` attends,
    in ONE softmax, over

      - the exact rotated keys/values of its own window, ``window*W(t) <=
        n <= t``;
      - one summary key and one summary value per chunk of every EARLIER
        window, ``c < (window/chunk) * W(t)``: softmax-pooled over the
        chunk's rows by two learned vectors per K/V head (`_summarize`).

    No biases. Projections, RoPE and the grouped softmax are the parent's.

    Serving (`_paged_step`): summary rows have the shape of K/V rows and
    live in the same ``k_pages``/``v_pages`` under a table of their own
    (``summary_table``: [B, blocks/chunk] page ids, carried in the state
    and written by the engine when it claims a summary page). A page holds
    ``block`` chunk summaries, i.e. ``chunk`` exact blocks' worth of
    positions, so a closed window keeps ``window/(block*chunk)`` pages where
    it held ``window/block``: the engine hands the exact pages back at the
    window's roll (`blocks_needed`, `page_recycling`). The program's shape
    is still the logical table's bucket ``nb``: the exact view is the
    ``window/block`` blocks from block ``(window/block)*W`` and the summary
    view the first ``ceil(nb/chunk)`` summary pages, both static in ``nb``.
    A step never branches on a window or chunk boundary: the chunks its rows
    fall in are summarised again from the pages after the write (at T=1 the
    current chunk's <= ``chunk`` rows) and overwritten. The read is over one
    page list, ``[exact view | summary view]``, with the count of rows each
    page holds for the query: at T=1 on a TPU a fused walk over the pages
    whose count is above 0 (`fused_read_engages`,
    `ops.paged_read.paged_read_attention`), else the list gathered whole at
    the bucket's width with the counts as a mask. T > 1 must be a
    multiple of ``chunk`` starting on a chunk boundary and inside one
    window: the engine's chunk rule (`DecodeScheduler._pick_chunk`)."""

    def init_params(self, key, dtype=jnp.float32):
        p = super().init_params(key, dtype)
        del p["b"]
        Dh = self._head_dim()
        for i, name in enumerate(("mu", "phi")):
            p[name] = (jax.random.normal(jax.random.fold_in(key, i),
                                         (self._kv_heads(), Dh), jnp.float32)
                       * Dh ** -0.5).astype(dtype)
        return p

    def _out(self, params, o, B, T):
        return self.activation_fn()(jnp.einsum(
            "btm,mn->btn", o.reshape(B, T, self.conf.n_out), params["Wo"]))

    def _geometry(self):
        Wn, C = int(self.conf.window_size), int(self.conf.chunk_size)
        if C < 1 or Wn % C:
            raise ValueError(f"window_size={Wn} must be a multiple of "
                             f"chunk_size={C}")
        return Wn, C

    def page_recycling(self):
        return self._geometry()

    def blocks_needed(self, depth: int, block: int) -> int:
        """Exact blocks of the open window, plus a summary page per
        ``block`` chunks begun (the open window's included)."""
        if depth <= 0:
            return 0
        Wn, C = self._geometry()
        w0 = (depth - 1) // Wn * Wn
        return -(-(depth - w0) // block) + -(-depth // (C * block))

    def _summarize(self, params, k, v, ok):
        """Chunk summaries. k, v: [B, chunks, chunk, Hkv, Dh] rotated rows;
        ok: [B, chunks, chunk] rows that exist. Per head, with s = 1/sqrt(Dh):
        k~ = sum_n softmax_n(s mu.k_n) k_n, v~ = sum_n softmax_n(s phi.k_n)
        v_n -> [B, chunks, Hkv, Dh] each. (EVA's control variate with one
        deterministic proposal per head; the two logits as recalled from
        the EvaByte release, `benchmark/configs/evabyte-d16.json`.)"""
        s = 1.0 / jnp.sqrt(jnp.float32(k.shape[-1]))
        off = jnp.where(ok, 0.0, jnp.finfo(jnp.float32).min)[..., None]

        def pool(w, rows):
            lg = jnp.einsum("bcnhd,hd->bcnh", k, params[w])
            pr = jax.nn.softmax(lg.astype(jnp.float32) * s + off, axis=2)
            return jnp.einsum("bcnh,bcnhd->bchd", pr.astype(k.dtype), rows)

        with jax.named_scope("eva_summarize"):
            return pool("mu", k), pool("phi", v)

    def forward(self, params, x, *, train=False, rng=None, variables=None,
                mask=None):
        """The full-sequence function (training, scoring, the tests'
        comparison with the plain reference)."""
        x = self._dropout(x, train, rng)
        B, T, _ = x.shape
        Wn, C = self._geometry()
        q, k, v = self._qkv(params, x)
        nC = -(-T // C)

        def chunked(a):
            a = jnp.pad(a, ((0, 0), (0, nC * C - T), (0, 0), (0, 0)))
            return a.reshape((B, nC, C) + a.shape[2:])

        ok = (jnp.arange(nC * C) < T).reshape(1, nC, C)
        ks, vs = self._summarize(params, chunked(k), chunked(v),
                                 jnp.broadcast_to(ok, (B, nC, C)))
        t = jnp.arange(T)
        w0 = t // Wn * Wn
        exact = (t[None, :] <= t[:, None]) & (t[None, :] >= w0[:, None])
        summary = jnp.arange(nC)[None, :] < (w0 // C)[:, None]
        with jax.named_scope("eva_attention"):
            o = self._grouped_attention(
                q, jnp.concatenate([k, ks], 1), jnp.concatenate([v, vs], 1),
                causal=True,
                valid=jnp.concatenate([exact, summary], 1)[None])
        if mask is not None:
            o = o * mask[:, :, None, None].astype(o.dtype)
        return self._out(params, o, B, T), variables or {}

    def forward_with_state(self, params, x, state0, *, train=False, rng=None,
                           mask=None):
        if not train and state0 is not None and "k_pages" not in state0:
            raise NotImplementedError(
                "EvaAttentionLayer streams through the paged pool only "
                "(DecodeScheduler(kv_pool_mb=...)): a contiguous stripe "
                "has no rows for the chunk summaries")
        return super().forward_with_state(params, x, state0, train=train,
                                          rng=rng, mask=mask)

    def _paged_step(self, params, x, state0, *, mask=None):
        """See the class docstring. ``table`` [B, nb] maps the logical
        blocks of the OPEN window to pages (closed windows' entries are
        scratch); ``wmask`` as in the parent."""
        Wn, C = self._geometry()
        B, T, _ = x.shape
        pos, table = state0["pos"], state0["table"]
        kp, vp = state0["k_pages"], state0["v_pages"]
        Bk, nb = kp.shape[1], table.shape[1]
        if Wn % Bk or Bk % C or (T > 1 and T % C):
            raise ValueError(
                f"EVA paging needs chunk_size={C} | kv_block={Bk} | "
                f"window_size={Wn}, and chunks of a multiple of {C} "
                f"tokens (got {T})")
        ns = -(-nb // C)
        stable = state0["summary_table"]
        wmask = state0.get("wmask")
        if wmask is None:
            wmask = jnp.ones((B, T), bool)
        overflow = (pos + T) > nb * Bk
        q, k_new, v_new = self._qkv(params, x, pos0=pos)
        i32 = lambda n: jnp.arange(n, dtype=pos.dtype)
        p = pos[:, None] + i32(T)[None, :]                        # [B, T]
        blk, off = self._page_of(table, p, Bk, wmask)
        keep = wmask[..., None, None]  # pages hold finite rows only
        kp2 = kp.at[blk, off].set(jnp.where(keep, k_new, 0))
        vp2 = vp.at[blk, off].set(jnp.where(keep, v_new, 0))
        # the chunks these rows fall in, summarised from the pages
        nC = max(1, T // C)
        last = pos + jnp.sum(wmask, axis=1, dtype=pos.dtype) - 1  # [B]
        chunk = pos[:, None] // C + i32(nC)[None, :]              # [B, nC]
        rows = chunk[:, :, None] * C + i32(C)[None, None, :]      # [B,nC,C]
        rblk, roff = self._page_of(table, rows.reshape(B, nC * C), Bk)
        tail = kp.shape[2:]
        ks, vs = self._summarize(
            params, kp2[rblk, roff].reshape((B, nC, C) + tail),
            vp2[rblk, roff].reshape((B, nC, C) + tail),
            rows <= last[:, None, None])
        # a chunk is written where this step gave it a real row: never by a
        # masked slot, whose table may name pages that are another's by now
        begun = (chunk * C <= last[:, None]) & (last >= pos)[:, None]
        sblk, soff = self._page_of(stable[:, :ns], chunk, Bk, begun)
        kp2 = kp2.at[sblk, soff].set(jnp.where(begun[..., None, None], ks, 0))
        vp2 = vp2.at[sblk, soff].set(jnp.where(begun[..., None, None], vs, 0))
        # one page list: the open window's blocks, then the summary pages
        E = min(Wn // Bk, nb)
        W = pos // Wn                                             # [B]
        epages = jnp.take_along_axis(
            table, jnp.minimum(W[:, None] * (Wn // Bk) + i32(E)[None, :],
                               nb - 1), axis=1)
        pages = jnp.concatenate([epages, stable[:, :ns]], axis=1)
        if self.fused_read_engages(state0.get("paged_kernel", "auto"), T,
                                   q.dtype, state0.get("mesh"), slots=B,
                                   pages=E + ns, block=Bk):
            # rows each page holds for this query: the open window up to
            # pos, a summary row per closed chunk, nothing for a lane off
            erows = pos[:, None] + 1 - (W[:, None] * Wn + i32(E)[None, :] * Bk)
            srows = (W * (Wn // C))[:, None] - i32(ns)[None, :] * Bk
            rows = jnp.where(wmask[:, :1], jnp.clip(
                jnp.concatenate([erows, srows], axis=1), 0, Bk), 0)
            from ...ops.paged_read import paged_read_attention
            with jax.named_scope("eva_attention"):
                o = paged_read_attention(
                    q, kp2, vp2, pages, rows,
                    interpret=jax.default_backend() != "tpu")
        else:
            L = (E + ns) * Bk
            kc = kp2[pages].reshape((B, L) + tail)
            vc = vp2[pages].reshape((B, L) + tail)
            exact = (W[:, None] * Wn + i32(E * Bk)[None, :])[:, None, :] \
                <= p[:, :, None]                                  # [B,T,E*Bk]
            summary = i32(ns * Bk)[None, :] < (W * (Wn // C))[:, None]
            valid = jnp.concatenate(
                [exact,
                 jnp.broadcast_to(summary[:, None, :], (B, T, ns * Bk))],
                axis=-1)
            with jax.named_scope("eva_attention"):
                o = self._grouped_attention(q, kc, vc, causal=True,
                                            valid=valid)
        if mask is not None:
            o = o * mask[:, :, None, None].astype(o.dtype)
        y = self._out(params, o, B, T)
        y = jnp.where(overflow[:, None, None],
                      jnp.asarray(jnp.nan, y.dtype), y)
        next_pos = jnp.where(overflow, jnp.asarray(1 << 30, jnp.int32),
                             pos + T)
        return y, {"k_pages": kp2, "v_pages": vp2, "pos": next_pos,
                   "summary_table": stable}


@register_impl("LatentAttentionLayer")
class LatentAttentionLayerImpl(SelfAttentionLayerImpl):
    """Multi-head latent attention (MLA). With ``x`` a row of the input,
    ``C = kv_lora_rank``, ``dn``/``dr``/``dv`` the nope, rope and value
    widths of a head and every norm an RMSNorm with a gain:

        c_q = norm_q(x Wdq);   q = c_q Wuq -> heads of [q_n (dn) | q_r (dr)]
        [c_kv | k_r] = x Wdkv; c = norm_kv(c_kv)
        q_r, k_r <- RoPE (k_r: one rotated key shared by all heads)

    The cached row of a position is ``[c | k_r]``, ``C + dr`` wide. Two
    forms of the same function of it, with ``Wukv`` split per head into
    ``Wuk`` (C x dn) and ``Wuv`` (C x dv):

      expanded  k_h = [c Wuk_h | k_r], v_h = c Wuv_h; softmax(s q.k) v
                per head (`_expanded`): the full forward (training,
                scoring), where every row is new and its keys and values
                are built once;
      absorbed  q~_h = q_n,h Wuk_h^T (C wide), score s (q~_h.c + q_r.k_r),
                o_h = (sum p c) Wuv_h (`_absorbed`): every paged step, a
                decode row or a prefill chunk, which never rebuilds a key
                or a value. By operations a chunk of 171 queries or more
                would be cheaper expanded (rebuilding costs `2 C H (dn +
                dv)` a cached row once, a query saves `2 H (2 C - dn - dv)`
                on it); on a v5e it is not, at any measured chunk and depth
                (512 queries over 8,192 rows: 8.0 ms absorbed, 13.4 ms
                expanded; PERF.md section 6, PR 34): the rebuilt keys and
                values of a whole table bucket are written and read again.

    ``s = (dn + dr)^-1/2 m^2`` with YaRN's ``m = 0.1 mscale_all_dim ln(factor)
    + 1``; scores and softmax are float32. No biases.

    Serving (`_paged_step`) is through the paged pool alone, ONE page leaf
    ``c_pages`` (`paged_leaves`: [pages, block / k, k (C + dr)], the rows of
    k positions side by side so that a page's last dimension is a multiple
    of 128 lanes, `_rows_packed`: k = 2 at C + dr = 576), one row a
    position kept to the request's end (`page_recycling` is None:
    the prefix trie shares latent pages as it shares K/V pages). The T = 1
    read on a TPU is the fused paged read in its one-buffer form
    (`fused_read_engages`, `ops.paged_read.paged_read_attention` with no
    value pages: a page is brought in once, as that ``[block / k, k (C +
    dr)]`` matrix, and is key to the absorbed query and value in one; the
    softmax's scale is this layer's ``s``; ``Wuv`` and ``Wo`` stay in XLA).
    A prefill chunk, ``"off"`` and ``"auto"`` off the TPU read through XLA's
    gather of the slot's table at the bucket's width, the body the tests
    compare the kernel with."""

    WEIGHT_KEYS = ("Wdq", "Wuq", "Wdkv", "Wukv", "Wo")
    # queries of a long chunk attend this many at a time, so that the
    # float32 scores of 64 heads over a deep table stay a few hundred MB
    _QBLOCK = 128
    # slots whose tables the gather body of a paged step gathers at a time
    _SLOTS = 16

    def _dims(self):
        c = self.conf
        return (c.n_heads, int(c.qk_nope_head_dim), int(c.qk_rope_head_dim),
                int(c.v_head_dim), int(c.kv_lora_rank))

    def init_params(self, key, dtype=jnp.float32):
        conf = self.conf
        H, dn, dr, dv, C = self._dims()
        dist = conf.dist.spec() if getattr(conf, "dist", None) is not None \
            else None
        mk = lambda k, i, o: winit.init_weights(
            k, (i, o), conf.weight_init or "xavier", dist, dtype)
        ks = jax.random.split(key, 5)
        Q = int(conf.q_lora_rank)
        return {"Wdq": mk(ks[0], conf.n_in, Q),
                "q_gain": jnp.ones((Q,), dtype),
                "Wuq": mk(ks[1], Q, H * (dn + dr)),
                "Wdkv": mk(ks[2], conf.n_in, C + dr),
                "kv_gain": jnp.ones((C,), dtype),
                "Wukv": mk(ks[3], C, H * (dn + dv)),
                "Wo": mk(ks[4], H * dv, conf.n_out)}

    def init_state(self, batch: int, dtype=jnp.float32):
        _, _, dr, _, C = self._dims()
        L = int(getattr(self.conf, "max_cache_len", 1024))
        return {"c": jnp.zeros((batch, L, C + dr), dtype),
                "pos": jnp.zeros((), jnp.int32)}

    def _rows_packed(self, block: int) -> int:
        """Positions that share one row of a page: the fewest that make the
        row a multiple of 128 values (2 for a 576-wide latent row), where
        ``block`` holds a whole number of such rows, else 1. A page whose
        last dimension is no multiple of the TPU's 128 lanes is padded, and
        the compiler then hands the pool back in a layout of its own and
        copies every layer's whole pool twice a program (2.9 ms a copy at
        736 MB; PERF.md section 6, PR 34)."""
        _, _, dr, _, C = self._dims()
        k = 128 // math.gcd(C + dr, 128)
        return k if block % k == 0 else 1

    def paged_leaves(self, block, dtype, cache_dtype=None):
        if cache_dtype is not None:
            raise ValueError("LatentAttentionLayer keeps its latent rows in "
                             f"the compute dtype: {cache_dtype} pages are "
                             "not served yet")
        _, _, dr, _, C = self._dims()
        k = self._rows_packed(int(block))
        return {"c_pages": ((int(block) // k, k * (C + dr)),
                            jnp.dtype(dtype))}

    def _position_values(self) -> int:
        _, _, dr, _, C = self._dims()
        return C + dr

    # -- the pieces of the written equations ----------------------------------
    def _inv_freq(self):
        """Inverse frequencies of the ``dr / 2`` rotated pairs: plain RoPE,
        or YaRN's blend of it with the same divided by ``factor``, by a
        linear ramp between the correction dims of ``beta_fast`` and
        ``beta_slow`` over ``yarn_original_max`` positions."""
        conf = self.conf
        dr = int(conf.qk_rope_head_dim)
        base = float(conf.rope_base)
        freq = base ** (-jnp.arange(dr // 2, dtype=jnp.float32) / (dr // 2))
        factor = float(conf.yarn_factor)
        if factor <= 1.0:
            return freq

        def correction_dim(rotations):
            return dr * math.log(conf.yarn_original_max
                                 / (rotations * 2 * math.pi)) \
                / (2 * math.log(base))

        low = max(math.floor(correction_dim(conf.yarn_beta_fast)), 0)
        high = min(math.ceil(correction_dim(conf.yarn_beta_slow)), dr - 1)
        ramp = jnp.clip((jnp.arange(dr // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        return freq / factor * ramp + freq * (1.0 - ramp)

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1.0 else 1.0

    def _scale(self) -> float:
        """The softmax scale: ``(dn + dr)^-1/2 m^2``."""
        conf = self.conf
        _, dn, dr, _, _ = self._dims()
        m = self._mscale(float(conf.yarn_factor),
                         float(conf.yarn_mscale_all_dim))
        return (dn + dr) ** -0.5 * m * m

    def _rope(self, a, pos0):
        """Rotate-half RoPE on [B, T, H, dr] at the layer's frequencies,
        cos/sin scaled by ``mscale(factor, mscale) / mscale(factor,
        mscale_all_dim)``; ``pos0`` a scalar or [B]."""
        conf = self.conf
        B, T, H, D = a.shape
        half = D // 2
        f = float(conf.yarn_factor)
        amp = self._mscale(f, float(conf.yarn_mscale)) \
            / self._mscale(f, float(conf.yarn_mscale_all_dim))
        pos = jnp.asarray(pos0, jnp.float32).reshape(-1, 1)        # [B|1, 1]
        ang = (pos + jnp.arange(T, dtype=jnp.float32)[None, :])[..., None] \
            * self._inv_freq()                                  # [B|1, T, half]
        cos = (jnp.cos(ang) * amp)[:, :, None, :].astype(a.dtype)
        sin = (jnp.sin(ang) * amp)[:, :, None, :].astype(a.dtype)
        a1, a2 = a[..., :half], a[..., half:]
        return jnp.concatenate([a1 * cos - a2 * sin, a1 * sin + a2 * cos],
                               axis=-1)

    def _rms(self, x, gain):
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.conf.eps).astype(x.dtype) * gain

    def _project(self, params, x, pos0=0):
        """x [B, T, n_in] at positions from ``pos0`` -> q_n [B, T, H, dn],
        q_r [B, T, H, dr] (rotated) and the rows to cache [B, T, C + dr]."""
        H, dn, dr, _, C = self._dims()
        B, T, _ = x.shape
        cq = self._rms(jnp.einsum("btf,fo->bto", x, params["Wdq"]),
                       params["q_gain"])
        q = jnp.einsum("btq,qo->bto", cq, params["Wuq"]).reshape(
            B, T, H, dn + dr)
        ckv = jnp.einsum("btf,fo->bto", x, params["Wdkv"])
        c = self._rms(ckv[..., :C], params["kv_gain"])
        kr = self._rope(ckv[..., None, C:], pos0)[:, :, 0]
        return (q[..., :dn], self._rope(q[..., dn:], pos0),
                jnp.concatenate([c, kr], axis=-1))

    def _softmax(self, s, valid):
        s = jnp.where(valid[:, None], s * self._scale(),
                      jnp.finfo(jnp.float32).min)
        return jax.nn.softmax(s, axis=-1)

    def _expanded(self, params, q_n, q_r, rows, valid):
        """Keys and values rebuilt from ``rows`` [B, L, C + dr], once; the
        queries attend ``_QBLOCK`` at a time. ``valid`` [B, T, L]."""
        H, dn, dr, dv, C = self._dims()
        B, L, _ = rows.shape
        kv = jnp.einsum("blc,co->blo", rows[..., :C], params["Wukv"]
                        ).reshape(B, L, H, dn + dv)
        k_n, v, k_r = kv[..., :dn], kv[..., dn:], rows[..., C:]
        f32 = jnp.float32

        def attend(q_n, q_r, valid):
            s = jnp.einsum("bthd,blhd->bhtl", q_n, k_n,
                           preferred_element_type=f32) \
                + jnp.einsum("bthr,blr->bhtl", q_r, k_r,
                             preferred_element_type=f32)
            p = self._softmax(s, valid).astype(v.dtype)
            return jnp.einsum("bhtl,blhd->bthd", p, v)

        return self._by_query_blocks(attend, q_n, q_r, valid)

    def _absorbed(self, params, q_n, q_r, rows, valid):
        """``Wuk`` folded into the query, ``Wuv`` applied after the weighted
        sum of latents: nothing is rebuilt a row."""

        def attend(q, valid):
            s = jnp.einsum("bthr,blr->bhtl", q, rows,
                           preferred_element_type=jnp.float32)
            p = self._softmax(s, valid).astype(rows.dtype)
            # over the whole row, the rotated key's 64 columns dropped
            # after: a slice of the rows first is a copy of the gather
            return jnp.einsum("bhtl,blr->bthr", p, rows)

        return self._absorb(params, q_n, q_r, lambda q: self._by_query_blocks(
            attend, q, valid))

    def _absorb(self, params, q_n, q_r, read):
        """The absorbed form around its read: ``read`` takes the query
        ``[q_n Wuk^T | q_r]`` ([B, T, H, C + dr], the key of a cached row)
        and gives ``sum p row`` over the rows as wide; the first C columns
        of that go through ``Wuv``."""
        H, dn, dr, dv, C = self._dims()
        w = params["Wukv"].reshape(C, H, dn + dv)
        q = jnp.concatenate(
            [jnp.einsum("bthd,chd->bthc", q_n, w[..., :dn]), q_r], axis=-1)
        return jnp.einsum("bthc,chd->bthd", read(q)[..., :C], w[..., dn:])

    def _by_query_blocks(self, attend, *args):
        """``attend`` over the T axis (axis 1 of every argument) in blocks
        of ``_QBLOCK`` queries where T is a multiple of it and longer."""
        T, qb = args[0].shape[1], self._QBLOCK
        if T <= qb or T % qb:
            return attend(*args)
        split = lambda a: jnp.moveaxis(
            a.reshape((a.shape[0], T // qb, qb) + a.shape[2:]), 1, 0)
        out = jax.lax.map(lambda blk: attend(*blk), tuple(map(split, args)))
        out = jnp.moveaxis(out, 0, 1)
        return out.reshape((out.shape[0], T) + out.shape[3:])

    def _out(self, params, o, B, T):
        return self.activation_fn()(jnp.einsum(
            "btm,mn->btn", o.reshape(B, T, -1), params["Wo"]))

    def forward(self, params, x, *, train=False, rng=None, variables=None,
                mask=None):
        """The full-sequence function, expanded (training, scoring, the
        tests' comparison with the plain reference)."""
        x = self._dropout(x, train, rng)
        B, T, _ = x.shape
        q_n, q_r, rows = self._project(params, x)
        t = jnp.arange(T)
        with jax.named_scope("latent_attention"):
            o = self._expanded(params, q_n, q_r, rows,
                               (t[None, :] <= t[:, None])[None])
        if mask is not None:
            o = o * mask[:, :, None, None].astype(o.dtype)
        return self._out(params, o, B, T), variables or {}

    def forward_with_state(self, params, x, state0, *, train=False, rng=None,
                           mask=None):
        if not train and state0 is not None and "c_pages" not in state0:
            raise NotImplementedError(
                "LatentAttentionLayer streams through the paged pool only "
                "(DecodeScheduler(kv_pool_mb=...)): rnn_time_step over a "
                "contiguous stripe is not served yet")
        if train or state0 is None:
            y, _ = self.forward(params, x, train=train, rng=rng, mask=mask)
            return y, state0
        return self._paged_step(params, x, state0, mask=mask)

    @classmethod
    def _write_rows(cls, cp, table, pos, rows, wmask, Bk):
        """``cp`` with the rows [B, T, R] of positions ``pos`` on laid into
        the pages ``table`` names, one whole page row at a time: each page
        row the step touches (``J`` of them: T // 2 + 1 at k = 2, T at
        k = 1, 1 at T = 1) is gathered, the halves that a live position of
        this step owns are laid over it, and it is written back by one
        scatter whose window is the row's whole last dimension, the class
        of the K/V layers' ``kp.at[blk, off].set``. A window of R lanes at
        ``(off % k) R`` compiled to a ``while`` of one trip a position on a
        v5e (PERF.md section 6, PR 37). A row that no live position in the
        table owns (``wmask`` off, beyond the table, or the extra row) goes
        to the scratch page with the scratch row's own values, so that no
        two entries address one row of a slot's page and the scratch page
        keeps finite rows."""
        B, T, R = rows.shape
        nb, k = table.shape[1], cp.shape[2] // R
        J = (T + 2 * k - 2) // k
        r = pos[:, None] // k + jnp.arange(J, dtype=pos.dtype)      # [B, J]
        t = (r[..., None] * k + jnp.arange(k, dtype=pos.dtype)
             - pos[:, None, None]).reshape(B, J * k)  # step index of a half
        ts = jnp.clip(t, 0, T - 1)
        own = (t >= 0) & (t < T) & (r * k // Bk < nb).repeat(k, 1)
        if wmask is not None:
            own &= jnp.take_along_axis(jnp.broadcast_to(wmask, (B, T)), ts,
                                       1)
        blk, off = cls._page_of(table, r * k, Bk)
        page = jnp.where(own.reshape(B, J, k).any(-1), blk, 0)
        row = off // k
        new = jnp.take_along_axis(rows, ts[..., None], 1)
        old = cp[page, row].reshape(B, J * k, R)
        return cp.at[page, row].set(
            jnp.where(own[..., None], new, old).reshape(B, J, k * R))

    def _paged_step(self, params, x, state0, *, mask=None):
        """``table`` [B, nb] and ``wmask`` [B, T] as in the parent: a row
        ``wmask`` holds off is never written to a slot's page. The rows go
        into the pool a whole page row at a time (`_write_rows`). The step
        attends absorbed, whatever T, and at T = 1 through the fused paged
        read where the rule engages it (the class docstring)."""
        B, T, _ = x.shape
        _, _, dr, _, C = self._dims()
        R = C + dr
        pos, table = state0["pos"], state0["table"]
        cp = state0["c_pages"]                     # [pages, Bk / k, k * R]
        k = cp.shape[2] // R
        Bk, nb = cp.shape[1] * k, table.shape[1]
        L = nb * Bk
        wmask = state0.get("wmask")
        overflow = (pos + T) > L
        q_n, q_r, rows_new = self._project(params, x, pos0=pos)
        p = pos[:, None] + jnp.arange(T, dtype=pos.dtype)[None, :]   # [B, T]
        cp2 = self._write_rows(cp, table, pos, rows_new, wmask, Bk)
        def read(table, q_n, q_r, p):
            rows = cp2[table].reshape(table.shape[0], L, R)
            valid = jnp.arange(L, dtype=pos.dtype)[None, None, :] \
                <= p[:, :, None]
            return self._absorbed(params, q_n, q_r, rows, valid)

        G = self._SLOTS
        with jax.named_scope("latent_attention"):
            if self.fused_read_engages(state0.get("paged_kernel", "auto"), T,
                                       q_n.dtype, state0.get("mesh"), slots=B,
                                       pages=nb, block=Bk):
                # the pages as the pool lays them out, each brought in
                # once as key and value
                from ...ops.paged_read import paged_read_attention
                o = self._absorb(
                    params, q_n, q_r, lambda q: paged_read_attention(
                        q, cp2, None, table,
                        self._rows_held(pos, nb, Bk, wmask),
                        scale=self._scale(),
                        interpret=jax.default_backend() != "tpu"))
            elif B <= G or B % G:
                o = read(table, q_n, q_r, p)
            else:
                # the gathered rows of `_SLOTS` slots at a time: at 48 slots
                # and a table of 16,384 positions the gather and its two
                # relayouts are 2.8 GB of temporaries at once, a third of
                # that in groups
                o = jax.lax.map(lambda a: read(*a), tuple(
                    a.reshape((B // G, G) + a.shape[1:])
                    for a in (table, q_n, q_r, p)))
                o = o.reshape((B,) + o.shape[2:])
        if mask is not None:
            o = o * mask[:, :, None, None].astype(o.dtype)
        y = self._out(params, o, B, T)
        y = jnp.where(overflow[:, None, None],
                      jnp.asarray(jnp.nan, y.dtype), y)
        next_pos = jnp.where(overflow, jnp.asarray(1 << 30, jnp.int32),
                             pos + T)
        return y, {"c_pages": cp2, "pos": next_pos}
