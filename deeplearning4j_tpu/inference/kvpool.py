"""Block-pooled KV store: paged live-decode backing + radix-trie prefix index.

Real serving traffic is dominated by shared prompt prefixes (system
prompts, few-shot templates, chat history) and wildly mixed prompt
lengths, yet a contiguous decode cache hands every slot a
``max_cache_len`` stripe of K/V — HBM cost ``slots × max_cache_len``
regardless of actual lengths. This module is the block-level KV
management of modern inference engines (vLLM's PagedAttention block
tables, SGLang's RadixAttention prefix tree).

The pool IS the live decode cache (``DecodeScheduler(kv_pool_mb=...)``).
The engine owns one pool-wide page array per layer
(``k_pages``/``v_pages``: ``[capacity+1, block, Hkv, Dh]``, index 0 a
scratch page that absorbs padded writes and is never handed out) and
gives each slot an int32 *block table* mapping logical block index →
page row; the jitted decode/prefill programs read and write K/V through
the table (`nn/layers/attention.py` paged step). :class:`KVPool`
allocates nothing on the device — it is the host-side metadata: the free
list, the trie, and per-node refcounts. Consequences that fall out of
the layout:

  - slot capacity is bounded by total pool bytes, not
    ``slots × max_cache_len`` — dozens of short sequences share the
    pages one long one would have monopolized;
  - prefix restore is a **block-table remap**: cached blocks are
    *referenced*, never gathered (zero K/V copies), with copy-on-write
    on the first write into a shared block;
  - publish at finish is the same move in reverse: the slot's full
    prompt blocks are *adopted* by the trie (ownership transfer, no
    scatter);
  - under pool pressure the scheduler preempts the latest-submitted slot
    (blocks released, sequence requeued) and resumes it later.

The **radix/trie prefix index** has one node per full block of token
ids, children keyed by the block's token tuple, so a prefix lookup walks
the trie in O(prompt/block) dict hops and returns the longest chain of
cached blocks. Only COMPLETE blocks are indexed — a partial tail block
is never shared (its K/V would depend on tokens the next request may not
send). Blocks are refcounted through the trie nodes that own them and
LRU-evicted (unreferenced leaves first) when the free list runs dry.

Soundness: reuse is only valid for **pos-0-anchored prefixes**. Cached
keys are stored pre-rotated at their absolute positions (RoPE commutes
with the cache — nn/layers/attention.py), so a prefix starting at
position 0 is bit-identical across requests and can be referenced
instead of recomputed; a mid-sequence match would need re-rotation and
is not attempted.

Threading: the pool's host-side metadata (trie, free list, refcounts) is
owned by the engine's scheduler thread — every mutation happens between
engine steps on that single thread, the same single-writer discipline
``DecodeScheduler._slots`` uses — so it needs no lock of its own.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from . import failpoints
from .metrics import MetricsRegistry
from .trace import FlightRecorder

# page 0 is the scratch block: padded table lanes point at it and masked
# writes land in it, so bucketed programs never need a mask — real blocks
# are numbered from 1
SCRATCH_BLOCK = 0

# every pool-wide page-array key a paged attention state may carry: K/V
# pages plus (int8 KV mode) their per-row dequantization scales, or a
# latent layer's one leaf of [latent | rotated key] rows. Which of them a
# layer has, and what shape a page of ``block`` positions is, the layer
# says itself (`SelfAttentionLayerImpl.paged_leaves`). The single source
# of truth for "this leaf is SHARED pool storage, not a per-slot row"
# across the engine's slice/scatter/zero/freeze/COW paths.
PAGE_KEYS = ("k_pages", "v_pages", "k_scales", "v_scales", "c_pages")


class _Node:
    """One full block of a cached prefix: ``key`` is the block's token
    tuple (the edge label from the parent), ``block_id`` its storage row.
    ``lock`` counts live sequences pinning this node (admission locks the
    deepest matched node; publish pins its extension path while
    allocating) — locked nodes and interior nodes are never evicted."""

    __slots__ = ("key", "block_id", "parent", "children", "last_access",
                 "lock", "hash")

    def __init__(self, key: Tuple[int, ...], block_id: int,
                 parent: Optional["_Node"]):
        self.key = key
        self.block_id = block_id
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.last_access = 0
        self.lock = 0
        #: content-addressed chain hash (kvtier.chain_hash over the
        #: ancestor chain); computed only when a TierManager is armed —
        #: None otherwise, and "" at the root
        self.hash: Optional[str] = None


class KVPool:
    """Refcounted block pool + trie prefix index over per-layer K/V.

    ``leaves``: what each attention layer keeps in the pool, as the layer
    states it (``{layer: impl.paged_leaves(block, dtype, kv_dtype)}``, i.e.
    ``{layer: {leaf: (page shape, dtype)}}`` with a page what ``block``
    positions hold: their key rows and value rows, int8 values beside their
    scales, or their latent rows). A block costs one page of every leaf of
    every layer. The engine owns the page arrays (they
    live inside its jitted state pytree, where the programs
    scatter/gather them); this object allocates NOTHING on device and is
    pure metadata — free list, trie, refcounts — plus the ``kv_pool_*``
    gauges. The byte budget covers EVERYTHING the engine allocates for
    the pool (scratch block included): ``capacity_blocks`` usable blocks
    cost ``(capacity_blocks + 1) * bytes_per_block <= budget_bytes``.

    ``shard_factor``: tensor-parallel device count when the K/V head
    axis is sharded over a mesh (`inference/sharding.py`). Each device
    then holds only ``Hkv / shard_factor`` heads of every block, so
    ``budget_bytes`` is the PER-DEVICE byte budget and
    ``bytes_per_block`` the per-device cost — at fixed per-device HBM a
    ``tp``-wide mesh holds ``tp×`` the blocks. The block/trie/refcount
    metadata is device-count-agnostic (one logical pool).
    """

    def __init__(self, leaves: Dict, *, block: int, budget_bytes: int,
                 shard_factor: int = 1,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[FlightRecorder] = None):
        if block < 1:
            raise ValueError(f"block must be >= 1, got {block}")
        self.block = int(block)
        self.shard_factor = max(1, int(shard_factor))
        # flight recorder (trace.py): eviction/publish instants on the
        # `kvpool` track; None (standalone pool) records nothing
        self._tracer = tracer
        self.budget_bytes = int(budget_bytes)
        per_block = sum(
            int(math.prod(page)) * int(jnp.dtype(dtype).itemsize)
            for layer in leaves.values() for page, dtype in layer.values())
        # per-DEVICE block cost: the head axis splits evenly over the
        # mesh (the engine refuses to shard otherwise), so a block costs
        # each device 1/shard_factor of its total bytes
        per_block = per_block // self.shard_factor
        self.bytes_per_block = per_block
        total = self.budget_bytes // per_block if per_block else 0
        # one block of the budget is the scratch row
        self.capacity_blocks = max(0, int(total) - 1)
        self._free: List[int] = list(range(1, self.capacity_blocks + 1))
        self._root = _Node((), SCRATCH_BLOCK, None)
        self._root.hash = ""
        #: optional kvtier.TierManager — armed by the engine before any
        #: traffic. When set, every trie node is chain-hashed, inserts
        #: publish to the prefix directory, and LRU evictions offer the
        #: victim's pages for demotion instead of silently freeing them.
        self.tier = None
        self._clock = 0  # logical LRU clock (monotonic per pool op)
        self._metrics = metrics
        self._g_live = self._g_free = self._g_dev_used = None
        if metrics is not None:
            self._m_evicted = metrics.counter(
                "prefix_cache_evicted_blocks_total")
            # pool occupancy: live = every allocated block (slot-owned
            # + trie-cached), free = the free list. The utilization
            # ratio is derived at snapshot time so it can never go stale
            # between scrapes.
            self._g_live = metrics.gauge("kv_pool_blocks_live")
            self._g_free = metrics.gauge("kv_pool_blocks_free")
            cap_g = metrics.gauge("kv_pool_blocks_capacity")
            cap_g.set(self.capacity_blocks)
            metrics.ratio("kv_pool_utilization", self._g_live, cap_g)
            # per-DEVICE pool footprint (scratch included): under a tp
            # mesh each device holds its head slice of every page, so
            # used bytes track utilization per device
            metrics.gauge("kv_pool_device_bytes").set(
                (self.capacity_blocks + 1) * self.bytes_per_block)
            self._g_dev_used = metrics.gauge("kv_pool_device_used_bytes")
            self._sync_gauges()

    # -- host-side bookkeeping ---------------------------------------------
    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _hash_and_publish(self, node: _Node) -> None:
        """Chain-hash a freshly attached node and publish it to the
        prefix directory — only when a TierManager is armed (the
        tierless pool pays nothing, not even the sha1)."""
        tier = self.tier
        if tier is None:
            return
        parent_hash = node.parent.hash
        if parent_hash is None:
            return  # ancestor predates arming; leave the branch unhashed
        from .kvtier import chain_hash
        node.hash = chain_hash(parent_hash, node.key)
        tier.note_resident(node.hash, parent_hash, node.key)

    def _sync_gauges(self) -> None:
        if self._g_live is not None:
            self._g_live.set(self.used_blocks)
            self._g_free.set(len(self._free))
            self._g_dev_used.set(self.used_bytes)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.capacity_blocks - len(self._free)

    @property
    def used_bytes(self) -> int:
        """Logical bytes held by indexed blocks (the eviction pressure
        signal; allocation itself is fixed at capacity)."""
        return self.used_blocks * self.bytes_per_block

    def outstanding_refs(self) -> int:
        """Total live sequence references across the trie — zero when no
        admitted sequence holds a prefix pin (the cancel-leak invariant)."""
        return sum(n.lock for n in self._walk())

    def refcounts(self) -> Dict[int, int]:
        """block_id -> live sequence references on its node."""
        return {n.block_id: n.lock for n in self._walk() if n.lock}

    def stats(self) -> dict:
        """One JSON-able occupancy/trie census for `GET /debug/engine`:
        block accounting plus the prefix index's shape (node count =
        indexed blocks, pinned refs, max chain depth). O(trie) — a
        diagnostics read, not a hot-path one."""
        nodes = depth = refs = 0
        stack = [(c, 1) for c in self._root.children.values()]
        while stack:
            n, d = stack.pop()
            nodes += 1
            refs += n.lock
            depth = max(depth, d)
            stack.extend((c, d + 1) for c in n.children.values())
        return {
            "capacity_blocks": self.capacity_blocks,
            "block_positions": self.block,
            "bytes_per_block": self.bytes_per_block,
            "free_blocks": len(self._free),
            "used_blocks": self.used_blocks,
            "utilization": round(
                self.used_blocks / self.capacity_blocks, 4)
            if self.capacity_blocks else 0.0,
            "trie": {"nodes": nodes, "max_depth_blocks": depth,
                     "pinned_refs": refs},
        }

    def _walk(self):
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            yield n
            stack.extend(n.children.values())

    # -- prefix lookup ------------------------------------------------------
    def _walk_prefix(self, tokens: Sequence[int], max_blocks: int
                     ) -> Tuple[_Node, List[int]]:
        """Descend the deepest cached prefix of ``tokens`` (full blocks
        only, capped at ``max_blocks``), ticking ``last_access`` on the
        path — the single definition of the trie walk shared by
        :meth:`match` / :meth:`adopt`. Returns the
        deepest node and the block ids along the path."""
        node, ids = self._root, []
        B = self.block
        while len(ids) < max_blocks:
            child = node.children.get(
                tuple(int(t) for t in tokens[len(ids) * B:(len(ids) + 1) * B]))
            if child is None:
                break
            node = child
            node.last_access = self._tick()
            ids.append(node.block_id)
        return node, ids

    def match(self, tokens: Sequence[int], max_blocks: int
              ) -> Tuple[int, List[int], Optional[_Node]]:
        """Longest cached prefix of ``tokens``, capped at ``max_blocks``
        full blocks. Returns ``(n_blocks, block_ids, node)`` and takes one
        reference on the deepest matched node (release with
        :meth:`release` when the sequence leaves its slot); no hit returns
        ``(0, [], None)`` and takes no reference."""
        node, ids = self._walk_prefix(tokens, max_blocks)
        if not ids:
            return 0, [], None
        node.lock += 1
        return len(ids), ids, node

    def release(self, node: _Node) -> None:
        if node.lock <= 0:
            raise AssertionError("release() without a matching reference")
        node.lock -= 1

    # -- the pool as the live decode cache ----------------------------------
    def alloc(self) -> Optional[int]:
        """One free block for a slot's table (lazy allocation as ``pos``
        crosses a block boundary), LRU-evicting unreferenced cached
        blocks under pressure. ``None`` means even eviction could not
        free a block — every block is owned by a live slot or pinned,
        and the scheduler must preempt. The returned block is OWNED by
        the caller: it is in no trie node and no free list, so nothing
        else can touch it until `free_block` or `adopt`."""
        failpoints.fire("pool.alloc")  # chaos seam: injected OOM/crash
        bid = self._alloc()
        self._sync_gauges()
        return bid

    def free_block(self, block_id: int) -> None:
        """Return a slot-owned block (never a trie-owned one — those are
        freed by eviction) to the free list."""
        if block_id == SCRATCH_BLOCK:
            raise AssertionError("the scratch block is never owned")
        self._free.append(block_id)
        self._sync_gauges()

    def adopt(self, tokens: Sequence[int], block_ids: Sequence[int]
              ) -> List[int]:
        """Zero-copy publish: index ``tokens``'s full blocks by
        REFERENCE. ``block_ids[j]`` is the slot-owned page already
        holding block ``j``'s K/V (the slot's table — prefill wrote the
        pages in place, so there is nothing to scatter). Walks the
        existing trie prefix, attaches a node per missing block that
        simply takes over the caller's page, and returns the adopted
        ids — the caller must NOT free those (ownership moved to the
        trie; eviction frees them eventually)."""
        B = self.block
        n_total = len(tokens) // B
        node, matched = self._walk_prefix(tokens, n_total)
        i = len(matched)
        adopted: List[int] = []
        for j in range(i, n_total):
            key = tuple(int(t) for t in tokens[j * B:(j + 1) * B])
            child = _Node(key, int(block_ids[j]), node)
            node.children[key] = child
            self._hash_and_publish(child)
            node = child
            node.last_access = self._tick()
            adopted.append(int(block_ids[j]))
        if adopted and self._tracer is not None:
            self._tracer.instant("pool_publish", track="kvpool",
                                 args={"blocks": len(adopted),
                                       "used_blocks": self.used_blocks,
                                       "zero_copy": True})
        return adopted

    def reclaimable_blocks(self) -> int:
        """Free blocks plus cached blocks eviction could actually free
        (everything not on a pinned trie path) — the scheduler's
        admission gate: admitting a prompt needing more than this would
        immediately preempt a live slot."""
        pinned = set()
        for n in self._walk():
            if n.lock:
                p = n
                while p is not None and id(p) not in pinned:
                    pinned.add(id(p))
                    p = p.parent
        return len(self._free) + sum(
            1 for n in self._walk() if id(n) not in pinned)

    # -- eviction -----------------------------------------------------------
    def _alloc(self) -> Optional[int]:
        if not self._free:
            self._evict_lru()
        return self._free.pop() if self._free else None

    def _evict_lru(self) -> None:
        """Free one block: the least-recently-used unreferenced LEAF, if
        there is one. Interior nodes are never evicted directly — their
        children would become unreachable prefixes (a parent whose last
        child went is a leaf for the next call)."""
        victim = min((n for n in self._walk()
                      if not n.children and not n.lock),
                     key=lambda n: n.last_access, default=None)
        if victim is None:
            return
        del victim.parent.children[victim.key]
        if self.tier is not None:
            # demotion interception: capture the page row BEFORE the id
            # returns to the free list (the captured device snapshot has
            # buffers of its own and is dispatched before any later write
            # of the pool, so the reused id can be rewritten immediately)
            self.tier.offer_spill(victim.hash, victim.block_id)
        self._free.append(victim.block_id)
        if self._metrics is not None:
            self._m_evicted.inc()
            self._sync_gauges()
        if self._tracer is not None:
            self._tracer.instant("pool_evict", track="kvpool",
                                 args={"blocks": 1,
                                       "used_blocks": self.used_blocks})
