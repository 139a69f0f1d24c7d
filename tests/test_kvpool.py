"""Prefix KV reuse: the paged pool's radix-trie prefix cache (ISSUE 4, on
the ISSUE 6 layout).

The contract: a repeated prompt is served from the pages its first run
wrote — a block-table remap, no copy — and reaches its first token in
<= 1/4 the engine steps of a cold prefill, with greedy outputs
token-identical to solo decoding, asserted under
``transfer_guard="disallow"`` like the rest of the equivalence suite.
Only full blocks are shared, refcounts are leak-free across cancel
paths, eviction respects the byte budget, a warmed engine compiles
nothing while the trie is in use, the removed side pool's flag and
keyword are refused, and oversize prompts are HTTP 413 at the serving
layer. (The zero-copy remap and copy-on-write themselves:
tests/test_paged_decode.py.)
"""
import json
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import CompileCounter
from deeplearning4j_tpu.inference import (DecodeHandle, DecodeScheduler,
                                          KVPool, MetricsRegistry,
                                          PromptTooLongError)
from deeplearning4j_tpu.inference.engine import _ActiveSeq
from deeplearning4j_tpu.inference.kvpool import SCRATCH_BLOCK
from deeplearning4j_tpu.models.sampling import generate_transformer
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph


def _lm(v=13, cache=96):
    conf = transformer_lm(vocab_size=v, d_model=16, n_heads=2, n_blocks=2,
                          rope=True)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()


def _fake_attn_states(n_layers=2, Hkv=2, Dh=8, cache_dtype=None):
    """What a `KVPool` is handed: each layer's paged leaves, as the layer
    states them (`SelfAttentionLayerImpl.paged_leaves`)."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.base import impl_for
    impl = impl_for(SelfAttentionLayer(n_in=Hkv * Dh, n_out=Hkv * Dh,
                                       n_heads=Hkv))
    return {f"l{i}": impl.paged_leaves(4, jnp.float32, cache_dtype)
            for i in range(n_layers)}


# bytes per (k+v, 2-layer, Hkv=2, Dh=8, f32) block of B positions: B * 256
def _pool_mb(blocks, block):
    """MiB budget buying exactly ``blocks`` usable blocks (+1 scratch)."""
    return (blocks + 1) * block * 256 / float(1 << 20)


def _publish(pool, tokens):
    """What the engine does with a finished prompt: one slot-owned block
    per full block of ``tokens`` (`alloc`, evicting under pressure), the
    trie adopts those it does not index yet, the rest go back to the
    free list. Returns the adopted ids ([] when the pool ran dry)."""
    n = len(tokens) // pool.block
    ids = [pool.alloc() for _ in range(n)]
    owned = [b for b in ids if b is not None]
    adopted = (pool.adopt(tokens[:n * pool.block], ids)
               if len(owned) == n else [])
    for b in owned:
        if b not in adopted:
            pool.free_block(b)
    return adopted


# ------------------------------------------------------------- pool unit --
def test_pool_capacity_respects_budget_and_reserves_scratch():
    st = _fake_attn_states()
    # bytes/block: 2 layers * (k+v) * block4 * 2 * 8 * 4B = 1024
    pool = KVPool(st, block=4, budget_bytes=5 * 1024)
    assert pool.bytes_per_block == 1024
    # 5 blocks of budget = scratch + 4 usable; allocation never exceeds it
    assert pool.capacity_blocks == 4
    ids = [pool.alloc() for _ in range(4)]
    assert len(set(ids)) == 4
    assert SCRATCH_BLOCK not in ids  # block 0 is never handed out
    assert pool.used_blocks == 4 and pool.used_bytes == 4 * 1024
    assert pool.alloc() is None


def test_kvpool_has_one_mode_and_int8_needs_none():
    """There is no ``paged`` switch to pass, and an int8 pool is asked
    for by the layers' int8 leaves alone (no ``cache_dtype`` of the pool's
    own): the same budget holds more blocks."""
    st = _fake_attn_states()
    with pytest.raises(TypeError, match="paged"):
        KVPool(st, block=4, budget_bytes=5 * 1024, paged=True)
    with pytest.raises(TypeError, match="cache_dtype"):
        KVPool(st, block=4, budget_bytes=5 * 1024, cache_dtype="int8")
    m = MetricsRegistry()
    pool = KVPool(_fake_attn_states(cache_dtype="int8"), block=4,
                  budget_bytes=5 * 1024, metrics=m)
    # 2 layers * (k+v) * block4 * (2*8 int8 + 2 f32 scales) = 384
    assert pool.bytes_per_block == 384
    assert pool.capacity_blocks == 5 * 1024 // 384 - 1
    assert not hasattr(pool, "paged") and not hasattr(pool, "storage")
    # the pool's own gauges are the only occupancy instruments
    assert len(_publish(pool, list(range(8)))) == 2
    assert m.gauge("kv_pool_blocks_live").value == 2
    assert m.gauge("kv_pool_device_used_bytes").value == 2 * 384
    gauges = m.snapshot()["gauges"]
    assert not [g for g in gauges if g.startswith("prefix_cache_")]


def test_pool_match_adopt_release_and_refcounts():
    pool = KVPool(_fake_attn_states(), block=4, budget_bytes=32 * 1024)
    toks = [1, 2, 3, 4, 5, 6, 7, 8]
    assert pool.match(toks, max_blocks=2) == (0, [], None)
    ids = _publish(pool, toks)
    assert len(ids) == 2 and pool.used_blocks == 2
    n, got, node = pool.match(toks + [9, 9, 9], max_blocks=5)
    assert n == 2 and got == ids
    assert pool.outstanding_refs() == 1
    assert pool.refcounts() == {ids[1]: 1}  # deepest matched node holds it
    # a second reader shares the same blocks (refcount, not a copy)
    n2, got2, node2 = pool.match(toks, max_blocks=2)
    assert got2 == ids and pool.outstanding_refs() == 2
    pool.release(node)
    pool.release(node2)
    assert pool.outstanding_refs() == 0 and pool.refcounts() == {}
    with pytest.raises(AssertionError):
        pool.release(node)
    # extending reuses the shared prefix: only the suffix is adopted
    ids2 = _publish(pool, toks + [9, 9, 9, 9])
    assert len(ids2) == 1 and ids2[0] not in ids
    assert pool.used_blocks == 3


def test_pool_lru_eviction_skips_locked_and_interior_nodes():
    pool = KVPool(_fake_attn_states(), block=4, budget_bytes=5 * 1024)
    assert pool.capacity_blocks == 4
    _publish(pool, [1] * 8)   # chain of 2: interior + leaf
    _publish(pool, [2] * 4)
    _publish(pool, [3] * 4)
    assert pool.used_blocks == 4
    n, _, node = pool.match([2] * 4, max_blocks=1)  # pin b's leaf
    assert n == 1
    d = _publish(pool, [4] * 4)  # full: must evict an unlocked leaf
    assert len(d) == 1
    # b is locked; a's interior block survives only if its leaf does not
    assert pool.match([2] * 4, max_blocks=1)[0] == 1  # b still cached
    assert pool.used_blocks <= pool.capacity_blocks
    pool.release(node)


def test_pool_full_of_referenced_blocks_fails_allocation_gracefully():
    pool = KVPool(_fake_attn_states(), block=4, budget_bytes=3 * 1024)
    assert pool.capacity_blocks == 2
    assert len(_publish(pool, [1] * 8)) == 2
    _, _, node = pool.match([1] * 8, max_blocks=2)
    # nothing evictable (a pinned leaf and its interior parent): the
    # caller is told so, and the cached chain is untouched
    assert pool.alloc() is None
    assert _publish(pool, [9] * 8) == []
    assert pool.match([1] * 8, max_blocks=2)[0] == 2
    pool.release(node)  # the probe above pinned the same leaf
    pool.release(node)


# ----------------------------------------------------- engine equivalence --
def test_partial_hit_cold_suffix_crossing_chunk_bucket_boundary():
    """A prompt sharing only part of a cached prefix is pointed at the
    common FULL blocks (the block where the two diverge is not shared)
    and chunk-prefills a cold suffix that spans a chunk bucket boundary
    (21 tokens -> a 16-chunk + a 5-tail) — still token-identical to
    solo decoding."""
    V = 13
    net = _lm(V, cache=96)
    rng = np.random.default_rng(1)
    base = list(rng.integers(0, V, 32))
    other = base[:24] + list(rng.integers(0, V, 21))  # diverges in block 3
    solo = [generate_transformer(net, p, 5, V, use_cache=True)
            for p in (base, other)]
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=2.0, kv_block=8, metrics=m,
                          transfer_guard="disallow").start()
    try:
        assert eng.submit(base, 5).result(120) == solo[0]
        h = eng.submit(other, 5)
        assert h.result(120) == solo[1]
    finally:
        eng.stop()
    # 24 shared tokens restored; 21-token suffix = 2 chunk steps
    assert m.counter("prefix_cache_hit_tokens_total").value == 24
    assert h.steps_to_first_token == 2


def test_concurrent_slots_share_prefix_blocks_without_aliasing():
    """Two live slots whose tables point at the SAME pool blocks: each
    writes only pages it owns (its cold suffix and decode rows land past
    the shared chain), so both decode token-identically to solo while
    the shared blocks carry two references."""
    V = 13
    net = _lm(V, cache=160)
    rng = np.random.default_rng(2)
    prefix = list(rng.integers(0, V, 32))
    p1 = prefix + list(rng.integers(0, V, 8))
    p2 = prefix + list(rng.integers(0, V, 11))
    solo = [generate_transformer(net, p, 64, V, use_cache=True)
            for p in (p1, p2)]
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=2.0, kv_block=8,
                          metrics=MetricsRegistry(),
                          transfer_guard="disallow").start()
    try:
        eng.submit(prefix + [1], 2).result(120)  # publish the prefix
        h1 = eng.submit(p1, 64)
        h2 = eng.submit(p2, 64)
        deadline = time.monotonic() + 30
        while eng.pool.outstanding_refs() < 2:
            assert time.monotonic() < deadline, \
                "both slots should pin the shared prefix while resident"
            time.sleep(0.005)
        assert max(eng.pool.refcounts().values()) == 2  # same deepest node
        assert h1.result(120) == solo[0]
        assert h2.result(120) == solo[1]
        assert eng.pool.outstanding_refs() == 0
    finally:
        eng.stop()


@pytest.mark.parametrize("n_prompt", [40, 32])
def test_seeded_sampling_matches_solo_through_a_prefix_hit(n_prompt):
    """RNG consumption order is unchanged by a restore: the first draw
    still comes from the last REAL prompt token's distribution — with a
    cold tail after the hit (40 = 5 blocks, capped one token short) and
    through the copy-on-write refeed of a block-aligned prompt (32)."""
    V = 13
    net = _lm(V, cache=96)
    prompt = list(np.random.default_rng(4).integers(0, V, n_prompt))
    kw = dict(temperature=0.8, top_k=5, top_p=0.9, seed=11)
    solo = generate_transformer(net, prompt, 6, V, use_cache=True, **kw)
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=2.0, kv_block=8, metrics=m,
                          transfer_guard="disallow").start()
    try:
        assert eng.generate(prompt, 6, timeout=120, **kw) == solo
        assert eng.generate(prompt, 6, timeout=120, **kw) == solo  # hit
    finally:
        eng.stop()
    assert m.counter("prefix_cache_hit_tokens_total").value == n_prompt - 1


# ------------------------------------------------------- refcount leaks ---
def test_cancel_mid_prefill_releases_pool_references():
    """The ISSUE 4 cancel satellite, deterministically: admit + restore a
    sequence (its slot pins the matched trie node and its table points
    at the shared pages), cancel BEFORE prefill finishes, and the
    eviction sweep must return every pool refcount to zero and the table
    to scratch — no publish of the half-written prompt either."""
    V = 13
    net = _lm(V, cache=96)
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(0, V, 48))
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=16,
                          kv_pool_mb=2.0, kv_block=8,
                          metrics=m).start()
    eng.generate(prompt + [1, 2, 3], 2, timeout=120)  # publish the prefix
    eng.stop()  # scheduler thread joined: internals are single-threaded
    used_before = eng.pool.used_blocks
    prompt = prompt + [4, 5, 6, 7, 8]  # 6 shared blocks, then a cold tail
    seq = _ActiveSeq(DecodeHandle(len(prompt), 4), prompt, 0.0, None, None,
                     0, None)
    eng._reset_slot_state(0)
    eng._slots[0] = seq
    eng._try_restore(0, seq)
    assert seq.fed == 48 < len(seq.prompt) - 1  # genuinely mid-prefill
    assert (eng._table[0, :6] != SCRATCH_BLOCK).all()
    assert eng.pool.outstanding_refs() == 1
    seq.handle.cancel()
    eng._evict_cancelled()
    assert eng.pool.outstanding_refs() == 0
    assert eng.pool.refcounts() == {}
    assert eng._slots[0] is None and seq.handle.done()
    assert eng.pool.used_blocks == used_before  # nothing published
    assert (eng._table == SCRATCH_BLOCK).all()
    assert m.counter("decode_cancelled_total").value == 1


# ------------------------------------------------------- compile budgets --
def test_warmed_engine_compiles_nothing_while_the_trie_is_in_use():
    """Restore is a table remap and publish an ownership transfer:
    neither has a program family of its own. After `warmup()` a window
    of misses, partial hits, full hits with their copy-on-write refeed
    and evictions compiles NOTHING — every tracked family is where the
    warm-up left it, and there is no restore or publish family."""
    V = 13
    net = _lm(V, cache=128)
    rng = np.random.default_rng(7)
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=32,
                          kv_pool_mb=_pool_mb(12, 8), kv_block=8,
                          metrics=MetricsRegistry())
    eng.warmup()
    audit = CompileCounter.for_scheduler(eng)
    warmed = audit.counts()
    assert not [k for k in warmed if k.startswith("prefix_")]
    eng.start()
    base = list(rng.integers(0, V, 64))
    try:
        for p in [base, base, base[:40] + [1] * 9, list(rng.integers(0, V, 17)),
                  base[:16] + [2] * 3, base, [3, 4]]:
            eng.generate(p, 3, timeout=120)
    finally:
        eng.stop()
    assert audit.counts() == warmed
    audit.assert_within_budget()
    m = eng.metrics
    assert m.counter("prefix_cache_hits_total").value >= 4
    assert m.counter("prefix_cache_evicted_blocks_total").value >= 1


def test_pool_disabled_paths_are_untouched():
    """kv_pool_mb=0 (the default) leaves the scheduler contiguous: no
    pool, no prefix cache, no prefix metrics."""
    V = 13
    net = _lm(V, cache=48)
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=16,
                          metrics=m).start()
    try:
        prompt = [1, 2, 3, 4, 5]
        solo = generate_transformer(net, prompt, 3, V, use_cache=True)
        assert eng.generate(prompt, 3, timeout=120) == solo
    finally:
        eng.stop()
    assert eng.pool is None and not eng.paged
    assert "prefix_cache_hit_tokens_total" not in m.snapshot()["counters"]


# ------------------------------------------------------------- serving ----
def test_server_rejects_oversize_prompt_with_413_and_counts_it():
    """The prompt-length satellite: a /generate request that cannot fit
    the KV cache is refused up front with HTTP 413 (not admitted to die
    on the attention overflow guard mid-decode), counted in
    decode_rejected_total, and the server keeps serving."""
    from deeplearning4j_tpu.serving import InferenceServer
    V = 13
    net = _lm(V, cache=32)
    srv = InferenceServer(net=net, decode_vocab=V, decode_slots=1,
                          prefill_chunk=16).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1] * 30,
                             "max_new_tokens": 10}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 413
        snap = json.loads(urllib.request.urlopen(base + "/metrics").read())
        assert snap["counters"]["decode_rejected_total"] == 1
        ok = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1, 2], "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        assert len(json.loads(
            urllib.request.urlopen(ok).read())["tokens"]) == 2
    finally:
        srv.stop()


def test_engine_submit_oversize_prompt_raises_typed_error():
    V = 13
    net = _lm(V, cache=16)
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=1, metrics=m).start()
    try:
        with pytest.raises(PromptTooLongError, match="max_cache_len"):
            eng.submit(list(range(10)), 10)
        assert isinstance(PromptTooLongError("x"), ValueError)  # compat
        assert m.counter("decode_rejected_total").value == 1
    finally:
        eng.stop()


def test_server_generate_with_prefix_cache_hits_over_http():
    from deeplearning4j_tpu.serving import InferenceServer
    V = 13
    net = _lm(V, cache=96)
    prompt = [int(t) for t in np.random.default_rng(8).integers(0, V, 40)]
    solo = generate_transformer(net, prompt, 4, V, use_cache=True)
    srv = InferenceServer(net=net, decode_vocab=V, decode_slots=2,
                          prefill_chunk=16, kv_pool_mb=2.0,
                          kv_block=8).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        body = json.dumps({"prompt": prompt, "max_new_tokens": 4}).encode()
        for _ in range(2):
            req = urllib.request.Request(
                base + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            assert json.loads(urllib.request.urlopen(req).read())["tokens"] \
                == solo
        snap = json.loads(urllib.request.urlopen(base + "/metrics").read())
        # 5 full blocks hit, capped one token short of the prompt
        assert snap["counters"]["prefix_cache_hit_tokens_total"] == 39
        assert snap["ratios"]["prefix_cache_hit_rate"] > 0.3
        text = urllib.request.urlopen(
            base + "/metrics?format=text").read().decode()
        assert "prefix_cache_hit_rate" in text
    finally:
        srv.stop()


# the removed option, spelled in two pieces so that a search for it finds
# only uses
_GONE_KW = "prefix_" + "cache_mb"
_GONE_FLAG = "--prefix-" + "cache-mb"


def _serve_refuses(capsys):
    from deeplearning4j_tpu.cli.main import build_parser
    serve = ["serve", "--model", "m.zip", "--generate", "--kv-block", "32"]
    ok = build_parser().parse_args(serve + ["--kv-pool-mb", "64"])
    assert ok.kv_pool_mb == 64.0 and ok.kv_block == 32
    with pytest.raises(SystemExit) as ei:
        build_parser().parse_args(serve + [_GONE_FLAG, "64"])
    assert ei.value.code == 2
    return capsys.readouterr().err


def _replica_refuses(capsys):
    from deeplearning4j_tpu.serving import replica
    with pytest.raises(SystemExit) as ei:
        replica.main(["--announce", "http://127.0.0.1:1", _GONE_FLAG, "8"])
    assert ei.value.code == 2
    return capsys.readouterr().err


def _server_refuses(capsys):
    from deeplearning4j_tpu.serving import InferenceServer
    with pytest.raises(TypeError) as ei:
        InferenceServer(net=object(), decode_vocab=13, **{_GONE_KW: 2.0})
    return str(ei.value)


@pytest.mark.parametrize("refuses, named", [
    (_serve_refuses, _GONE_FLAG),
    (_replica_refuses, _GONE_FLAG),
    (_server_refuses, _GONE_KW),
], ids=["serve", "replica", "InferenceServer"])
def test_the_side_pools_flag_and_keyword_are_refused(refuses, named, capsys):
    """The paged pool is the only prefix cache: the side pool's flag is
    an unknown argument and its keyword an unexpected one, like any
    other — no shim, no silent ignore."""
    assert named in refuses(capsys)
