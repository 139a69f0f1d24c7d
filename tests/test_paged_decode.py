"""Paged KV decode: block tables over the unified pool (ISSUE 6).

The acceptance contract: with ``kv_pool_mb`` set, the live decode cache
is the block pool itself — per-slot block tables over pool-wide pages —
and paged decode is TOKEN-IDENTICAL to contiguous decode and solo
decoding (greedy, seeded-sampled, and the LSTM fallback path) under
``transfer_guard="disallow"``. Prefix restore on a full-block hit is a
zero-copy block-table remap (no gather program exists; the only device
work is one pos write), a full-prompt hit's one-token refeed
copy-on-writes the shared tail block without corrupting the cached
original, preempt-and-resume under pool pressure loses no tokens,
admission is pool-bytes-based (a prompt longer than ``max_cache_len``
decodes fine; one bigger than the whole pool is 413 with the block
math in the body), tiny-pool eviction interleaving stays correct, and
the paged program families hold their CompileCounter budgets (block
tables are padded to pow2 bucket widths — no per-length recompiles).
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.analysis import CompileCounter
from deeplearning4j_tpu.inference import (DecodeScheduler, MetricsRegistry,
                                          PromptTooLongError)
from deeplearning4j_tpu.inference.kvpool import SCRATCH_BLOCK
from deeplearning4j_tpu.inference.trace import FlightRecorder
from deeplearning4j_tpu.models.sampling import generate_transformer
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph

V = 13


def _lm(cache=96):
    conf = transformer_lm(vocab_size=V, d_model=16, n_heads=2, n_blocks=2,
                          rope=True)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()


# bytes per (k+v, 2-layer, Hkv=2, Dh=8, f32) block of B positions: B * 256
def _pool_mb(blocks, block):
    """MiB budget buying exactly ``blocks`` usable blocks (+1 scratch)."""
    return (blocks + 1) * block * 256 / float(1 << 20)


# --------------------------------------------------------- token identity --
def test_paged_greedy_token_identical_to_contiguous_and_solo():
    """Mixed prompt lengths across concurrent slots, paged vs contiguous
    vs solo — all token-identical, under the device-residency audit (the
    block table ships as an explicit jnp.asarray-of-ndarray transfer)."""
    net = _lm(cache=96)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, V, n)) for n in (7, 23, 40, 61)]
    solo = [generate_transformer(net, p, 6, V, use_cache=True)
            for p in prompts]
    cont = DecodeScheduler(net, V, n_slots=4, prefill_chunk=16,
                           metrics=MetricsRegistry(),
                           transfer_guard="disallow").start()
    try:
        cont_out = [h.result(120) for h in
                    [cont.submit(p, 6) for p in prompts]]
    finally:
        cont.stop()
    paged = DecodeScheduler(net, V, n_slots=4, prefill_chunk=16,
                            kv_pool_mb=_pool_mb(32, 8), kv_block=8,
                            metrics=MetricsRegistry(),
                            transfer_guard="disallow").start()
    try:
        assert paged.paged and paged.pool.capacity_blocks == 32
        paged_out = [h.result(120) for h in
                     [paged.submit(p, 6) for p in prompts]]
    finally:
        paged.stop()
    assert cont_out == solo
    assert paged_out == solo
    assert paged.pool.outstanding_refs() == 0


def test_lstm_fallback_warns_and_stays_token_identical():
    """kv_pool_mb on a recurrent net (no position-addressed KV rows to
    page) must fall back to contiguous state with a warning — and still
    decode identically to a plain engine."""
    from deeplearning4j_tpu.models.zoo import char_rnn_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    rnn = MultiLayerNetwork(char_rnn_lstm(vocab_size=V, hidden=8)).init()
    with pytest.warns(RuntimeWarning, match="paged KV decode is DISABLED"):
        eng = DecodeScheduler(rnn, V, n_slots=1, prefill_chunk=8,
                              kv_pool_mb=2.0, metrics=MetricsRegistry())
    assert not eng.paged and eng.pool is None
    ref = DecodeScheduler(rnn, V, n_slots=1, prefill_chunk=8,
                          metrics=MetricsRegistry()).start()
    eng.start()
    try:
        p = [1, 2, 3, 4, 5]
        assert eng.generate(p, 4, timeout=120) == \
            ref.generate(p, 4, timeout=120)
    finally:
        eng.stop()
        ref.stop()


# --------------------------------------------- zero-copy restore and COW --
@pytest.mark.parametrize("n_prompt, block", [(32, 8), (64, 16)])
def test_full_block_hit_is_zero_copy_remap_with_cow_refeed(n_prompt, block):
    """A prompt of exactly 4 full blocks served repeatedly: the repeat
    restores ALL 4 blocks by table remap (no gather/scatter program
    exists), re-feeds only the last token — 1 engine step to the first
    token where the cold run took one per chunk, <= 1/4 at 64 tokens
    (the ISSUE 4 acceptance ratio) — and that write COWs the shared tail
    block: the cached original must stay intact for the third request.
    Runs under transfer_guard: the remap is pure host-side table surgery
    plus one explicit pos write."""
    net = _lm(cache=96)
    prompt = list(np.random.default_rng(2).integers(0, V, n_prompt))
    solo = generate_transformer(net, prompt, 5, V, use_cache=True)
    m = MetricsRegistry()
    tr = FlightRecorder(4096)
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(32, block), kv_block=block,
                          metrics=m, tracer=tr,
                          transfer_guard="disallow").start()
    try:
        handles = []
        for _ in range(3):  # cold: publish; remap + COW; cache intact
            handles.append(eng.submit(prompt, 5))
            assert handles[-1].result(120) == solo
    finally:
        eng.stop()
    assert [h.steps_to_first_token for h in handles] == \
        [n_prompt // 16, 1, 1]
    # hit = full 4 blocks, capped one token short, per repeat
    assert m.counter("prefix_cache_hit_tokens_total").value == \
        2 * (n_prompt - 1)
    assert m.counter("prefix_cache_hits_total").value == 2
    assert m.counter("prefix_cache_lookups_total").value == 3
    assert m.snapshot()["ratios"]["prefix_cache_hit_rate"] > 0.6
    assert eng.pool.outstanding_refs() == 0
    names = [e["name"] for e in tr.events()]
    assert names.count("block_cow") == 2  # one per warm repeat
    remaps = [e for e in tr.events() if e["name"] == "prefix_restore"
              and e["ph"] == "E" and e.get("args", {}).get("remap_blocks")]
    assert remaps and all(e["args"]["kv_copies"] == 0 for e in remaps)


def test_publish_is_ownership_transfer_not_copy():
    """Finish hands the prompt's blocks to the trie in place: pool
    occupancy must equal the adopted blocks (nothing double-allocated),
    and a second engine pass restores from exactly those pages."""
    net = _lm(cache=96)
    prompt = list(np.random.default_rng(3).integers(0, V, 24))  # 3 blocks
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(16, 8), kv_block=8,
                          metrics=m).start()
    try:
        eng.generate(prompt, 3, timeout=120)
        # slot freed: only the adopted prompt blocks remain live
        assert eng.pool.used_blocks == 3
        assert eng.pool.match(prompt, 3)[0] == 3
        n, ids, node = eng.pool.match(prompt, 3)
        eng.pool.release(node)
        eng.pool.release(node)  # drop the probe references
        assert SCRATCH_BLOCK not in ids
    finally:
        eng.stop()


# ------------------------------------------------------ preempt / resume --
def test_preempt_and_resume_mid_decode_is_token_identical():
    """Two sequences whose decode growth exceeds the pool: the
    latest-submitted slot is swapped out (blocks released, requeued) and
    resumed after the first finishes — outputs identical to solo, swap
    visible in metrics and trace instants. Runs under the armed resource
    ledger (graftleak): the preempt's release-and-requeue and the
    resume's re-acquire must balance every block/pin/slot to zero."""
    from deeplearning4j_tpu.analysis import resource_ledger
    net = _lm(cache=96)
    rng = np.random.default_rng(4)
    p1, p2 = [list(rng.integers(0, V, 6)) for _ in range(2)]
    solo1 = generate_transformer(net, p1, 10, V, use_cache=True)
    solo2 = generate_transformer(net, p2, 10, V, use_cache=True)
    m = MetricsRegistry()
    tr = FlightRecorder(8192)
    with resource_ledger() as led:
        # each sequence needs ceil((6+10-1)/4) = 4 blocks; 7 cannot hold 8
        eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                              kv_pool_mb=_pool_mb(7, 4), kv_block=4,
                              metrics=m, tracer=tr).start()
        try:
            h1 = eng.submit(p1, 10)
            h2 = eng.submit(p2, 10)
            assert h1.result(120) == solo1
            assert h2.result(120) == solo2
            assert eng.pool.outstanding_refs() == 0
        finally:
            eng.stop()
    led.assert_clean()
    assert m.counter("decode_preempted_total").value >= 1
    names = [e["name"] for e in tr.events()]
    assert names.count("preempt") >= 1
    assert names.count("resume") >= 1
    # the swap gap is a span on the request track: every preempted B has
    # a matching E (resume or cancel closed it)
    pre = [e for e in tr.events() if e["name"] == "preempted"]
    assert len([e for e in pre if e["ph"] == "B"]) == \
        len([e for e in pre if e["ph"] == "E"]) >= 1


def test_preempted_sampled_sequence_resumes_with_same_rng_stream():
    """Token identity through a swap must hold for SAMPLED decoding too:
    the resumed re-prefill recomputes K/V but never touches the
    sequence's host RNG, so the draw order is unchanged."""
    net = _lm(cache=96)
    rng = np.random.default_rng(5)
    p1, p2 = [list(rng.integers(0, V, 6)) for _ in range(2)]
    kw = dict(temperature=0.9, top_k=6, seed=7)
    solo2 = generate_transformer(net, p2, 10, V, use_cache=True, **kw)
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(7, 4), kv_block=4,
                          metrics=m).start()
    try:
        h1 = eng.submit(p1, 10)
        h2 = eng.submit(p2, 10, **kw)  # admitted second -> the victim
        h1.result(120)
        assert h2.result(120) == solo2
    finally:
        eng.stop()
    assert m.counter("decode_preempted_total").value >= 1


# --------------------------------------------------- admission / eviction --
def test_admission_is_pool_bytes_not_max_cache_len():
    """The oversize-413 satellite: a prompt LONGER than max_cache_len
    decodes fine when the pool holds it (no per-slot stripe to outgrow);
    one bigger than the whole pool raises the typed error carrying the
    block math."""
    net = _lm(cache=32)  # conf cap far below the pool
    prompt = list(np.random.default_rng(6).integers(0, V, 48))
    solo = generate_transformer(net, prompt, 4, V, use_cache=False)
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(8, 8), kv_block=8,
                          metrics=m).start()
    try:
        assert eng._cache_cap == 64  # pool positions, not max_cache_len
        assert eng.generate(prompt, 4, timeout=120) == solo
        with pytest.raises(PromptTooLongError, match="KV blocks") as ei:
            eng.submit(list(np.random.default_rng(7).integers(0, V, 70)), 4)
        assert ei.value.blocks_needed == 10
        assert ei.value.blocks_available == 8
        assert m.counter("decode_rejected_total").value == 1
    finally:
        eng.stop()


def test_server_413_body_reports_blocks_needed_vs_available():
    from deeplearning4j_tpu.serving import InferenceServer
    net = _lm(cache=32)
    srv = InferenceServer(net=net, decode_vocab=V, decode_slots=1,
                          prefill_chunk=16, kv_block=16,
                          kv_pool_mb=_pool_mb(4, 16)).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1] * 70,
                             "max_new_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 413
        body = json.loads(ei.value.read())
        assert body["blocks_needed"] == 5 and body["blocks_available"] == 4
        # a prompt beyond max_cache_len=32 but inside the pool SERVES
        ok = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1] * 40,
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        assert len(json.loads(
            urllib.request.urlopen(ok).read())["tokens"]) == 2
    finally:
        srv.stop()


@pytest.mark.parametrize("n_slots, lengths, blocks, block, evicted", [
    (2, (20, 9, 26, 14), 9, 4, 1),
    (1, (32, 32, 32, 32), 6, 8, 4),
], ids=["two-slots-swap", "one-slot-stream"])
def test_tiny_pool_admission_eviction_interleaving_stays_correct(
        n_slots, lengths, blocks, block, evicted):
    """A stream of distinct prompts through a pool barely bigger than
    one sequence: publishes evict earlier prefixes (counted), admission
    gates on reclaimable blocks, slots swap — every output must stay
    correct, a re-serve of an evicted prefix included (a miss, not
    garbage), and occupancy within the byte budget throughout."""
    net = _lm(cache=96)
    rng = np.random.default_rng(8)
    prompts = [list(rng.integers(0, V, n)) for n in lengths]
    solos = [generate_transformer(net, p, 4, V, use_cache=True)
             for p in prompts]
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=n_slots, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(blocks, block),
                          kv_block=block, metrics=m).start()
    try:
        assert eng.pool.capacity_blocks == blocks
        for rep in range(2):
            handles = [eng.submit(p, 4) for p in prompts]
            for h, solo in zip(handles, solos):
                assert h.result(120) == solo
            assert eng.pool.used_blocks <= eng.pool.capacity_blocks
        assert eng.pool.outstanding_refs() == 0
    finally:
        eng.stop()
    assert m.counter("prefix_cache_evicted_blocks_total").value >= evicted
    assert m.gauge("kv_pool_blocks_live").max <= blocks
    budget = _pool_mb(blocks, block) * (1 << 20)
    assert m.gauge("kv_pool_device_bytes").value <= budget
    assert m.gauge("kv_pool_device_used_bytes").max \
        <= m.gauge("kv_pool_device_bytes").value
    snap = m.snapshot()
    assert 0.0 <= snap["ratios"]["kv_pool_utilization"] <= 1.0


# ------------------------------------------------------- compile budgets --
def test_paged_program_families_hold_compile_budgets():
    """Block tables are padded to pow2 bucket widths: a mixed workload
    (lengths straddling table buckets, hits, COWs, preemptions) compiles
    at most one decode program per table bucket, one prefill program per
    (chunk, table) bucket pair, and exactly one setpos + one COW
    program — never one per sequence length."""
    net = _lm(cache=96)
    rng = np.random.default_rng(9)
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(16, 8), kv_block=8,
                          metrics=m).start()
    audit = CompileCounter.for_scheduler(eng)
    base = list(rng.integers(0, V, 32))
    try:
        for p in [base, base, base[:16] + [1] * 5, [2, 3],
                  list(rng.integers(0, V, 49)), base]:
            eng.generate(p, 3, timeout=120)
    finally:
        eng.stop()
    audit.assert_within_budget()
    counts = audit.counts()
    assert counts["decode"] >= 1
    assert counts["restore_setpos"] == 1
    assert counts["block_cow"] == 1  # the full-match refeed COW compiled
    assert eng.table_buckets == [1, 2, 4, 8, 16]


@pytest.mark.parametrize("leave", ["cancel", "stop"])
def test_paged_slot_release_returns_every_block(leave):
    """Every slot-freeing path (finish, cancel, stop) must return owned
    blocks and the trie pin — the ISSUE 4 refcount-leak tests on the
    paged layout. After a cancel the pool keeps serving hits."""
    import time as _t
    net = _lm(cache=128)
    prompt = list(np.random.default_rng(10).integers(0, V, 24))
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=4,
                          kv_pool_mb=_pool_mb(16, 8), kv_block=8,
                          metrics=m).start()
    try:
        eng.generate(prompt, 2, timeout=120)  # publish 3 blocks
        live_after_publish = eng.pool.used_blocks
        long = prompt + list(np.random.default_rng(11).integers(0, V, 80))
        h = eng.submit(long, 8)
        deadline = _t.monotonic() + 30
        while eng.pool.outstanding_refs() == 0:
            assert _t.monotonic() < deadline, "restore never pinned"
            _t.sleep(0.002)
        if leave == "cancel":
            h.cancel()
            while eng.pool.outstanding_refs() != 0:
                assert _t.monotonic() < deadline, "cancel leaked a pin"
                _t.sleep(0.005)
            deadline = _t.monotonic() + 30
            while eng.pool.used_blocks != live_after_publish:
                assert _t.monotonic() < deadline, "cancel leaked blocks"
                _t.sleep(0.005)
            # the pool still serves hits after the cancelled sequence
            solo = generate_transformer(net, prompt + [2], 3, V,
                                        use_cache=True)
            assert eng.generate(prompt + [2], 3, timeout=120) == solo
            assert m.counter("prefix_cache_hits_total").value == 2
    finally:
        eng.stop()
    if leave == "stop":  # stopped with the sequence resident
        with pytest.raises(RuntimeError, match="scheduler stopped"):
            h.result(5)
    assert eng.pool.outstanding_refs() == 0
    assert eng.pool.used_blocks == live_after_publish
    assert (eng._table == SCRATCH_BLOCK).all()


def test_too_small_a_pool_warns_and_serves_contiguous_without_a_pool():
    """kv_pool_mb too small for even two blocks: the engine says so and
    serves from contiguous stripes — no pool, no prefix cache, and
    nothing else the budget could have bought."""
    net = _lm(cache=32)
    m = MetricsRegistry()
    with pytest.warns(RuntimeWarning, match="paged KV decode is DISABLED"
                                            ".*byte budget"):
        eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=8,
                              kv_pool_mb=1e-6, kv_block=8, metrics=m)
    assert eng.paged is False and eng.pool is None
    prompt = list(np.random.default_rng(12).integers(0, V, 20))
    solo = generate_transformer(net, prompt, 3, V, use_cache=False)
    eng.start()
    try:
        assert eng.generate(prompt, 3, timeout=120) == solo
        assert eng.generate(prompt, 3, timeout=120) == solo
    finally:
        eng.stop()
    assert "prefix_cache_hit_tokens_total" not in m.snapshot()["counters"]


def test_full_pool_full_prompt_hit_converges_instead_of_livelocking():
    """A block-aligned prompt whose published blocks fill the ENTIRE
    pool, resubmitted: the full-hit refeed needs a COW page that can
    never exist (every page backs this very prompt's pinned prefix).
    The starved attempt must fall back to a one-block-short hit — not
    spin preempt/restore forever."""
    net = _lm(cache=96)
    prompt = list(np.random.default_rng(13).integers(0, V, 32))  # 4 blocks
    solo = generate_transformer(net, prompt, 1, V, use_cache=False)
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=8,
                          kv_pool_mb=_pool_mb(4, 8), kv_block=8,
                          metrics=m).start()
    try:
        assert eng.generate(prompt, 1, timeout=120) == solo  # publish 4/4
        assert eng.pool.free_blocks == 0
        assert eng.generate(prompt, 1, timeout=120) == solo  # the trap
    finally:
        eng.stop()
    # exactly one starved preempt cycle, then the capped hit converges
    assert m.counter("decode_preempted_total").value == 1
