"""A.X-K1's block through the serving path (ISSUE 34): `submit` -> `_admit`
-> chunked prefill -> `_step_once`, latent rows in the paged pool (one leaf
a layer), the prefix trie, preemption, and the routing counts that ride back
with the probabilities. What each served token was sampled from is compared
with the plain reference's full forward, on log-probabilities. Small CPU
size: hidden 64, 4 heads, ranks 24 / 16, 16 experts of which 2 a token, a
share of 8 (experts 4..11) held, 3 layers, vocabulary 96, block 8, prefill
chunks of 16."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from axk1_util import BLOCK, BLOCK_BYTES, CFG, cfg, load, pool_mb

from deeplearning4j_tpu.analysis import CompileCounter
from deeplearning4j_tpu.inference import DecodeScheduler, MetricsRegistry
from deeplearning4j_tpu.inference.trace import FlightRecorder

V = CFG["vocab_size"]
SHARE = cfg(4, 8)


@pytest.fixture(scope="module")
def small():
    return load(conf=SHARE)


class Served:
    """An engine whose every sampled-from distribution is kept."""

    def __init__(self, net, blocks, n_slots=2, itemsize=4, **kw):
        self.eng = eng = DecodeScheduler(
            net, V, n_slots=n_slots, prefill_chunk=16, kv_block=BLOCK,
            kv_pool_mb=pool_mb(blocks, itemsize), metrics=MetricsRegistry(),
            tracer=FlightRecorder(1 << 15), **kw)
        assert eng.paged and eng.pool.capacity_blocks == blocks
        self.rows = {}
        consume = eng._consume

        def consume_and_keep(slot, seq, probs_row):
            self.rows.setdefault(seq.handle.request_id, []).append(
                np.array(probs_row, np.float64))
            return consume(slot, seq, probs_row)

        eng._consume = consume_and_keep
        eng.start()

    def logprobs(self, handle):
        return np.log(np.stack(self.rows[handle.request_id]))

    def counters(self):
        return self.eng.metrics.snapshot()["counters"]


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, V, n).tolist()


def _ref_logprobs(fam, params, prompt, tokens):
    ids = np.array([prompt + tokens], np.int32)
    pos = (len(prompt) - 1 + np.arange(len(tokens)))[None].astype(np.int32)
    lg = fam.reference.logits_at(params, SHARE, jnp.asarray(ids),
                                 jnp.asarray(pos))
    return np.asarray(jax.nn.log_softmax(lg, -1), np.float64)[0]


# float32 engine against the float32 reference at `highest`: the order of
# the sums differs (chunked prefill, the absorbed form, the gathered pages,
# the sorted tiles), measured 1e-6. A bfloat16 engine reads 1e-2 and more
# (last test)
TOL = 2e-5


def test_chunked_prefill_then_paged_decode_is_the_reference(small):
    """Two slots at different depths in the same steps: 53 prompt tokens in
    chunks of 16 (the last one 5) and 9 in one, then 24 decoded each."""
    fam, params, net = small
    s = Served(net, blocks=40)
    try:
        a, b = _prompt(53, 0), _prompt(9, 1)
        ha, hb = s.eng.submit(a, 24), s.eng.submit(b, 24)
        ta, tb = ha.result(300), hb.result(300)
        for h, p, t in ((ha, a, ta), (hb, b, tb)):
            got, ref = s.logprobs(h), _ref_logprobs(fam, params, p, t)
            assert got.shape == ref.shape == (24, V)
            assert np.abs(got - ref).max() < TOL
            assert t == ref.argmax(-1).tolist()
        c = s.counters()
        CompileCounter.for_scheduler(s.eng).assert_within_budget()
    finally:
        s.eng.stop()
    assert s.eng.pool.used_blocks <= -(-53 // BLOCK) + 1   # the trie's
    # 23 decode steps a request; the two final chunks (5 and 9 tokens) are
    # the prefill dispatches whose counts are read; 2 routed layers, 2 a token
    assert c["moe_pairs_routed_total"] == (2 * 23 + 5 + 9) * 2 * 2
    assert 0 < c["moe_pairs_held_total"] < c["moe_pairs_routed_total"]
    assert c["moe_expert_slots_total"] % (2 * 8) == 0
    assert 0 < c["moe_experts_hit_total"] <= min(
        c["moe_expert_slots_total"], c["moe_pairs_held_total"])
    depths = sum(range(53 + 1, 53 + 24)) + sum(range(9 + 1, 9 + 24))
    assert c["mla_rows_read_total"] == depths


def test_routing_counts_ride_back_exactly(small):
    """`_pack_counts` / `_unpack_counts`: counts up to a dispatch's 65,535
    tokens come back whole through rows of the probabilities' dtype,
    bfloat16 too (what a bfloat16 graph hands back); more is refused."""
    _, _, net = small
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16, kv_block=BLOCK,
                          kv_pool_mb=pool_mb(8), metrics=MetricsRegistry())
    counts = np.array([[0, 1, 255, 256, 257, 512, 4096, 65535],
                       [7, 300, 48, 2, 0, 0, 9, 12345]], np.int32)
    for dt in (jnp.float32, jnp.bfloat16):
        probs = jnp.full((2, V), 0.5, dt)
        rows = np.asarray(eng._pack_counts(probs, jnp.asarray(counts)))
        assert rows.shape == (4, V) and rows.dtype == dt
        back, got = eng._unpack_counts(rows)
        assert back.shape == (2, V) and (got == counts).all()
    with pytest.raises(ValueError, match="65,536 tokens or more"):
        DecodeScheduler(net, V, n_slots=2, prefill_chunk=65536,
                        kv_block=BLOCK, kv_pool_mb=pool_mb(8))


def test_a_trie_hit_on_latent_pages_serves_the_same_tokens(small):
    """The same 40-token prompt twice: the second request is given the
    first's five pages of latent rows by a table remap and prefills
    nothing but the tail."""
    fam, params, net = small
    s = Served(net, blocks=40, n_slots=1)
    try:
        prompt = _prompt(45, 4)
        h1 = s.eng.submit(prompt, 12)
        t1 = h1.result(300)
        fed1 = s.counters()["prefill_tokens_total"]
        h2 = s.eng.submit(prompt, 12)
        t2 = h2.result(300)
        c = s.counters()
        ref = _ref_logprobs(fam, params, prompt, t1)
    finally:
        s.eng.stop()
    assert t1 == t2 == ref.argmax(-1).tolist()
    assert c["prefix_cache_hits_total"] == 1
    assert c["prefix_cache_hit_tokens_total"] == 40
    assert fed1 == 45 and c["prefill_tokens_total"] == 45 + 5
    assert np.abs(s.logprobs(h2) - ref).max() < TOL


def test_preempt_and_resume_reproduces_the_tokens(small):
    """Three requests grow from 20 to 100 positions (13 blocks each) in a
    pool of 30: the latest is preempted, gives its pages back, and resumes
    by prefilling its prompt and its own tokens so far."""
    fam, params, net = small
    prompts = [_prompt(20, 10 + i) for i in range(3)]
    solo = Served(net, blocks=30, n_slots=1)
    try:
        alone = [solo.eng.submit(p, 80).result(300) for p in prompts]
    finally:
        solo.eng.stop()
    s = Served(net, blocks=30, n_slots=3)
    try:
        hs = [s.eng.submit(p, 80) for p in prompts]
        got = [h.result(600) for h in hs]
        assert s.counters()["decode_preempted_total"] >= 1
    finally:
        s.eng.stop()
    assert got == alone
    assert alone[0] == _ref_logprobs(fam, params, prompts[0],
                                     alone[0]).argmax(-1).tolist()


def test_a_pool_block_costs_one_leaf(small):
    """`block x (kv_lora_rank + rope) x itemsize` a layer, where the same
    heads as keys and values would cost `2 x block x 4 x (24 + 16)`."""
    _, _, net = small
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16, kv_block=BLOCK,
                          kv_pool_mb=pool_mb(12), metrics=MetricsRegistry())
    assert eng.pool.bytes_per_block == BLOCK_BYTES == 3 * BLOCK * 24 * 4
    assert eng.pool.capacity_blocks == 12
    for key in ("attn0", "attn1", "attn2"):
        st = eng._states[key]
        assert sorted(st) == ["c_pages", "pos"]
        assert st["c_pages"].shape == (13, BLOCK, 24)
    assert eng.paged_kernel_status()["engaged"] is False
    assert eng._moe == ["moe1", "moe2"] and eng._moe_held == 8


@pytest.mark.parametrize("kw,what", [
    (dict(kv_pool_mb=pool_mb(8), kv_dtype="int8"), "int8 pages"),
    (dict(kv_pool_mb=pool_mb(8), mesh=2), "a tp mesh"),
    (dict(kv_pool_mb=pool_mb(8), speculate=2), "speculation"),
    (dict(), "a contiguous cache"),
    (dict(kv_pool_mb=pool_mb(8), speculate=2, kv_dtype="int8"),
     "int8 pages.*speculation"),
])
def test_the_refusals_name_the_layer(small, kw, what):
    _, _, net = small
    with pytest.raises(ValueError,
                       match=f"LatentAttentionLayer 'attn0'.*{what}"):
        DecodeScheduler(net, V, n_slots=1, prefill_chunk=16, kv_block=BLOCK,
                        metrics=MetricsRegistry(), **kw)


def test_rnn_time_step_and_a_pool_of_one_block_are_refused(small):
    _, _, net = small
    with pytest.raises(NotImplementedError,
                       match="LatentAttentionLayer.*rnn_time_step"):
        net.rnn_time_step(jax.nn.one_hot(jnp.zeros((1, 1), jnp.int32), V))
    net.rnn_clear_previous_state()
    with pytest.raises(ValueError, match="LatentAttentionLayer is served "
                                         "through the paged pool only"):
        DecodeScheduler(net, V, n_slots=1, prefill_chunk=16, kv_block=BLOCK,
                        kv_pool_mb=pool_mb(0), metrics=MetricsRegistry())


def test_a_bfloat16_engine_would_fail_the_tolerance():
    fam, params, net = load(dtype="bfloat16", conf=SHARE)
    s = Served(net, blocks=40, itemsize=2)
    try:
        prompt = _prompt(50, 3)
        h = s.eng.submit(prompt, 8)
        tokens = h.result(300)
        gap = np.abs(s.logprobs(h)
                     - _ref_logprobs(fam, params, prompt, tokens)).max()
        assert gap > 100 * TOL
    finally:
        s.eng.stop()
