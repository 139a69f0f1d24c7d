"""Nemotron-H's blocks through the serving path (ISSUE 36): `submit` ->
`_admit` -> chunked prefill -> `_step_once`, a fixed-size recurrent state a
slot beside the paged K/V pool, the write mask that keeps a padded lane's
state still, slot reuse, preemption, the prefix trie standing aside, and the
refusals. What each served token was sampled from is compared with the plain
reference's full forward, on log-probabilities. Small CPU size
(tests/nemotron_util.py): the pattern's first 13 letters `MEMEM*EMEMEM*`,
hidden 48, a share of 8 experts (4..11) of 16 held, vocabulary 96, block 8,
prefill chunks of 16."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nemotron_util import (BLOCK, CFG, N_MAMBA, N_MOE, STATE_BYTES, cfg, load,
                           pool_mb)

from deeplearning4j_tpu.analysis import CompileCounter
from deeplearning4j_tpu.inference import DecodeScheduler, MetricsRegistry
from deeplearning4j_tpu.inference.trace import FlightRecorder

V = CFG["vocab_size"]
SHARE = cfg(4, 8)
TOL = 5e-5      # float32 both sides; measured 2e-6


@pytest.fixture(scope="module")
def small():
    return load(conf=SHARE)


class Served:
    """An engine whose every sampled-from distribution is kept."""

    def __init__(self, net, blocks, n_slots=3, **kw):
        self.eng = eng = DecodeScheduler(
            net, V, n_slots=n_slots, prefill_chunk=16, kv_block=BLOCK,
            kv_pool_mb=pool_mb(blocks), metrics=MetricsRegistry(),
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
    ids = jnp.asarray([prompt + list(tokens)], jnp.int32)
    pos = jnp.asarray([len(prompt) - 1 + np.arange(len(tokens))], jnp.int32)
    return np.asarray(jax.nn.log_softmax(
        fam.reference.logits_at(params, SHARE, ids, pos), -1)[0])


def test_prefill_and_decode_follow_the_references_full_forward(small):
    """Prompts of 37 (chunks of 16, 16 and 5: the last padded to its bucket
    of 16 under the mask), 21 and 5 tokens together in three slots, then one
    of 50 admitted into a slot another just left; twelve tokens each. The
    logits after prefill and at every decoded position against the
    reference, which has no state, no cache and no chunks."""
    fam, params, net = small
    s = Served(net, blocks=40)
    try:
        eng = s.eng
        assert eng._ssm and len(eng._ssm) == N_MAMBA
        assert eng.slot_state_bytes == STATE_BYTES
        assert eng.debug_snapshot()["slot_state"] == {
            "layers": N_MAMBA, "bytes_per_slot": STATE_BYTES,
            "bytes": 3 * STATE_BYTES}
        prompts = [_prompt(n, 20 + n) for n in (37, 21, 5, 50)]
        handles = [eng.submit(p, 12) for p in prompts]
        for p, h in zip(prompts, handles):
            toks = h.result(timeout=600)
            assert len(toks) == 12
            got = s.logprobs(h)
            want = _ref_logprobs(fam, params, p, toks)
            assert got.shape == want.shape
            assert np.abs(got - want).max() < TOL, len(p)
        c = s.counters()
        # every decode dispatch names, and today steps, every slot's row
        assert c["ssm_rows_bucket_total"] == c["ssm_rows_stepped_total"] > 0
        assert c["ssm_rows_bucket_total"] % 3 == 0
        # a state row is live where a slot holds a request: the same gauge
        assert eng.metrics.gauge("decode_active_slots").max == 3
        # the routed blocks' counters and the pages' are registered too
        assert c["moe_pairs_routed_total"] % (3 * N_MOE) == 0
        assert 0 < c["moe_pairs_held_total"] < c["moe_pairs_routed_total"]
        assert c["kv_pages_bucket_total"] >= c["kv_pages_read_total"] > 0
    finally:
        s.eng.stop()


def test_a_reused_slot_equals_its_solo_run(small):
    """One slot: the second request starts from what admission zeroed, not
    from the first one's state; its tokens and distributions are its solo
    run's."""
    fam, params, net = small
    a, b = _prompt(30, 1), _prompt(19, 2)
    s = Served(net, blocks=20, n_slots=1)
    try:
        s.eng.submit(a, 6).result(timeout=600)
        hb = s.eng.submit(b, 8)
        tb = hb.result(timeout=600)
        again = s.logprobs(hb)
    finally:
        s.eng.stop()
    solo = Served(net, blocks=20, n_slots=1)
    try:
        h = solo.eng.submit(b, 8)
        assert h.result(timeout=600) == tb
        np.testing.assert_array_equal(solo.logprobs(h), again)
    finally:
        solo.eng.stop()
    assert np.abs(again - _ref_logprobs(fam, params, b, tb)).max() < TOL


def test_preemption_under_a_squeezed_pool_resumes_token_for_token(small):
    """Seven blocks for two requests that need five and four: the later one
    is swapped out, its pages freed and its state abandoned; on resume the
    prompt and its tokens so far are prefilled again from a zeroed state, and
    the tokens are those of an unsqueezed run."""
    fam, params, net = small
    a, b = _prompt(24, 3), _prompt(17, 4)
    wide = Served(net, blocks=40, n_slots=2)
    try:
        want = [wide.eng.submit(p, 14).result(timeout=600) for p in (a, b)]
    finally:
        wide.eng.stop()
    s = Served(net, blocks=7, n_slots=2)
    try:
        hs = [s.eng.submit(p, 14) for p in (a, b)]
        got = [h.result(timeout=600) for h in hs]
        assert s.counters()["decode_preempted_total"] >= 1
    finally:
        s.eng.stop()
    assert got == want
    ref = _ref_logprobs(fam, params, a, got[0])
    assert np.abs(s.logprobs(hs[0])[-14:] - ref).max() < TOL


def test_the_trie_restores_and_publishes_nothing_and_counts_it(small):
    """The same 32-token prompt twice: a trie hit would move `pos` past the
    hit with nothing holding the state at that position, so nothing is
    looked up, nothing adopted, and the second run prefills every token."""
    _, _, net = small
    p = _prompt(32, 5)
    s = Served(net, blocks=30, n_slots=1)
    try:
        t1 = s.eng.submit(p, 4).result(timeout=600)
        fed = s.counters()["prefill_tokens_total"]
        t2 = s.eng.submit(p, 4).result(timeout=600)
        c = s.counters()
        assert t1 == t2
        assert c["prefill_tokens_total"] == 2 * fed
        assert c["prefix_publish_skipped_total"] == 2
        assert c["prefix_cache_lookups_total"] == 0
        assert c["prefix_cache_hit_tokens_total"] == 0
        st = s.eng.pool.stats()
        assert st["used_blocks"] == 0 and st["trie"]["nodes"] == 0
    finally:
        s.eng.stop()


@pytest.mark.parametrize("kw, what", [
    ({"kv_dtype": "int8"}, "int8 pages"),
    ({"mesh": 2}, "a tp mesh"),
    ({"speculate": 2}, "speculation"),
    ({"kv_pool_mb": 0.0}, "a contiguous cache"),
    ({"prefill_chunk": 1}, "a contiguous cache"),
])
def test_what_is_not_served_yet_is_refused_by_the_layers_name(small, kw, what):
    _, _, net = small
    args = {"n_slots": 2, "prefill_chunk": 16, "kv_block": BLOCK,
            "kv_pool_mb": pool_mb(10), **kw}
    with pytest.raises(ValueError, match="Mamba2Layer 'mamba0'") as e:
        DecodeScheduler(net, V, metrics=MetricsRegistry(), **args)
    assert what in str(e.value)


def test_a_pool_of_no_two_blocks_is_refused(small):
    _, _, net = small
    with pytest.warns(RuntimeWarning, match="byte budget"):
        with pytest.raises(ValueError, match="state-space layer"):
            DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                            kv_block=BLOCK, kv_pool_mb=1e-4,
                            metrics=MetricsRegistry())


def test_the_program_family_is_fixed_and_the_state_is_donated(small):
    """One decode program a table bucket and one prefill program a (chunk,
    table) pair, as for every paged engine: the state rows add no program.
    The carried state goes into each program donated (the per-slot leaves
    beside the page arrays), so the old buffers are gone after a step."""
    _, _, net = small
    s = Served(net, blocks=24, n_slots=2)
    try:
        eng = s.eng
        eng.submit(_prompt(20, 6), 3).result(timeout=600)
        before = jax.tree_util.tree_leaves(eng._states)
        counts = CompileCounter.for_scheduler(eng).counts()
        eng.submit(_prompt(20, 7), 3).result(timeout=600)
        assert CompileCounter.for_scheduler(eng).counts() == counts
        assert all(leaf.is_deleted() for leaf in before)
    finally:
        s.eng.stop()
