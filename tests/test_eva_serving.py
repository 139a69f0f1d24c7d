"""EvaByte's block through the serving path (ISSUE 30): `submit` -> `_admit`
-> chunked prefill -> `_step_once`, the paged pool with two lifetimes of
page. What each served token was sampled from is compared with the plain
reference's full forward, on log-probabilities; what each request holds in
the pool is compared with its layers' `blocks_needed` after every
scheduler iteration. Small CPU size: hidden 64, 4 heads of 16, window 32,
chunk 4, block 8, 2 layers, vocabulary 320, prefill chunks of 16 (so every
second chunk boundary is a window boundary)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eva_util import BLOCK, CFG, WINDOW, load, pool_mb

from deeplearning4j_tpu.analysis import CompileCounter
from deeplearning4j_tpu.inference import (DecodeScheduler, MetricsRegistry,
                                          PromptTooLongError)
from deeplearning4j_tpu.inference.trace import FlightRecorder

V = CFG["vocab_size"]


@pytest.fixture(scope="module")
def small():
    return load()


class Served:
    """An engine whose every sampled-from distribution and every
    iteration's pool accounting is kept."""

    def __init__(self, net, blocks, n_slots=2, itemsize=4, **kw):
        self.eng = eng = DecodeScheduler(
            net, V, n_slots=n_slots, prefill_chunk=16, kv_block=BLOCK,
            kv_pool_mb=pool_mb(blocks, itemsize), metrics=MetricsRegistry(),
            tracer=FlightRecorder(1 << 15), **kw)
        assert eng.paged and eng.pool.capacity_blocks == blocks
        self.rows, self.held, self.depths = {}, [], []
        consume, step = eng._consume, eng._step_once

        def consume_and_keep(slot, seq, probs_row):
            self.rows.setdefault(seq.handle.request_id, []).append(
                np.array(probs_row, np.float64))
            return consume(slot, seq, probs_row)

        def step_and_count():
            busy = step()
            live = [s for s in eng._slots if s is not None]
            want = sum(eng._blocks_held(s.written) for s in live)
            self.held.append((eng.pool.used_blocks, want))
            self.depths.append(sorted(s.written for s in live
                                      if s.sampling))
            return busy

        eng._consume, eng._step_once = consume_and_keep, step_and_count
        eng.start()

    def logprobs(self, handle):
        return np.log(np.stack(self.rows[handle.request_id]))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, V, n).tolist()


def _ref_logprobs(fam, params, prompt, tokens):
    ids = np.array([prompt + tokens], np.int32)
    pos = (len(prompt) - 1 + np.arange(len(tokens)))[None].astype(np.int32)
    lg = fam.reference.logits_at(params, CFG, jnp.asarray(ids),
                                 jnp.asarray(pos))
    return np.asarray(jax.nn.log_softmax(lg, -1), np.float64)[0]


# float32 engine against the float32 reference at `highest`: the order of
# the sums differs (chunked prefill, the gathered pages, XLA's CPU matmul),
# measured 2e-6. A bfloat16 engine reads 1e-2 and more (last test)
TOL = 2e-5


# the T = 1 read both ways: the gather body ("off", what "auto" is on the
# CPU) and the fused paged read (ISSUE 31), interpreted here
BOTH_READS = pytest.mark.parametrize("paged_kernel", ["off", "on"])


@BOTH_READS
def test_chunked_prefill_then_paged_decode_is_the_reference(small,
                                                            paged_kernel):
    """107 prompt bytes in chunks of 16 (boundaries at 16, 48, 80 inside a
    window; 32, 64, 96 on one; the last chunk is 11 bytes), then 60 decoded:
    the request crosses the window boundaries at 32, 64 and 96 while
    prefilling and 128 and 160 while decoding."""
    fam, params, net = small
    s = Served(net, blocks=40, paged_kernel=paged_kernel)
    assert s.eng.paged_kernel_status()["engaged"] == (paged_kernel == "on")
    try:
        prompt = _prompt(107, 0)
        h = s.eng.submit(prompt, 60)
        tokens = h.result(300)
        got, ref = s.logprobs(h), _ref_logprobs(fam, params, prompt, tokens)
        assert got.shape == ref.shape == (60, V)
        assert np.abs(got - ref).max() < TOL
        assert tokens == ref.argmax(-1).tolist()
        c = s.eng.metrics.snapshot()["counters"]
        assert c["eva_windows_rolled_total"] == 5
        assert c["eva_blocks_recycled_total"] == 5 * (WINDOW // BLOCK)
        # decode token j attends from position 107 + j - 1... the first comes
        # out of the last prefill chunk; 59 decode steps, positions 107..165
        pos = np.arange(107, 166)
        assert c["eva_rows_exact_total"] == int((pos % WINDOW + 1).sum())
        assert c["eva_rows_summary_total"] == int(
            (pos // WINDOW * (WINDOW // CFG["chunk_size"])).sum())
        assert c["prefix_publish_skipped_total"] == 1
        assert c["prefix_cache_lookups_total"] == 0
        rolls = [e for e in s.eng.tracer.events()
                 if e["name"] == "window_roll" and e["ph"] == "B"]
        assert [e["args"]["window"] for e in rolls] == [1, 2, 3, 4, 5]
        assert all(e["args"]["blocks_freed"] == 4 and
                   e["args"]["request"] == h.request_id for e in rolls)
        assert s.eng.profiler.phase_seconds["roll"] > 0
        # every program of the run within its family's budget: no window
        # or chunk boundary compiled one of its own
        CompileCounter.for_scheduler(s.eng).assert_within_budget()
    finally:
        s.eng.stop()
    assert all(used == want for used, want in s.held), s.held
    assert s.held[-1] == (0, 0) and s.eng.pool.used_blocks == 0


@BOTH_READS
def test_two_slots_at_different_depths_in_one_step(small, paged_kernel):
    """A request deep in its fourth window and one in its first decode in
    the same steps: per-slot windows, per-slot summary tables."""
    fam, params, net = small
    s = Served(net, blocks=40, paged_kernel=paged_kernel)
    try:
        a, b = _prompt(100, 1), _prompt(9, 2)
        ha, hb = s.eng.submit(a, 40), s.eng.submit(b, 40)
        ta, tb = ha.result(300), hb.result(300)
        for h, p, t in ((ha, a, ta), (hb, b, tb)):
            assert np.abs(s.logprobs(h)
                          - _ref_logprobs(fam, params, p, t)).max() < TOL
    finally:
        s.eng.stop()
    both = [d for d in s.depths if len(d) == 2]
    assert both and any(hi // WINDOW >= 3 and lo // WINDOW == 0
                        for lo, hi in both)
    assert all(used == want for used, want in s.held), s.held


@BOTH_READS
def test_pages_named_and_pages_read_are_counted(small, paged_kernel):
    """`eva_pages_bucket_total` is slots x (open-window pages + summary
    pages of the table bucket) a decode dispatch; `eva_pages_read_total` the
    pages holding a row a fed slot attends over where the fused read
    engages, the bucket's where it does not. 32 prompt bytes are two whole
    chunks, so every decode dispatch feeds one sampling slot: positions 32
    to 50, the second window, table bucket 8 (4 exact + 2 summary pages)."""
    _, _, net = small
    s = Served(net, blocks=40, paged_kernel=paged_kernel)
    try:
        assert len(s.eng.submit(_prompt(32, 5), 20).result(300)) == 20
        c = s.eng.metrics.snapshot()["counters"]
    finally:
        s.eng.stop()
    at = np.arange(32, 51)
    # depths seen after each iteration: 32 once the prompt is in, then one
    # more a decode dispatch, each fed at the depth before it
    seen = [d[0] for d in s.depths if d]
    assert seen[:19] == at.tolist() and len(seen) <= 20
    named = len(at) * 2 * (WINDOW // BLOCK + 8 // CFG["chunk_size"])
    summaries = at // WINDOW * (WINDOW // CFG["chunk_size"])
    read = int((-(-(at % WINDOW + 1) // BLOCK) + -(-summaries // BLOCK)).sum())
    assert (named, read) == (228, 52)
    assert c["eva_pages_bucket_total"] == named
    assert c["eva_pages_read_total"] == (read if paged_kernel == "on"
                                         else named)


@pytest.mark.parametrize("seam", ["unregistered", "registered"])
def test_a_bfloat16_one_row_per_position_step_is_untouched(seam):
    """Off the TPU under "auto" a bfloat16 `SelfAttentionLayer` step
    (grouped heads, RoPE) is the gather body: it lowers to the same text,
    with no kernel call in it, whether or not the `paged_decode_attention`
    seam is registered (it declines bfloat16), and `ops/paged_read` (its
    T = 1 read on a TPU since ISSUE 33) is not traced."""
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.base import impl_for
    from deeplearning4j_tpu.ops import helpers, pallas_kernels
    impl = impl_for(SelfAttentionLayer(n_in=64, n_out=64, n_heads=4,
                                       n_kv_heads=2, rope=True,
                                       activation="identity"))
    dt = jnp.bfloat16
    params = jax.tree_util.tree_map(
        lambda a: a.astype(dt), impl.init_params(jax.random.PRNGKey(0)))
    state = {"k_pages": jnp.zeros((9, BLOCK, 2, 16), dt),
             "v_pages": jnp.zeros((9, BLOCK, 2, 16), dt),
             "pos": jnp.asarray([3, 20], jnp.int32),
             "table": jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4),
             "wmask": jnp.ones((2, 1), bool)}

    def text():
        return jax.jit(lambda p, x, st: impl._paged_step(
            p, x, {**st, "paged_kernel": "auto", "mesh": None})).lower(
                params, jnp.zeros((2, 1, 64), dt), state).as_text()

    assert helpers.get_helper("paged_decode_attention") is None
    plain = text()
    if seam == "registered":
        pallas_kernels.enable_paged_decode(interpret=True)
        try:
            assert text() == plain
        finally:
            pallas_kernels.disable()
    assert "custom_call" not in plain and "paged_read" not in plain
    assert "gather" in plain


def test_preempt_and_resume_reproduces_the_tokens(small):
    """Three requests grow from 20 to 120 positions (7 blocks each at the
    close of their third window) in a pool of 16: the latest is preempted,
    gives every page back, exact and summary, and resumes by prefilling its
    prompt and its own tokens so far."""
    fam, params, net = small
    prompts = [_prompt(20, 10 + i) for i in range(3)]
    solo = Served(net, blocks=16, n_slots=1)
    try:
        alone = [solo.eng.submit(p, 100).result(300) for p in prompts]
    finally:
        solo.eng.stop()
    s = Served(net, blocks=16, n_slots=3)
    try:
        hs = [s.eng.submit(p, 100) for p in prompts]
        got = [h.result(600) for h in hs]
        assert s.eng.metrics.snapshot()["counters"][
            "decode_preempted_total"] >= 1
    finally:
        s.eng.stop()
    assert got == alone
    assert alone[0] == _ref_logprobs(fam, params, prompts[0],
                                     alone[0]).argmax(-1).tolist()
    assert all(used == want for used, want in s.held), s.held
    assert max(used for used, _ in s.held) <= 16 and s.held[-1] == (0, 0)


def test_what_the_engine_refuses(small):
    _, _, net = small
    with pytest.raises(ValueError, match="paged pool only"):
        DecodeScheduler(net, V, n_slots=1, prefill_chunk=16,
                        metrics=MetricsRegistry())
    with pytest.raises(ValueError, match="paged pool only"):
        DecodeScheduler(net, V, n_slots=1, prefill_chunk=16, kv_block=BLOCK,
                        kv_pool_mb=pool_mb(8), speculate=2,
                        metrics=MetricsRegistry())
    with pytest.raises(ValueError, match="never straddles"):
        DecodeScheduler(net, V, n_slots=1, prefill_chunk=64, kv_block=BLOCK,
                        kv_pool_mb=pool_mb(8), metrics=MetricsRegistry())
    with pytest.raises(ValueError, match="never straddles"):
        DecodeScheduler(net, V, n_slots=1, prefill_chunk=16, kv_block=3,
                        kv_pool_mb=pool_mb(8), metrics=MetricsRegistry())
    eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=16,
                          kv_block=BLOCK, kv_pool_mb=pool_mb(12),
                          metrics=MetricsRegistry())
    # 12 blocks hold a request of any depth (it never holds more than 4
    # exact blocks and a summary page a window), but its block table is one
    # entry per 8 positions: 97 cached positions are 13 entries (the last
    # byte sampled is never fed back, so 90 + 7 cache 96)
    assert eng._blocks_peak(96) == 7 and eng._blocks_peak(97) == 7
    eng.start()
    try:
        assert len(eng.submit(_prompt(90, 0), 7).result(300)) == 7
        with pytest.raises(PromptTooLongError):
            eng.submit(_prompt(90, 0), 8)
    finally:
        eng.stop()


def test_a_bfloat16_engine_would_fail_the_tolerance():
    fam, params, net = load(dtype="bfloat16")
    s = Served(net, blocks=40, itemsize=2)
    try:
        prompt = _prompt(70, 3)
        h = s.eng.submit(prompt, 8)
        tokens = h.result(300)
        assert s.rows[h.request_id][0].dtype == np.float64
        gap = np.abs(s.logprobs(h)
                     - _ref_logprobs(fam, params, prompt, tokens)).max()
        assert gap > 100 * TOL
    finally:
        s.eng.stop()
