"""EvaAttentionLayer and the block around it (ISSUE 30): RMSNorm with the
unit offset, the gated FFN, float32 logits, and EVA's attention — a query
attends exactly over its own window and, in the same softmax, over one
summary per chunk of every earlier window — against the plain reference
`benchmark/families/evabyte/reference.py`, at the small CPU size."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from eva_util import CFG, CHUNK, WINDOW, load

from deeplearning4j_tpu.nn.conf import serde
from deeplearning4j_tpu.nn.conf.layers import (EvaAttentionLayer,
                                               LayerNormalization,
                                               RnnOutputLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.layers.base import impl_for

T = 150          # four whole windows of 32 and a part: 4 boundaries crossed


@pytest.fixture(scope="module")
def small():
    return load()


def _ids(rows=2, t=T, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (rows, t)).astype(np.int32)


def _net_logprobs(net, ids, dtype):
    x = jax.nn.one_hot(ids, CFG["vocab_size"], dtype=dtype)
    with jax.default_matmul_precision("highest"):
        out = net.output(x)
    out = out[0] if isinstance(out, (list, tuple)) else out
    return np.log(np.asarray(out, np.float64))


def _ref_logprobs(fam, params, ids):
    pos = np.tile(np.arange(ids.shape[1], dtype=np.int32), (len(ids), 1))
    lg = fam.reference.logits_at(params, CFG, jnp.asarray(ids),
                                 jnp.asarray(pos))
    return np.asarray(jax.nn.log_softmax(lg, -1), np.float64)


# float32 against float32 at `highest`: what is left is the order of the
# sums (the program's one softmax over [window | summaries] against the
# reference's, XLA's fused reductions). Measured 1e-6; a bfloat16 program
# reads 3e-2 and more (below)
TOL = 2e-5


def test_the_graph_s_forward_is_the_reference(small):
    fam, params, net = small
    ids = _ids()
    got, ref = _net_logprobs(net, ids, jnp.float32), \
        _ref_logprobs(fam, params, ids)
    assert got.shape == ref.shape == (2, T, CFG["vocab_size"])
    assert np.abs(got - ref).max() < TOL


def test_a_lower_precision_would_fail_the_tolerance():
    fam, params, net = load(dtype="bfloat16")
    ids = _ids(rows=1)
    got = _net_logprobs(net, ids, jnp.bfloat16)
    assert np.abs(got - _ref_logprobs(fam, params, ids)).max() > 100 * TOL


def test_the_layer_alone_is_the_reference_s_attention(small):
    """One layer, weights of its own: q, k, v, the two poolings and the one
    softmax, against the reference's `_summaries` and `_attention`."""
    fam, _, _ = small
    ref = fam.reference
    conf = EvaAttentionLayer(n_in=64, n_out=64, n_heads=4, rope=True,
                             rope_base=1e5, window_size=WINDOW,
                             chunk_size=CHUNK, activation="identity")
    impl = impl_for(conf)
    p = impl.init_params(jax.random.PRNGKey(3))
    assert sorted(p) == ["Wk", "Wo", "Wq", "Wv", "mu", "phi"]   # no bias
    p = {**p, "mu": 8 * p["mu"], "phi": -8 * p["phi"]}  # far from uniform
    x = jax.random.normal(jax.random.PRNGKey(4), (2, T, 64))
    with jax.default_matmul_precision("highest"):
        y, _ = impl.forward(p, x)
        q, k, v = (ref._rope((x @ p[w]).reshape(2, T, 4, 16), 1e5)
                   if w != "Wv" else (x @ p[w]).reshape(2, T, 4, 16)
                   for w in ("Wq", "Wk", "Wv"))
        ks, vs = ref._summaries(k, v, p["mu"], p["phi"], CHUNK)
        want = ref._attention(q, k, v, ks, vs, WINDOW, CHUNK) \
            .reshape(2, T, 64) @ p["Wo"]
    assert np.abs(np.asarray(y) - np.asarray(want)).max() < 1e-5


def test_what_a_query_sees(small):
    """The mask, read off the function: a change to position n moves the
    output at t exactly where n is in t's own window up to t, or in a chunk
    of an earlier window (through that chunk's summary) — so every earlier
    position is seen one way or the other, and no later one."""
    conf = EvaAttentionLayer(n_in=16, n_out=16, n_heads=2, rope=True,
                             window_size=8, chunk_size=2,
                             activation="identity")
    impl = impl_for(conf)
    p = impl.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 20, 16))
    y0, _ = impl.forward(p, x)
    for n in (0, 5, 8, 13):
        y1, _ = impl.forward(p, x.at[0, n].add(1.0))
        moved = np.abs(np.asarray(y1 - y0)).max(-1)[0] > 1e-7
        assert not moved[:n].any() and moved[n:].all(), n
    # and the summaries are not the exact rows: with the window as long as
    # the sequence it is plain causal attention, with a short one it is not
    full = impl_for(SelfAttentionLayer(n_in=16, n_out=16, n_heads=2,
                                       rope=True, causal=True,
                                       activation="identity"))
    pf = {**{k_: p[k_] for k_ in ("Wq", "Wk", "Wv", "Wo")},
          "b": jnp.zeros(16)}
    yf, _ = full.forward(pf, x)
    one = impl_for(EvaAttentionLayer(n_in=16, n_out=16, n_heads=2,
                                     rope=True, window_size=32, chunk_size=2,
                                     activation="identity"))
    assert np.abs(np.asarray(one.forward(p, x)[0] - yf)).max() < 1e-5
    assert np.abs(np.asarray(y0 - yf))[0, :8].max() < 1e-5
    assert np.abs(np.asarray(y0 - yf))[0, 8:].max() > 1e-3


@pytest.mark.parametrize("depth,held", [
    (1, 2), (64, 2), (65, 3), (2048, 34), (2049, 4), (8192 + 100, 11),
    (16384, 48), (16640, 21)])
def test_blocks_needed_at_evabyte_s_geometry(depth, held):
    """Exact blocks of the open window plus a summary page per 64 chunks
    begun: 48 at 16,384 positions where a full cache holds 256."""
    eva = impl_for(EvaAttentionLayer(n_in=8, n_out=8, n_heads=1,
                                     window_size=2048, chunk_size=16))
    full = impl_for(SelfAttentionLayer(n_in=8, n_out=8, n_heads=1))
    assert eva.blocks_needed(depth, 64) == held
    assert full.blocks_needed(depth, 64) == -(-depth // 64)
    assert eva.page_recycling() == (2048, 16)
    assert full.page_recycling() is None


def test_rmsnorm_with_the_unit_offset():
    conf = LayerNormalization(n_in=6, n_out=6, eps=1e-5, rms=True,
                              unit_offset=True, activation="identity")
    impl = impl_for(conf)
    p = impl.init_params(jax.random.PRNGKey(0))
    assert list(p) == ["gain"] and not np.asarray(p["gain"]).any()
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 6)) + 2.0
    g = jnp.linspace(-0.5, 0.5, 6)
    y, _ = impl.forward({"gain": g}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * (1 + g)
    assert np.allclose(y, want, atol=1e-6)
    # the mean stays: not LayerNorm
    assert abs(float(jnp.mean(impl.forward(p, x)[0]))) > 0.5
    plain = impl_for(LayerNormalization(n_in=6, n_out=6))
    assert sorted(plain.init_params(jax.random.PRNGKey(0))) == \
        ["beta", "gain"]


def test_float32_logits_under_a_bfloat16_net():
    x = jnp.ones((1, 2, 8), jnp.bfloat16)
    for dt, want in ((None, jnp.bfloat16), ("float32", jnp.float32)):
        impl = impl_for(RnnOutputLayer(n_in=8, n_out=5, activation="softmax",
                                       logits_dtype=dt))
        p = impl.init_params(jax.random.PRNGKey(0), jnp.bfloat16)
        y, z, _ = impl.forward_with_preout(p, x)
        assert y.dtype == z.dtype == want


@pytest.mark.parametrize("conf", [
    EvaAttentionLayer(n_in=8, n_out=8, n_heads=2, window_size=64,
                      chunk_size=4, rope=True),
    LayerNormalization(n_in=8, n_out=8, rms=True, unit_offset=True),
    RnnOutputLayer(n_in=8, n_out=4, logits_dtype="float32")],
    ids=lambda c: type(c).__name__)
def test_the_new_conf_fields_round_trip(conf):
    assert serde.from_json(serde.to_json(conf)) == conf


def test_streaming_without_the_pool_says_so(small):
    _, _, net = small
    x = jax.nn.one_hot(_ids(1, 4), CFG["vocab_size"])
    with pytest.raises(NotImplementedError, match="paged pool"):
        net.rnn_time_step(x)
