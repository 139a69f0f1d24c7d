"""`LatentAttentionLayer` (ISSUE 34) at the tests' small size (hidden 64, 4
heads of 16 nope + 8 rope query/key dims and 16 value dims, ranks 24 / 16),
seeded weights, against the `axk1` family's plain reference: the layer's full
forward, its two forms, YaRN's frequencies and scale against the written
formula, and chunked prefill then decode through the one paged leaf."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from axk1_util import BLOCK, CFG, ROW, load

from deeplearning4j_tpu.nn.layers.attention import LatentAttentionLayerImpl

T = 45
# A.X-K1's published widths, for the tests that hold the written numbers
WIDTHS = dict(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
              qk_rope_head_dim=64, v_head_dim=128)


@pytest.fixture(scope="module")
def small():
    fam, params, net = load()
    impl = net._impls["attn1"]
    assert isinstance(impl, LatentAttentionLayerImpl)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, T, CFG["hidden_size"]))
    return fam, params["blocks"][1], impl, net.params["attn1"], x


def _ref_dims(fam):
    yarn = CFG["rope_scaling"]
    return {"H": 4, "dn": 16, "dr": 8, "dv": 16, "C": 16, "eps": 1e-6,
            "theta": 10000.0,
            "yarn": tuple(sorted((k, v) for k, v in yarn.items()
                                 if k != "type"))}


def test_the_full_forward_is_the_reference_s_mla(small):
    fam, p, impl, lp, x = small
    with jax.default_matmul_precision("highest"):
        want = fam.reference._mla(x, p, _ref_dims(fam), None)
        got, _ = impl.forward(lp, x)
    # float32 both sides, sums in another order: measured 3e-7
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 3e-6


@pytest.mark.parametrize("qblock", [128, 8], ids=["whole", "query-blocks"])
def test_expanded_and_absorbed_are_the_same_function(small, qblock,
                                                     monkeypatch):
    _, _, impl, lp, x = small
    monkeypatch.setattr(LatentAttentionLayerImpl, "_QBLOCK", qblock)
    xx = x[:, :40]
    q_n, q_r, rows = impl._project(lp, xx)
    t = jnp.arange(40)
    valid = jnp.broadcast_to((t[None, :] <= t[:, None])[None], (2, 40, 40))
    with jax.default_matmul_precision("highest"):
        a = impl._absorbed(lp, q_n, q_r, rows, valid)
        b = impl._expanded(lp, q_n, q_r, rows, valid)
    assert a.shape == b.shape == (2, 40, 4, 16)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < 2e-6
    assert float(jnp.abs(a).max()) > 1e-2


def test_a_paged_step_never_rebuilds_keys_or_values(small, monkeypatch):
    """Whatever T: the chip measured the absorbed form cheaper at every
    chunk and depth (PERF.md section 6, PR 34), so `_expanded` serves the
    full forward alone."""
    _, _, impl, lp, x = small

    def never(*a, **k):
        raise AssertionError("a paged step rebuilt keys and values")

    monkeypatch.setattr(LatentAttentionLayerImpl, "_expanded", never)
    for t in (1, 16):
        _paged(impl, lp, x[:, :32 + t], t)
    with pytest.raises(AssertionError, match="rebuilt"):
        impl.forward(lp, x)


def test_yarn_frequencies_and_scale_are_the_written_formula(small):
    """A.X-K1's own numbers: 64 rope dims at theta 10,000, factor 32 over
    4,096 positions, beta 32 / 1, mscale = mscale_all_dim = 1."""
    from deeplearning4j_tpu.nn.conf.layers import LatentAttentionLayer
    fam = small[0]
    impl = LatentAttentionLayerImpl(LatentAttentionLayer(
        n_in=7168, n_out=7168, n_heads=64, **WIDTHS, yarn_factor=32.0,
        yarn_original_max=4096, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        yarn_mscale=1.0, yarn_mscale_all_dim=1.0))
    i = np.arange(32)
    plain = 10000.0 ** (-2.0 * i / 64)
    dim = lambda r: 64 * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(1e4))
    low, high = math.floor(dim(32)), math.ceil(dim(1))
    assert (low, high) == (10, 23)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = plain / 32 * ramp + plain * (1 - ramp)
    got = np.asarray(impl._inv_freq())
    assert np.allclose(got, want, rtol=1e-6)
    assert np.allclose(got[:11], plain[:11]) and np.allclose(
        got[23:], plain[23:] / 32)
    yarn = (("beta_fast", 32), ("beta_slow", 1), ("factor", 32),
            ("mscale", 1), ("mscale_all_dim", 1),
            ("original_max_position_embeddings", 4096))
    assert np.allclose(np.asarray(fam.reference._inv_freq(64, 1e4, yarn)),
                       want, rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert impl._scale() == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    # without YaRN both fall back to plain RoPE and 1/sqrt(dn + dr)
    off = LatentAttentionLayerImpl(LatentAttentionLayer(
        n_in=7168, n_out=7168, n_heads=64, **WIDTHS))
    assert np.allclose(np.asarray(off._inv_freq()), plain, rtol=1e-6)
    assert off._scale() == pytest.approx(192 ** -0.5)


def _paged(impl, lp, x, chunk, block=BLOCK):
    """x [B, T, d] through `_paged_step`: chunks of `chunk` rows (the last
    one padded and masked), then one row at a time from row 32 on; each
    slot's pages interleaved in the pool."""
    B, n = x.shape[0], x.shape[1]
    nb = -(-(n + chunk) // block)
    table = (1 + jnp.arange(B * nb, dtype=jnp.int32)).reshape(nb, B).T
    page, dt = impl.paged_leaves(block, "float32")["c_pages"]
    state = {"c_pages": jnp.zeros((B * nb + 1,) + page, dt),
             "pos": jnp.zeros((B,), jnp.int32)}
    outs, at = [], 0
    while at < n:
        step = chunk if at < 32 else 1
        real = min(step, n - at)
        xs = jnp.pad(x[:, at:at + real], ((0, 0), (0, step - real), (0, 0)))
        wmask = jnp.broadcast_to(jnp.arange(step) < real, (B, step))
        y, st = impl._paged_step(lp, xs, {**state, "table": table,
                                          "wmask": wmask})
        state = {"c_pages": st["c_pages"], "pos": state["pos"] + real}
        outs.append(y[:, :real])
        at += real
    return jnp.concatenate(outs, 1), state


@pytest.mark.parametrize("form", ["absorbed", "expanded"])
@pytest.mark.parametrize("chunk,slots,block",
                         [(16, 16, 8), (8, 16, 8), (16, 1, 8), (16, 16, 16)],
                         ids=["16", "8", "16-slot-groups", "16-packed-rows"])
def test_chunked_prefill_then_decode_through_the_paged_leaf(small, form,
                                                            chunk, slots,
                                                            block,
                                                            monkeypatch):
    """Two chunks (or four) of prompt, then 13 single rows, through one
    leaf of [latent | rotated key] rows, against the full forward: as the
    step attends (absorbed), and with the expanded form put in its place
    (the same function under the paged mask too)."""
    _, _, impl, lp, x = small
    with jax.default_matmul_precision("highest"):
        want, _ = impl.forward(lp, x)
    if form == "expanded":
        monkeypatch.setattr(LatentAttentionLayerImpl, "_absorbed",
                            LatentAttentionLayerImpl._expanded)
    # the tables of `_SLOTS` slots are gathered at a time: both of them
    # at once, or one after the other
    monkeypatch.setattr(LatentAttentionLayerImpl, "_SLOTS", slots)
    with jax.default_matmul_precision("highest"):
        got, state = _paged(impl, lp, x, chunk, block)
    # float32, other order of sums: measured 4e-7. Scores rounded to
    # bfloat16 read 3.5e-5, nine times the tolerance (next test)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 4e-6
    assert state["pos"].tolist() == [T, T]
    # a page's last dimension: the rows of k positions side by side, k the
    # fewest that make it a multiple of 128 (16 at this row of 24, which a
    # block of 16 holds and a block of 8 does not)
    pages = np.asarray(state["c_pages"])
    assert pages.shape[1:] == ((1, 16 * ROW) if block == 16 else (8, ROW))
    assert not pages[0].any() and np.abs(pages[1:1 + 2 * 3]).max() > 0


def test_a_bfloat16_score_path_fails_that_tolerance(small, monkeypatch):
    _, _, impl, lp, x = small
    softmax = LatentAttentionLayerImpl._softmax
    monkeypatch.setattr(
        LatentAttentionLayerImpl, "_softmax",
        lambda self, s, valid: softmax(
            self, s.astype(jnp.bfloat16).astype(jnp.float32), valid))
    with jax.default_matmul_precision("highest"):
        got, _ = _paged(impl, lp, x, 16)
    monkeypatch.undo()
    with jax.default_matmul_precision("highest"):
        want, _ = impl.forward(lp, x)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() > 5 * 4e-6


def test_one_leaf_and_no_contiguous_stripe(small):
    _, _, impl, lp, x = small
    assert impl.paged_leaves(8, "float32") == {
        "c_pages": ((8, ROW), jnp.dtype("float32"))}
    assert impl.paged_leaves(32, "bfloat16") == {
        "c_pages": ((2, 16 * ROW), jnp.dtype("bfloat16"))}
    from deeplearning4j_tpu.nn.conf.layers import LatentAttentionLayer
    real = LatentAttentionLayerImpl(LatentAttentionLayer(
        n_in=7168, n_out=7168, n_heads=64, **WIDTHS))
    assert real.paged_leaves(64, "bfloat16") == {
        "c_pages": ((32, 1152), jnp.dtype("bfloat16"))}
    assert impl.page_recycling() is None and impl.blocks_needed(17, 8) == 3
    # the parent's rule with this layer's page (ISSUE 35; the read itself
    # is tests/test_latent_paged_read.py's): 73,728 B at the real widths
    engages = impl.fused_read_engages
    assert engages("on", 1, jnp.float32, slots=2, pages=4, block=8)
    assert not engages("on", 16, jnp.float32, slots=2, pages=4, block=8)
    assert not engages("off", 1, jnp.float32, slots=2, pages=4, block=8)
    assert real._position_values() * 64 * 2 == 73728
    with pytest.raises(ValueError, match="LatentAttentionLayer.*int8"):
        impl.paged_leaves(8, "float32", "int8")
    with pytest.raises(NotImplementedError,
                       match="LatentAttentionLayer.*rnn_time_step"):
        impl.forward_with_state(lp, x[:, :1], impl.init_state(2))


@pytest.mark.parametrize("left_out", sorted(WIDTHS))
def test_a_width_is_the_model_s_and_has_no_default(left_out):
    from deeplearning4j_tpu.nn.conf.layers import LatentAttentionLayer
    given = {k: v for k, v in WIDTHS.items() if k != left_out}
    with pytest.raises(ValueError,
                       match=f"LatentAttentionLayer needs {left_out}"):
        LatentAttentionLayer(n_in=64, n_out=64, n_heads=4, **given)
