"""The latent pool write of `LatentAttentionLayerImpl._paged_step` (ISSUE
37: `_write_rows`, one whole page row an entry) against the write it
replaced, kept here as the reference: one window of ``C + dr`` lanes a
position at ``(page, off // k, (off % k) (C + dr))`` (`lax.scatter`), which
compiled to a ``while`` of one trip a position on a v5e. After one step,
every page but the scratch page is the same bytes, and the scratch page
holds finite rows. The layer is `test_latent_paged_read`'s (a row of 64, so
two positions share a 128-wide page row, as two of A.X-K1's 576 share
1,152); a block of 5 positions holds no whole number of packed rows, the
``k = 1`` layout; a row of 24 packs sixteen positions to a 384-wide row,
as the engine tests' small A.X-K1 does. Last, the lowered program: every
scatter into the pool has a window of the row's whole last dimension."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.layers import LatentAttentionLayer
from deeplearning4j_tpu.nn.layers.base import impl_for

HEADS, C, DR, NB = 4, 48, 16, 8
ROW = C + DR


def _layer(c=C, dr=DR):
    impl = impl_for(LatentAttentionLayer(
        n_in=64, n_out=64, n_heads=HEADS, q_lora_rank=24, kv_lora_rank=c,
        qk_nope_head_dim=16, qk_rope_head_dim=dr, v_head_dim=16,
        activation="identity"))
    return impl, impl.init_params(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def layer():
    return _layer()


def _window_scatter(impl, params, x, st):
    """The write before ISSUE 37: a position's row is zeroed where
    ``wmask`` holds it off, and goes as one window of R lanes."""
    B, T, _ = x.shape
    cp, pos, wmask = st["c_pages"], st["pos"], st.get("wmask")
    _, _, rows = impl._project(params, x, pos0=pos)
    R = rows.shape[2]
    k = cp.shape[2] // R
    Bk = cp.shape[1] * k
    p = pos[:, None] + jnp.arange(T, dtype=pos.dtype)[None, :]
    blk, off = impl._page_of(st["table"], p, Bk, wmask)
    if wmask is not None:
        rows = jnp.where(wmask[..., None], rows, 0)
    return jax.lax.scatter(
        cp, jnp.stack([blk, off // k, off % k * R], -1).reshape(-1, 3),
        rows.reshape(-1, R),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2)))


# (depths, T, block, live lanes or None[, row]): at a row of 64 a block of
# 8 is k = 2, of 5 k = 1; at a row of 24 a block of 16 is k = 16
CASES = {
    "T1-several-slots": ([0, 5, 13, 30, 63], 1, 8, None),
    "T1-lanes-held-off": ([4, 9, 21], 1, 8, [[1], [0], [1]]),
    "chunk-even-start": ([4], 12, 8, None),
    "chunk-odd-start": ([3], 12, 8, None),
    "odd-T": ([2, 7], 7, 8, None),
    "even-T-across-pages": ([6, 1], 16, 8, None),
    "long-chunk-mid-page": ([13], 40, 8, None),
    "lanes-held-off": ([3, 8, 0], 5, 8,
                       [[1, 1, 0, 1, 0], [0, 0, 0, 0, 0], [1, 1, 1, 0, 0]]),
    "overflow": ([60, 57], 7, 8, None),
    "k1-layout": ([3, 10], 6, 5, None),
    "k1-held-off-and-overflow": ([36, 2], 5, 5, [[1, 1, 1, 1, 1],
                                                 [1, 0, 1, 1, 0]]),
    "k16-chunk-across-pages": ([5, 30], 21, 16, [[1] * 21, [1] * 17 + [0] * 4],
                               24),
    "k16-decode": ([0, 15, 16, 77], 1, 16, None, 24),
}


def _state(depths, T, block, live, seed, row=ROW):
    """Random finite pages, each slot's table a random draw of its own
    pages; page 0 the scratch page."""
    rng = np.random.default_rng(seed)
    B = len(depths)
    k = 128 // np.gcd(row, 128)
    k = k if block % k == 0 else 1
    pages = 1 + B * NB + 3
    st = {"c_pages": jnp.asarray(
              rng.normal(size=(pages, block // k, k * row)), jnp.float32),
          "pos": jnp.asarray(depths, jnp.int32),
          "table": jnp.asarray(
              1 + rng.permutation(pages - 1)[:B * NB].reshape(B, NB),
              jnp.int32)}
    if live is not None:
        st["wmask"] = jnp.asarray(live, bool)
    return st


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("seed", [0, 1])
def test_the_write_is_the_window_scatter_s_bytes(layer, case, seed):
    depths, T, block, live, *row = CASES[case]
    row = row[0] if row else ROW
    impl, params = layer if row == ROW else _layer(16, row - 16)
    st = _state(depths, T, block, live, seed, row)
    assert impl._rows_packed(block) == st["c_pages"].shape[2] // row
    x = jnp.asarray(np.random.default_rng(seed + 9).normal(
        size=(len(depths), T, 64)), jnp.float32)
    step = jax.jit(lambda p, x, st: impl._paged_step(
        p, x, {**st, "paged_kernel": "off"})[1]["c_pages"])
    got = np.asarray(step(params, x, st))
    want = np.asarray(jax.jit(
        lambda p, x, st: _window_scatter(impl, p, x, st))(params, x, st))
    np.testing.assert_array_equal(got[1:], want[1:])
    assert np.isfinite(got[0]).all()
    # the step wrote something: the test does not pass on an untouched pool
    assert not np.array_equal(got[1:], np.asarray(st["c_pages"])[1:])


def _scatters_into_the_pool(text, pool):
    """(update type, dimension numbers) of each scatter whose operand is a
    tensor of the pool's type in a lowered module's text."""
    out = []
    for m in re.finditer(r'"stablehlo\.scatter"\((.*?)\n\s*\}\)\s*:\s*'
                         r'\((.*?)\)\s*->', text, re.S):
        types = [t.strip() for t in re.split(r",\s*(?=tensor)", m[2])]
        if types[0] == pool:
            dims = re.search(r"#stablehlo\.scatter<(.*?)>", m[1])
            out.append((types[2], dims[1]))
    return out


@pytest.mark.parametrize("slots,T", [(1, 24), (6, 1)],
                         ids=["chunk", "decode"])
def test_every_scatter_into_the_pool_is_a_whole_row(layer, slots, T):
    """The lowered StableHLO on any backend: each scatter into ``c_pages``
    takes windows of the row's whole 128 lanes at ``(page, row)``, the
    form the compiler makes natively."""
    impl, params = layer
    st = _state([3] * slots, T, 8, None, 0)
    pool = "tensor<{}xf32>".format("x".join(map(str, st["c_pages"].shape)))
    x = jnp.zeros((slots, T, 64), jnp.float32)
    text = jax.jit(lambda p, x, st: impl._paged_step(
        p, x, {**st, "paged_kernel": "off"})).lower(params, x, st).as_text()
    scatters = _scatters_into_the_pool(text, pool)
    assert scatters
    for upd, dims in scatters:
        assert upd.endswith(f"x{2 * ROW}xf32>"), upd
        assert re.search(r"inserted_window_dims = \[0, 1\]", dims), dims
        assert re.search(r"update_window_dims = \[\d+\]", dims), dims
