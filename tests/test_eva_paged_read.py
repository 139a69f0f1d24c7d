"""The fused paged read of `EvaAttentionLayerImpl._paged_step` at T = 1
(ISSUE 31, `ops/paged_read.py`, here through the Pallas interpreter) against
the gather body it stands in for, on one set of pages: the layer's own step
with ``paged_kernel`` ``"on"`` and ``"off"``. Window 32, chunk 4, 4 heads of
16, pages of 16 rows (so a window's 8 summaries half fill a page), a table
bucket of 8 blocks: two exact pages and two summary pages a slot."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.layers import EvaAttentionLayer
from deeplearning4j_tpu.nn.layers.base import impl_for
from deeplearning4j_tpu.ops.paged_read import paged_read_attention

WINDOW, CHUNK, BLOCK, NB, HEADS, DH = 32, 4, 16, 8, 4, 16
NS = NB // CHUNK
PAGES = 1 + 3 * (NB + NS) + 4     # scratch, three slots' tables, spare

# What the two paths' own difference reads on the layer's output (magnitude
# 0.7 to 2.3) over these cases and four seeds of pages each. float32: the
# order of the sums, 2e-7 to 1e-6. bfloat16: 0.0039 to 0.0078, one step of
# the output's own rounding (2^-8 at magnitude 1 to 2): the gather body also
# rounds its scores to bfloat16 before the softmax, the fused read keeps them
# float32. The limits: ten times the float32 reading, four times the other
TOL = {"float32": 1e-5, "bfloat16": 3e-2}


@pytest.fixture(scope="module")
def layer():
    impl = impl_for(EvaAttentionLayer(
        n_in=HEADS * DH, n_out=HEADS * DH, n_heads=HEADS, rope=True,
        rope_base=1e5, window_size=WINDOW, chunk_size=CHUNK,
        activation="identity"))
    return impl, impl.init_params(jax.random.PRNGKey(3))


def _state(depths, live, dtype, seed=0):
    """Pages as an engine would have left them at these depths (random
    finite rows everywhere), each slot's table and summary table its own."""
    rng = np.random.default_rng(seed)
    B = len(depths)
    shape = (PAGES, BLOCK, HEADS, DH)
    table = 1 + np.arange(B * NB, dtype=np.int32).reshape(B, NB)
    stable = 1 + B * NB + np.arange(B * NS, dtype=np.int32).reshape(B, NS)
    return {"k_pages": jnp.asarray(rng.normal(size=shape), dtype),
            "v_pages": jnp.asarray(rng.normal(size=shape), dtype),
            "pos": jnp.asarray(depths, jnp.int32),
            "table": jnp.asarray(table), "summary_table": jnp.asarray(stable),
            "wmask": jnp.asarray(live, bool)[:, None]}


def _unread(state):
    """Mask [PAGES, BLOCK] of the rows no live slot's step at these depths
    reads or summarises: beyond its count in a page it reads (and beyond
    the chunk the step summarises again), every row of every other page but
    the scratch page."""
    free = np.ones((PAGES, BLOCK), bool)
    free[0] = False
    pos, live = np.asarray(state["pos"]), np.asarray(state["wmask"])[:, 0]
    for b in np.flatnonzero(live):
        t = int(pos[b])
        w0, chunk_end = t // WINDOW * WINDOW, (t // CHUNK + 1) * CHUNK
        for n in range(w0, chunk_end):
            free[int(state["table"][b, n // BLOCK]), n % BLOCK] = False
        for c in range(w0 // CHUNK):
            free[int(state["summary_table"][b, c // BLOCK]), c % BLOCK] = False
        # the row this step writes the open chunk's summary to
        c = t // CHUNK
        free[int(state["summary_table"][b, c // BLOCK]), c % BLOCK] = False
    return free


def _step(layer, state, mode, x):
    impl, params = layer
    fn = jax.jit(lambda p, x, st: impl._paged_step(
        p, x, {**st, "paged_kernel": mode}))
    y, out = fn(params, x, state)
    return np.asarray(y, np.float32), out


def test_the_rule_that_engages_it(layer):
    """One query row a slot, bfloat16 or float32, no mesh, not "off"; on the
    CPU only "on" (interpreted), which is how every test here gets in."""
    engages = layer[0].fused_read_engages
    for dt in (jnp.float32, jnp.bfloat16):
        assert engages("on", 1, dt)
        assert not engages("auto", 1, dt)       # the backend is the CPU
        assert not engages("off", 1, dt)
        assert not engages("on", CHUNK, dt)     # a prefill chunk
        assert not engages("on", 1, dt, mesh=object())
    assert not engages("on", 1, jnp.float16)


CASES = {
    "inside_first_window": ([5], [True]),
    "last_row_of_a_window": ([WINDOW - 1], [True]),
    "first_row_after_the_roll": ([WINDOW], [True]),
    "three_closed_windows_half_a_summary_page": ([3 * WINDOW + 7], [True]),
    "two_slots_at_different_depths": ([3 * WINDOW + 21, 5], [True, True]),
    "a_slot_with_wmask_off": ([2 * WINDOW + 9, 40, 3], [True, False, True]),
    "no_slot_live": ([2 * WINDOW + 9, 40], [False, False]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_fused_read_is_the_gather_body(layer, case, dtype):
    depths, live = CASES[case]
    dt = jnp.dtype(dtype)
    impl, params = layer
    params = jax.tree_util.tree_map(lambda a: a.astype(dt), params)
    state = _state(depths, live, dt)
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(len(depths), 1, HEADS * DH)), dt)
    got, st_on = _step((impl, params), state, "on", x)
    ref, st_off = _step((impl, params), state, "off", x)
    on = np.asarray(live)
    assert np.isfinite(got).all()
    # a masked slot reads nothing: zeros, not 0/0 (no bias, identity)
    assert (got[~on] == 0).all()
    if on.any():
        assert np.abs(got[on] - ref[on]).max() < TOL[dtype]
        assert np.abs(ref[on]).max() > 0.1
    # the writes are the step's, not the read's: the same pages either way
    for name in ("k_pages", "v_pages", "pos", "summary_table"):
        assert np.array_equal(np.asarray(st_on[name], np.float32),
                              np.asarray(st_off[name], np.float32)), name


def test_rows_beyond_the_count_and_pages_of_count_0_are_not_used(layer):
    """Every row the step does not read holds NaN: beyond the count inside
    the pages it reads, and all of every page no count reaches (the exact
    pages past the depth, the summary pages past the closed windows, the
    masked slot's pages, the spare pages)."""
    depths, live = [3 * WINDOW + 7, 40, 5], [True, False, True]
    clean = _state(depths, live, jnp.float32)
    free = jnp.asarray(_unread(clean))[:, :, None, None]
    assert int(free.sum()) > PAGES * BLOCK // 2
    dirty = {**clean, "k_pages": jnp.where(free, jnp.nan, clean["k_pages"]),
             "v_pages": jnp.where(free, jnp.nan, clean["v_pages"])}
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(3, 1, HEADS * DH)), jnp.float32)
    got, _ = _step(layer, dirty, "on", x)
    ref, _ = _step(layer, clean, "off", x)
    assert np.isfinite(got).all()
    assert np.abs(got[[0, 2]] - ref[[0, 2]]).max() < TOL["float32"]
    # and the gather body does read them: the NaN is a real trap
    poisoned, _ = _step(layer, dirty, "off", x)
    assert np.isnan(poisoned[[0, 2]]).any()


def test_one_row_per_position_is_a_case_of_the_signature():
    """`rows = clip(pos + 1 - j * block, 0, block)` over a block table is
    plain causal paged decode, grouped heads included (ROADMAP Speed 1 (b)'s
    second caller): against a softmax over the gathered rows."""
    rng = np.random.default_rng(4)
    B, H, Hkv, nb = 3, 8, 2, 4
    q = jnp.asarray(rng.normal(size=(B, 1, H, DH)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(20, BLOCK, Hkv, DH)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(20, BLOCK, Hkv, DH)), jnp.float32)
    table = jnp.asarray(rng.permutation(19)[:B * nb].reshape(B, nb) + 1,
                        jnp.int32)
    pos = jnp.asarray([0, 37, nb * BLOCK - 1], jnp.int32)
    rows = jnp.clip(pos[:, None] + 1 - jnp.arange(nb)[None] * BLOCK, 0, BLOCK)
    got = paged_read_attention(q, kp, vp, table, rows, interpret=True)
    kc = kp[table].reshape(B, nb * BLOCK, Hkv, DH)
    vc = vp[table].reshape(B, nb * BLOCK, Hkv, DH)
    s = jnp.einsum("bhgd,bkhd->bhgk", q.reshape(B, Hkv, H // Hkv, DH), kc)
    seen = (jnp.arange(nb * BLOCK)[None] <= pos[:, None])[:, None, None]
    s = jnp.where(seen, s / jnp.sqrt(jnp.float32(DH)), -jnp.inf)
    ref = jnp.einsum("bhgk,bkhd->bhgd", jax.nn.softmax(s, -1), vc)
    assert np.abs(np.asarray(got).reshape(B, Hkv, H // Hkv, DH)
                  - np.asarray(ref)).max() < 1e-5
    with pytest.raises(ValueError, match="one query row"):
        paged_read_attention(jnp.zeros((B, 2, H, DH)), kp, vp, table, rows,
                             interpret=True)
