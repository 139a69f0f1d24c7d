"""The fused paged read of `EvaAttentionLayerImpl._paged_step` at T = 1
(ISSUE 31, `ops/paged_read.py`, here through the Pallas interpreter) against
the gather body it stands in for, on one set of pages: the layer's own step
with ``paged_kernel`` ``"on"`` and ``"off"``. Below that, the same for
`SelfAttentionLayerImpl._paged_step` and its cache of one row per position
(ISSUE 33), with work items of several pages. Window 32, chunk 4, 4 heads of
16, pages of 16 rows (so a window's 8 summaries half fill a page), a table
bucket of 8 blocks: two exact pages and two summary pages a slot."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.layers import (EvaAttentionLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayerImpl
from deeplearning4j_tpu.nn.layers.base import impl_for
from deeplearning4j_tpu.ops import paged_read
from deeplearning4j_tpu.ops.paged_read import (pages_per_item,
                                               paged_read_attention)

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


# ---- the second caller (ISSUE 33): a cache of one row per position, at
# StarCoder2's head shapes, with work items of several pages ----
SC2 = {"3b": (24, 2), "7b": (36, 4)}    # query heads, KV heads; block 64
SC2_BLOCK, SC2_NB, SC2_DH = 64, 16, 128
# three slots' depths (positions held before this step's row) and which are
# fed: ends inside a page and on its edge; inside a group of 4 and of 8, on
# the edge of both, the whole table; nothing to read between two that have
ROW_CASES = {
    "inside_a_page_on_its_edge_and_a_first_row":
        ([2 * 64 + 17, 3 * 64 - 1, 0], [True, True, True]),
    "inside_a_group_on_its_edge_and_the_whole_table":
        ([10 * 64 + 5, 8 * 64 - 1, 16 * 64 - 1], [True, True, True]),
    "a_slot_with_no_row_between_two_with_rows":
        ([5 * 64 + 3, 300, 9 * 64], [True, False, True]),
}


def _one_row_per_position(shape, depths, live, dtype, seed=0):
    H, Hkv = SC2[shape]
    rng = np.random.default_rng(seed)
    B = len(depths)
    P = 1 + B * SC2_NB
    q = jnp.asarray(rng.normal(size=(B, 1, H, SC2_DH)), dtype)
    kp = jnp.asarray(rng.normal(size=(P, SC2_BLOCK, Hkv, SC2_DH)), dtype)
    vp = jnp.asarray(rng.normal(size=(P, SC2_BLOCK, Hkv, SC2_DH)), dtype)
    table = jnp.asarray(rng.permutation(P - 1).reshape(B, SC2_NB) + 1,
                        jnp.int32)
    pos = jnp.asarray(depths, jnp.int32)
    rows = jnp.where(jnp.asarray(live)[:, None], jnp.clip(
        pos[:, None] + 1 - jnp.arange(SC2_NB)[None] * SC2_BLOCK,
        0, SC2_BLOCK), 0)
    return q, kp, vp, table, pos, rows


@pytest.fixture
def pages_an_item(monkeypatch):
    """Set ``G`` by what an item aims to bring in, as a larger or smaller
    page would: the kernel is jitted, so what it traced is dropped on both
    sides."""
    def set_to(group, page_bytes):
        monkeypatch.setattr(paged_read, "_ITEM_BYTES", group * page_bytes)

    paged_read_attention.clear_cache()
    yield set_to
    paged_read_attention.clear_cache()


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("shape", list(SC2))
@pytest.mark.parametrize("case", list(ROW_CASES))
def test_several_pages_an_item_is_the_gather_body(case, shape, group,
                                                  pages_an_item):
    depths, live = ROW_CASES[case]
    q, kp, vp, table, pos, rows = _one_row_per_position(
        shape, depths, live, jnp.float32)
    pages_an_item(group, kp[0].nbytes)
    assert paged_read._items(SC2_NB, kp[0].nbytes) == (group,
                                                       SC2_NB // group)
    got = np.asarray(paged_read_attention(q, kp, vp, table, rows,
                                          interpret=True))
    B, L = len(depths), SC2_NB * SC2_BLOCK
    ref = np.asarray(SelfAttentionLayerImpl._grouped_attention(
        None, q, kp[table].reshape((B, L) + kp.shape[2:]),
        vp[table].reshape((B, L) + vp.shape[2:]), causal=True, qpos0=pos))
    on = np.asarray(live)
    assert (got[~on] == 0).all()
    assert np.abs(got[on] - ref[on]).max() < TOL["float32"]
    assert np.abs(ref[on]).max() > 0.1


def test_pages_an_item_follow_the_page_s_bytes():
    """No knob: 16 at the 3B's 32 KB page (64 x 2 x 128 bfloat16), 8 at the
    7B's 64 KB, 1 at EvaByte's 512 KB and at anything larger."""
    assert [pages_per_item(64 * hkv * 128 * 2) for hkv in (2, 4, 32, 64)] \
        == [16, 8, 1, 1]


@pytest.fixture(scope="module")
def sc2_layer():
    impl = impl_for(SelfAttentionLayer(n_in=64, n_out=64, n_heads=4,
                                       n_kv_heads=2, rope=True,
                                       activation="identity"))
    return impl, impl.init_params(jax.random.PRNGKey(5))


def _sc2_state(depths, live, dtype, nb=4, seed=0):
    rng = np.random.default_rng(seed)
    B = len(depths)
    shape = (1 + B * nb + 2, BLOCK, 2, 16)
    return {"k_pages": jnp.asarray(rng.normal(size=shape), dtype),
            "v_pages": jnp.asarray(rng.normal(size=shape), dtype),
            "pos": jnp.asarray(depths, jnp.int32),
            "table": jnp.asarray(1 + np.arange(B * nb).reshape(B, nb),
                                 jnp.int32),
            "wmask": jnp.asarray(live, bool)[:, None]}


def _sc2_step(layer, state, x, **injected):
    impl, params = layer
    params = jax.tree_util.tree_map(lambda a: a.astype(x.dtype), params)
    fn = jax.jit(lambda p, x, st: impl._paged_step(p, x,
                                                   {**st, **injected}))
    return fn, (params, x, state)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_parent_s_step_reads_through_it_at_t1(sc2_layer, dtype):
    """`SelfAttentionLayerImpl._paged_step` with the fused read against its
    own gather body: a live slot mid-page, an idle one (`wmask` off), one on
    the table's last row, one overflowed (its output is NaN either way and
    its position the sentinel)."""
    dt = jnp.dtype(dtype)
    nb = 4
    depths = [BLOCK + 5, 2 * BLOCK + 3, nb * BLOCK - 1, nb * BLOCK]
    live = [True, False, True, True]
    state = _sc2_state(depths, live, dt)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 1, 64)), dt)
    outs = {}
    for mode in ("on", "off"):
        fn, args = _sc2_step(sc2_layer, state, x, paged_kernel=mode)
        assert ("paged_read" in fn.lower(*args).as_text()) == (mode == "on")
        y, st = fn(*args)
        outs[mode] = np.asarray(y, np.float32), st
    (got, st_on), (ref, st_off) = outs["on"], outs["off"]
    assert np.isnan(got[3]).all() and np.isnan(ref[3]).all()
    assert np.isfinite(got[:3]).all()
    assert np.abs(got[[0, 2]] - ref[[0, 2]]).max() < TOL[dtype]
    assert np.abs(ref[[0, 2]]).max() > 0.1
    assert int(st_on["pos"][3]) == 1 << 30
    for name in ("k_pages", "v_pages", "pos"):
        assert np.array_equal(np.asarray(st_on[name], np.float32),
                              np.asarray(st_off[name], np.float32)), name


def test_what_keeps_the_gather_body(sc2_layer, monkeypatch):
    """A chunk (T > 1), int8 pages, a mesh, "off", "auto" off the TPU and a
    page list beyond the kernel's SMEM: no kernel in the lowered step."""
    from deeplearning4j_tpu.ops.kvquant import quantize_kv_rows
    state = _sc2_state([BLOCK + 5, 3], [True, True], jnp.float32)
    x1 = jnp.zeros((2, 1, 64), jnp.float32)

    def fused(st, x, **injected):
        fn, args = _sc2_step(sc2_layer, st, x, **injected)
        return "paged_read" in fn.lower(*args).as_text()

    assert fused(state, x1, paged_kernel="on")
    assert not fused(state, jnp.zeros((2, 4, 64), jnp.float32),
                     paged_kernel="on")
    kq, ks = quantize_kv_rows(state["k_pages"])
    assert not fused({**state, "k_pages": kq, "v_pages": kq, "k_scales": ks,
                      "v_scales": ks}, x1, paged_kernel="on")
    assert not fused(state, x1, paged_kernel="on", mesh=object())
    assert not fused(state, x1, paged_kernel="off")
    assert not fused(state, x1, paged_kernel="auto")    # the CPU
    # the bound is on the lists as the kernel pads them: this layer's page
    # of 8 rows is 1 KB, so 513 entries a slot are two items of 512
    engages = sc2_layer[0].fused_read_engages
    assert 65 * 513 < 64 * 1024 == paged_read.MAX_ENTRIES
    assert engages("on", 1, jnp.float32, slots=64, pages=513, block=8)
    assert not engages("on", 1, jnp.float32, slots=65, pages=513, block=8)
    monkeypatch.setattr(paged_read, "MAX_ENTRIES", 2 * 4 - 1)
    assert not fused(state, x1, paged_kernel="on")      # 2 slots x 4 pages


@pytest.mark.parametrize("paged_kernel", ["on", "off"])
def test_pages_named_and_pages_read_of_a_block_table(paged_kernel):
    """`kv_pages_bucket_total` is slots x the table bucket a decode dispatch;
    `kv_pages_read_total` the pages holding a row a fed slot attends over
    where the fused read engages, the bucket's where it does not. A prompt of
    20 (blocks of 8) and 14 tokens: decode dispatches at depths 20 to 32,
    one fed slot of two; buckets 4 (to depth 31) and 8."""
    from deeplearning4j_tpu.inference import DecodeScheduler, MetricsRegistry
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    net = ComputationGraph(transformer_lm(
        vocab_size=13, d_model=16, n_heads=2, n_blocks=2, rope=True)).init()
    eng = DecodeScheduler(net, 13, n_slots=2, prefill_chunk=16, kv_block=8,
                          kv_pool_mb=12 * 8 * 256 / float(1 << 20),
                          paged_kernel=paged_kernel,
                          metrics=MetricsRegistry())
    depths, step = [], eng._step_once

    def step_and_keep():
        fed = [s.written for s in eng._slots if s is not None and s.sampling]
        before = eng.metrics.snapshot()["counters"].get(
            "kv_pages_bucket_total", 0)
        busy = step()
        if eng.metrics.snapshot()["counters"].get(
                "kv_pages_bucket_total", 0) > before:
            depths.extend(fed)
        return busy

    eng._step_once = step_and_keep
    eng.start()
    try:
        prompt = list(np.random.default_rng(0).integers(0, 13, 20))
        assert len(eng.submit(prompt, 14).result(300)) == 14
        c = eng.metrics.snapshot()["counters"]
        status = eng.paged_kernel_status()
    finally:
        eng.stop()
    assert depths == list(range(20, 33))
    named = sum(2 * (4 if d + 1 <= 32 else 8) for d in depths)
    read = sum(-(-(d + 1) // 8) for d in depths)
    # 12 dispatches at bucket 4 and one at 8; 4 x 3 + 8 x 4 + 5 pages
    assert (named, read) == (112, 49)
    assert c["kv_pages_bucket_total"] == named
    assert c["kv_pages_read_total"] == (read if paged_kernel == "on"
                                        else named)
    assert status["engaged"] == (paged_kernel == "on")
    assert "eva_pages_read_total" not in c
