"""The fused paged read of `LatentAttentionLayerImpl._paged_step` at T = 1
(ISSUE 35: `ops/paged_read.py` in its one-buffer form, here through the
Pallas interpreter) against the gather body it stands in for (`_absorbed`
over ``cp2[table]``), on one set of pages: the layer's own step with
``paged_kernel`` ``"on"`` and ``"off"``. A layer of 4 heads whose cached row
is 48 + 16 = 64 wide, so that two positions share a 128-wide page row as two
of A.X-K1's 576 share 1,152; pages of 8 positions (4 rows), a table bucket of
8 blocks. Below that the engine: the same tokens either way, and what it
counts. Last, the kernel, and the layer's write into the pool (ISSUE 37), at
A.X-K1's widths through the chip's compiler."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from axk1_util import BLOCK_BYTES, CFG, load

from deeplearning4j_tpu.nn.conf.layers import LatentAttentionLayer
from deeplearning4j_tpu.nn.layers.base import impl_for
from deeplearning4j_tpu.ops import paged_read
from deeplearning4j_tpu.ops.paged_read import paged_read_attention

HEADS, C, DR, BLOCK, NB = 4, 48, 16, 8, 8
ROW = C + DR

# What the two paths' own difference reads on the layer's output (magnitude
# 0.3 to 1.5) over these cases. float32: the order of the sums, 1e-7 to 5e-7.
# bfloat16: 0.002 to 0.008, a step or two of the output's own rounding (both
# paths keep float32 scores and round the probabilities; the online softmax
# rounds them against another maximum). The limits: ten times the float32
# reading, four times the other
TOL = {"float32": 5e-6, "bfloat16": 3e-2}


@pytest.fixture(scope="module")
def layer():
    impl = impl_for(LatentAttentionLayer(
        n_in=64, n_out=64, n_heads=HEADS, q_lora_rank=24, kv_lora_rank=C,
        qk_nope_head_dim=16, qk_rope_head_dim=DR, v_head_dim=16,
        yarn_factor=4.0, yarn_original_max=32, yarn_mscale=1.0,
        yarn_mscale_all_dim=1.0, activation="identity"))
    assert impl._rows_packed(BLOCK) == 2
    assert impl._scale() != pytest.approx((16 + DR) ** -0.5)  # YaRN's m^2
    return impl, impl.init_params(jax.random.PRNGKey(3))


def _state(depths, live, dtype, seed=0):
    """Pages as an engine would have left them at these depths (random
    finite rows everywhere), each slot's table its own, two spare pages."""
    rng = np.random.default_rng(seed)
    B = len(depths)
    shape = (1 + B * NB + 2, BLOCK // 2, 2 * ROW)
    return {"c_pages": jnp.asarray(rng.normal(size=shape), dtype),
            "pos": jnp.asarray(depths, jnp.int32),
            "table": jnp.asarray(1 + np.arange(B * NB).reshape(B, NB),
                                 jnp.int32),
            "wmask": jnp.asarray(live, bool)[:, None]}


def _step_fn(layer, x, **injected):
    impl, params = layer
    params = jax.tree_util.tree_map(lambda a: a.astype(x.dtype), params)
    fn = jax.jit(lambda p, x, st: impl._paged_step(p, x, {**st, **injected}))
    return fn, params


def _step(layer, state, mode, x):
    fn, params = _step_fn(layer, x, paged_kernel=mode)
    y, out = fn(params, x, state)
    return np.asarray(y, np.float32), out


def _x(n, dtype, seed=1):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, 1, 64)),
                       dtype)


def test_the_rule_that_engages_it(layer):
    """The parent's rule with this layer's page: one query row a slot,
    bfloat16 or float32, no mesh, not "off", page lists that fit; on the
    CPU only "on" (interpreted), which is how every test here gets in."""
    engages = layer[0].fused_read_engages
    at = dict(slots=3, pages=NB, block=BLOCK)
    for dt in (jnp.float32, jnp.bfloat16):
        assert engages("on", 1, dt, **at)
        assert not engages("auto", 1, dt, **at)     # the backend is the CPU
        assert not engages("off", 1, dt, **at)
        assert not engages("on", 16, dt, **at)      # a prefill chunk
        assert not engages("on", 1, dt, mesh=object(), **at)
    assert not engages("on", 1, jnp.float16, **at)
    # A.X-K1's cell: a page of 64 x 576 bfloat16 is 73,728 B, so 7 pages an
    # item, and 48 slots x 37 items x 7 entries at bucket 256 fit the list
    real = impl_for(LatentAttentionLayer(
        n_in=7168, n_out=7168, n_heads=64, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128))
    assert paged_read._items(256, 73728) == (7, 37)
    assert 48 * 37 * 7 == 12432 < paged_read.MAX_ENTRIES
    assert real.fused_read_engages("on", 1, jnp.bfloat16, slots=48,
                                   pages=256, block=64)
    assert not real.fused_read_engages("on", 1, jnp.bfloat16, slots=48,
                                       pages=256 * 6, block=64)


# depths: positions held before this step's row, so the step attends over
# depth + 1 rows; an even depth ends on the first position of a packed row
CASES = {
    "a_first_row": ([0], [True]),
    "odd_count_the_second_position_of_a_row_is_not_attended": ([20], [True]),
    "even_count": ([21], [True]),
    "the_last_row_of_a_page_and_the_first_of_the_next":
        ([BLOCK - 1, BLOCK], [True, True]),
    "the_whole_table_beside_a_shallow_slot":
        ([NB * BLOCK - 1, 2], [True, True]),
    "a_slot_with_wmask_off_between_two": ([37, 40, 3], [True, False, True]),
    "no_slot_live": ([37, 40], [False, False]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_fused_read_is_the_gather_body(layer, case, dtype):
    depths, live = CASES[case]
    dt = jnp.dtype(dtype)
    state, x = _state(depths, live, dt), _x(len(depths), dt)
    got, st_on = _step(layer, state, "on", x)
    ref, st_off = _step(layer, state, "off", x)
    on = np.asarray(live)
    assert np.isfinite(got).all()
    # a masked slot reads nothing: zeros, not 0/0 (no bias, identity)
    assert (got[~on] == 0).all()
    if on.any():
        assert np.abs(got[on] - ref[on]).max() < TOL[dtype]
        assert np.abs(ref[on]).max() > 0.1
    # the write is the step's, not the read's: the same pages either way
    for name in ("c_pages", "pos"):
        assert np.array_equal(np.asarray(st_on[name], np.float32),
                              np.asarray(st_off[name], np.float32)), name


@pytest.fixture
def pages_an_item(monkeypatch):
    """Set ``G`` by what an item aims to bring in, as a larger or smaller
    page would: the kernel is jitted, so what it traced is dropped on both
    sides."""
    def set_to(group):
        monkeypatch.setattr(paged_read, "_ITEM_BYTES",
                            group * BLOCK * ROW * 4)

    paged_read_attention.clear_cache()
    yield set_to
    paged_read_attention.clear_cache()


@pytest.mark.parametrize("group", [1, 3, 8])
def test_several_pages_an_item_and_a_list_padded_to_whole_items(
        layer, group, pages_an_item):
    """Items of 1, 3 and 8 pages over lists of 8: at 3 the list is padded
    to 9 entries, a slot ends inside an item, and a slot with no row lies
    between two that have."""
    pages_an_item(group)
    assert paged_read._items(NB, BLOCK * ROW * 4) == (group, -(-NB // group))
    depths, live = [3 * BLOCK + 4, 17, NB * BLOCK - 1, 2 * BLOCK], \
        [True, False, True, True]
    state, x = _state(depths, live, jnp.float32), _x(4, jnp.float32)
    got, _ = _step(layer, state, "on", x)
    ref, _ = _step(layer, state, "off", x)
    assert (got[1] == 0).all()
    assert np.abs(got[[0, 2, 3]] - ref[[0, 2, 3]]).max() < TOL["float32"]


def test_rows_beyond_the_count_and_pages_of_count_0_are_not_used(
        layer, pages_an_item):
    """Every position the step does not attend over holds NaN: the second
    half of the packed row an odd count ends in, the rows after it, all of
    every page no count reaches (the live slots' deeper pages, which share
    their items, the masked slot's, the spare pages)."""
    pages_an_item(4)
    depths, live = [20, 40, 5], [True, False, True]
    clean = _state(depths, live, jnp.float32)
    x = _x(3, jnp.float32, seed=2)
    free = np.ones(clean["c_pages"].shape[:2] + (2,), bool)
    free[0] = False
    for b in (0, 2):
        for n in range(depths[b] + 1):
            free[int(clean["table"][b, n // BLOCK]), n % BLOCK // 2,
                 n % 2] = False
    assert free.sum() > free.size // 2
    lanes = jnp.asarray(np.repeat(free, ROW, axis=2))
    dirty = {**clean, "c_pages": jnp.where(lanes, jnp.nan, clean["c_pages"])}
    got, _ = _step(layer, dirty, "on", x)
    ref, _ = _step(layer, clean, "off", x)
    assert np.isfinite(got).all()
    assert np.abs(got[[0, 2]] - ref[[0, 2]]).max() < TOL["float32"]
    # and the gather body does read them: the NaN is a real trap
    poisoned, _ = _step(layer, dirty, "off", x)
    assert np.isnan(poisoned[[0, 2]]).any()


def test_the_kernel_takes_the_scale_it_is_given():
    """One buffer, key and value, every head on every key, against a plain
    softmax over the gathered rows at two scales; the K/V form still
    defaults to ``Dh ** -0.5``."""
    rng = np.random.default_rng(4)
    B, H, nb = 2, 8, 4
    q = jnp.asarray(rng.normal(size=(B, 1, H, ROW)), jnp.float32)
    cp = jnp.asarray(rng.normal(size=(12, BLOCK // 2, 2 * ROW)), jnp.float32)
    table = jnp.asarray(rng.permutation(11)[:B * nb].reshape(B, nb) + 1,
                        jnp.int32)
    pos = jnp.asarray([13, nb * BLOCK - 1], jnp.int32)
    rows = jnp.clip(pos[:, None] + 1 - jnp.arange(nb)[None] * BLOCK, 0, BLOCK)
    g = cp[table].reshape(B, nb * BLOCK, ROW)
    seen = (jnp.arange(nb * BLOCK)[None] <= pos[:, None])[:, None]
    for scale in (0.05, 0.4):
        got = paged_read_attention(q, cp, None, table, rows, scale=scale,
                                   interpret=True)
        s = jnp.where(seen, jnp.einsum("bhr,blr->bhl", q[:, 0], g) * scale,
                      -jnp.inf)
        ref = jnp.einsum("bhl,blr->bhr", jax.nn.softmax(s, -1), g)
        assert np.abs(np.asarray(got[:, 0]) - np.asarray(ref)).max() < 5e-6
    with pytest.raises(ValueError, match="one query row"):
        paged_read_attention(jnp.zeros((B, 2, H, ROW)), cp, None, table, rows,
                             interpret=True)
    with pytest.raises(ValueError, match="whole 48-wide rows"):
        paged_read_attention(jnp.zeros((B, 1, H, 48)), cp, None, table, rows,
                             interpret=True)


def _eqns(jaxpr):
    """Every equation, at any depth."""
    from jax.extend import core as jcore
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, jcore.Jaxpr):
                    yield from _eqns(inner)


def test_what_keeps_the_gather_body(layer, monkeypatch):
    """With the kernel engaged the step holds one `paged_read_rows` call and
    no gather of ``c_pages`` by the table; a chunk (T > 1), a mesh, "off",
    "auto" off the TPU and a page list beyond the kernel's SMEM hold the
    gather and no kernel."""
    state = _state([BLOCK + 5, 3], [True, True], jnp.float32)
    x1 = jnp.zeros((2, 1, 64), jnp.float32)
    shape = state["c_pages"].shape

    def fused(x, **injected):
        fn, params = _step_fn(layer, x, **injected)
        eqns = list(_eqns(jax.make_jaxpr(fn)(params, x, state).jaxpr))
        kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
        # a gather of whole pages; the write's of the rows it touches is
        # one of rows
        gathers = [e for e in eqns if e.primitive.name == "gather"
                   and e.invars[0].aval.shape == shape
                   and e.params["slice_sizes"] == (1,) + shape[1:]]
        assert all(e.params["name"] == "paged_read_rows" for e in kernels)
        assert (len(kernels), len(gathers)) in ((1, 0), (0, 1))
        return bool(kernels)

    assert fused(x1, paged_kernel="on")
    wmask = jnp.ones((2, 4), bool)
    assert not fused(jnp.zeros((2, 4, 64), jnp.float32), paged_kernel="on",
                     wmask=wmask)
    assert not fused(x1, paged_kernel="on", mesh=object())
    assert not fused(x1, paged_kernel="off")
    assert not fused(x1, paged_kernel="auto")       # the CPU
    monkeypatch.setattr(paged_read, "MAX_ENTRIES", 2 * NB - 1)
    assert not fused(x1, paged_kernel="on")         # 2 slots x 8 pages


# ---- the engine: the small A.X-K1 of the other tests (a cached row of 24,
# so sixteen positions to a 384-wide page row at blocks of 16) ----
V = CFG["vocab_size"]
EBLOCK = 16


def _engine(net, paged_kernel, n_slots=2, blocks=24):
    from deeplearning4j_tpu.inference import DecodeScheduler, MetricsRegistry
    eng = DecodeScheduler(
        net, V, n_slots=n_slots, prefill_chunk=16, kv_block=EBLOCK,
        kv_pool_mb=(blocks + 1) * BLOCK_BYTES * 2 / float(1 << 20),
        paged_kernel=paged_kernel, metrics=MetricsRegistry())
    assert eng.pool.capacity_blocks == blocks
    assert eng._states["attn1"]["c_pages"].shape[1:] == (1, 16 * 24)
    return eng


@pytest.fixture(scope="module")
def small_net():
    return load()[2]


def test_chunked_prefill_then_decode_gives_the_same_tokens(small_net):
    """Two requests at different depths in the same steps, 53 prompt tokens
    in chunks of 16 and 9 in one, then 24 decoded each: the tokens of "on"
    are those of "off", and only "on" engages."""
    rng = np.random.default_rng(0)
    a, b = rng.integers(0, V, 53).tolist(), rng.integers(0, V, 9).tolist()
    tokens = {}
    for mode in ("on", "off"):
        eng = _engine(small_net, mode)
        eng.start()
        try:
            ha, hb = eng.submit(a, 24), eng.submit(b, 24)
            tokens[mode] = ha.result(300), hb.result(300)
            status = eng.paged_kernel_status()
        finally:
            eng.stop()
        assert status["engaged"] == (mode == "on")
        assert set(status["buckets"].values()) == {
            "paged_read" if mode == "on" else False}
    assert tokens["on"] == tokens["off"]
    assert [len(t) for t in tokens["on"]] == [24, 24]


@pytest.mark.parametrize("paged_kernel", ["on", "off"])
def test_pages_named_and_pages_read_of_a_latent_table(small_net,
                                                      paged_kernel):
    """`mla_pages_bucket_total` is slots x the table bucket a decode
    dispatch; `mla_pages_read_total` the pages holding a row a fed slot
    attends over where the fused read engages, the bucket's where it does
    not; a latent net has no `kv_pages_*`. A prompt of 20 (blocks of 16) and
    14 tokens: decode dispatches at depths 20 to 32, one fed slot of two;
    buckets 2 (to depth 31) and 4."""
    eng = _engine(small_net, paged_kernel)
    depths, step = [], eng._step_once

    def step_and_keep():
        fed = [s.written for s in eng._slots if s is not None and s.sampling]
        before = eng.metrics.snapshot()["counters"].get(
            "mla_pages_bucket_total", 0)
        busy = step()
        if eng.metrics.snapshot()["counters"].get(
                "mla_pages_bucket_total", 0) > before:
            depths.extend(fed)
        return busy

    eng._step_once = step_and_keep
    eng.start()
    try:
        prompt = np.random.default_rng(0).integers(0, V, 20).tolist()
        assert len(eng.submit(prompt, 14).result(300)) == 14
        c = eng.metrics.snapshot()["counters"]
    finally:
        eng.stop()
    assert depths == list(range(20, 33))
    named = sum(2 * (2 if d + 1 <= 32 else 4) for d in depths)
    read = sum(-(-(d + 1) // EBLOCK) for d in depths)
    # 12 dispatches at bucket 2 and one at 4; 12 x 2 + 3 pages
    assert (named, read) == (56, 27)
    assert c["mla_pages_bucket_total"] == named
    assert c["mla_pages_read_total"] == (read if paged_kernel == "on"
                                         else named)
    assert not [name for name in c if name.startswith(("kv_pages_",
                                                       "eva_pages_"))]
    assert c["mla_rows_read_total"] == sum(d + 1 for d in depths)


# ---- A.X-K1's widths through the chip's compiler, no chip attached: what
# the interpreter cannot refuse (lane windows of a 1,152-wide row that start
# at lane 576, an item of 224 buffer rows, the kernel's fast memory) ----
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernel_compiles_for_a_v5e_at_the_cell_s_shapes(one_chip):
    """48 slots, 64 heads, a row of 576, pages ``[32, 1152]`` bfloat16 in a
    pool of 9,986, the deepest table bucket: one `tpu_custom_call`, and
    temporaries that are lists and no copy of a page."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn = jax.jit(lambda q, cp, table, rows: paged_read_attention(
        q, cp, None, table, rows, scale=0.1352))
    compiled = fn.lower(
        sds((48, 1, 64, 576), jnp.bfloat16),
        sds((9986, 32, 1152), jnp.bfloat16),
        sds((48, 256), jnp.int32), sds((48, 256), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("slots,T", [(1, 512), (48, 1)],
                         ids=["chunk", "decode"])
def test_the_pool_write_compiles_to_no_loop_for_a_v5e(one_chip, monkeypatch,
                                                      slots, T):
    """The layer's paged step at A.X-K1's widths over the cell's pool: the
    rows go in by one native scatter, and no ``while`` carries the pool (the
    window scatter it replaced was one of a trip a position, ISSUE 37). The
    step as the chip traces it: the fused read at T = 1."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    impl = impl_for(LatentAttentionLayer(
        n_in=7168, n_out=7168, n_heads=64, q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, activation="identity"))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: impl.init_params(jax.random.PRNGKey(0), jnp.bfloat16)))
    state = {"c_pages": sds((9986, 32, 1152), jnp.bfloat16),
             "pos": sds((slots,), jnp.int32),
             "table": sds((slots, 32), jnp.int32),
             "wmask": sds((slots, T), jnp.bool_)}
    text = jax.jit(lambda p, x, st: impl._paged_step(p, x, st)[1],
                   donate_argnums=(2,)).lower(
        params, sds((slots, T, 7168), jnp.bfloat16), state
    ).compile().as_text()
    loops = [ln for ln in text.splitlines() if " while(" in ln]
    assert not [ln for ln in loops if "bf16[9986,32,1152]" in ln]
    assert len(re.findall(r"bf16\[9986,32,1152\]\S* scatter\(", text)) == 1
