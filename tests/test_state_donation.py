"""The carried KV state is updated in place (ISSUE 27).

The engine's rule: a jitted program that takes the carried state and
returns it DONATES it, and every caller rebinds from the result; a
program that only reads the state does not. What is pinned here, on the
CPU with a toy engine:

(a) every such program family, compiled, aliases at least the bytes of
    the state's big arrays (``memory_analysis().alias_size_in_bytes``):
    the compiler used the donation, so no step, chunk or admission-path
    program writes a fresh copy of the pool;
(b) real traffic consumes the old buffers and leaves live ones bound;
(c) tokens stay identical to solo decoding, with JAX's "Some donated
    buffers were not usable" warning turned into an error;
(d) ``warmup()`` rebinds from every donating call, so it can run twice
    and the engine still serves;
(e) the same under a 2-device tp mesh, where an output whose sharding
    differs from the donated input's could not alias.
"""
import warnings

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.inference import DecodeScheduler, MetricsRegistry
from deeplearning4j_tpu.inference.kvpool import SCRATCH_BLOCK
from deeplearning4j_tpu.models.sampling import generate_transformer
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph

V = 13
B = 8        # kv_block
CHUNK = 16   # prefill_chunk
BIG = ("k", "v", "k_pages", "v_pages", "k_scales", "v_scales")


def _lm(cache=96):
    conf = transformer_lm(vocab_size=V, d_model=16, n_heads=2, n_blocks=2,
                          rope=True)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()


# 2 layers x (k+v) x Hkv2 x Dh8 x f32 = 256 bytes per cache position in
# all; each of ``tp`` devices holds 256/tp
def _pool_mb(blocks, tp=1):
    return (blocks + 1) * B * 256 / tp / float(1 << 20)


ENGINES = {
    "plain": dict(kv_pool_mb=_pool_mb(32), kv_block=B),
    "int8": dict(kv_pool_mb=_pool_mb(32), kv_block=B, kv_dtype="int8"),
    "spec": dict(kv_pool_mb=_pool_mb(32), kv_block=B, speculate=3),
    "tiered": dict(kv_pool_mb=_pool_mb(32), kv_block=B, host_cache_mb=4.0),
    "contiguous": dict(),
    "contiguous_spec": dict(speculate=3),
    "tp2": dict(kv_pool_mb=_pool_mb(32, tp=2), kv_block=B, mesh=2),
}


@pytest.fixture(scope="module")
def net():
    return _lm()


@pytest.fixture(scope="module")
def solo(net):
    # 40 tokens: three chunks of 16 and six blocks of 8; six more tokens
    # cross into the next block
    prompt = list(np.random.default_rng(1).integers(0, V, 40))
    return prompt, generate_transformer(net, prompt, 6, V, use_cache=True)


def _engine(net, kind):
    return DecodeScheduler(net, V, n_slots=2, prefill_chunk=CHUNK,
                           metrics=MetricsRegistry(),
                           transfer_guard="disallow", **ENGINES[kind])


@pytest.fixture(scope="module")
def built(net):
    """One engine per kind, never started: the lowering cases only read
    shapes and placements, and consume nothing."""
    cache = {}

    def get(kind):
        if kind not in cache:
            cache[kind] = _engine(net, kind)
        return cache[kind]
    yield get
    for eng in cache.values():
        eng.stop()


@pytest.fixture
def donation_warning_is_error():
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message="Some donated buffers were not usable")
        yield


def _big_bytes_per_device(states, tp):
    """Bytes one device holds of the state's K/V arrays (stripes, pages
    and int8 scale pages): what a program that copied the state would
    write afresh."""
    return sum(a.nbytes for st in states.values() if isinstance(st, dict)
               for k, a in st.items() if k in BIG) // tp


def _lowered(eng, family):
    """``family`` lowered with the arguments live dispatch gives it."""
    arr, idx = eng._dev_array, eng._dev_index
    p, v = eng._params, eng._variables
    ids = arr(np.zeros((eng.n_slots,), np.int32))
    live = arr(np.zeros((eng.n_slots,), bool))
    cids = arr(np.zeros((CHUNK,), np.int32))
    s0, one = idx(0), idx(1)
    table = ((arr(np.full((eng.n_slots, eng.table_buckets[0]),
                          SCRATCH_BLOCK, np.int32)),)
             if eng.paged else ())
    mstate = arr(np.zeros((eng.n_slots,), np.int32))
    if eng.speculate:
        dp, dv = eng._draft_params, eng._draft_variables
        ids2 = arr(np.zeros((eng.n_slots, eng.speculate + 1), np.int32))
        mstate2 = arr(np.zeros((eng.n_slots, eng.speculate + 1), np.int32))
        posv = arr(np.zeros((eng.n_slots,), np.int32))
        nomask = arr(np.zeros((eng.n_slots,), bool))
    st, dst = eng._states, eng._draft_states
    if family == "step":
        return eng._jstep.lower(p, v, ids, live, *table, st), st
    if family == "prefill":
        return eng._jprefill.lower(p, v, s0, cids, one, *table, st), st
    if family == "step_masked":
        return eng._jstep_m.lower(p, v, ids, live, *table, mstate,
                                  eng._masks, st), st
    if family == "zero":
        return eng._jzero.lower(st, s0), st
    if family == "setpos":
        return eng._jsetpos.lower(st, s0, s0), st
    if family == "cow":
        return eng._jcow.lower(st, one, one), st
    if family == "tier_restore":
        rows = eng._jtier_spill(st, s0)
        return eng._jtier_restore.lower(st, s0, rows), st
    if family == "verify":
        return eng._jverify.lower(p, v, ids2, live, *table, st), st
    if family == "verify_masked":
        return eng._jverify_m.lower(p, v, ids2, live, *table, mstate2,
                                    eng._masks, st), st
    if family == "fixpos":
        return eng._jfixpos.lower(st, posv, nomask), st
    if family == "draft_step":
        return eng._jdraft_step.lower(dp, dv, ids, live, dst), dst
    if family == "draft_step_masked":
        return eng._jdraft_step_m.lower(dp, dv, ids, live, mstate,
                                        eng._masks, dst), dst
    if family == "draft_prefill":
        return eng._jdraft_prefill.lower(dp, dv, s0, cids, one, dst), dst
    if family == "draft_zero":
        return eng._jdraft_zero.lower(dst, s0), dst
    if family == "draft_fixpos":
        return eng._jdraft_fixpos.lower(dst, posv, nomask), dst
    raise AssertionError(family)


CASES = [
    ("plain", "step"), ("plain", "prefill"), ("plain", "step_masked"),
    ("plain", "zero"), ("plain", "setpos"), ("plain", "cow"),
    ("int8", "step"), ("int8", "prefill"), ("int8", "cow"),
    ("tiered", "tier_restore"),
    ("spec", "verify"), ("spec", "verify_masked"), ("spec", "fixpos"),
    ("spec", "draft_step"), ("spec", "draft_step_masked"),
    ("spec", "draft_prefill"), ("spec", "draft_zero"),
    ("spec", "draft_fixpos"),
    ("contiguous", "step"), ("contiguous", "prefill"),
    ("contiguous", "step_masked"), ("contiguous", "zero"),
    ("contiguous_spec", "verify"), ("contiguous_spec", "fixpos"),
    ("tp2", "step"), ("tp2", "prefill"), ("tp2", "zero"), ("tp2", "cow"),
]


# ------------------------------------------ (a) the compiler aliases it --
@pytest.mark.parametrize("kind,family", CASES,
                         ids=[f"{k}-{f}" for k, f in CASES])
def test_program_aliases_the_carried_state(built, kind, family,
                                           donation_warning_is_error):
    eng = built(kind)
    lowered, states = _lowered(eng, family)
    need = _big_bytes_per_device(states, eng.tp)
    assert need > 0
    donated = sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                  for a in jax.tree_util.tree_leaves(lowered.args_info)
                  if a.donated)
    assert donated >= need * eng.tp
    aliased = lowered.compile().memory_analysis().alias_size_in_bytes
    assert aliased >= need, (kind, family, aliased, need)


def test_readers_of_the_state_do_not_donate(built):
    """`_jtier_spill` returns slices of the pool: the state lives on, so
    it may not consume it."""
    eng = built("tiered")
    spill = eng._jtier_spill.lower(eng._states, eng._dev_index(0))
    assert not any(a.donated for a in
                   jax.tree_util.tree_leaves(spill.args_info))


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_every_state_leaf_has_a_buffer_of_its_own(built, kind):
    """A leaf that appears twice in the carried state would make JAX
    refuse the call (the same buffer donated twice)."""
    eng = built(kind)
    for states in (eng._states, eng._draft_states):
        leaves = jax.tree_util.tree_leaves(states)
        assert len({id(a) for a in leaves}) == len(leaves)
        # per device: on the CPU the replicas of one replicated leaf
        # (``pos`` under a mesh) may share their host memory
        ptrs = [(s.device.id, s.data.unsafe_buffer_pointer())
                for a in leaves for s in a.addressable_shards]
        assert len(set(ptrs)) == len(ptrs)


# ------------------------ (b) (c) traffic consumes, rebinds, and agrees --
@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_traffic_consumes_old_state_and_tokens_match_solo(
        net, solo, kind, donation_warning_is_error):
    prompt, expect = solo
    eng = _engine(net, kind)
    before = (jax.tree_util.tree_leaves(eng._states)
              + jax.tree_util.tree_leaves(eng._draft_states))
    eng.start()
    try:
        assert eng.submit(prompt, 6).result(300) == expect
        # again: in a pool, a prefix hit (table remap + copy-on-write)
        assert eng.submit(prompt, 6).result(300) == expect
    finally:
        eng.stop()
    assert before and all(a.is_deleted() for a in before)
    after = (jax.tree_util.tree_leaves(eng._states)
             + jax.tree_util.tree_leaves(eng._draft_states))
    assert not any(a.is_deleted() for a in after)


# ----------------------------------------------- (d) warm-up rebinds too --
@pytest.mark.parametrize("kind", ["plain", "spec", "tiered",
                                  "contiguous", "contiguous_spec", "tp2"])
def test_warmup_twice_then_serve(net, solo, kind,
                                 donation_warning_is_error):
    prompt, expect = solo
    eng = _engine(net, kind)
    eng.warmup(masks=True)
    eng.warmup(masks=True)
    for states in (eng._states, eng._draft_states):
        for a in jax.tree_util.tree_leaves(states):
            assert not a.is_deleted()
            assert np.isfinite(np.asarray(a, np.float32)).all()
    warmed = eng._compile_counter.counts()
    eng.start()
    try:
        assert eng.generate(prompt, 6, timeout=300) == expect
    finally:
        eng.stop()
    # and warm-up still warms: the request compiled nothing new
    assert eng._compile_counter.counts() == warmed
