"""Fused Pallas paged-attention decode kernel (ISSUE 15).

The acceptance contract: with the ops/pallas_kernels plugin enabled, the
T=1 paged decode step can dispatch through the ``paged_decode_attention``
seam — a FlashDecoding-style kernel that walks the slot's block table
with an online softmax instead of gathering the whole logical cache —
and is TOKEN-IDENTICAL to the XLA gather path (greedy AND seeded-
sampled, fp32 AND int8 KV, contiguous-fallback AND paged, tp1 AND tp2)
under ``transfer_guard="disallow"``. The seam itself is covered too:
forced ``paged_kernel="on"|"off"|"auto"`` modes, autotune decision
caching + ``clear_autotune_cache`` for the new family, fallback on
unsupported shapes (prefill chunks / T>1 stay XLA; K/V writes including
the wmask scratch redirect always run in the XLA prologue), warmed-zero-
compile serving with the kernel engaged, and the tp2 collective audit
unchanged (exactly 2 all-reduces per block, 0 resharding).

Everything runs the kernel through the Pallas INTERPRETER on CPU
(enable(interpret=True) — the same seam discipline as
tests/test_pallas_kernels.py); on TPU the same tests compile for real.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.inference import DecodeScheduler, MetricsRegistry
from deeplearning4j_tpu.inference import sharding as shd
from deeplearning4j_tpu.models.sampling import generate_transformer
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.ops import helpers as ophelpers
from deeplearning4j_tpu.ops import kvquant
from deeplearning4j_tpu.ops import pallas_kernels as pk

V = 13
N_BLOCKS = 2


@pytest.fixture(autouse=True)
def _kernel_seam():
    """Register the Pallas kernels (interpreter on CPU) around every
    test, with a clean autotune slate each side."""
    pk.enable(interpret=True)
    pk.clear_autotune_cache()
    yield
    pk.clear_autotune_cache()
    pk.disable()


def _lm(cache=96):
    conf = transformer_lm(vocab_size=V, d_model=16, n_heads=2,
                          n_blocks=N_BLOCKS, rope=True)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()


# bytes per (k+v, 2-layer, Hkv=2, Dh=8, f32) block of B positions: B*256
def _pool_mb(blocks, block, tp=1):
    return (blocks + 1) * block * 256 / tp / float(1 << 20)


@pytest.fixture(scope="module")
def net():
    return _lm()


@pytest.fixture(scope="module")
def solo(net):
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, V, n)) for n in (7, 23, 40)]
    outs = [generate_transformer(net, p, 6, V, use_cache=True)
            for p in prompts]
    return prompts, outs


def _engine(net, mode, *, tp=1, kv_dtype=None, n_slots=2, blocks=8):
    return DecodeScheduler(net, V, n_slots=n_slots, prefill_chunk=16,
                           kv_pool_mb=_pool_mb(blocks, 8, tp), kv_block=8,
                           kv_dtype=kv_dtype, paged_kernel=mode,
                           mesh=tp if tp > 1 else None,
                           metrics=MetricsRegistry(),
                           transfer_guard="disallow")


# ----------------------------------------------------- kernel vs oracle --
def test_kernel_matches_xla_reference_directly():
    """Engine-free bit-level check: the kernel (both grid variants,
    fp32 and int8 pages) against the standalone XLA gather oracle on a
    random table with per-row depths — max |diff| at f32 rounding."""
    rng = np.random.default_rng(3)
    B, H, Hkv, Dh, block, nb = 3, 4, 2, 8, 8, 4
    pages = B * nb + 1
    kp = np.asarray(rng.normal(size=(pages, block, Hkv, Dh)), np.float32)
    vp = np.asarray(rng.normal(size=(pages, block, Hkv, Dh)), np.float32)
    table = np.asarray(rng.permutation(np.arange(1, B * nb + 1))
                       .reshape(B, nb), np.int32)
    pos = np.asarray([0, 17, 31], np.int32)  # incl. the 1-token edge
    q = np.asarray(rng.normal(size=(B, 1, H, Dh)), np.float32)
    ref = pk._xla_paged_reference(q, kp, vp, table, pos)
    for variant in ("bh", "hb"):
        out = pk._paged_decode_call(q, kp, vp, table, pos,
                                    variant=variant)
        assert float(np.max(np.abs(np.asarray(out - ref)))) < 1e-5
    kq, ks = kvquant.quantize_kv_rows(kp)
    vq, vs = kvquant.quantize_kv_rows(vp)
    ref8 = pk._xla_paged_reference(q, kq, vq, table, pos, ks, vs)
    out8 = pk._paged_decode_call(q, kq, vq, table, pos, ks, vs)
    assert float(np.max(np.abs(np.asarray(out8 - ref8)))) < 1e-5


def test_sub_f32_compute_dtype_falls_back_to_xla():
    """The kernel accumulates in f32; a bf16 engine's XLA reference
    contracts in bf16, so the seam must DECLINE sub-f32 queries (None =
    run the reference) rather than engage and break token identity."""
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    kp = jnp.asarray(rng.normal(size=(3, 8, 2, 8)), jnp.bfloat16)
    q = jnp.asarray(rng.normal(size=(1, 1, 2, 8)), jnp.bfloat16)
    table = jnp.asarray([[1, 2]], jnp.int32)
    pos = jnp.asarray([7], jnp.int32)
    assert pk.paged_decode_attention_pallas(
        q, kp, kp, table, pos, mode="on") is None
    assert pk.paged_decode_attention_pallas(
        q.astype(jnp.float32), kp.astype(jnp.float32),
        kp.astype(jnp.float32), table, pos, mode="on") is not None


# ----------------------------------------------------- token identity --
def test_greedy_token_identical_kernel_on_off_and_contiguous(net, solo):
    """Greedy decode, mixed prompt lengths spanning table buckets:
    kernel-on, kernel-off, and the CONTIGUOUS engine (no pages — the
    kernel cannot engage even though the helper is registered) all
    match solo decoding bit-for-bit under the residency audit."""
    prompts, expect = solo
    fused = []
    for build in (lambda: _engine(net, "on"),
                  lambda: _engine(net, "off"),
                  lambda: DecodeScheduler(net, V, n_slots=2,
                                          prefill_chunk=16,
                                          metrics=MetricsRegistry(),
                                          transfer_guard="disallow")):
        eng = build().start()
        try:
            outs = [h.result(300) for h in
                    [eng.submit(p, 6) for p in prompts]]
        finally:
            eng.stop()
        assert outs == expect
        fused.append(eng.paged_kernel_status()["buckets"])
    # the paged kernel-on engine really did run fused: since ISSUE 33 the
    # T=1 read of an un-quantized engine off a mesh is `ops/paged_read`,
    # ahead of the seam's kernel
    assert fused[0] and set(fused[0].values()) == {"paged_read"}
    assert not any(fused[1].values()) and not fused[2]


def test_seeded_sampling_token_identical(net):
    """Seeded-sampled decode (temperature/top_k/top_p) through the
    kernel matches solo decoding — the sampled-path arm of the
    acceptance matrix."""
    rng = np.random.default_rng(1)
    prompt = list(rng.integers(0, V, 23))
    kw = dict(temperature=0.8, top_k=5, top_p=0.9, seed=11)
    ref = generate_transformer(net, prompt, 6, V, use_cache=True, **kw)
    eng = _engine(net, "on").start()
    try:
        assert eng.generate(prompt, 6, timeout=300, **kw) == ref
    finally:
        eng.stop()


def test_int8_kv_kernel_token_identical_to_xla_int8(net):
    """int8 KV pages: the kernel's fused in-loop dequant must agree
    with the XLA gather's dequantize-then-einsum token-for-token (int8
    is lossy vs f32, so the reference is the kernel-OFF int8 engine)."""
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, V, n)) for n in (9, 26)]
    outs = {}
    for mode in ("off", "on"):
        eng = _engine(net, mode, kv_dtype="int8").start()
        try:
            assert eng.kv_dtype == "int8"
            outs[mode] = [h.result(300) for h in
                          [eng.submit(p, 5) for p in prompts]]
        finally:
            eng.stop()
    assert outs["on"] == outs["off"]


def test_tp2_token_identical_and_collective_audit(net, solo):
    """tp=2 head-sharded engine with the kernel forced on: greedy
    outputs match solo decoding, and the compiled per-token decode
    program still carries ONLY the Megatron all-reduces (2 per block,
    0 resharding collectives) — the kernel runs per-shard inside
    shard_map and never communicates."""
    prompts, expect = solo
    eng = _engine(net, "on", tp=2)
    eng.warmup()
    eng.start()
    try:
        assert eng.tp == 2 and eng.paged
        outs = [h.result(300) for h in
                [eng.submit(p, 6) for p in prompts]]
        assert eng.paged_kernel_status()["engaged"]
    finally:
        eng.stop()
    assert outs == expect
    counts = shd.collective_counts(shd.decode_program_hlo(eng))
    shd.assert_hot_path_collectives(counts, N_BLOCKS)
    assert sum(counts.get(op, 0)
               for op in shd.RESHARD_COLLECTIVES) == 0
    assert counts.get("all-reduce", 0) == 2 * N_BLOCKS


# --------------------------------------------------------- seam modes --
@pytest.mark.slow
def test_forced_modes_and_prefill_fallback(net, solo, monkeypatch):
    """mode="off" never invokes the kernel; mode="on" invokes it for
    every (attention layer x table bucket) DECODE trace and never for
    prefill chunks (T>1) or K/V writes — warmup traces the full
    program family, so counting seam entries during warmup enumerates
    exactly the fused call sites."""
    calls = []
    real = pk._paged_decode_call

    def spy(q, *a, **k):
        calls.append(tuple(q.shape))
        return real(q, *a, **k)

    monkeypatch.setattr(pk, "_paged_decode_call", spy)
    # int8 pages: what the seam still stands in front of (since ISSUE 33
    # an un-quantized T=1 read off a mesh is `ops/paged_read`'s)
    eng = _engine(net, "off", kv_dtype="int8")
    eng.warmup()
    assert calls == []
    assert not eng.paged_kernel_status()["engaged"]
    eng2 = _engine(net, "on", kv_dtype="int8")
    eng2.warmup()
    # one seam entry per attention layer per decode table bucket; every
    # q is a single-token [n_slots, 1, H, Dh] batch — prefill's T>1
    # chunks fell back to the XLA body without touching the kernel
    assert len(calls) == N_BLOCKS * len(eng2.table_buckets)
    assert all(s[1] == 1 for s in calls)
    assert eng2.paged_kernel_status()["engaged"]
    # engagements are MODE-keyed: the on-engine's truthy verdicts over
    # the same shapes must not leak into the off-engine's status (the
    # co-resident A/B topology the bench runs)
    assert not eng.paged_kernel_status()["engaged"]


@pytest.mark.slow
def test_auto_under_interpreter_keeps_xla_and_caches_decision(net, solo):
    """mode="auto" on a non-TPU backend: the autotune answer is XLA
    (probing the interpreter would measure the interpreter), cached per
    shape, and decode stays token-identical — the autotune-picks-XLA
    fallback arm."""
    prompts, expect = solo
    eng = _engine(net, "auto").start()
    try:
        outs = [h.result(300) for h in
                [eng.submit(p, 6) for p in prompts]]
    finally:
        eng.stop()
    assert outs == expect
    st = eng.paged_kernel_status()
    assert not st["engaged"]
    assert any(k[0] == "paged_decode" and v is False
               for k, v in pk.autotune_decisions().items())


@pytest.mark.slow
def test_autotune_decision_cached_and_cleared(net, monkeypatch):
    """The per-shape decision is probed ONCE per shape key, shared by
    later traces (a second engine over the same shapes re-probes
    nothing), exposed via autotune_decisions(), and re-probed after
    clear_autotune_cache() — the cuDNN find-algorithm discipline for
    the new family."""
    probes = []

    def fake_probe(B, nb, block, Hkv, H, Dh, dtype, quantized):
        probes.append((B, nb, block, Hkv, H, Dh, quantized))
        return "bh"

    monkeypatch.setattr(pk, "_autotune_paged_decode", fake_probe)
    eng = _engine(net, "auto")
    eng.warmup()
    # one probe per table bucket (both attention layers share the
    # shape, so the cache collapses them)
    assert len(probes) == len(eng.table_buckets)
    assert eng.paged_kernel_status()["engaged"]
    dec = pk.autotune_decisions()
    keys = [k for k in dec if k[0] == "paged_decode"]
    assert len(keys) == len(eng.table_buckets)
    assert all(dec[k] == "bh" for k in keys)
    # same shapes again: fully cached, no new probes
    eng2 = _engine(net, "auto")
    eng2.warmup()
    assert len(probes) == len(eng.table_buckets)
    pk.clear_autotune_cache()
    assert not [k for k in pk.autotune_decisions()
                if k[0] == "paged_decode"]
    eng3 = _engine(net, "auto")
    eng3.warmup()
    assert len(probes) == 2 * len(eng.table_buckets)


# ------------------------------------------- warmed serving + budgets --
def test_warmed_zero_compile_serving_with_kernel_engaged(net, solo):
    """warmup() covers the kernel variant: after it, live traffic over
    every bucket compiles NOTHING new (the kernel lives inside the same
    per-table-bucket decode programs) and the engine's own
    CompileCounter budgets hold."""
    prompts, expect = solo
    eng = _engine(net, "on")
    eng.warmup()
    base = {"step": eng._jstep._cache_size(),
            "prefill": eng._jprefill._cache_size()}
    eng.start()
    try:
        outs = [h.result(300) for h in
                [eng.submit(p, 6) for p in prompts]]
    finally:
        eng.stop()
    assert outs == expect
    assert eng._jstep._cache_size() == base["step"]
    assert eng._jprefill._cache_size() == base["prefill"]
    eng._compile_counter.assert_within_budget()


@pytest.mark.slow
def test_observability_gauge_costs_and_debug_snapshot(net):
    """The ISSUE 15 observability satellite: `paged_kernel_engaged`
    gauge, the /debug/engine ``paged_kernel`` block (mode + per-bucket
    fused-vs-XLA verdicts + the family's autotune view), and the cost
    table naming which decode buckets run fused."""
    m = MetricsRegistry()
    eng = DecodeScheduler(_lm(), V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(8, 8), kv_block=8,
                          paged_kernel="on", metrics=m)
    eng.warmup()
    assert m.gauge("paged_kernel_engaged").value == 1
    snap = eng.debug_snapshot()
    blk = snap["paged_kernel"]
    assert blk["mode"] == "on" and blk["engaged"]
    assert set(blk["buckets"]) == set(eng.table_buckets)
    # an un-quantized engine off a mesh: `ops/paged_read` (ISSUE 33), by
    # the layer's static rule, ahead of the seam's autotuned variants
    assert all(v == "paged_read" for v in blk["buckets"].values())
    assert "autotune" in blk
    from deeplearning4j_tpu.inference.profiler import program_costs
    costs = program_costs(eng)
    for nb in eng.table_buckets:
        assert costs[("decode", nb)]["fused"] == 1.0
    # and an OFF engine's cost table says so (the A/B the bench reads)
    eng_off = DecodeScheduler(_lm(), V, n_slots=2, prefill_chunk=16,
                              kv_pool_mb=_pool_mb(8, 8), kv_block=8,
                              paged_kernel="off",
                              metrics=MetricsRegistry())
    eng_off.warmup()
    costs_off = program_costs(eng_off)
    for nb in eng_off.table_buckets:
        assert costs_off[("decode", nb)]["fused"] == 0.0


@pytest.mark.slow
def test_unregistered_seam_is_silent_fallback(net, solo):
    """disable() restores the pre-kernel world: paged_kernel="on" with
    no registered helper degrades silently to the XLA gather (the
    reference seam semantics — callers never change)."""
    pk.disable()
    prompts, expect = solo
    assert ophelpers.paged_decode_attention(
        None, None, None, None, None, mode="on") is None

    def run(eng):
        eng.start()
        try:
            return [h.result(300) for h in
                    [eng.submit(p, 6) for p in prompts]]
        finally:
            eng.stop()

    # un-quantized "on": the seam is not asked since ISSUE 33, the read is
    # `ops/paged_read` (interpreted), and the tokens are still solo's
    eng = _engine(net, "on")
    assert run(eng) == expect
    assert set(eng.paged_kernel_status()["buckets"].values()) == {
        "paged_read"}
    # int8 pages: the read the seam still stands in front of; with no
    # helper registered "on" is the gather body, as "off" is
    outs = {}
    for mode in ("on", "off"):
        eng = _engine(net, mode, kv_dtype="int8")
        outs[mode] = run(eng)
        assert not eng.paged_kernel_status()["engaged"]
    assert outs["on"] == outs["off"]


def test_bad_mode_rejected(net):
    with pytest.raises(ValueError, match="paged_kernel"):
        DecodeScheduler(net, V, paged_kernel="maybe")


def test_enable_paged_decode_registers_only_the_paged_seam():
    """The serve CLI's arming path must not reroute anything else: a
    --paged-kernel server's /predict forwards and GQA contraction stay
    on their XLA defaults (full enable() would register the attention
    helper and, on CPU, the conv/bn interpreter kernels too)."""
    pk.disable()
    pk.enable_paged_decode()
    try:
        assert ophelpers.get_helper("paged_decode_attention") is not None
        for other in ("attention", "conv2d_bias_act", "bn_act_pool",
                      "lstm_sequence"):
            assert ophelpers.get_helper(other) is None, other
    finally:
        pk.disable()


# ------------------------------------------------- heavy compositions --
@pytest.mark.slow
def test_tp2_int8_sampled_composition(net):
    """The heaviest acceptance composition: tp=2 head-sharded int8
    pages, seeded sampling, kernel on vs off — token-identical, audit
    unchanged."""
    rng = np.random.default_rng(5)
    prompt = list(rng.integers(0, V, 26))
    kw = dict(temperature=0.7, top_k=6, seed=3)
    outs = {}
    for mode in ("off", "on"):
        eng = _engine(net, mode, tp=2, kv_dtype="int8").start()
        try:
            assert eng.tp == 2 and eng.kv_dtype == "int8"
            outs[mode] = eng.generate(prompt, 5, timeout=300, **kw)
        finally:
            eng.stop()
        if mode == "on":
            counts = shd.collective_counts(shd.decode_program_hlo(eng))
            shd.assert_hot_path_collectives(counts, N_BLOCKS)
    assert outs["on"] == outs["off"]


@pytest.mark.slow
def test_supervisor_crash_rebuild_warmup_keeps_kernel_and_budgets(net):
    """Across a supervisor crash -> rebuild -> warmup cycle (the
    acceptance's CompileCounter arm): the crashed request replays
    token-identically on the rebuilt engine, which comes back with the
    kernel engaged and the decode family still <= 1 program per table
    bucket."""
    from deeplearning4j_tpu.inference import failpoints
    from deeplearning4j_tpu.inference.supervisor import EngineSupervisor
    from deeplearning4j_tpu.inference.trace import FlightRecorder

    sup = EngineSupervisor(lambda: _engine(net, "on"),
                           hang_timeout_s=60.0,
                           metrics=MetricsRegistry(),
                           tracer=FlightRecorder(1024))
    try:
        rng = np.random.default_rng(6)
        prompt = list(rng.integers(0, V, 9))
        ref = sup.submit(prompt, 4).result(300)
        old = sup.engine
        failpoints.arm("dispatch.decode", "crash@once")
        try:
            out = sup.submit(prompt, 4).result(300)
        finally:
            failpoints.disarm()
        assert out == ref  # replayed on the rebuilt, rewarmed engine
        assert sup.restarts >= 1 and sup.engine is not old
        assert sup.engine.paged_kernel_status()["engaged"]
        sup.engine._compile_counter.assert_within_budget()
    finally:
        sup.stop()
