"""Tensor-parallel decode over a tp device mesh (ISSUE 9).

The acceptance contract: with ``mesh=N`` the whole decode stack —
decode step, chunked prefill, prefix restore, COW forks, preemption —
runs tensor-parallel over an N-device ``tp`` mesh (attention heads /
FFN hidden dims sharded Megatron-style, the paged KV pool sharded by
head with PER-DEVICE byte budgets, block tables and ``pos`` replicated)
and is TOKEN-IDENTICAL to the 1-device engine under
``transfer_guard="disallow"``. CompileCounter budgets are unchanged per
mesh size (no per-device-count program blowup), and the compiled
per-token program family carries ONLY the Megatron all-reduces — a
resharding collective (all-gather / all-to-all / collective-permute /
reduce-scatter) on the hot path fails the audit.

Everything runs in-process: tests/conftest.py forces an 8-device
virtual CPU host mesh, so 1/2/4-device engines share one pytest run.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.analysis import CompileCounter
from deeplearning4j_tpu.analysis.runtime import device_residency
from deeplearning4j_tpu.inference import (DecodeScheduler, MetricsRegistry,
                                          PromptTooLongError)
from deeplearning4j_tpu.inference import sharding as shd
from deeplearning4j_tpu.models.sampling import generate_transformer
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph

V = 13
N_BLOCKS = 2


def _lm(cache=96, n_heads=4, n_kv_heads=None):
    conf = transformer_lm(vocab_size=V, d_model=32, n_heads=n_heads,
                          n_blocks=N_BLOCKS, rope=True,
                          n_kv_heads=n_kv_heads)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()


# 2 layers x (k+v) x Hkv4 x Dh8 x f32 = 512 bytes per cache position
# TOTAL; each of ``tp`` devices holds 512/tp
def _pool_mb(blocks, block, tp=1):
    """PER-DEVICE MiB budget buying exactly ``blocks`` usable blocks
    (+1 scratch) on a ``tp``-wide mesh."""
    return (blocks + 1) * block * 512 / tp / float(1 << 20)


@pytest.fixture(scope="module")
def net():
    return _lm()


@pytest.fixture(scope="module")
def solo(net):
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, V, n)) for n in (7, 23, 40, 61)]
    outs = [generate_transformer(net, p, 6, V, use_cache=True)
            for p in prompts]
    return prompts, outs


# ------------------------------------------------------- token identity --
def test_paged_greedy_token_identical_across_mesh_sizes(net, solo):
    """Greedy decode, mixed prompt lengths, paged pool: tp=2 and tp=4
    engines produce bit-identical token streams to the 1-device engine
    (and to solo decoding) under the device-residency audit — and at
    fixed PER-DEVICE pool bytes, capacity_blocks is device-invariant
    (each device holds 1/tp of every block)."""
    prompts, expect = solo
    # tp=1 is the existing single-device paged path (mesh=1 normalizes
    # to no mesh — covered by tests/test_paged_decode.py against the
    # same solo reference), so tier-1 spends its budget on real meshes
    for tp in (2, 4):
        eng = DecodeScheduler(net, V, n_slots=4, prefill_chunk=16,
                              kv_pool_mb=_pool_mb(32, 8, tp), kv_block=8,
                              mesh=tp, metrics=MetricsRegistry(),
                              transfer_guard="disallow").start()
        try:
            assert eng.tp == tp and eng.paged
            assert eng.pool.capacity_blocks == 32
            outs = [h.result(120) for h in
                    [eng.submit(p, 6) for p in prompts]]
        finally:
            eng.stop()
        assert outs == expect, f"tp={tp} diverged from solo decode"
        assert eng.pool.outstanding_refs() == 0


def test_seeded_sampling_prefix_restore_and_cow_identical(net):
    """Seeded-sampled decode through a paged tp=2 engine: the cold run,
    the prefix-restored repeat (zero-copy table remap), and the
    full-prompt-hit repeat whose one-token refeed copy-on-writes the
    shared tail block all match solo decoding bit-for-bit."""
    rng = np.random.default_rng(1)
    prompt = list(rng.integers(0, V, 40))  # 5 full 8-blocks: full hit
    kw = dict(temperature=0.8, top_k=5, top_p=0.9, seed=11)
    ref = generate_transformer(net, prompt, 6, V, use_cache=True, **kw)
    m = MetricsRegistry()
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(32, 8, 2), kv_block=8,
                          mesh=2, metrics=m,
                          transfer_guard="disallow").start()
    try:
        assert eng.generate(prompt, 6, timeout=120, **kw) == ref
        # repeat: full-block prefix hit -> COW refeed of the last block
        assert eng.generate(prompt, 6, timeout=120, **kw) == ref
        assert m.counter("prefix_cache_hits_total").value >= 1
    finally:
        eng.stop()


@pytest.mark.slow
def test_contiguous_mode_sharded(net, solo):
    """The contiguous layout (per-slot stripes, head-sharded, no pool)
    runs the mesh too: a decode and its repeat in the reused slot match
    solo."""
    prompts, expect = solo
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          mesh=2, metrics=MetricsRegistry(),
                          transfer_guard="disallow").start()
    try:
        assert eng.tp == 2 and not eng.paged and eng.pool is None
        assert eng.generate(prompts[2], 6, timeout=120) == expect[2]
        assert eng.generate(prompts[2], 6, timeout=120) == expect[2]
    finally:
        eng.stop()


@pytest.mark.slow
def test_preemption_under_pool_pressure_sharded(net):
    """A tp=2 pool that decode growth overflows still preempt-and-swaps
    and resumes token-identically (host-side table surgery never
    notices the mesh)."""
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, V, 6)) for _ in range(2)]
    expect = [generate_transformer(net, p, 20, V, use_cache=True)
              for p in prompts]
    m = MetricsRegistry()
    # each sequence grows to ceil((6+20-1)/8) = 4 blocks; 6 < 8
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(6, 8, 2), kv_block=8,
                          mesh=2, metrics=m,
                          transfer_guard="disallow").start()
    try:
        outs = [h.result(120) for h in
                [eng.submit(p, 20) for p in prompts]]
    finally:
        eng.stop()
    assert outs == expect
    assert m.counter("decode_preempted_total").value >= 1


def test_admission_gate_reserves_resident_prefill_claims(net):
    """The paged admission gate debits RESIDENT slots' not-yet-allocated
    prefill blocks (chunked prefill allocates lazily, so without the
    debit admission races ahead of allocation): a prompt mix whose
    joint block need overflows the pool serializes through admission
    with ZERO preemptions instead of admit-then-preempt churn — and the
    peak-resident gauge reads the pool's true concurrency."""
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, V, 64)) for _ in range(8)]
    expect = [generate_transformer(net, p, 4, V, use_cache=True)
              for p in prompts]
    m = MetricsRegistry()
    # 8 blocks per prompt (+1 decode tail), 19-block pool: ~2 resident
    eng = DecodeScheduler(net, V, n_slots=8, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(19, 8, 1), kv_block=8,
                          metrics=m).start()
    try:
        outs = [h.result(240) for h in
                [eng.submit(p, 4) for p in prompts]]
    finally:
        eng.stop()
    assert outs == expect
    assert m.counter("decode_preempted_total").value == 0
    assert m.gauge("decode_active_slots").max <= 3


# ------------------------------------------- program-family discipline --
def test_compile_budgets_unchanged_per_mesh_size(net, solo):
    """CompileCounter budgets hold at every mesh size AND the compiled
    program counts are identical across sizes — sharding multiplies
    devices, never the program family."""
    prompts, expect = solo
    compiled = {}
    for tp in (1, 2):
        eng = DecodeScheduler(net, V, n_slots=4, prefill_chunk=16,
                              kv_pool_mb=_pool_mb(32, 8, tp), kv_block=8,
                              mesh=tp, metrics=MetricsRegistry(),
                              transfer_guard="disallow")
        counter = CompileCounter.for_scheduler(eng)
        eng.start()
        try:
            outs = [h.result(120) for h in
                    [eng.submit(p, 6) for p in prompts]]
            # repeat -> prefix restore + COW paths compile too
            outs2 = eng.generate(prompts[1], 6, timeout=120)
        finally:
            eng.stop()
        assert outs == expect and outs2 == expect[1]
        counter.assert_within_budget()
        compiled[tp] = counter.counts()
    assert compiled[1] == compiled[2], (
        "per-device-count program blowup: " + repr(compiled))


@pytest.mark.slow
def test_warmup_covers_the_sharded_family(net, solo):
    """A warmed tp=2 engine (the supervisor's recovery/drain path)
    serves the full workload with ZERO further compiles."""
    prompts, expect = solo
    eng = DecodeScheduler(net, V, n_slots=4, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(32, 8, 2), kv_block=8,
                          mesh=2, metrics=MetricsRegistry(),
                          transfer_guard="disallow")
    eng.warmup()
    counter = CompileCounter.for_scheduler(eng)
    eng.start()
    try:
        outs = [h.result(120) for h in
                [eng.submit(p, 6) for p in prompts]]
    finally:
        eng.stop()
    assert outs == expect
    assert all(n == 0 for n in counter.counts().values()), counter.counts()


# --------------------------------------------- collective-count audit --
def test_decode_program_reduce_only_collectives(net):
    """THE hot-path invariant: the compiled per-token decode program
    contains exactly the Megatron partial-sum all-reduces (one per
    attention block + one per FFN) and NO resharding collective. Same
    audit for a prefill-chunk program."""
    eng = DecodeScheduler(net, V, n_slots=4, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(32, 8, 4), kv_block=8,
                          mesh=4, metrics=MetricsRegistry())
    counts = shd.collective_counts(shd.decode_program_hlo(eng))
    shd.assert_hot_path_collectives(counts, n_blocks=N_BLOCKS)
    assert counts["all-reduce"] == 2 * N_BLOCKS, counts
    assert all(counts[op] == 0 for op in shd.RESHARD_COLLECTIVES), counts
    pcounts = shd.collective_counts(shd.prefill_program_hlo(eng))
    shd.assert_hot_path_collectives(pcounts, n_blocks=N_BLOCKS)
    assert all(pcounts[op] == 0 for op in shd.RESHARD_COLLECTIVES), pcounts
    eng.stop()


def test_collective_audit_catches_a_resharding():
    """The audit itself must fail when handed a program containing a
    resharding collective (gate-of-the-gate)."""
    hlo = ("%x = f32[4,8] all-gather(f32[4,2] %p), dimensions={1}\n"
           "%y = f32[4,8] all-reduce(f32[4,8] %x)\n")
    counts = shd.collective_counts(hlo)
    assert counts["all-gather"] == 1 and counts["all-reduce"] == 1
    with pytest.raises(AssertionError, match="resharding"):
        shd.assert_hot_path_collectives(counts, n_blocks=2)


# ------------------------------------------------- residency, gating --
def test_multi_device_residency_fixture(net, solo):
    """The process-wide transfer-guard fixture (analysis/runtime.py)
    extended to a mesh engine: a full generate at tp=2 crosses the
    host<->device boundary only at the declared points — replicated
    `device_put` feeds in, `host_read` of the replicated distribution
    out — on every thread."""
    prompts, expect = solo
    eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(32, 8, 2), kv_block=8,
                          mesh=2, metrics=MetricsRegistry()).start()
    try:
        with device_residency("disallow"):
            assert eng.generate(prompts[0], 6, timeout=120) == expect[0]
    finally:
        eng.stop()


def test_mesh_disabled_when_heads_do_not_divide():
    """tp=3 cannot split 4 KV heads: tensor parallelism disables with a
    warning and the engine serves single-device, token-identically."""
    net = _lm()
    ref = generate_transformer(net, [1, 2, 3, 4, 5], 4, V, use_cache=True)
    with pytest.warns(RuntimeWarning, match="not divisible by the tp"):
        eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                              mesh=3, metrics=MetricsRegistry())
    assert eng.tp == 1 and eng.mesh is None
    eng.start()
    try:
        assert eng.generate([1, 2, 3, 4, 5], 4, timeout=120) == ref
    finally:
        eng.stop()


def test_mesh_disabled_for_recurrent_nets():
    from deeplearning4j_tpu.models.zoo import char_rnn_lstm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    rnn = MultiLayerNetwork(char_rnn_lstm(vocab_size=V, hidden=8)).init()
    with pytest.warns(RuntimeWarning,
                      match="tensor-parallel decode is DISABLED"):
        eng = DecodeScheduler(rnn, V, n_slots=1, prefill_chunk=8, mesh=2,
                              metrics=MetricsRegistry())
    assert eng.tp == 1 and eng.mesh is None


def test_mesh_without_tp_axis_warns_and_disables():
    """A mesh lacking a tp axis must say so, not silently single-device."""
    from deeplearning4j_tpu.parallel.mesh import default_mesh
    net = _lm()
    with pytest.warns(RuntimeWarning, match="no 'tp' axis"):
        eng = DecodeScheduler(net, V, n_slots=1, prefill_chunk=16,
                              mesh=default_mesh(2),
                              metrics=MetricsRegistry())
    assert eng.tp == 1 and eng.mesh is None


@pytest.mark.slow
def test_gqa_heads_shard_and_net_params_untouched(net):
    """A GQA net (Hkv=2 < H=4) shards at tp=2 on the KV heads; and the
    engine holds sharded COPIES — the caller's net params keep their
    original single-device placement."""
    import jax
    gqa = _lm(n_heads=4, n_kv_heads=2)
    ref = generate_transformer(gqa, [1, 2, 3, 4, 5, 6, 7], 4, V,
                               use_cache=True)
    eng = DecodeScheduler(gqa, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=_pool_mb(32, 8, 2), kv_block=8,
                          mesh=2, metrics=MetricsRegistry(),
                          transfer_guard="disallow").start()
    try:
        assert eng.tp == 2
        assert eng.generate([1, 2, 3, 4, 5, 6, 7], 4, timeout=120) == ref
    finally:
        eng.stop()
    for lp in gqa.params.values():
        for arr in lp.values():
            assert len(arr.devices()) == 1, \
                "sharding the engine mutated the caller's net"


# ----------------------------------------------- serving integration --
def test_per_device_pool_budget_and_mesh_gauges(net):
    """At fixed PER-DEVICE bytes a tp=4 pool holds 4x the blocks of the
    1-device pool — the effective-slots scaling the bench floors — and
    the mesh topology / per-device pool bytes surface as gauges."""
    per_device_mb = _pool_mb(16, 8, 1)  # 16 blocks' worth on 1 device
    caps = {}
    for tp in (1, 4):
        m = MetricsRegistry()
        eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                              kv_pool_mb=per_device_mb, kv_block=8,
                              mesh=tp, metrics=m)
        caps[tp] = eng.pool.capacity_blocks
        if tp > 1:
            snap = m.snapshot()
            assert snap["gauges"]["decode_mesh_devices"]["value"] == tp
            dev_bytes = snap["gauges"]["kv_pool_device_bytes"]["value"]
            assert dev_bytes <= per_device_mb * (1 << 20)
        eng.stop()
    assert caps[4] >= 4 * caps[1] - 4, caps
    # pool-bytes admission scales with it: a prompt too long for the
    # 1-device pool fits the 4-device one
    long_prompt = list(range(1, 9)) * 16  # 128 tokens = 16 blocks
    eng1 = DecodeScheduler(net, V, n_slots=1, prefill_chunk=16,
                           kv_pool_mb=per_device_mb, kv_block=8, mesh=1,
                           metrics=MetricsRegistry()).start()
    try:
        with pytest.raises(PromptTooLongError):
            eng1.submit([t % V for t in long_prompt], 8)
    finally:
        eng1.stop()


def test_server_exposes_mesh_topology(net):
    """InferenceServer(decode_tp=2): /metrics carries the mesh gauges,
    /info the topology, and /generate serves sharded."""
    import json
    import urllib.request

    from deeplearning4j_tpu.serving import InferenceServer

    srv = InferenceServer(net=net, decode_vocab=V, decode_slots=2,
                          prefill_chunk=16, kv_pool_mb=_pool_mb(32, 8, 2),
                          kv_block=8, decode_tp=2).start()
    try:
        port = srv.port
        ref = generate_transformer(net, [1, 2, 3, 4, 5], 4, V,
                                   use_cache=True)
        body = json.dumps({"prompt": [1, 2, 3, 4, 5],
                           "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req).read())
        assert out["tokens"] == ref
        metrics = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics").read())
        assert metrics["gauges"]["decode_mesh_devices"]["value"] == 2
        assert "kv_pool_device_bytes" in metrics["gauges"]
        info = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/info").read())
        assert info["mesh"]["tp"] == 2
        assert info["mesh"]["devices"] >= 2
    finally:
        srv.stop()
