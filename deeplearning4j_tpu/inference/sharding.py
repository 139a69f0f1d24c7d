"""Tensor-parallel sharding for the decode engine (ISSUE 9 tentpole).

The training side already shards weights Megatron-style over a mesh axis
(`parallel/tensor_parallel.py`); this module applies the same scheme to
the *serving* hot path so model size and KV-pool capacity scale past one
chip's HBM. Everything is pure annotation: params, variables, and the
engine's carried state pytree are placed with `NamedSharding`s on a 1-D
``tp`` mesh, and GSPMD partitions the existing jitted decode / prefill /
COW program families — no program body changes.

Sharding plan (the weight-update-sharding / array-redistribution papers,
arxiv 2004.13336 / 2112.01075: pick shardings so the steady-state loop
needs no resharding collectives):

  - attention Wq/Wk/Wv column-parallel (head dim over ``tp``), Wo
    row-parallel, bias replicated — one all-reduce per attention block;
  - FFN up-projection column-parallel (hidden dim over ``tp``), its bias
    sharded with it, down-projection row-parallel — one all-reduce per
    FFN;
  - embeddings, LayerNorms, and the OUTPUT head replicated. The training
    scheme column-shards any activated DenseLayer, which would include a
    softmax output head — sharding the vocab axis would put softmax
    reductions and a per-token host gather of the sampled distribution
    on the hot path, so decode keeps heads replicated;
  - the KV cache (contiguous ``k``/``v`` stripes and paged
    ``k_pages``/``v_pages`` alike) sharded on its **Hkv head axis**:
    each device holds only its heads' rows, so at fixed per-device HBM
    the pool holds ``tp×`` the blocks. ``pos``, token ids, the ``live``
    mask, and the host-authoritative block tables are replicated —
    paged attention, prefix restore remaps, COW, and preemption are
    host-side table surgery that never notices the mesh.

Consequence (provable, see :func:`collective_counts`): the per-token
decode program contains ONLY the two all-reduces per transformer block
(attention output + FFN output). Anything else — an all-gather,
all-to-all, reduce-scatter, or collective-permute — means a chosen
sharding disagreed with the dataflow and GSPMD inserted a resharding on
the per-token path; the runtime audit (tests/test_sharded_decode.py)
fails the build when that happens.

CPU verification: ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
gives N host "devices" whose collectives run the real partitioner, so
token-identity and the collective budget are tier-1-testable without
accelerators (tests/conftest.py already forces an 8-device mesh).
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

TP_AXIS = "tp"

# HLO collective ops that may legitimately appear in a tensor-parallel
# decode step (reductions of row-parallel partial sums) vs. the ones
# whose presence means a resharding snuck onto the hot path
REDUCE_COLLECTIVES = ("all-reduce",)
RESHARD_COLLECTIVES = ("all-gather", "all-to-all", "reduce-scatter",
                       "collective-permute", "ragged-all-to-all")
ALL_COLLECTIVES = REDUCE_COLLECTIVES + RESHARD_COLLECTIVES


def decode_mesh(n_devices: int, axis: str = TP_AXIS) -> Mesh:
    """1-D tensor-parallel mesh over the first ``n_devices`` local
    devices. The serving CLI's ``--tp N`` resolves through here."""
    devs = jax.devices()
    if n_devices > len(devs):
        raise ValueError(
            f"tp={n_devices} needs {n_devices} devices, have {len(devs)} "
            "(CPU: set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return Mesh(np.asarray(devs[:n_devices]), (axis,))


def decode_param_specs(conf, axis: str = TP_AXIS) -> Dict[str, Dict[str, P]]:
    """Per-vertex PartitionSpecs for DECODE: the training Megatron scheme
    (`parallel.tensor_parallel._tp_specs_for_graph`) with every output
    vertex forced replicated — a column-parallel softmax head would shard
    the vocab axis and put softmax collectives + a sharded host readback
    on the per-token path."""
    from ..parallel.tensor_parallel import _tp_specs_for_graph
    specs = _tp_specs_for_graph(conf, axis)
    for out in conf.network_outputs:
        specs[out] = {}
    return specs


def shard_decode_params(net, mesh: Mesh, axis: str = TP_AXIS
                        ) -> Tuple[Dict, Dict]:
    """(sharded params, replicated variables) COPIES placed on ``mesh``.

    Unlike the training-side `shard_transformer_tp` this never mutates
    ``net`` — the caller's net keeps its original placement, so a
    1-device reference engine over the same net stays single-device.
    A spec dim the mesh axis does not divide falls back to replication
    with a warning (same contract as training)."""
    specs = decode_param_specs(net.conf, axis)
    repl = NamedSharding(mesh, P())

    def put(arr, spec, pname):
        for d, ax in enumerate(spec):
            if ax is not None and arr.shape[d] % mesh.shape[ax]:
                import warnings
                warnings.warn(
                    f"shard_decode_params: {pname} dim {d} (size "
                    f"{arr.shape[d]}) is not divisible by mesh axis "
                    f"'{ax}' ({mesh.shape[ax]}); replicating this param",
                    stacklevel=4)
                spec = P()
                break
        return jax.device_put(arr, NamedSharding(mesh, spec))

    params = {
        name: {pname: put(arr, specs.get(name, {}).get(pname, P()),
                          f"{name}/{pname}")
               for pname, arr in lp.items()}
        for name, lp in net.params.items()}
    variables = jax.tree_util.tree_map(
        lambda a: jax.device_put(a, repl), net.variables)
    return params, variables


def state_shardings(states, mesh: Mesh, axis: str = TP_AXIS):
    """NamedSharding pytree for the engine's carried state: K/V rows
    (contiguous ``k``/``v``: [n_slots, L, Hkv, Dh]; paged
    ``k_pages``/``v_pages``: [pages, block, Hkv, Dh]) sharded on the
    head axis 2, everything else (``pos``, recurrent h/c) replicated."""
    from .kvpool import PAGE_KEYS
    repl = NamedSharding(mesh, P())
    # axis 2 is Hkv for K/V rows ([.., .., Hkv, Dh]) AND for the int8
    # dequant scale pages ([pages, block, Hkv]) — one spec serves both;
    # PAGE_KEYS is the single source of truth for what counts as
    # shared pool storage (a new page-array key lands here for free)
    head = NamedSharding(mesh, P(None, None, axis))
    out = {}
    for key, st in states.items():
        if isinstance(st, dict) and (
                ("k" in st and "v" in st) or "k_pages" in st):
            out[key] = {k: (head if k in ("k", "v") + PAGE_KEYS
                            else repl) for k in st}
        else:
            out[key] = jax.tree_util.tree_map(lambda _: repl, st)
    return out


def paged_kernel_shard_specs(axis: str = TP_AXIS) -> Dict[str, P]:
    """PartitionSpecs for the fused paged-decode kernel's shard_map
    (ops/pallas_kernels.py, ISSUE 15) — the SAME head-axis split the
    engine already places its state with, so handing the kernel its
    per-shard view costs zero resharding collectives:

      - ``rows``: q [B, 1, H, Dh] / page arrays [pages, block, Hkv, Dh]
        / the kernel output — head axis 2 over ``axis`` (matches
        `state_shardings`' page placement and the column-parallel Wq's
        propagated q split);
      - ``scales``: int8 dequant scale pages [pages, block, Hkv] —
        trailing head axis over ``axis``;
      - ``host``: block tables and ``pos`` — replicated, like every
        other host-authoritative input.

    The kernel grids over the LOCAL Hkv shard inside the shard_map and
    never communicates, so the per-token program keeps the Megatron
    budget: exactly the two all-reduces per transformer block
    (:func:`assert_hot_path_collectives` verifies this with the kernel
    engaged, same audit as the XLA path)."""
    return {"rows": P(None, None, axis, None),
            "scales": P(None, None, axis),
            "host": P()}


def kv_heads_shardable(abstract_states, attn_keys, tp: int) -> bool:
    """True when every attention layer's Hkv head count divides by
    ``tp`` — the hard requirement for head-sharding the KV cache (param
    sharding can fall back per-weight; the cache cannot)."""
    return bool(attn_keys) and all(
        abstract_states[key]["k"].shape[2] % tp == 0 for key in attn_keys)


# -- compiled-program collective audit -------------------------------------
def collective_counts(hlo_text: str) -> Dict[str, int]:
    """Instances of each collective op in compiled HLO text. Ops are
    counted at their definition site (`... = shape all-reduce(...)`,
    async variants included) so operand references and metadata lines
    don't inflate the count."""
    return {op: len(re.findall(
        rf"\s{re.escape(op)}(?:-start)?\(", hlo_text))
        for op in ALL_COLLECTIVES}


def decode_program_hlo(engine) -> str:
    """Compiled HLO of the engine's per-token decode program, lowered
    with the exact arg placements live dispatch uses (same jit cache
    key — auditing a warmed engine compiles nothing new)."""
    from .kvpool import SCRATCH_BLOCK
    ids = engine._dev_array(np.zeros((engine.n_slots,), np.int32))
    live = engine._dev_array(np.zeros((engine.n_slots,), bool))
    if engine.paged:
        nb = engine.table_buckets[0]
        table = engine._dev_array(
            np.full((engine.n_slots, nb), SCRATCH_BLOCK, np.int32))
        lowered = engine._jstep.lower(engine._params, engine._variables,
                                      ids, live, table, engine._states)
    else:
        lowered = engine._jstep.lower(engine._params, engine._variables,
                                      ids, live, engine._states)
    return lowered.compile().as_text()


def prefill_program_hlo(engine, bucket: Optional[int] = None) -> str:
    """Compiled HLO of one prefill-chunk program (smallest bucket by
    default) — the other half of the steady-state program family."""
    from .kvpool import SCRATCH_BLOCK
    b = bucket or engine.prefill_buckets[0]
    slot0 = engine._dev_index(0)
    one = engine._dev_index(1)
    ids = engine._dev_array(np.zeros((b,), np.int32))
    if engine.paged:
        nb = engine.table_buckets[0]
        table = engine._dev_array(
            np.full((engine.n_slots, nb), SCRATCH_BLOCK, np.int32))
        lowered = engine._jprefill.lower(
            engine._params, engine._variables, slot0, ids, one, table,
            engine._states)
    else:
        lowered = engine._jprefill.lower(
            engine._params, engine._variables, slot0, ids, one,
            engine._states)
    return lowered.compile().as_text()


def verify_program_hlo(engine) -> str:
    """Compiled HLO of the engine's speculative multi-token VERIFY
    program (ISSUE 10) with live-dispatch placements — it must obey the
    same zero-resharding discipline as decode: the chain axis is just a
    wider T, so the Megatron all-reduce count per block is unchanged."""
    from .kvpool import SCRATCH_BLOCK
    ids = engine._dev_array(
        np.zeros((engine.n_slots, engine.speculate + 1), np.int32))
    live = engine._dev_array(np.zeros((engine.n_slots,), bool))
    if engine.paged:
        nb = engine.table_buckets[0]
        table = engine._dev_array(
            np.full((engine.n_slots, nb), SCRATCH_BLOCK, np.int32))
        lowered = engine._jverify.lower(
            engine._params, engine._variables, ids, live, table,
            engine._states)
    else:
        lowered = engine._jverify.lower(
            engine._params, engine._variables, ids, live,
            engine._states)
    return lowered.compile().as_text()


def draft_program_hlo(engine) -> str:
    """Compiled HLO of the speculative DRAFT step (the shallow-exit /
    draft-net single-token forward): a prefix of the target's blocks
    under the same param specs, so its per-token program is bounded by
    the same audit — zero resharding, <= 2 all-reduces per draft
    block."""
    ids = engine._dev_array(np.zeros((engine.n_slots,), np.int32))
    live = engine._dev_array(np.zeros((engine.n_slots,), bool))
    lowered = engine._jdraft_step.lower(
        engine._draft_params, engine._draft_variables, ids, live,
        engine._draft_states)
    return lowered.compile().as_text()


def assert_hot_path_collectives(counts: Dict[str, int],
                                n_blocks: int) -> None:
    """The collective-count budget for a per-token program: resharding
    collectives are FORBIDDEN, and reduce ops are bounded by the
    Megatron shape (attention + FFN all-reduce per block, with slack
    for partitioner-introduced mask/select reductions)."""
    bad = {op: n for op in RESHARD_COLLECTIVES
           if (n := counts.get(op, 0))}
    if bad:
        raise AssertionError(
            f"resharding collective(s) on the per-token hot path: {bad} "
            "— a chosen sharding disagrees with the dataflow "
            "(see inference/sharding.py docstring)")
    budget = 4 * n_blocks
    n_reduce = sum(counts.get(op, 0) for op in REDUCE_COLLECTIVES)
    if n_reduce > budget:
        raise AssertionError(
            f"{n_reduce} reduce collectives in the per-token program, "
            f"budget is {budget} (4 per transformer block): the program "
            "is reducing more than the two Megatron partial sums per "
            "block")
