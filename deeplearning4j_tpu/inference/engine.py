"""Slot-based continuous-batching decode scheduler for generative LMs.

`models/sampling.generate_transformer` decodes ONE sequence at a time: a
serving host running it back-to-back leaves (slots-1)/slots of every decode
step's batch dimension empty. This engine is the Orca-style iteration-level
scheduler (continuous batching) over the existing attention KV cache:

  - a fixed number of decode *slots* (the batch dimension of one shared,
    per-layer KV cache / recurrent state pytree);
  - each engine step runs ALL slots through ONE jitted single-token
    forward — int32 token ids in (the one-hot is built on device inside
    the program, so per-step host->device traffic is n_slots ints, not a
    dense [n_slots, 1, vocab] float batch), next-token distributions out.
    The XLA program is compiled exactly once and never recompiles as
    sequences come and go;
  - new sequences are admitted into free slots *between* steps (their
    slot's state rows are zeroed and, for attention layers, the per-slot
    cache position — `nn/layers/attention.py` vector-``pos`` plumbing —
    restarts at 0; stale K/V beyond a row's own position is causally
    masked, so slot reuse needs no cache wipe to be correct);
  - finished sequences (max tokens or EOS) are evicted the step they
    finish, freeing the slot for the next queued request.

Chunked prefill (the ISSUE 2 tentpole): prompts no longer prefill
token-by-token. A second family of jitted programs — one per power-of-two
chunk bucket (16/32/64/... up to ``prefill_chunk``, reusing the batcher's
bucket helper) — runs C prompt tokens through the net in ONE forward for a
single slot: the slot's state rows are sliced out of the shared pytree,
the chunk writes K/V rows ``[pos, pos+C)`` in one offset
`dynamic_update_slice` (RoPE phases from the slot's absolute positions,
causal masking within the chunk), and the rows are scattered back. Nets
with recurrent h/c state (LSTM/GRU facades) prefill through an equivalent
`lax.scan` chunk program — C single-token steps fused into one device
dispatch, padded steps masked out of the state carry. Time-to-first-token
drops from O(prompt_len) to O(prompt_len / C) engine steps.

Scheduling is Sarathi-style: each iteration runs AT MOST ONE bounded
prefill chunk alongside the regular all-slots decode step, so decode
latency for resident sequences stays protected while admitted prompts
still prefill C tokens per iteration. Slots that are mid-prefill (or idle)
are masked out of the decode step *inside* the jitted program — their
recurrent state and cache position are frozen by a `live` mask, so the
shared-batch step cannot corrupt a half-prefilled slot.

Paged KV decode (the ISSUE 6 tentpole, ``kv_pool_mb > 0``): the live
decode cache itself becomes the block pool. Per-layer K/V moves from
``[n_slots, max_cache_len]`` stripes into pool-wide page arrays
(``[capacity+1, kv_block]`` rows, page 0 scratch) and each slot reaches
its rows through a host-authoritative int32 block table shipped per
dispatch, padded to pow2 bucket widths (one XLA program per bucket — no
per-length recompiles). HBM cost stops being ``slots × max_cache_len``:
admission is bounded by POOL bytes (oversize prompts 413 only when they
cannot fit the whole pool), blocks allocate lazily as ``pos`` crosses
block boundaries, prefix restore/publish degenerate to zero-copy
block-table remaps against the pool's trie (copy-on-write duplicates
the one shared block a full-prompt hit's refeed writes), and under pool
pressure the latest-submitted slot is preempted — blocks released,
sequence requeued at the front, resumed later by re-prefilling prompt +
generated-so-far (host RNG untouched, so the resumed output is
token-identical to an unpreempted run). The pool is also the only prefix
cache (`inference/kvpool.py`): admission walks its radix trie over the
prompt's full ``kv_block``-sized blocks, points the slot's table at the
longest cached chain and advances ``pos`` past the hit, so chunked
prefill only runs the cold suffix; a finished prompt's full blocks are
adopted by the trie in place. Cached keys are stored pre-rotated at
absolute positions, so a pos-0-anchored prefix is bit-identical across
requests. Contiguous ``[n_slots, max_cache_len]`` stripes (the default)
carry no prefix cache.

Token selection reuses `models/sampling.sample_logits`, so greedy engine
output is token-identical to solo `generate_transformer(use_cache=True)`
decoding (tested, chunked and token-by-token, prefix-restored and cold,
paged and contiguous), and seeded sampled output matches too (same
per-sequence RNG consumption order).

Works for both facades: transformer ComputationGraphs (KV-cache states)
and recurrent MultiLayerNetworks (h/c states — admitting a sequence zeroes
its slot's rows).
"""
from __future__ import annotations

import contextlib
import threading
import time
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.runtime import (CompileCounter, device_index, host_read,
                                ledger_check_request, ledger_check_zero,
                                ledger_forget, ledger_note)
from ..models.sampling import sample_logits
from ..nn.layers.attention import LatentAttentionLayerImpl
from ..nn.layers.experts import RoutedExpertsLayerImpl
from ..nn.layers.recurrent import (BaseRecurrentImpl,
                                   _materialize_rnn_states)
from ..nn.multilayer import _compute_dtype_of
from ..ops.grouped_matmul import row_tile, tile_visits
from . import failpoints
from .batcher import QueueFullError, bucket_for, pow2_buckets
from .kvpool import PAGE_KEYS, SCRATCH_BLOCK, KVPool
from .logitproc import CompiledGrammar, LogitState, MaskPool
from .metrics import MetricsRegistry, default_registry
from .profiler import StepPhaseProfiler, program_costs
from .sharding import (TP_AXIS, decode_mesh, kv_heads_shardable,
                       shard_decode_params, state_shardings)
from .speculative import ForkGroup, accept_tokens, build_shallow_draft
from .trace import FlightRecorder, default_recorder, new_request_id

# chunk buckets never go below this (a 3-token tail still pads to one
# small program instead of compiling a 3-wide one-off); buckets smaller
# than 16 only exist when prefill_chunk itself is smaller
_MIN_CHUNK_BUCKET = 16

# the resource kinds THIS module's ledger seams own (graftleak's runtime
# half, `analysis.runtime.resource_ledger`): request-end and stop-time
# balance checks judge only these, so an in-process router's still-open
# journal record for the same request id is never misread as an engine
# leak
_LEDGER_KINDS = frozenset(
    ("trie_pin", "pool_block", "mask_row", "engine_slot"))


def _keeps_pages(impl) -> bool:
    """This impl's state is a cache addressed by position (a contiguous
    stripe, or pool pages): asked of the layer (`keeps_pages`)."""
    return isinstance(impl, BaseRecurrentImpl) and impl.keeps_pages()


def _is_paged(st) -> bool:
    """This state entry is a paged attention layer's (pool-wide page arrays
    under `PAGE_KEYS` beside the per-slot leaves)."""
    return isinstance(st, dict) and any(k in st for k in PAGE_KEYS)


class _EngineFenced(Exception):
    """Internal: a fenced (supervisor-disowned) scheduler thread woke up
    mid-iteration; unwind out of the loop without touching handles."""


class PromptTooLongError(ValueError):
    """The request cannot fit the KV cache. Contiguous mode:
    ``len(prompt) + max_new_tokens - 1 > max_cache_len``. Paged mode the
    bound is the WHOLE pool — rejected only when the request's block
    count exceeds ``capacity_blocks`` (``blocks_needed`` /
    ``blocks_available`` attributes carry the admission math for the
    serving layer's 413 body). Raised at submit time (never admitted,
    never queued) so the serving layer can answer HTTP 413 instead of
    the sequence dying mid-decode on the attention layer's
    cache-overflow guard."""

    blocks_needed: Optional[int] = None
    blocks_available: Optional[int] = None


class LoadSheddedError(QueueFullError):
    """The request was dropped from the queue by the graceful-degradation
    ladder (`inference/supervisor.py` level >= 1: queued load below the
    surviving priority line is shed before the engine melts). A
    QueueFullError subclass so the serving layer's existing 503 mapping
    (retryable, not a client error) applies unchanged."""


class EngineCrashedError(RuntimeError):
    """The scheduler loop died (uncaught exception or injected fault)
    with this request in flight and no supervisor attached to recover
    it. Supervised engines never surface this — the supervisor requeues
    the request onto the rebuilt engine instead."""


class DecodeHandle:
    """Completion handle for one submitted generation request."""

    def __init__(self, prompt_len: int, max_new_tokens: int,
                 request_id: Optional[str] = None, priority: int = 0):
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.request_id = request_id or new_request_id()
        self.priority = int(priority)
        self.retries = 0  # crash-recovery resubmissions (supervisor)
        self.tokens: List[int] = []
        # why the request ended: "length" | "eos" | "stop" | "grammar"
        # | "cancelled" (None while decoding / on error) — echoed in
        # the /generate response and the SSE terminal event
        self.finish_reason: Optional[str] = None
        # per-request token event queue (logitproc.TokenStream) for SSE
        # streaming; the scheduler pushes released tokens as they
        # decode, _finish() closes it with the terminal event
        self.stream = None
        self._done = threading.Event()
        self._cancel = threading.Event()
        self._error: Optional[BaseException] = None
        self.t_submit = time.monotonic()
        # lifecycle timestamps stamped by the scheduler thread: the
        # request's wall time splits into four CONTIGUOUS phases —
        # queued [submit, admitted], restore [admitted, restored] (slot
        # reset + prefix-cache restore), prefill [restored, first token],
        # decode [first token, done] — so the `timings()` breakdown sums
        # to the end-to-end latency by construction
        self.t_admitted: Optional[float] = None
        self.t_restored: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.t_done: Optional[float] = None
        # engine iterations this sequence was stepped before its first
        # token (the bench's TTFT-in-steps: prompt_len token-by-token,
        # ceil(prompt_len / chunk) chunked)
        self.steps_to_first_token: Optional[int] = None

    def timings(self) -> Dict[str, float]:
        """Per-phase wall-time breakdown (ms). Phases are contiguous
        segments of [t_submit, t_done], so ``queue_ms + restore_ms +
        prefill_ms + decode_ms == total_ms`` (a request cancelled before
        a boundary reports 0 for the phases it never reached)."""
        end = self.t_done if self.t_done is not None else time.monotonic()
        admitted = self.t_admitted if self.t_admitted is not None else end
        restored = self.t_restored if self.t_restored is not None \
            else admitted
        first = self.t_first_token if self.t_first_token is not None else end
        first = max(first, restored)
        return {
            "queue_ms": round((admitted - self.t_submit) * 1e3, 3),
            "restore_ms": round((restored - admitted) * 1e3, 3),
            "prefill_ms": round((first - restored) * 1e3, 3),
            "decode_ms": round((end - first) * 1e3, 3),
            "total_ms": round((end - self.t_submit) * 1e3, 3),
        }

    def _finish(self, err: Optional[BaseException] = None) -> None:
        if self._done.is_set():
            return  # first finisher wins (supervisor shutdown can race
            # the engine's own teardown sweep over the same handle)
        self._error = err
        self.t_done = time.monotonic()
        self._done.set()
        if self.stream is not None:
            # the stream's terminal event (tokens are FINAL here — stop
            # truncation happens before _finish): flushes any tokens the
            # stop hold-back withheld, then the done record
            self.stream.close(self, err)

    def _reset_for_retry(self) -> None:
        """Crash recovery (`inference/supervisor.py`): wipe the partial
        progress so a resubmission re-runs the request from scratch on
        the rebuilt engine. Decode is deterministic per request — the
        resubmitted `_ActiveSeq` reseeds `default_rng(seed)` — so the
        re-run reproduces the SAME token sequence the crashed attempt
        was mid-way through (token-identity across restarts). t_submit
        survives: recovered-request latency is measured from the
        ORIGINAL submit, crash included."""
        assert not self._done.is_set(), \
            "cannot retry a handle that already finished"
        self.retries += 1
        self.tokens = []
        self._error = None
        # one statement, GIL-atomic per store: only the supervisor calls
        # this, only for handles of a FENCED engine (its thread joined or
        # exiting at the fence check), so no writer races it; a client
        # thread calling timings() mid-reset reads each phase stamp
        # either old or None — both of which timings() already clamps
        self.t_admitted = self.t_restored = None  # graftlint: disable=CC005
        self.t_first_token = self.t_done = None  # graftlint: disable=CC005
        self.steps_to_first_token = None
        self.finish_reason = None
        # self.stream is deliberately KEPT: its index-deduplicated
        # pushes make the token-identical re-decode invisible to a
        # streaming client (already-streamed indices are skipped)

    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self) -> None:
        """Ask the scheduler to evict this sequence at its next step.

        Without this, a caller that times out waiting on `result()` leaks
        its slot: the sequence keeps decoding to max_new_tokens with
        nobody reading the answer. Cancellation is asynchronous — the
        scheduler thread frees the slot, counts `decode_cancelled_total`,
        and marks the handle done (with whatever tokens were produced).
        Cancelling a finished handle is a no-op."""
        self._cancel.set()

    def cancelled(self) -> bool:
        return self._cancel.is_set()

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._done.wait(timeout):
            raise TimeoutError("generation not finished")
        if self._error is not None:
            raise self._error
        return self.tokens


class _ActiveSeq:
    """Book-keeping for one slot-resident sequence."""
    __slots__ = ("handle", "prompt", "fed", "rng", "temperature", "top_k",
                 "top_p", "eos_id", "steps", "pool_node", "block_ids",
                 "shared", "written", "phase", "resumed", "folded",
                 "cow_starved", "fork", "draft_fed", "proc", "rolled",
                 "summary_ids")

    def __init__(self, handle: DecodeHandle, prompt: Sequence[int],
                 temperature: float, top_k: Optional[int],
                 top_p: Optional[float], seed: int, eos_id: Optional[int]):
        self.handle = handle
        self.prompt = [int(t) for t in prompt]
        self.fed = 0  # prompt tokens fed so far
        self.rng = np.random.default_rng(seed)
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.steps = 0  # engine iterations that advanced this sequence
        self.pool_node = None  # locked trie node of the restored prefix
        # -- paged-mode bookkeeping (engine.paged) --
        self.block_ids: List[int] = []  # table entries, logical order
        self.shared: List[bool] = []    # True = trie-owned (COW on write)
        self.written = 0  # host mirror of the slot's device cache pos
        # a net whose attention recycles pages (EVA, `_roll_window`):
        # logical blocks [0, rolled) went back to the pool when their
        # windows closed (their `block_ids` entries are scratch), and the
        # chunk summaries live in pages of their own, for the request's life
        self.rolled = 0
        self.summary_ids: List[int] = []
        # request-track span currently open ("queued" -> "prefill" ->
        # "decode", with "preempted" bridging a swap-out) — the single
        # source of truth for span transitions, because a RESUMED
        # sequence re-enters prefill with t_first_token already stamped
        self.phase = "queued"
        self.resumed = False  # has been preempted at least once
        self.folded = 0  # generated tokens already folded into `prompt`
        # set when a COW duplicate could not get a page even by
        # preempting (every page backs this very prompt): the resume's
        # restore caps its hit one block short so no write ever lands in
        # a shared block — without this a full-pool full-prompt hit
        # would preempt/restore/starve forever
        self.cow_starved = False
        # -- best-of-n fork group (speculative.ForkGroup, or None) --
        self.fork = None
        # -- speculative decoding: tokens of `full_context()` the DRAFT
        # net has ingested (its contiguous cache row count / pos mirror)
        self.draft_fed = 0
        # per-request logit-processor pipeline (logitproc.LogitState):
        # penalty counts, grammar DFA state, stop matcher, device-mask
        # residency. None for plain requests — the hot path unchanged.
        self.proc: Optional[LogitState] = None

    @property
    def blocks_held(self) -> int:
        """Pool blocks this sequence's table entries stand for now."""
        return len(self.block_ids) - self.rolled + len(self.summary_ids)

    def full_context(self) -> List[int]:
        """Every token the sequence is conditioned on so far (prompt —
        which absorbs preempt-folded generations — plus the unfolded
        generated tail). The draft net's catch-up target."""
        return self.prompt + self.handle.tokens[self.folded:]

    def known_tokens(self) -> int:
        """len(full_context()) without building the list."""
        return len(self.prompt) + len(self.handle.tokens) - self.folded

    def tail_context(self, k: int) -> List[int]:
        """The last ``k`` tokens of `full_context` as an O(k) slice —
        the speculative lockstep only ever feeds the trailing lag<=2
        tokens, and copying a multi-thousand-token context per slot per
        iteration onto the hot path would tax the very loop speculation
        exists to speed up."""
        gen = self.handle.tokens[self.folded:] if k > 0 else []
        if len(gen) >= k:
            return gen[len(gen) - k:]
        return self.prompt[len(self.prompt) - (k - len(gen)):] + gen

    def next_input(self) -> int:
        """Token to feed this step: the next prompt token while prefilling,
        else the last generated token."""
        if self.fed < len(self.prompt):
            return self.prompt[self.fed]
        return self.handle.tokens[-1]

    @property
    def sampling(self) -> bool:
        """Past the last prompt token, every step's output is sampled."""
        return self.fed >= len(self.prompt)


class DecodeScheduler:
    """Continuous-batching decode over a shared model and KV cache.

    ``net``: a trained ComputationGraph (e.g. `models/zoo.transformer_lm`,
    causal attention) or recurrent MultiLayerNetwork whose output is a
    next-token distribution. The engine owns a private state pytree — it
    never touches ``net._rnn_state``, so callers may keep using the net's
    own streaming API concurrently (single-threaded model access is still
    required; the engine's step thread is that single thread while
    running).

    ``prefill_chunk``: max prompt tokens per prefill program (the TTFT /
    decode-latency knob — bigger chunks reach the first token in fewer
    iterations but each chunked iteration holds the device longer, adding
    tail latency to resident decodes). <= 1 disables chunked prefill and
    restores token-by-token prompt feeding through the decode step.

    ``kv_pool_mb``: byte budget (MiB) for the PAGED live-decode KV pool
    (`inference/kvpool.py`, the ISSUE 6 tentpole). > 0 replaces the
    per-slot contiguous ``max_cache_len`` stripes with pool-wide
    fixed-size pages reached through per-slot block tables: slot
    capacity is bounded by pool bytes (admission is pool-sized, not
    ``max_cache_len``-sized), blocks allocate lazily as ``pos`` crosses
    block boundaries, prefix restore/publish are zero-copy block-table
    remaps against the built-in trie prefix index, and under pool
    pressure the latest-submitted slot is preempted (blocks released,
    sequence requeued and later resumed, token-identically). Attention
    nets only; recurrent nets fall back to contiguous with a warning.
    What a request holds is asked of the net's attention layers
    (``blocks_needed``): a net with `EvaAttentionLayer` gives its exact
    pages back as each window closes and keeps one summary page per
    ``kv_block`` chunks (`_roll_window`); such a net is served through
    the pool only, and the prefix trie stands aside for it
    (docs/serving.md, "The EVA cache"). A net that also has layers whose
    state is a fixed size a slot and not addressed by position
    (`Mamba2Layer`) keeps those per-slot leaves beside the page arrays,
    outside this budget (``slot_state_bytes``), steps them under the same
    write mask as the pages, and is served through the pool only, one
    device, without the trie (docs/serving.md, "State-space layers").

    ``kv_block``: positions per pool block — only full blocks of a
    prompt are shared, so smaller blocks match more but cost more
    metadata. The pool only engages for attention nets (pos-0-anchored
    KV prefixes; recurrent h/c state has no position-addressed rows).

    ``tracer``: span flight recorder (`inference/trace.py`, default the
    process-wide one). Every request's lifecycle is recorded — queued /
    prefix_restore / prefill (per-chunk spans on the slot track) /
    decode / finish-or-cancel, plus slot occupancy, compile, and
    pool-eviction instants — as O(1) lock-free ring appends, cheap
    enough to stay on in production. `GET /trace` on the serving server
    and `DecodeHandle.timings()` read it back.

    ``mesh``: tensor-parallel device mesh (ISSUE 9). An int ``N > 1``
    builds a 1-D ``tp`` mesh over the first N local devices
    (`inference/sharding.py`); a `jax.sharding.Mesh` with a ``tp`` axis
    is used as-is. Attention heads and FFN hidden dims shard across the
    axis (Megatron pairing, output head replicated), the KV cache —
    contiguous stripes and paged ``k_pages``/``v_pages`` alike — shards
    on its Hkv head axis (the ``kv_pool_mb`` budget becomes PER-DEVICE
    bytes: at fixed per-device HBM the pool holds ``tp×`` the blocks),
    and everything host-authoritative (block
    tables, ids, masks, ``pos``) replicates — so paged attention,
    prefix restore, COW, and preemption run unchanged per shard. The
    per-token program's only collectives are the two Megatron
    all-reduces per block (audited: `sharding.collective_counts`).
    Requires a transformer ComputationGraph whose every Hkv divides the
    axis size; otherwise tensor parallelism is DISABLED with a warning
    and the engine runs single-device. The engine never mutates
    ``net`` — it holds sharded param COPIES, so a live-trained net's
    updates stop reaching a sharded engine (rebuild to pick them up).

    ``speculate``: speculative decoding (ISSUE 10). ``G > 0`` drafts G
    tokens per decode-ready slot per iteration with a cheap draft model
    and verifies them in ONE multi-token target forward; acceptance
    samples each position from the TARGET distribution with the
    sequence's own RNG, so output is token-identical to ``G = 0`` by
    construction — only tokens/s changes (multiplicatively on
    high-acceptance traffic, mildly negative on adversarially random
    traffic). ``draft_blocks``: depth of the default SELF-speculative
    draft — the target's first K transformer blocks rewired into its
    own output head, params shared by reference (default: half the
    blocks). ``draft_net``: an explicit draft ComputationGraph instead
    (same vocab/head contract); required for models the shallow-exit
    surgery cannot cut (non-zoo graph shapes disable speculation with
    a RuntimeWarning).

    ``mask_rows``: device rows of the grammar mask table
    (`inference/logitproc.py`, ISSUE 14) — a fixed ``[mask_rows,
    vocab]`` additive table (row 0 reserved admit-all) that
    grammar-constrained requests' per-DFA-state token masks upload
    into once at admission; the masked decode/verify/draft program
    variants gather one row per slot and add it (0 allowed / -inf
    forbidden) to the output distribution. <= 1 disables the device
    table; grammars then mask host-side only (always correct — the
    exact allow row applies at sampling either way).

    ``kv_dtype``: ``"int8"`` quantizes the PAGED pool's page arrays
    (per-(position, head) max-abs scales stored alongside; quantize on
    write, dequantize on gather) — less than half the bytes per block,
    so a fixed ``kv_pool_mb`` holds 2x+ the blocks. Lossy: decode is
    plausible but not bit-identical to the f32 cache. Paged mode only.

    ``paged_kernel``: how the T=1 decode step reads the paged cache.
    ``"auto"`` (default): on a TPU, in bfloat16 or float32, off a mesh
    and with pages that are not int8, the fused paged read
    (ops/paged_read.py, ISSUE 31/33: a step reads the pages its fed
    slots hold rows in and no others), by the layer's own static rule
    (`fused_read_engages`); elsewhere the XLA gather at the table
    bucket's width, or, for what the rule leaves (int8 pages, a mesh)
    in float32 with `pallas_kernels.enable()`, the older per-shape
    autotuned page-walk kernel (ISSUE 15). ``"on"`` takes the fused
    read on any backend (interpreted off the TPU) and forces the older
    kernel where only that applies; ``"off"`` pins the XLA gather.
    Either way prefill chunks, verify programs, and K/V writes stay in
    XLA and the decision is trace-time (no extra programs — decode
    stays <= 1 program per table bucket). `paged_kernel_engaged` gauge
    + the ``paged_kernel`` block of :meth:`debug_snapshot` report the
    per-bucket verdicts; `kv_pages_read_total` over
    `kv_pages_bucket_total` (`eva_` / `mla_` for an EVA / a latent net)
    says how much of the tables a step read.

    ``transfer_guard``: device-residency audit mode. When set (e.g.
    "disallow"), every scheduler iteration runs under that thread-local
    ``jax.transfer_guard`` level: any *implicit* host<->device transfer in
    the hot loop raises, proving the loop only crosses the boundary at its
    declared points — `analysis.runtime.host_read` for the sampled-token
    readback, `device_index`/`jnp.asarray`-of-ndarray for the token feed.
    The tier-1 residency tests run the engine this way permanently.
    """

    def __init__(self, net, vocab_size: int, *, n_slots: int = 4,
                 max_queue: int = 64, prefill_chunk: int = 64,
                 kv_block: int = 16,
                 kv_pool_mb: float = 0.0, kv_dtype: Optional[str] = None,
                 paged_kernel: str = "auto",
                 host_cache_mb: float = 0.0, disk_cache_mb: float = 0.0,
                 tier_dir: Optional[str] = None, tier_chunk_kib: int = 512,
                 mask_rows: int = 64,
                 mesh=None, speculate: int = 0,
                 draft_blocks: Optional[int] = None, draft_net=None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[FlightRecorder] = None,
                 profiler: Optional[StepPhaseProfiler] = None,
                 profile: bool = True,
                 transfer_guard: Optional[str] = None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        if paged_kernel not in ("auto", "on", "off"):
            raise ValueError(
                f"paged_kernel must be 'auto', 'on' or 'off', got "
                f"{paged_kernel!r}")
        self.net = net
        self.vocab_size = int(vocab_size)
        self.n_slots = int(n_slots)
        self.max_queue = int(max_queue)
        self.prefill_chunk = int(prefill_chunk)
        self.metrics = metrics if metrics is not None else default_registry()
        # span flight recorder (trace.py): every request's lifecycle is
        # recorded as spans/instants — O(1) lock-free ring appends, cheap
        # enough to default ON (the process-wide recorder). Tracks are
        # scoped per scheduler instance: a second scheduler sharing this
        # recorder must not interleave same-name spans on "scheduler"/
        # "slot N" tracks (the export pairs B/E LIFO per track)
        self.tracer = tracer if tracer is not None else default_recorder()
        # step-phase profiler + cost attribution (profiler.py, ISSUE 11):
        # per-iteration phase decomposition and the rolling FLOPs/MFU
        # window. Single-writer state written by the scheduler thread
        # only (the flight recorder's discipline); profile=False (or an
        # injected disabled profiler) reduces every stamp to one
        # attribute test — the bench-gated disarmed configuration
        sfx = self.tracer.track_scope("engine")
        self._sched_track = "scheduler" + sfx
        self.profiler = profiler if profiler is not None else \
            StepPhaseProfiler(self.metrics, enabled=bool(profile))
        # one `sched_iter` record per booked iteration on our track
        self.profiler.attach(self.tracer, self._sched_track)
        # serializes attribute_costs' seconds-long first computation:
        # two concurrent /debug/engine reads must not both trace the
        # whole program family (never touched by the scheduler thread)
        self._attr_lock = threading.Lock()
        self._attr_failed = False  # one-shot: a backend without a cost
        # model fails ONCE, not seconds of re-tracing per /debug poll
        self._slot_tracks = [f"slot {i}{sfx}" for i in range(self.n_slots)]
        self._graph = hasattr(net.conf, "vertices")  # facade detection
        self._dtype = _compute_dtype_of(net.conf.conf)
        self._cache_cap = self._min_cache_len()
        # abstract shapes first (jax.eval_shape — no device allocation):
        # paged mode replaces the contiguous [n_slots, max_cache_len]
        # stripes with pool pages, and materializing stripes only to
        # throw them away would make startup peak HBM stripes + pool —
        # the exact cost the paged layout exists to eliminate
        abstract_states = jax.eval_shape(self._init_states)
        self._states = None  # materialized once the KV layout is known
        self._slots: List[Optional[_ActiveSeq]] = [None] * self.n_slots
        self._queue: List[_ActiveSeq] = []
        self._cond = threading.Condition()
        self._running = False
        self._thread: Optional[threading.Thread] = None
        self._transfer_guard = transfer_guard
        # -- fault-tolerance surface (inference/supervisor.py) --
        # heartbeat: stamped once per loop pass (idle passes included —
        # the idle wait wakes every 0.1s), so a watchdog distinguishes
        # "quiet" from "stuck" by staleness alone. Plain float store:
        # atomic under the GIL, torn-read-free.
        self.heartbeat = time.monotonic()
        self.iterations = 0  # loop passes completed (watchdog progress)
        # set (with the exception) when the loop dies instead of the old
        # behavior — a daemon thread evaporating and every in-flight
        # handle blocking out its full timeout in silence
        self.crashed: Optional[BaseException] = None
        # fence(): a supervisor that declared this engine dead sets the
        # fence BEFORE requeueing its in-flight work elsewhere; a hung
        # loop thread that later wakes sees the fence at its next
        # iteration boundary (and _consume guards it) and exits without
        # touching handles the replacement engine now owns
        self._fenced = False
        # supervisor crash hook: called (with the exception) from the
        # dying loop thread. When None, the engine self-cleans: every
        # in-flight/queued handle fails fast with EngineCrashedError
        self._on_crash = None
        # degradation ladder level >= 2 caps prefill chunks (the pow2
        # family already contains every smaller bucket — changing the
        # cap compiles nothing new)
        self.chunk_cap: Optional[int] = None
        if self.prefill_chunk > 1:
            lo = min(_MIN_CHUNK_BUCKET, self.prefill_chunk)
            self.prefill_buckets = [b for b in pow2_buckets(self.prefill_chunk)
                                    if b >= lo]
        else:
            self.prefill_buckets = []
        # three things are asked of each stateful layer, and one answer
        # no longer stands for the others. Does its step take a chunk
        # (`takes_chunk`: the attention KV cache does, by offset writes and
        # an in-chunk causal mask; so does a state-space layer, in the
        # chunked form; h/c state steps one token at a time, and a net with
        # such a layer prefills through the lax.scan chunk program
        # instead)? Does it keep pages (`keeps_pages`: a cache addressed by
        # position, which the pool can hold)? Does it keep per-slot leaves
        # of a fixed size that it holds still itself under the write mask
        # (`masks_own_lanes`: they live in `self._states` beside the page
        # arrays, zeroed at admission, sliced and written back by slot in a
        # chunk, donated with the rest)?
        stateful = {key: impl for key, impl in self._impl_items()
                    if isinstance(impl, BaseRecurrentImpl)}
        self._chunk_dense = bool(stateful) and all(
            impl.takes_chunk() for impl in stateful.values())
        attn_keys = [key for key, impl in stateful.items()
                     if impl.keeps_pages()]
        self._ssm = [key for key, impl in stateful.items()
                     if impl.masks_own_lanes()]
        # what a request holds in the pool is asked of the layer kind
        # (`blocks_needed`), and so is whether its pages are recycled
        # while it lives (`page_recycling`: EVA's (window, chunk), None
        # for a cache of one row per position). One block table serves
        # every layer, so every layer must answer alike
        self._attn_impl = stateful[attn_keys[0]] if attn_keys else None
        recycling = {stateful[key].page_recycling() for key in attn_keys}
        self._eva: Optional[Tuple[int, int]] = None
        if recycling - {None}:
            if len(recycling) > 1:
                raise ValueError(
                    "attention layers that recycle pages differently "
                    f"({sorted(map(str, recycling))}) cannot share one "
                    "block table: window and global layers in one model "
                    "are not served yet")
            self._eva = recycling.pop()
            window, chunk = self._eva
            if not (kv_pool_mb and kv_pool_mb > 0) or kv_dtype \
                    or speculate or (mesh is not None and mesh != 1):
                raise ValueError(
                    "a net with EvaAttentionLayer is served through the "
                    "paged pool only (kv_pool_mb > 0), single-device, with "
                    "no int8 KV and no speculation")
            if window % self.prefill_chunk or self.prefill_chunk % chunk \
                    or window % int(kv_block) or int(kv_block) % chunk:
                raise ValueError(
                    f"EVA paging needs chunk_size={chunk} | kv_block="
                    f"{kv_block} | window_size={window} and chunk_size | "
                    f"prefill_chunk={self.prefill_chunk} | window_size: a "
                    "chunk of prompt never straddles a window or splits a "
                    "summary chunk")
        # a latent layer keeps one leaf of [latent | rotated key] rows, in
        # the pool alone and in the compute dtype: what is not served yet
        # for it is refused here, by name
        latent = next((key for key, impl in self._impl_items()
                       if isinstance(impl, LatentAttentionLayerImpl)), None)
        if latent is not None:
            unserved = [what for what, asked in (
                ("int8 pages (kv_dtype)", kv_dtype),
                ("a tp mesh", mesh is not None and mesh != 1),
                ("speculation", speculate),
                ("a contiguous cache (kv_pool_mb = 0, as rnn_time_step "
                 "steps it)", not (kv_pool_mb and kv_pool_mb > 0))) if asked]
            if unserved:
                raise ValueError(
                    f"LatentAttentionLayer {latent!r} is served from the "
                    "paged pool, one device, in the compute dtype; not "
                    "served yet for it: " + ", ".join(unserved))
        # a layer whose state is not addressed by position is stepped under
        # the write mask that only the paged programs hand down, and a
        # position cannot be rolled back or looked up in it: what is not
        # served yet for it is refused here, by name
        if self._ssm:
            unserved = [what for what, asked in (
                ("int8 pages (kv_dtype)", kv_dtype),
                ("a tp mesh", mesh is not None and mesh != 1),
                ("speculation (a rejected token's state cannot be rolled "
                 "back)", speculate),
                ("a contiguous cache (kv_pool_mb = 0, as rnn_time_step "
                 "steps it)", not (kv_pool_mb and kv_pool_mb > 0)
                 or not attn_keys or self.prefill_chunk <= 1)) if asked]
            if unserved:
                kind = type(stateful[self._ssm[0]].conf)
                raise ValueError(
                    f"{kind.__name__} {self._ssm[0]!r} is served beside a "
                    "paged pool (kv_pool_mb > 0, attention layers that keep "
                    "pages, chunked prefill), one device, in the compute "
                    "dtype; not served yet for it: " + ", ".join(unserved))
        # the routed-experts layers whose per-dispatch routing counts ride
        # back with the probabilities (`_forward`, `_pack_counts`)
        self._moe = [key for key, impl in self._impl_items()
                     if isinstance(impl, RoutedExpertsLayerImpl)] \
            if self._graph else []
        shares = {(impl._held()[1], int(impl.conf.top_k))
                  for key, impl in self._impl_items() if key in self._moe}
        if len(shares) > 1:
            raise ValueError(
                "routed-experts layers that hold or choose different "
                f"numbers of experts ({sorted(shares)}) are not served yet")
        self._moe_held, self._moe_top_k = shares.pop() if shares else (0, 0)
        if self._moe and max(n_slots, prefill_chunk) >= 65536:
            raise ValueError(
                "routing counts ride back as two base-256 digits "
                "(`_pack_counts`): a dispatch of 65,536 tokens or more "
                "is not served for a net with routed experts")
        # -- tensor-parallel mesh (inference/sharding.py, ISSUE 9) --
        # resolved BEFORE the KV layout: pool byte budgets are per-device
        # (each device holds Hkv/tp heads per block), and the pool must
        # know the shard factor to size capacity_blocks
        if isinstance(mesh, int):
            mesh = decode_mesh(mesh) if mesh > 1 else None
        self.mesh = None
        self.tp = 1
        self._repl = None  # replicated NamedSharding for host feeds
        if mesh is not None and mesh.shape.get(TP_AXIS, 1) <= 1:
            # a mesh without a real tp axis would be SILENTLY ignored
            # below — name the contract instead
            warnings.warn(
                f"mesh {dict(mesh.shape)} has no '{TP_AXIS}' axis of "
                "size > 1; tensor-parallel decode is DISABLED "
                "(build the mesh with inference.sharding.decode_mesh, "
                "or pass mesh=<device count>)",
                RuntimeWarning, stacklevel=2)
        if mesh is not None and mesh.shape.get(TP_AXIS, 1) > 1:
            tp = int(mesh.shape[TP_AXIS])
            if not (self._graph and self._chunk_dense
                    and kv_heads_shardable(abstract_states, attn_keys,
                                           tp)):
                warnings.warn(
                    f"mesh tp={tp} requested but tensor-parallel decode "
                    "is DISABLED (single-device engine instead): "
                    + ("the model is not a transformer ComputationGraph "
                       "with an attention KV cache to shard"
                       if not (self._graph and self._chunk_dense
                               and attn_keys)
                       else "an attention layer's n_kv_heads is not "
                            f"divisible by the tp axis size {tp} (the "
                            "head-sharded cache cannot split a head)"),
                    RuntimeWarning, stacklevel=2)
            else:
                self.mesh = mesh
                self.tp = tp
                from jax.sharding import NamedSharding, PartitionSpec
                self._repl = NamedSharding(mesh, PartitionSpec())
        self._sharded_params = self._sharded_variables = None
        if self.mesh is not None:
            # sharded COPIES — net keeps its own placement (a 1-device
            # reference engine over the same net stays single-device).
            # Unsharded engines read net.params LIVE at each dispatch
            # (the _params property), preserving the pre-mesh contract
            # that a retrained net's rebound params are picked up
            self._sharded_params, self._sharded_variables = \
                shard_decode_params(net, self.mesh)
        # KV memory layout (kvpool.py):
        #   kv_pool_mb > 0 -> PAGED, attention nets only (the pool manages
        #     position-addressed K/V rows, which recurrent h/c state does
        #     not have): the pool IS the live decode cache (per-layer page
        #     arrays in self._states, per-slot block tables, zero-copy
        #     prefix restore/publish, preempt-and-swap)
        #   otherwise      -> contiguous [n_slots, max_cache_len] stripes
        #     and no prefix cache (the token-identity reference)
        self.kv_block = int(kv_block)
        self.kv_dtype: Optional[str] = None  # set when int8 KV engages
        # fused Pallas decode-kernel mode (ISSUE 15): injected into the
        # paged attention step as a trace-time constant next to the
        # block table; "auto" defers to the ops/pallas_kernels per-shape
        # autotune (silent XLA fallback when no kernel is registered)
        self.paged_kernel = paged_kernel
        self.pool: Optional[KVPool] = None
        self.paged = False
        self.table_buckets: List[int] = []
        self._jsetpos = None
        self._jcow = None
        self._table: Optional[np.ndarray] = None
        if kv_pool_mb and kv_pool_mb > 0:
            if self._chunk_dense and attn_keys and self.kv_block >= 1:
                # which page arrays a layer keeps, and the shape of a
                # page, is asked of the layer
                impls = dict(self._impl_items())
                leaves = {key: impls[key].paged_leaves(
                    self.kv_block, self._dtype, kv_dtype)
                    for key in attn_keys}
                pool = KVPool(leaves, block=self.kv_block,
                              budget_bytes=int(kv_pool_mb * (1 << 20)),
                              shard_factor=self.tp,
                              metrics=self.metrics, tracer=self.tracer)
                if pool.capacity_blocks > 0:
                    self.pool = pool
                    self.paged = True
                    # the contiguous [n_slots, max_cache_len] stripes are
                    # replaced by ONE pool-wide page array per layer
                    # (page 0 = scratch); a slot's reach is its block
                    # table, so capacity is pool bytes, not slots x cap
                    pages = pool.capacity_blocks + 1
                    # materialize straight into the paged layout: the
                    # contiguous stripes are never allocated. Zeros match
                    # init_state for every entry — paged requires
                    # _chunk_dense, so every stateful layer either keeps
                    # pages (below) or keeps per-slot leaves of a fixed
                    # size, [n_slots, ...] here beside the page arrays.
                    # Under a mesh the page arrays stay HOST numpy here:
                    # the total pool is tp x one device's budget, so a
                    # device-side transient would OOM the very layout
                    # sharding exists to escape — the state_shardings
                    # device_put below ships each device ONLY its head
                    # slice (host zeros are calloc'd virtual pages, ~free)
                    zeros = (np.zeros if self.mesh is not None
                             else jnp.zeros)
                    self._states = {
                        key: jax.tree_util.tree_map(
                            lambda s: zeros(s.shape, s.dtype), st)
                        for key, st in abstract_states.items()
                        if key not in attn_keys}
                    self.kv_dtype = kv_dtype
                    for key in attn_keys:
                        # int8 pages come with their float32 per-row
                        # scales (attention quantizes on write and
                        # dequantizes on gather), a latent layer with
                        # one leaf: as the layer's `paged_leaves` says
                        pos = abstract_states[key]["pos"]
                        self._states[key] = {
                            **{leaf: zeros((pages,) + tuple(page), dt)
                               for leaf, (page, dt) in leaves[key].items()},
                            "pos": zeros(pos.shape, pos.dtype)}
                        if self._eva is not None:
                            # summary block -> page, per slot: carried
                            # on the device and written by `_sumtab_fn`
                            # when a summary page is claimed, so the
                            # programs keep one [n_slots, nb] table
                            self._states[key]["summary_table"] = zeros(
                                (self.n_slots,
                                 -(-pool.capacity_blocks // self._eva[1])),
                                jnp.int32)
                    self._cache_cap = pool.capacity_blocks * self.kv_block
                    self.table_buckets = pow2_buckets(pool.capacity_blocks)
                    self._table = np.full(
                        (self.n_slots, pool.capacity_blocks),
                        SCRATCH_BLOCK, np.int32)
            if not self.paged:
                warnings.warn(
                    f"kv_pool_mb={kv_pool_mb} requested but paged KV "
                    "decode is DISABLED (contiguous per-slot caches "
                    "instead): "
                    + ("the model has no attention KV cache to page"
                       if not self._chunk_dense or not attn_keys
                       else "the byte budget is smaller than two "
                            f"{self.kv_block}-position blocks"),
                    RuntimeWarning, stacklevel=2)
        if kv_dtype and not self.kv_dtype:
            warnings.warn(
                "kv_dtype='int8' requested but the paged KV pool did not "
                "engage (int8 KV quantization lives in the pool's page "
                "arrays); serving with the model-dtype cache instead",
                RuntimeWarning, stacklevel=2)
        if self._states is None:
            # contiguous layouts (and the LSTM fallback) materialize the
            # per-slot stripes the abstract pass only described
            self._states = self._init_states()
        if self.mesh is not None:
            # place the carried state on the mesh: K/V head-sharded,
            # everything else replicated. GSPMD propagates these
            # shardings through every program, so the carried output
            # stays head-sharded step over step — no resharding ever
            # (audited: sharding.collective_counts). The paged page
            # arrays arrive as HOST numpy (above), so each device
            # receives only its head slice — no single-device transient
            # of the tp-x-budget pool. Contiguous stripes (below) do
            # pass through device 0 first, but contiguous mode is by
            # definition single-chip-scale state
            self._states = jax.device_put(
                self._states, state_shardings(self._states, self.mesh))
        # THE donation rule: a program that takes the carried state and
        # returns it donates it (the pool is updated in place: no copy of
        # the page arrays on the device, no fresh output buffers from the
        # allocator on the host), and every caller rebinds from the
        # result; a program that only reads the state does not
        # (_jtier_spill)
        self._jstep = jax.jit(
            self._step_paged_fn if self.paged else self._step_fn,
            donate_argnames=("states",))
        # one prefill program per pow2 chunk bucket (the SAME jitted
        # callable; each distinct ids length C is its own XLA program,
        # compiled once and reused across requests — the batcher's
        # compile-once-per-bucket discipline applied to prefill). Paged
        # mode multiplies in the block-table width buckets: one program
        # per (chunk bucket, table bucket) pair, still a FIXED family.
        # n_real is data-dependent (real tokens in a padded chunk) and
        # MUST stay traced: static it would recompile per tail length,
        # defeating the bucket discipline.
        self._jprefill = jax.jit(
            self._prefill_paged_fn if self.paged
            else self._prefill_fn,
            donate_argnames=("states",))  # graftlint: disable=JG004
        # slot admission zeroes one slot's rows in ONE fused program
        # (eagerly tree-mapped .at[].set(0) dispatched per leaf AND fed
        # the slot index as an implicit scalar transfer per leaf)
        self._jzero = jax.jit(self._zero_fn, donate_argnames=("states",))
        if self.paged:
            # restore remaps the table host-side; the only device work is
            # setting the slot's pos past the hit (one tiny program) and
            # the occasional copy-on-write block duplication (one more)
            self._jsetpos = jax.jit(self._setpos_fn,
                                    donate_argnames=("states",))
            self._jcow = jax.jit(self._cow_fn,
                                 donate_argnames=("states",))
        self._jsumtab = None
        if (self._eva is not None or latent is not None or self._ssm) \
                and not self.paged:
            raise ValueError(
                f"kv_pool_mb={kv_pool_mb} holds no two blocks of "
                f"{self.kv_block} positions: a net with "
                + ("EvaAttentionLayer" if self._eva is not None else
                   "LatentAttentionLayer" if latent is not None else
                   f"a state-space layer ({self._ssm[0]!r})")
                + " is served through the paged pool only")
        # what a slot holds whatever its length (the per-slot leaves of the
        # layers in `_ssm`), beside the pool's budget and not inside it
        self.slot_state_bytes = sum(
            int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
            for key in self._ssm
            for leaf in jax.tree_util.tree_leaves(abstract_states[key]))
        # the prefix trie indexes pages by the tokens before them; where a
        # request's pages are recycled while it lives, or part of its state
        # is not addressed by position (a hit would move `pos` past it and
        # nothing holds the state there), the trie stands aside
        self._no_prefix = self._eva is not None or bool(self._ssm)
        if self._eva is not None:
            self._jsumtab = jax.jit(self._sumtab_fn,
                                    donate_argnames=("states",))
        # -- hierarchical KV tiering (ISSUE 19, kvtier.py) ------------------
        # opt-in (host_cache_mb=0 keeps the engine byte-identical to the
        # tierless build: no TierManager, no extra programs, no hot-path
        # work). When armed, pool evictions demote page rows to a host
        # ring (then disk) and trie hits promote them back; both device
        # programs are one fixed XLA program each (dynamic slice by a
        # traced block index), counted in the compile budget below.
        self.tier = None
        self._tier_chunk = int(tier_chunk_kib) << 10
        if host_cache_mb and host_cache_mb > 0:
            if not self.paged:
                warnings.warn(
                    f"host_cache_mb={host_cache_mb} requested but paged "
                    "KV decode is disabled — KV tiering needs the paged "
                    "pool and stays off", RuntimeWarning, stacklevel=2)
            else:
                from .kvtier import TierManager
                if disk_cache_mb and disk_cache_mb > 0 and not tier_dir:
                    import tempfile
                    tier_dir = tempfile.mkdtemp(prefix="kvtier-")
                self.tier = TierManager(
                    host_bytes=int(host_cache_mb * (1 << 20)),
                    disk_bytes=int(disk_cache_mb * (1 << 20)),
                    disk_dir=tier_dir,
                    chunk_bytes=self._tier_chunk,
                    metrics=self.metrics, tracer=self.tracer)
                # spill only READS the pool (its slices are outputs of
                # their own): not donated, the pool lives on
                self._jtier_spill = jax.jit(self._tier_spill_fn)
                self._jtier_restore = jax.jit(
                    self._tier_restore_fn, donate_argnames=("states",))
                self.pool.tier = self.tier
                self.tier.attach_engine(
                    self._tier_capture,
                    self.pool.bytes_per_block * self.pool.shard_factor,
                    self.kv_block)
        # -- grammar-constrained decoding (ISSUE 14, logitproc.py) ---------
        # a fixed [mask_rows, vocab] ADDITIVE device table (0 allowed,
        # -inf forbidden; row 0 reserved all-zeros = admit-all). Each
        # resident grammar's per-state rows upload ONCE at admission
        # (pow2-bucketed chunks — a fixed upload family, never per-token
        # work); the masked program variants gather one row per slot by
        # DFA state and add it to the output distribution, so the decode
        # family grows by at most one masked program per table bucket
        # and unconstrained traffic keeps dispatching the original
        # unmasked programs bit-for-bit.
        self.mask_rows = int(mask_rows)
        self.maskpool: Optional[MaskPool] = None
        self._masks = None
        self.mask_buckets: List[int] = []
        self._jstep_m = None
        self._jverify_m = None
        self._jdraft_step_m = None
        self._jmask_upload = None
        if self.mask_rows > 1:
            lo = min(8, self.mask_rows - 1)
            self.mask_buckets = [b for b in pow2_buckets(self.mask_rows - 1)
                                 if b >= lo]
            self.maskpool = MaskPool(self.mask_rows, self.mask_buckets)
            self._masks = self._dev_array(np.zeros(
                (self.mask_rows, self.vocab_size), np.dtype(self._dtype)))
            self._jstep_m = jax.jit(
                self._step_masked_paged_fn if self.paged
                else self._step_masked_fn,
                donate_argnames=("states",))
            self._jmask_upload = jax.jit(self._mask_upload_fn)
        # -- speculative decoding (ISSUE 10 tentpole) ----------------------
        # a cheap draft proposes `speculate` tokens per decode-ready slot
        # per iteration; ONE multi-token verify program (the chunked-
        # prefill forward with every position's logits retained) scores
        # all gamma+1 positions, and `speculative.accept_tokens` keeps the
        # longest prefix the target's own sampling confirms — output is
        # token-identical to solo decode by construction. Rejected rows
        # roll back via pos (and paged block-table truncation); the draft
        # is a self-speculative shallow exit over the first `draft_blocks`
        # transformer blocks unless an explicit `draft_net` is passed.
        self.speculate = 0
        self.draft = None
        self.draft_blocks = 0
        self._draft_states = None
        self._draft_cap: Optional[int] = None
        self._sharded_draft_params = self._sharded_draft_variables = None
        self._jdraft_step = self._jdraft_prefill = None
        self._jdraft_zero = self._jverify = None
        self._jfixpos = self._jdraft_fixpos = None
        if speculate and int(speculate) > 0:
            reason = None
            if not (self._graph and self._chunk_dense and attn_keys):
                reason = ("the model is not a transformer "
                          "ComputationGraph with an attention KV cache "
                          "to verify against")
            elif not self.prefill_buckets:
                reason = ("chunked prefill is disabled (prefill_chunk "
                          "<= 1) and the draft needs its chunk programs")
            draft = draft_net
            kk = int(draft_blocks) if draft_blocks else \
                max(1, len(attn_keys) // 2)
            if reason is None and draft is None:
                # paged engines decode past the conf's max_cache_len
                # (capacity is pool bytes), but the draft's private
                # cache is DENSE per-slot stripes — sizing it to the
                # whole pool depth would cost n_slots x pool-depth
                # rows per draft layer, unbounded by any budget knob.
                # Cap it at the model's own max_cache_len: sequences
                # past that depth simply decode plain (_spec_ready's
                # draft-headroom check), they never break
                draft_depth = None
                if self.paged:
                    draft_depth = min(self._cache_cap,
                                      self._min_cache_len() or
                                      self._cache_cap)
                try:
                    draft = build_shallow_draft(
                        net, kk, max_cache_len=draft_depth)
                except ValueError as e:
                    reason = f"no self-speculative draft ({e})"
            if reason is not None:
                warnings.warn(
                    f"speculate={speculate} requested but speculative "
                    f"decoding is DISABLED: {reason}; pass draft_net= "
                    "for models the shallow-exit surgery cannot cut",
                    RuntimeWarning, stacklevel=2)
            else:
                self.speculate = int(speculate)
                self.draft = draft
                self.draft_blocks = kk if draft_net is None else 0
                caps = [int(getattr(impl.conf, "max_cache_len", 1024))
                        for _, impl in self._draft_impl_items()
                        if _keeps_pages(impl)]
                self._draft_cap = min(caps) if caps else None
                # the draft's private KV cache: contiguous per-slot
                # stripes even under a paged main cache (K layers only,
                # and its rows are always re-derivable — no pool
                # metadata, no sharing, no preemption bookkeeping)
                self._draft_states = self._init_draft_states()
                if self.mesh is not None:
                    # the draft joins the mesh: same Megatron specs (its
                    # conf is a prefix of the target's), same head-axis
                    # cache sharding — and the same collective audit
                    # (sharding.draft_program_hlo)
                    self._sharded_draft_params, \
                        self._sharded_draft_variables = \
                        shard_decode_params(draft, self.mesh)
                    self._draft_states = jax.device_put(
                        self._draft_states,
                        state_shardings(self._draft_states, self.mesh))
                self._jdraft_step = jax.jit(
                    self._draft_step_fn, donate_argnames=("states",))
                self._jdraft_prefill = jax.jit(  # graftlint: disable=JG004
                    self._draft_prefill_fn, donate_argnames=("states",))
                self._jdraft_zero = jax.jit(
                    self._zero_fn, donate_argnames=("states",))
                self._jverify = jax.jit(
                    self._verify_paged_fn if self.paged
                    else self._verify_fn, donate_argnames=("states",))
                self._jfixpos = jax.jit(
                    self._fixpos_fn, donate_argnames=("states",))
                self._jdraft_fixpos = jax.jit(
                    self._fixpos_fn, donate_argnames=("states",))
                if self._masks is not None:
                    # masks compose with speculation: the draft proposes
                    # under the same mask the verify applies (per-round
                    # / per-position DFA states advanced host-side along
                    # the proposed chain), acceptance rule untouched
                    self._jverify_m = jax.jit(
                        self._verify_masked_paged_fn if self.paged
                        else self._verify_masked_fn,
                        donate_argnames=("states",))
                    self._jdraft_step_m = jax.jit(
                        self._draft_step_masked_fn,
                        donate_argnames=("states",))
        self._prefill_next = 0  # round-robin over prefilling slots
        self._emitted_this_iter = 0  # scheduler-thread-only tally
        m = self.metrics
        if self.tp > 1:
            # mesh topology for /metrics, the serve banner, and the UI
            # /serving page (per-device pool bytes are kvpool.py gauges)
            m.gauge("decode_mesh_devices").set(self.tp)
        self._m_queue_depth = m.gauge("decode_queue_depth")
        self._m_active = m.gauge("decode_active_slots")
        self._m_occupancy = m.histogram("decode_slot_occupancy", lo=1.0,
                                        hi=float(self.n_slots) + 1,
                                        per_decade=12)
        self._m_tokens = m.counter("decode_tokens_total")
        self._m_seqs = m.counter("decode_sequences_total")
        self._m_rejected = m.counter("decode_rejected_total")
        self._m_cancelled = m.counter("decode_cancelled_total")
        self._m_latency = m.histogram("decode_seq_latency_sec")
        self._m_step_time = m.histogram("decode_step_time_sec")
        self._m_prefill_tokens = m.counter("prefill_tokens_total")
        # TTFT observability (ISSUE 14 satellite): the histogram SSE
        # clients and the load-test phase table read, recorded at the
        # same instant the request-track `first_token` trace instant is
        # stamped (exemplar = request id, so a slow bucket links
        # straight into /trace)
        self._m_first_token = m.histogram(
            "generate_first_token_seconds",
            help="submit -> first output token (TTFT), seconds")
        self._m_constrained = m.counter(
            "constrained_requests_total",
            help="requests submitted with a grammar constraint")
        if self.maskpool is not None:
            self._m_mask_rows = m.gauge(
                "grammar_mask_rows_resident",
                help="device mask-table rows held by resident grammars")
            self._m_mask_spill = m.counter(
                "grammar_mask_spills_total",
                help="grammar admissions that fell back to host-only "
                     "masking (mask table full or grammar too large)")
        self._m_prefill_chunk = m.histogram(
            "prefill_chunk_size", lo=1.0,
            hi=float(max(self.prefill_buckets or [1])) + 1, per_decade=12)
        if self.paged:
            # fused-decode-kernel observability (ISSUE 15): 1 when any
            # decode table bucket traced through the Pallas kernel
            # (refreshed at warmup and on every /debug/engine read)
            self._m_paged_kernel = m.gauge(
                "paged_kernel_engaged",
                help="fused Pallas paged-decode kernel engaged on at "
                     "least one decode table bucket")
            self._m_preempted = m.counter("decode_preempted_total")
            # best-of-n COW forks: candidates that attached to a fork
            # group's published prompt blocks (zero-copy remaps)
            self._m_forks = m.counter("decode_forks_total")
        if self.paged:
            # per decode table bucket: whether the T=1 read goes through
            # `ops.paged_read` (the layer's own static rule, asked once)
            self._fused_read = {nb: self._fused_read_engages(nb)
                                for nb in self.table_buckets}
            # pages a decode dispatch names (every slot's page list at
            # the bucket's width) and pages it reads, from host-side
            # depths: equal unless the fused read engages. Named for what
            # a page list is: the block table (`kv_`; `mla_` where its
            # pages hold latent rows), or EVA's open-window pages and
            # summary pages (`eva_`)
            kind = ("eva" if self._eva is not None else
                    "mla" if latent is not None else "kv")
            self._m_pages_bucket = m.counter(
                f"{kind}_pages_bucket_total",
                help="pages in the page lists of decode dispatches: slots "
                     "x the pages a slot's list names at the table bucket")
            self._m_pages_read = m.counter(
                f"{kind}_pages_read_total",
                help="pages decode dispatches read: those holding a row a "
                     "fed slot attends over where the fused read engages, "
                     "else the bucket's")
        if self._eva is not None:
            self._m_eva_rolled = m.counter(
                "eva_windows_rolled_total",
                help="windows closed: their exact pages went back to the "
                     "pool, their chunk summaries stay")
            self._m_eva_recycled = m.counter("eva_blocks_recycled_total")
            # rows decode tokens attended over, from host-side depths
            self._m_eva_rows_exact = m.counter(
                "eva_rows_exact_total",
                help="exact rows of the open window attended by decode "
                     "tokens")
            self._m_eva_rows_summary = m.counter(
                "eva_rows_summary_total",
                help="chunk-summary rows of closed windows attended by "
                     "decode tokens")
        if self._no_prefix:
            self._m_publish_skipped = m.counter(
                "prefix_publish_skipped_total",
                help="finished prompts the prefix trie did not adopt: "
                     "their pages were recycled while the request lived, "
                     "or a state that no position addresses goes with them")
        if self._ssm:
            # rows of per-slot state a decode dispatch names (every slot's)
            # and rows it reads and writes, by the layer's one static rule:
            # the step computes every lane and selects, so the bucket's
            self._m_ssm_bucket = m.counter(
                "ssm_rows_bucket_total",
                help="state rows in decode dispatches: n_slots a dispatch")
            self._m_ssm_stepped = m.counter(
                "ssm_rows_stepped_total",
                help="state rows decode dispatches read and wrote: every "
                     "slot's while the step computes all lanes and selects")
        if self._moe:
            # from the routing counts each dispatch hands back
            # (`_note_routing`): pairs are (token, chosen expert), a layer
            self._m_moe_routed = m.counter(
                "moe_pairs_routed_total",
                help="token-expert pairs the routers chose, over all "
                     "experts: tokens x top_k x routed layers, of the "
                     "dispatches whose counts were read")
            self._m_moe_held = m.counter(
                "moe_pairs_held_total",
                help="of those, the pairs on experts this engine holds")
            self._m_moe_slots = m.counter(
                "moe_expert_slots_total",
                help="held experts x routed layers, a decode dispatch")
            self._m_moe_hit = m.counter(
                "moe_experts_hit_total",
                help="of those, the experts at least one token chose")
            self._m_moe_passes = m.counter(
                "moe_weight_passes_total",
                help="(expert, row tile) visits of the grouped-matmul "
                     "kernel, decode dispatches: over moe_experts_hit_total "
                     "how often a hit expert's weights are walked")
        if latent is not None:
            # rows decode tokens attended over, from host-side depths
            self._m_mla_rows = m.counter(
                "mla_rows_read_total",
                help="latent rows attended by decode tokens: their depths")
        self._latent = latent is not None
        if self.speculate:
            self._m_spec_proposed = m.counter("spec_tokens_proposed_total")
            self._m_spec_accepted = m.counter("spec_tokens_accepted_total")
            m.ratio("spec_acceptance_rate", self._m_spec_accepted,
                    self._m_spec_proposed)
        if self.paged:
            self._m_prefix_lookups = m.counter("prefix_cache_lookups_total")
            self._m_prefix_hits = m.counter("prefix_cache_hits_total")
            self._m_prefix_lookup_tokens = m.counter(
                "prefix_cache_lookup_tokens_total")
            self._m_prefix_hit_tokens = m.counter(
                "prefix_cache_hit_tokens_total")
            m.ratio("prefix_cache_hit_rate", self._m_prefix_hit_tokens,
                    self._m_prefix_lookup_tokens)
        if self.tier is not None:
            self._m_tier_promoted = m.counter(
                "kv_tier_promoted_blocks_total",
                "tiered blocks adopted back into the HBM trie")
            self._m_tier_tokens = m.counter(
                "kv_tier_restored_tokens_total",
                "prompt tokens served from tier promotions instead of "
                "recompute (mid-prefill upgrades)")
        # compile-event tracing: the scheduler polls its own program
        # families' jit-cache sizes (the same CompileCounter budgets the
        # tests assert) once per iteration and stamps an instant event
        # whenever one grew — a chunk bucket's first-call compile shows
        # up ON the trace timeline, right where the stall happened
        self._compile_counter = CompileCounter.for_scheduler(self)
        self._compile_seen: Dict[str, int] = {}

    @property
    def _params(self):
        """Dispatch-time params: the sharded copies under a mesh, the
        net's LIVE tree otherwise (a rebound-after-fit() net keeps
        serving fresh weights — sharded engines must rebuild instead,
        as the class docstring documents)."""
        return self._sharded_params if self._sharded_params is not None \
            else self.net.params

    @property
    def _variables(self):
        return self._sharded_variables \
            if self._sharded_variables is not None else self.net.variables

    # -- host->device placement --------------------------------------------
    def _dev_array(self, a) -> jax.Array:
        """A host array as an EXPLICIT device transfer, placed the way
        the compiled programs expect it: committed-replicated on the
        mesh under tensor parallelism (argument placement is part of the
        jit cache key, so warmup and live dispatch MUST place
        identically or the budgets double), plain ``jnp.asarray``
        otherwise. `jax.device_put` of an ndarray is explicit under the
        transfer guard, same contract as `device_index`."""
        if self._repl is not None:
            # np.asarray of a HOST ndarray is a no-op normalization, not
            # a device sync; the device_put is the explicit transfer
            return jax.device_put(np.asarray(a), self._repl)  # graftlint: disable=JG006
        return jnp.asarray(a)

    def _dev_index(self, v: int) -> jax.Array:
        """`analysis.runtime.device_index` under the same mesh-placement
        contract as `_dev_array`."""
        if self._repl is not None:
            return jax.device_put(np.asarray([v], np.int32), self._repl)
        return device_index(v)

    # -- model plumbing ----------------------------------------------------
    def _impl_items(self):
        impls = self.net._impls
        return impls.items() if isinstance(impls, dict) else enumerate(impls)

    def _min_cache_len(self) -> Optional[int]:
        caps = []
        for _, impl in self._impl_items():
            if _keeps_pages(impl):
                caps.append(int(getattr(impl.conf, "max_cache_len", 1024)))
        return min(caps) if caps else None

    def _init_states(self) -> Dict[Any, Any]:
        """Private per-layer state with batch dim = n_slots; attention
        cache positions become [n_slots] vectors so each slot decodes at
        its own depth."""
        states = _materialize_rnn_states(self._impl_items(), {},
                                         self.n_slots, self._dtype)
        for key, st in states.items():
            if isinstance(st, dict) and "pos" in st and st["pos"].ndim == 0:
                states[key] = {**st,
                               "pos": jnp.zeros((self.n_slots,), jnp.int32)}
        return states

    def _forward(self, params, variables, x, states, live=None):
        """One forward of [B, T, vocab] one-hots through the net with
        explicit states: ([B, T, vocab] distributions, new states, routing
        counts). The counts are None unless the net routes experts; then
        ``live`` (bool, [B] or [B, T]: the lanes that hold a token) goes in
        as the feature mask, so that a padded lane routes nowhere, and the counts
        are the layers' ``routing_counts``, int32 [layers, held]: the pairs
        that fell on each held expert in this dispatch."""
        if self._moe:
            fmasks = None if live is None else {
                self.net.conf.network_inputs[0]:
                    live.reshape(x.shape[:2]).astype(self._dtype)}
            acts, new_vars, new_states = self.net._forward_impl(
                params, variables, [x], train=False, rng=None, states=states,
                fmasks=fmasks)
            return acts[self.net.conf.network_outputs[0]], new_states, \
                jnp.stack([new_vars[key]["routing_counts"]
                           for key in self._moe])
        if self._graph:
            acts, _, new_states = self.net._forward_impl(
                params, variables, [x], train=False, rng=None, states=states)
            out = acts[self.net.conf.network_outputs[0]]
        else:
            acts, _, new_states = self.net._forward_impl(
                params, variables, x, train=False, rng=None, states=states)
            out = acts[-1]
        return out, new_states, None

    def _pack_counts(self, probs, counts):
        """The routing counts as further rows under ``probs`` ([rows,
        vocab]), so that they reach the host in the read the step makes
        anyway: a layer a row, zero-padded to the vocabulary's width. The
        graph hands the probabilities back in the compute dtype, and
        bfloat16 holds whole numbers to 256 only, so a count (at most a
        dispatch's tokens, ``__init__`` refuses 65,536) goes as two
        base-256 digits."""
        if counts is None:
            return probs
        digits = jnp.concatenate([counts % 256, counts // 256], axis=1)
        return jnp.concatenate(
            [probs, jnp.pad(digits, ((0, 0), (0, probs.shape[1]
                                              - digits.shape[1]))
                            ).astype(probs.dtype)], axis=0)

    def _unpack_counts(self, rows: np.ndarray):
        """`_pack_counts` read back on the host: (the probabilities, the
        counts as int64 [layers, held]); the rows whole where the net
        routes no experts."""
        n = len(self._moe)
        if not n:
            return rows, None
        d, held = rows[-n:].astype(np.int64), self._moe_held
        return rows[:-n], d[:, :held] + 256 * d[:, held:2 * held]

    def _freeze_states(self, new_states, old_states, live):
        """Keep only live slots' state transitions: masked rows (idle or
        mid-chunked-prefill slots stepped as padding of the shared batch)
        retain their previous recurrent state and cache position. K/V
        buffers are exempt — a masked slot's write lands at its own frozen
        `pos` row, which is overwritten by the slot's next real write (its
        next prefill chunk starts at `pos`) and causally invisible until
        then, so freezing the (large) cache buffers would be pure cost."""
        def sel(n, o):
            m = live.reshape((self.n_slots,) + (1,) * (n.ndim - 1))
            return jnp.where(m, n, o)
        out = {}
        for key, st in new_states.items():
            old = old_states[key]
            if key in self._ssm:
                # held still by the layer itself, under the same mask
                # (`_inject_paged`): a second select over the state's
                # gigabytes would be pure cost
                out[key] = st
            elif isinstance(st, dict):
                # pages (and their int8 dequant scales) are exempt like
                # k/v: a masked slot's paged write was redirected to the
                # scratch page in-program (wmask), so there is nothing
                # to roll back
                out[key] = {k: (v if k in ("k", "v") + PAGE_KEYS
                                else sel(v, old[k]))
                            for k, v in st.items()}
            else:
                out[key] = sel(st, old)
        return out

    def _step_fn(self, params, variables, ids, live, states):
        """One single-token forward for all slots. ``ids``: [n_slots]
        int32 token ids (the one-hot is built HERE, on device — the host
        ships vocab-fold less data per step); ``live``: [n_slots] bool,
        False rows are batch padding whose state must not advance.
        Returns ([n_slots, vocab] next-token distributions, new states)."""
        x = jax.nn.one_hot(ids, self.vocab_size, dtype=self._dtype)[:, None]
        out, new_states, counts = self._forward(params, variables, x, states,
                                                live)
        return self._pack_counts(out[:, -1, :], counts), \
            self._freeze_states(new_states, states, live)

    def _inject_paged(self, states, table, wmask):
        """Hand the per-call block table (and write mask) to every paged
        attention state entry. The table is HOST-authoritative (the
        scheduler mutates it between steps) and shipped per dispatch —
        never part of the carried device state — so allocation, restore
        remaps, COW swaps, and preemption are plain numpy writes with no
        device program of their own.

        ``paged_kernel``/``mesh`` ride along as TRACE-TIME constants
        (this runs inside the jitted step body, so plain Python values
        in the state dict are static — the layer reads them to pick the
        fused decode kernel vs the XLA gather, ISSUE 15); like the
        table, the layer never returns them."""
        out = {}
        for key, st in states.items():
            if _is_paged(st):
                out[key] = {**st, "table": table, "wmask": wmask,
                            "paged_kernel": self.paged_kernel,
                            "mesh": self.mesh}
            elif key in self._ssm:
                # a padded lane or a pad token may not advance a state
                # either: the layer holds its leaves still where it is off
                out[key] = {**st, "wmask": wmask}
            else:
                out[key] = st
        return out

    def _step_paged_fn(self, params, variables, ids, live, table, states):
        """Paged-mode decode step: `_step_fn` plus the block ``table``
        ([n_slots, nb], nb a pow2 bucket covering the deepest live slot).
        ``live`` doubles as the write mask — a masked (idle or
        mid-prefill) slot's K/V write is redirected to the scratch page
        inside the attention layer, so it can never corrupt a shared
        block at its own frontier (the contiguous-mode argument "the
        garbage row is overwritten by the slot's next real write" does
        not survive sharing). One XLA program per table bucket."""
        x = jax.nn.one_hot(ids, self.vocab_size, dtype=self._dtype)[:, None]
        sts = self._inject_paged(states, table, live[:, None])
        out, new_states, counts = self._forward(params, variables, x, sts,
                                                live)
        return self._pack_counts(out[:, -1, :], counts), \
            self._freeze_states(new_states, states, live)

    # -- grammar-mask programs (logitproc.py, ISSUE 14) --------------------
    def _mask_upload_fn(self, masks, start, rows):
        """Write one grammar's mask rows into the device table at
        ``start`` (1-element int32, same transfer contract as
        `_zero_fn`). ``rows`` is padded to a pow2 bucket; pad rows are
        zeros — admit-all rows inside the grammar's OWN allocation
        (MaskPool allocates bucket-sized chunks), never another
        grammar's. Admission-path only, one program per row bucket."""
        return jax.lax.dynamic_update_slice(masks, rows, (start[0], 0))

    def _step_masked_fn(self, params, variables, ids, live, mstate,
                        masks, states):
        """Decode step + grammar mask: gather each slot's current DFA
        state's ADDITIVE row (0 allowed / -inf forbidden) from the mask
        table and add it to the output distribution — one gather + add
        on top of the unchanged decode forward, so this family mirrors
        decode's bucketing exactly. Unconstrained slots point at row 0
        (all zeros): ``p + 0.0 == p`` bitwise, which is what makes an
        admit-everything grammar token-identical to unmasked decode."""
        out, new_states = self._step_fn(params, variables, ids, live,
                                        states)
        return self._add_masks(out, masks, mstate), new_states

    def _add_masks(self, out, masks, mstate):
        """The slots' mask rows onto their distributions; rows of routing
        counts below them (`_pack_counts`) pass."""
        rows = jnp.take(masks, mstate, axis=0)
        if not self._moe:
            return out + rows
        return out.at[:self.n_slots].add(rows)

    def _step_masked_paged_fn(self, params, variables, ids, live, table,
                              mstate, masks, states):
        out, new_states = self._step_paged_fn(params, variables, ids,
                                              live, table, states)
        return self._add_masks(out, masks, mstate), new_states

    def _verify_masked_fn(self, params, variables, ids, live, mstate2,
                          masks, states):
        """Masked multi-token verify: position j's row gets the mask of
        the DFA state the chain reaches after proposals[0..j-1]
        (``mstate2`` [n_slots, gamma+1], computed host-side while
        drafting) — the draft proposed under exactly these masks, so
        verify scores like with like and the acceptance rule (which
        re-applies the exact host-side allow row) is untouched."""
        out, new_states = self._verify_fn(params, variables, ids, live,
                                          states)
        return out + jnp.take(masks, mstate2, axis=0), new_states

    def _verify_masked_paged_fn(self, params, variables, ids, live,
                                table, mstate2, masks, states):
        out, new_states = self._verify_paged_fn(params, variables, ids,
                                                live, table, states)
        return out + jnp.take(masks, mstate2, axis=0), new_states

    def _draft_step_masked_fn(self, params, variables, ids, live, mstate,
                              masks, states):
        """Masked draft step: the lockstep proposal round under the SAME
        mask the verify applies — a draft that proposed out-of-grammar
        tokens would have its whole chain rejected every round, turning
        speculation into pure overhead on constrained traffic."""
        out, new_states = self._draft_step_fn(params, variables, ids,
                                              live, states)
        return out + jnp.take(masks, mstate, axis=0), new_states

    # -- chunked prefill programs ------------------------------------------
    def _slice_slot(self, states, slot):
        """One slot's rows of every state leaf, batch dim kept at 1.
        Paged page arrays pass through WHOLE by key (never sliced — they
        are pool-wide, and sniffing on ``shape[0] == n_slots`` could
        false-positive when the pool happens to hold n_slots+1 pages)."""
        def f(a):
            if hasattr(a, "ndim") and a.ndim >= 1 \
                    and a.shape[0] == self.n_slots:
                return jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0)
            return a
        out = {}
        for key, st in states.items():
            if _is_paged(st):
                out[key] = {k: (v if k in PAGE_KEYS else f(v))
                            for k, v in st.items()}
            else:
                out[key] = jax.tree_util.tree_map(f, st)
        return out

    def _scatter_slot(self, states, sub, slot):
        """Write a batch-1 state pytree back into one slot's rows. Paged
        page arrays REPLACE the full-state ones (the batch-1 program
        updated the shared pages in place, there is no row to scatter)."""
        def f(full, part):
            if hasattr(full, "ndim") and full.ndim >= 1 \
                    and full.shape[0] == self.n_slots:
                return jax.lax.dynamic_update_slice_in_dim(
                    full, part, slot, axis=0)
            return part
        out = {}
        for key, st in states.items():
            if _is_paged(st):
                out[key] = {k: (sub[key][k] if k in PAGE_KEYS
                                else f(v, sub[key][k]))
                            for k, v in st.items()}
            else:
                out[key] = jax.tree_util.tree_map(f, st, sub[key])
        return out

    def _prefill_fn(self, params, variables, slot, ids, n_real, states):
        """Prefill one chunk of ``ids`` (int32 [C], padded past ``n_real``)
        into ``slot``'s state, in ONE device dispatch. Returns the
        next-token distribution at the last REAL prompt token (only
        meaningful for the prompt's final chunk) and the updated shared
        states. Compiled once per chunk length C (the pow2 buckets).

        Dense path (attention nets): a single [1, C, vocab] forward —
        `nn/layers/attention.py` writes K/V rows [pos, pos+C) in one
        offset `dynamic_update_slice`, rotates RoPE at the slot's absolute
        positions, and masks causally within the chunk. Padded tail rows
        beyond n_real land at positions the corrected `pos` keeps causally
        invisible until the next real write overwrites them; `pos` itself
        advances by n_real, not C.

        Scan path (recurrent h/c state): C single-token steps fused into
        one `lax.scan` program; padded steps keep the carried state (the
        same mask-carry discipline the training scan uses).

        ``slot``/``n_real`` arrive as 1-element int32 arrays, not Python
        scalars: scalar feeds are *implicit* host->device transfers that
        the transfer-guard audit mode would reject every iteration."""
        slot = slot[0]
        n_real = n_real[0]
        sub = self._slice_slot(states, slot)
        if self._chunk_dense:
            x = jax.nn.one_hot(ids, self.vocab_size, dtype=self._dtype)[None]
            out, new_sub, counts = self._forward(
                params, variables, x, sub,
                jnp.arange(ids.shape[0], dtype=jnp.int32) < n_real
                if self._moe else None)
            probs = self._pack_chunk(jax.lax.dynamic_index_in_dim(
                out, n_real - 1, axis=1, keepdims=False)[0], counts)
            fixed = {}
            for key, st in new_sub.items():
                if isinstance(st, dict) and "pos" in st:
                    # the layer advanced pos by the PADDED chunk length;
                    # the sequence is only n_real tokens deeper. But keep
                    # the layer's L_cap+1 overflow-freeze sentinel (ADVICE
                    # r3): a chunk that overran the cache must stay
                    # poisoned, not resume over a corrupted cache
                    pos = sub[key]["pos"] + n_real
                    if "k" in st:
                        cap = st["k"].shape[1]
                        pos = jnp.where(st["pos"] > cap, st["pos"], pos)
                    fixed[key] = {**st, "pos": pos}
                else:
                    fixed[key] = st
            new_sub = fixed
        else:
            keep = jnp.arange(ids.shape[0], dtype=jnp.int32) < n_real

            def body(carry, inp):
                tok, k = inp
                x = jax.nn.one_hot(tok[None, None], self.vocab_size,
                                   dtype=self._dtype)
                out, ns, _ = self._forward(params, variables, x, carry)
                nxt = {}
                for key, st in ns.items():
                    old = carry[key]
                    if isinstance(st, dict):
                        nxt[key] = {k2: jnp.where(k, v2, old[k2])
                                    for k2, v2 in st.items()}
                    else:
                        nxt[key] = jnp.where(k, st, old)
                return nxt, out[0, -1, :]

            new_sub, probs_all = jax.lax.scan(body, sub, (ids, keep))
            probs = probs_all[n_real - 1]
        return probs, self._scatter_slot(states, new_sub, slot)

    def _pack_chunk(self, probs, counts):
        """A chunk's one distribution [vocab], with the chunk's routing
        counts under it where the net routes experts."""
        return probs if counts is None \
            else self._pack_counts(probs[None], counts)

    def _prefill_paged_fn(self, params, variables, slot, ids, n_real,
                          table, states):
        """Paged-mode chunk prefill: `_prefill_fn`'s dense path with the
        chunk's K/V rows scattered into pool pages through the slot's
        block table instead of a contiguous stripe. Lanes past ``n_real``
        write to the scratch page (in-program mask from the traced
        n_real — so padding allocates no blocks), and the scheduler
        presents a table bucket covering the PADDED chunk end
        (``pos + bucket``) so the layer's overflow guard never fires on
        padding. One XLA program per (chunk bucket, table bucket)."""
        s = slot[0]
        nr = n_real[0]
        sub = self._slice_slot(states, s)
        trow = jax.lax.dynamic_slice_in_dim(table, s, 1, axis=0)  # [1, nb]
        wmask = (jnp.arange(ids.shape[0], dtype=jnp.int32) < nr)[None, :]
        sts = self._inject_paged(sub, trow, wmask)
        x = jax.nn.one_hot(ids, self.vocab_size, dtype=self._dtype)[None]
        out, new_sub, counts = self._forward(params, variables, x, sts,
                                             wmask)
        probs = self._pack_chunk(jax.lax.dynamic_index_in_dim(
            out, nr - 1, axis=1, keepdims=False)[0], counts)
        fixed = {}
        for key, st in new_sub.items():
            if _is_paged(st):
                # the layer advanced pos by the PADDED chunk length; the
                # sequence is only n_real tokens deeper (no overflow
                # sentinel to preserve — paged bucketing covers the
                # padded end by construction)
                fixed[key] = {**st, "pos": sub[key]["pos"] + nr}
            else:
                fixed[key] = st
        return probs, self._scatter_slot(states, fixed, s)

    # -- speculative decoding programs -------------------------------------
    def _draft_impl_items(self):
        impls = self.draft._impls
        return impls.items() if isinstance(impls, dict) else enumerate(impls)

    @property
    def _draft_params(self):
        """Draft dispatch params: sharded copies under a mesh, else the
        LIVE arrays by name — the shallow-exit draft shares the target's
        weights, so a rebound-after-fit() net keeps drafting fresh."""
        if self._sharded_draft_params is not None:
            return self._sharded_draft_params
        return {name: self.net.params.get(name, p)
                for name, p in self.draft.params.items()} \
            if self.draft_blocks else self.draft.params

    @property
    def _draft_variables(self):
        return self._sharded_draft_variables \
            if self._sharded_draft_variables is not None \
            else self.draft.variables

    def _init_draft_states(self) -> Dict[Any, Any]:
        """The draft net's private per-layer state (its own contiguous
        KV cache over the first K blocks), per-slot pos vectors like the
        main cache."""
        states = _materialize_rnn_states(self._draft_impl_items(), {},
                                         self.n_slots, self._dtype)
        for key, st in states.items():
            if isinstance(st, dict) and "pos" in st and st["pos"].ndim == 0:
                states[key] = {**st,
                               "pos": jnp.zeros((self.n_slots,), jnp.int32)}
        return states

    def _draft_forward(self, params, variables, x, states):
        """One forward through the DRAFT graph (shallow exit or explicit
        draft net) with explicit states — the draft-side `_forward`."""
        acts, _, new_states = self.draft._forward_impl(
            params, variables, [x], train=False, rng=None, states=states)
        return acts[self.draft.conf.network_outputs[0]], new_states

    def _draft_step_fn(self, params, variables, ids, live, states):
        """One single-token draft forward for all slots (the lockstep
        proposal round): `_step_fn` against the draft graph and its
        contiguous cache. One XLA program, mesh sizes included."""
        x = jax.nn.one_hot(ids, self.vocab_size, dtype=self._dtype)[:, None]
        out, new_states = self._draft_forward(params, variables, x, states)
        return out[:, -1, :], self._freeze_states(new_states, states, live)

    def _draft_prefill_fn(self, params, variables, slot, ids, n_real,
                          states):
        """Chunked prefill into the draft cache: the dense path of
        `_prefill_fn` against the draft graph, one program per pow2
        chunk bucket. Runs piggybacked on every main prefill chunk (the
        draft must ingest the prompt to propose from it) and as the
        catch-up program after prefix restores/resumes jump the MAIN
        cache past tokens the draft never saw."""
        slot = slot[0]
        n_real = n_real[0]
        sub = self._slice_slot(states, slot)
        x = jax.nn.one_hot(ids, self.vocab_size, dtype=self._dtype)[None]
        out, new_sub = self._draft_forward(params, variables, x, sub)
        probs = jax.lax.dynamic_index_in_dim(out, n_real - 1, axis=1,
                                             keepdims=False)[0]
        fixed = {}
        for key, st in new_sub.items():
            if isinstance(st, dict) and "pos" in st:
                pos = sub[key]["pos"] + n_real
                if "k" in st:
                    cap = st["k"].shape[1]
                    pos = jnp.where(st["pos"] > cap, st["pos"], pos)
                fixed[key] = {**st, "pos": pos}
            else:
                fixed[key] = st
        return probs, self._scatter_slot(states, fixed, slot)

    def _verify_fn(self, params, variables, ids, live, states):
        """THE multi-token verify program: one target-model forward over
        ``ids`` [n_slots, gamma+1] chains, per-slot positions, retaining
        EVERY position's next-token distribution ([n_slots, gamma+1,
        vocab]) — the chunked-prefill machinery pointed at decode.
        Chain rows are written into the cache at [pos, pos+gamma+1);
        rejected rows are rolled back host-side by `_fixpos_fn` (they
        sit beyond the corrected pos, causally invisible and overwritten
        by the next real write — the same invariant slot reuse rests
        on). Masked slots are frozen exactly like the decode step."""
        x = jax.nn.one_hot(ids, self.vocab_size, dtype=self._dtype)
        out, new_states, _ = self._forward(params, variables, x, states)
        return out, self._freeze_states(new_states, states, live)

    def _verify_paged_fn(self, params, variables, ids, live, table,
                         states):
        """Paged verify: `_verify_fn` writing through the block table.
        ``live`` doubles as the write mask (broadcast over the chain
        lanes) — a masked slot's rows redirect to the scratch page. The
        scheduler pre-allocates blocks covering pos+gamma+1 and
        truncates the table back after acceptance."""
        x = jax.nn.one_hot(ids, self.vocab_size, dtype=self._dtype)
        sts = self._inject_paged(states, table, live[:, None])
        out, new_states, _ = self._forward(params, variables, x, sts)
        return out, self._freeze_states(new_states, states, live)

    def _fixpos_fn(self, states, posv, mask):
        """Post-verify rollback: set every attention layer's cache
        position to ``posv`` [n_slots] where ``mask`` is True (the slots
        that speculated this iteration), freeze the rest. The verify
        program advanced pos by the full padded chain; acceptance is
        decided host-side, so the correction is a separate (tiny, single)
        program — the rejected tail rows become causally invisible the
        moment pos steps back over them."""
        out = {}
        for key, st in states.items():
            if _is_paged(st) or (isinstance(st, dict) and "pos" in st
                                 and "k" in st):
                out[key] = {**st, "pos": jnp.where(mask, posv, st["pos"])}
            else:
                out[key] = st
        return out

    def _pick_chunk(self, seq: _ActiveSeq) -> Tuple[int, int]:
        """(bucket, n_real) for this sequence's next prefill chunk, or
        (0, 0) when no bucket fits the KV-cache headroom (the tail then
        prefills token-by-token through the decode step)."""
        remaining = len(seq.prompt) - seq.fed
        cap = self.prefill_chunk
        if self.chunk_cap:
            # degradation ladder (supervisor level >= 2): smaller chunks
            # shorten each iteration's device hold, trading TTFT for
            # decode tail latency under pressure. Smaller buckets are
            # already in the compiled family — no new programs.
            cap = max(1, min(cap, int(self.chunk_cap)))
        n_real = min(remaining, cap)
        if self._eva is not None:
            # a chunk lies inside one window and starts on a summary
            # chunk's boundary (the layer's T > 1 contract): a cap the
            # degradation ladder made uneven is rounded down to one
            window, chunk = self._eva
            n_real = min(n_real, window - seq.fed % window)
            if n_real < remaining:
                n_real -= n_real % chunk
        bucket = bucket_for(n_real, self.prefill_buckets)
        if self._cache_cap is not None and \
                seq.fed + bucket > self._cache_cap:
            # padded writes past the cap would trip the layer's overflow
            # guard even though the real tokens fit: shrink to the largest
            # bucket inside the headroom
            fitting = [b for b in self.prefill_buckets
                       if seq.fed + b <= self._cache_cap]
            if not fitting:
                return 0, 0
            bucket = fitting[-1]
            n_real = min(n_real, bucket)
        return bucket, n_real

    def _zero_fn(self, states, slot):
        """Zero one slot's rows across every state leaf (KV rows, cache
        position, LSTM h/c) so an admitted sequence starts clean. Jitted:
        one fused device program per admission instead of one eager
        dispatch per leaf, and no implicit scalar transfers (``slot`` is
        a 1-element int32 array, same contract as `_prefill_fn`). Paged
        page arrays are never touched — they are SHARED storage (another
        slot's blocks live there); a fresh slot starts clean because its
        table is reset to scratch host-side and its ``pos`` row to 0."""
        s = slot[0]

        def zero_row(a):
            if hasattr(a, "ndim") and a.ndim >= 1 and \
                    a.shape[0] == self.n_slots:
                return a.at[s].set(0)
            return a
        out = {}
        for key, st in states.items():
            if _is_paged(st):
                out[key] = {k: (v if k in PAGE_KEYS else zero_row(v))
                            for k, v in st.items()}
            else:
                out[key] = jax.tree_util.tree_map(zero_row, st)
        return out

    def _setpos_fn(self, states, slot, val):
        """Set one slot's attention cache position (paged prefix restore:
        the remap is host-side table surgery; the only device-visible
        effect is ``pos`` jumping past the hit). 1-element int32 array
        args, same transfer contract as `_zero_fn`."""
        s = slot[0]
        v = val[0]
        out = {}
        for key, st in states.items():
            if _is_paged(st):
                out[key] = {**st, "pos": st["pos"].at[s].set(v)}
            else:
                out[key] = st
        return out

    def _sumtab_fn(self, states, slot, col, bid):
        """Point one slot's summary block ``col`` at page ``bid`` in every
        EVA layer's carried ``summary_table`` (`_roll_window` claimed the
        page): 1-element int32 array args, as `_setpos_fn`. The slot's row
        needs no release: admission zeroes it with the slot's other rows."""
        out = {}
        for key, st in states.items():
            if _is_paged(st) and "summary_table" in st:
                out[key] = {**st, "summary_table": st["summary_table"]
                            .at[slot[0], col[0]].set(bid[0])}
            else:
                out[key] = st
        return out

    def _cow_fn(self, states, src, dst):
        """Copy-on-write block duplication: copy page ``src`` into the
        freshly-allocated page ``dst`` across every layer's K/V pages.
        Dispatched host-side BEFORE a write that would land in a shared
        (trie-owned) block; the writer's table then points at ``dst``."""
        s = src[0]
        d = dst[0]
        out = {}
        for key, st in states.items():
            if _is_paged(st):
                # scale pages (int8 KV mode) duplicate with their values
                out[key] = {
                    k: (v.at[d].set(v[s]) if k in PAGE_KEYS else v)
                    for k, v in st.items()
                }
            else:
                out[key] = st
        return out

    def _tier_spill_fn(self, states, bid):
        """Slice one page row (K/V pages + int8 scale rows) out of every
        layer's pool arrays — the device side of a tier demotion. The
        block index stays TRACED (dynamic slice), so the whole tier
        ladder costs exactly one XLA program regardless of which block
        spills; the result is a snapshot in buffers of its own (the
        pool is NOT donated here), enqueued before any later program
        that updates the pool in place, so it is safe against immediate
        reuse of the freed page."""
        b = bid[0]
        out = {}
        for key, st in states.items():
            if _is_paged(st):
                out[key] = {
                    pk: jax.lax.dynamic_index_in_dim(
                        st[pk], b, axis=0, keepdims=False)
                    for pk in PAGE_KEYS if pk in st}
        return out

    def _tier_restore_fn(self, states, bid, rows):
        """Write one promoted page row back into the pool arrays (the
        device side of a tier promotion) — the `_tier_spill_fn` slice in
        reverse, again one program for every block index."""
        b = bid[0]
        out = {}
        for key, st in states.items():
            if _is_paged(st) and key in rows:
                st2 = dict(st)
                for pk, row in rows[key].items():
                    st2[pk] = jax.lax.dynamic_update_index_in_dim(
                        st[pk], row.astype(st[pk].dtype), b, axis=0)
                out[key] = st2
            else:
                out[key] = st
        return out

    def _tier_capture(self, bid: int):
        """TierManager capture hook (scheduler thread, from the pool's
        `_evict_lru`): dispatch the spill slice and hand the device
        snapshot to the tier worker — the actual device->host read
        happens on the worker thread under the pacing budget, never
        here."""
        return self._jtier_spill(self._states, self._dev_index(bid))

    def _reset_slot_state(self, slot: int) -> None:
        # _states is single-writer by protocol: only the scheduler thread
        # mutates it once start() returns. warmup() — the one cross-thread
        # reader — runs exclusively inside supervisor-owned windows
        # (construction / recovery / drain-swap) while this engine's loop
        # is idle-by-construction (no slot admitted yet), and stop()'s
        # sweep runs after the join. CC005 cannot see that protocol.
        self._states = self._jzero(self._states, self._dev_index(slot))  # graftlint: disable=CC005
        if self.speculate:
            # the draft cache is slot-aligned with the main cache: a
            # reused slot starts the draft at row 0 too
            self._draft_states = self._jdraft_zero(  # graftlint: disable=CC005
                self._draft_states, self._dev_index(slot))

    def _release_pool(self, seq: _ActiveSeq) -> None:
        """Drop the sequence's prefix-trie reference (every slot-freeing
        path — finish, cancel, stop — must come through here, or the
        matched blocks stay pinned against eviction forever)."""
        if seq.pool_node is not None:
            self.pool.release(seq.pool_node)
            seq.pool_node = None
            ledger_note("trie_pin", seq.handle.request_id, -1)

    # -- paged mode: block tables, lazy alloc, COW, preempt-and-swap -------
    def _blocks_for(self, positions: int) -> int:
        """LOGICAL blocks of ``positions``: the block table's width."""
        return -(-positions // self.kv_block)

    def _blocks_held(self, depth: int) -> int:
        """Pool blocks a request holds with ``depth`` positions cached, as
        its attention layers say (`blocks_needed`): the logical count for a
        cache of one row per position, fewer where pages are recycled."""
        return self._attn_impl.blocks_needed(depth, self.kv_block)

    def _blocks_peak(self, depth: int) -> int:
        """The most a request holds on its way to ``depth`` positions: what
        admission and the pool-size check reserve. Where windows roll, the
        peak stands at the close of the last whole window."""
        held = self._blocks_held(depth)
        if self._eva is not None and depth > self._eva[0]:
            window = self._eva[0]
            held = max(held, self._blocks_held((depth - 1) // window * window))
        return held

    def _pages_listed(self, nb: int) -> int:
        """Pages a slot's page list names in a T=1 read at table bucket
        ``nb``: the table itself, or EVA's open-window pages and summary
        pages of the bucket."""
        if self._eva is None:
            return nb
        window, chunk = self._eva
        return min(window // self.kv_block, nb) + -(-nb // chunk)

    def _pages_with_rows(self, written: int) -> int:
        """Of those, the pages holding a row that a slot ``written``
        positions deep attends over in its next step."""
        bk = self.kv_block
        if self._eva is None:
            return -(-(written + 1) // bk)
        window, chunk = self._eva
        return (-(-(written % window + 1) // bk)
                + -(-(written // window * (window // chunk)) // bk))

    def _fused_read_engages(self, nb: int) -> bool:
        """The layer's rule for the fused paged read (`ops/paged_read`),
        on what the engine knows of its decode program at bucket ``nb``."""
        return self.kv_dtype != "int8" and self._attn_impl.fused_read_engages(
            self.paged_kernel, 1, self._dtype, self.mesh,
            slots=self.n_slots, pages=self._pages_listed(nb),
            block=self.kv_block)

    def _table_for(self, max_pos: int) -> np.ndarray:
        """The host table sliced to the pow2 bucket covering ``max_pos``
        positions — the per-step program shape. Shallow workloads gather
        (and attend over) only their own depth, not the whole pool."""
        nb = bucket_for(max(1, self._blocks_for(max_pos)),
                        self.table_buckets)
        return self._table[:, :nb]

    def _alloc_or_preempt(self, slot: int,
                          seq: _ActiveSeq) -> Optional[int]:
        """Claim one pool block under the preempt-and-swap policy: when
        allocation fails even after LRU-evicting unreferenced cached
        blocks, the LATEST-submitted live slot is preempted and the claim
        retried. None means ``seq`` itself was the victim (it is already
        requeued — the caller must skip its dispatch). The single home
        of the pool-pressure policy, shared by lazy growth and COW."""
        while True:
            bid = self.pool.alloc()
            if bid is not None:
                ledger_note("pool_block", seq.handle.request_id, +1)
                return bid
            victim = self._pick_victim()
            if victim is None or victim[1] is seq:
                self._preempt(slot, seq)
                return None
            self._preempt(*victim)

    def _ensure_blocks(self, slot: int, seq: _ActiveSeq,
                       upto_pos: int) -> bool:
        """Grow ``slot``'s block table to cover positions [0, upto_pos)
        — the lazy allocation of the paged layout: a block is claimed
        only when ``pos`` is about to cross into it. False means ``seq``
        was preempted by its own allocation (see _alloc_or_preempt)."""
        if self._eva is not None and \
                not self._roll_window(slot, seq, upto_pos):
            return False
        need = self._blocks_for(upto_pos)
        added = 0
        while len(seq.block_ids) < need:
            bid = self._alloc_or_preempt(slot, seq)
            if bid is None:
                return False
            j = len(seq.block_ids)
            seq.block_ids.append(bid)
            seq.shared.append(False)
            # host block table: scheduler-thread-only past start(), like
            # _states/pool above (stop() frees rows only after the join)
            self._table[slot, j] = bid  # graftlint: disable=CC005
            added += 1
        if added and self.tracer.enabled:
            self.tracer.instant(
                "block_alloc", track=self._slot_tracks[slot],
                args={"request": seq.handle.request_id, "blocks": added,
                      "free": self.pool.free_blocks})
        return True

    def _roll_window(self, slot: int, seq: _ActiveSeq,
                     upto_pos: int) -> bool:
        """The page lifetimes of a net that recycles (EVA), before the
        write of positions up to ``upto_pos``, all of one window (the
        chunk rule of `_pick_chunk`). Exact blocks of the windows that
        closed go back to the free list BEFORE the next claim needs one:
        no later query reads them, their chunks' summaries were written as
        their rows were. Then a summary page is claimed for every
        ``kv_block`` chunks begun; those live as long as the request.
        False means ``seq`` was preempted by its own claim."""
        window, chunk = self._eva
        per_window = window // self.kv_block
        first = (upto_pos - 1) // window * per_window
        if first > seq.rolled:
            rid = seq.handle.request_id
            with self.profiler.nested("roll"):
                if self.tracer.enabled:
                    self.tracer.begin(
                        "window_roll", track=self._sched_track,
                        args={"request": rid,
                              "window": (upto_pos - 1) // window,
                              "blocks_freed": first - seq.rolled})
                for j in range(seq.rolled, first):
                    self.pool.free_block(seq.block_ids[j])
                    seq.block_ids[j] = SCRATCH_BLOCK
                self._table[slot, seq.rolled:first] = SCRATCH_BLOCK
                ledger_note("pool_block", rid, seq.rolled - first)
                self._m_eva_rolled.inc((first - seq.rolled) // per_window)
                self._m_eva_recycled.inc(first - seq.rolled)
                seq.rolled = first
                self.tracer.end("window_roll", track=self._sched_track)
        need = -(-upto_pos // (chunk * self.kv_block))
        while len(seq.summary_ids) < need:
            bid = self._alloc_or_preempt(slot, seq)
            if bid is None:
                return False
            self._states = self._jsumtab(
                self._states, self._dev_index(slot),
                self._dev_index(len(seq.summary_ids)), self._dev_index(bid))
            seq.summary_ids.append(bid)
        return True

    def _ensure_writable(self, slot: int, seq: _ActiveSeq,
                         pos: int) -> bool:
        """Copy-on-write before the first write into a SHARED block: a
        restored (trie-owned) block the slot is about to write — the
        one-token refeed when a prefix hit covers the whole prompt —
        is duplicated into a fresh page and the table repointed, so the
        cached original stays bit-intact for its other readers. Only the
        first block of a write span can be shared (everything past the
        restore frontier was freshly allocated)."""
        j = pos // self.kv_block
        if j >= len(seq.block_ids) or not seq.shared[j]:
            return True
        bid = self._alloc_or_preempt(slot, seq)
        if bid is None:
            # self-preempted for the COW page: when every page backs
            # this prompt's own (pinned) prefix, no amount of retrying
            # can produce the duplicate — the resume must restore one
            # block short instead
            seq.cow_starved = True
            return False
        src = seq.block_ids[j]
        self._states = self._jcow(self._states, self._dev_index(src),
                                  self._dev_index(bid))
        seq.block_ids[j] = bid
        seq.shared[j] = False
        self._table[slot, j] = bid
        if self.tracer.enabled:
            self.tracer.instant(
                "block_cow", track=self._slot_tracks[slot],
                args={"request": seq.handle.request_id, "src": src,
                      "dst": bid, "block_index": j})
        return True

    def _pick_victim(self) -> Optional[Tuple[int, _ActiveSeq]]:
        """Preemption victim: the latest-SUBMITTED live slot (LIFO — the
        earliest request keeps its progress, vLLM's policy). Keyed on
        t_submit, not t_admitted: re-admission re-stamps t_admitted, so
        an admitted-time key would make a just-resumed old request the
        preferred victim again and thrash its re-prefill. May be the
        requester itself when it is the youngest."""
        cands = [(s.handle.t_submit, i, s)
                 for i, s in enumerate(self._slots) if s is not None]
        if not cands:
            return None
        _, i, s = max(cands)
        return i, s

    def _preempt(self, slot: int, seq: _ActiveSeq) -> None:
        """Swap a sequence out under pool pressure: release its owned
        blocks and trie pin (KV is dropped, not spilled — recompute is a
        prefill, which chunking makes cheap), fold the tokens generated
        so far into its prompt, and requeue it at the FRONT. On
        re-admission the re-prefill recomputes the same K/V and the
        final chunk's distribution yields exactly the token the
        interrupted decode would have produced next — the sequence's
        host-side RNG is untouched, so resumed output is token-identical
        to an unpreempted run."""
        self._m_preempted.inc()
        h = seq.handle
        tr = self.tracer
        if tr.enabled:
            if seq.phase == "prefill":
                tr.end("prefill", req=h.request_id,
                       args={"fed_tokens": seq.fed})
            elif seq.phase == "decode":
                tr.end("decode", req=h.request_id,
                       args={"tokens": len(h.tokens), "preempted": True})
            tr.instant("preempt", track=self._slot_tracks[slot],
                       args={"request": h.request_id,
                             "blocks_released": sum(
                                 1 for sh in seq.shared[seq.rolled:]
                                 if not sh) + len(seq.summary_ids),
                             "tokens_done": len(h.tokens)})
            # the swap gap on the request track: everything between
            # "preempt" and the matching "resume" is time the request
            # spent swapped out waiting for pool blocks
            tr.begin("preempted", req=h.request_id)
        self._release_pool(seq)
        self._release_slot_blocks(slot, seq)
        self._release_mask(seq)  # re-acquired (usually cached) on resume
        seq.prompt.extend(int(t) for t in h.tokens[seq.folded:])
        seq.folded = len(h.tokens)
        seq.fed = 0
        seq.written = 0
        seq.draft_fed = 0  # the draft cache re-ingests on resume too
        seq.phase = "preempted"
        seq.resumed = True
        # single-writer: _slots is mutated only on this scheduler thread
        # (same discipline as _step_once); _cond guards only the queue.
        # Cross-thread readers (inflight(), stop()'s post-join sweep)
        # read the list reference GIL-atomically and tolerate a one-
        # entry-stale view — CC005 cannot see the single-writer protocol
        self._slots[slot] = None  # graftlint: disable=CC004,CC005
        ledger_note("engine_slot", h.request_id, -1)
        with self._cond:
            self._queue.insert(0, seq)
            self._m_queue_depth.set(len(self._queue))
        self._m_active.set(sum(s is not None for s in self._slots))

    def _release_slot_blocks(self, slot: int, seq: _ActiveSeq,
                             keep: frozenset = frozenset()) -> None:
        """Return a slot's OWNED blocks to the pool (shared entries are
        trie-owned — releasing the trie pin is `_release_pool`'s job)
        and reset its table row to scratch. ``keep``: ids adopted by the
        trie at publish (ownership already transferred)."""
        freed = 0
        for bid, sh in zip(seq.block_ids[seq.rolled:],
                           seq.shared[seq.rolled:]):
            if not sh and bid not in keep:
                self.pool.free_block(bid)
                freed += 1
        for bid in seq.summary_ids:
            self.pool.free_block(bid)
        freed += len(seq.summary_ids)
        if freed:
            ledger_note("pool_block", seq.handle.request_id, -freed)
        seq.block_ids = []
        seq.shared = []
        seq.summary_ids = []
        seq.rolled = 0
        self._table[slot, :] = SCRATCH_BLOCK

    def _try_restore(self, slot: int, seq: _ActiveSeq) -> None:
        """Paged prefix restore = block-table remap: point the slot's
        table at the cached blocks (refcounted via the trie pin) and set
        ``pos`` past the hit. ZERO K/V copies — the pages are referenced
        where they lie; the only device work is the one-row pos write.
        The hit may cover the WHOLE prompt (full blocks): the last
        prompt token is then re-fed to produce the first output
        distribution, and its write copy-on-writes the final shared
        block (`_ensure_writable`)."""
        if self._no_prefix:
            return  # nothing was published (`_publish_prompt`): no lookup
        B = self.pool.block
        self._m_prefix_lookups.inc()
        self._m_prefix_lookup_tokens.inc(len(seq.prompt))
        max_hit = len(seq.prompt) // B
        if seq.cow_starved:
            # the previous attempt's full hit left no page for the
            # refeed's COW duplicate: leave the tail block unpinned (it
            # becomes evictable, freeing the page the re-prefill needs).
            # One-shot — a later ordinary preempt/resume gets the full
            # hit again; if the trap recurs the flag is simply re-set
            max_hit -= 1
            seq.cow_starved = False
        if max_hit < 1:
            return
        n_blk, ids, node = self.pool.match(seq.prompt, max_hit)
        seq.pool_node = node  # holds one reference until the slot frees
        if node is not None:
            ledger_note("trie_pin", seq.handle.request_id, +1)
        if self.tier is not None:
            # tier directory lookup past the resident frontier: queue
            # host/disk blocks for background promotion. The slot does
            # NOT wait — it prefills its cold suffix as usual, and a
            # landed promotion upgrades it mid-prefill (_tier_tick)
            frontier = node.hash if node is not None else ""
            if frontier is not None:
                ext = self.tier.lookup_extension(
                    frontier, seq.prompt, n_blk, max_hit)
                if ext:
                    self.tier.request_restore(ext)
        if not n_blk:
            return
        seq.block_ids = [int(b) for b in ids]
        seq.shared = [True] * n_blk
        self._table[slot, :n_blk] = ids
        fed = min(n_blk * B, len(seq.prompt) - 1)
        self._states = self._jsetpos(self._states,
                                     self._dev_index(slot),
                                     self._dev_index(fed))
        seq.fed = fed
        seq.written = fed
        self._m_prefix_hits.inc()
        self._m_prefix_hit_tokens.inc(fed)
        if seq.fork is not None \
                and seq.fork.primary_handle is not seq.handle \
                and not seq.resumed:
            # a best-of-n FOLLOWER attached to its group's published
            # prompt blocks: the COW fork proper (n candidates, one
            # prompt's worth of KV). The primary's own trie hit and
            # preempt-resume re-restores are ordinary prefix hits, not
            # forks — counting them would inflate the metric past n-1
            self._m_forks.inc()
            if self.tracer.enabled:
                self.tracer.instant(
                    "fork", track=self._slot_tracks[slot],
                    args={"request": seq.handle.request_id,
                          "role": "attach", "blocks": n_blk})

    def _publish_prompt(self, slot: int, seq: _ActiveSeq) -> frozenset:
        """Zero-copy publish: the finished sequence's full prompt blocks
        are ADOPTED by the trie in place (ownership transfer — the pages
        already hold the prefill-written K/V). Returns the transferred
        ids so the slot release does not free them. Blocks the trie
        already indexes (the restored prefix, or a COW'd duplicate of
        one) are skipped and freed normally."""
        B = self.pool.block
        n_full = len(seq.prompt) // B
        if self._no_prefix:
            # the prompt's pages were recycled as its windows closed, and
            # what is left stands for this request's own window; or the
            # pages are whole but the state that goes with them is not
            # addressed by position: the trie adopts nothing, and says so
            if n_full:
                self._m_publish_skipped.inc()
            return frozenset()
        if n_full < 1 or n_full > len(seq.block_ids):
            return frozenset()
        adopted = frozenset(self.pool.adopt(
            seq.prompt[:n_full * B], seq.block_ids[:n_full]))
        if adopted:
            # ownership transfer: the trie owns these pages now — the
            # request's debt is settled without a free_block
            ledger_note("pool_block", seq.handle.request_id,
                        -len(adopted))
        return adopted

    # -- grammar mask residency (logitproc.MaskPool) -----------------------
    def _attach_mask(self, slot: int, seq: _ActiveSeq) -> None:
        """Make an admitted request's grammar device-resident: acquire
        (or ref) its mask-row range and upload the additive table on
        first residency — at ADMISSION, off the per-token path, so
        constrained decode steps pay only the in-program gather. A
        grammar that cannot fit falls back to HOST-ONLY masking
        (mask_base None): the exact allow row still applies at sampling
        — correctness never depends on residency, only the device-side
        assist (and the draft's in-grammar proposals) does."""
        proc = seq.proc
        if proc is None or proc.grammar is None or self.maskpool is None:
            return
        g = proc.grammar
        start, upload = self.maskpool.acquire(g)
        if start is None:
            proc.mask_base = None
            self._m_mask_spill.inc()
            return
        if upload:
            bucket = bucket_for(g.n_states, self.mask_buckets)
            rows = np.zeros((bucket, self.vocab_size),
                            np.dtype(self._dtype))
            rows[:g.n_states] = g.mask_table(np.dtype(self._dtype))
            # _masks is scheduler-thread-only past start() (attach runs
            # in _admit), same single-writer protocol as _states
            self._masks = self._jmask_upload(  # graftlint: disable=CC005
                self._masks, self._dev_index(start),
                self._dev_array(rows))
        proc.mask_base = start
        ledger_note("mask_row", seq.handle.request_id, +1)
        self._m_mask_rows.set(self.maskpool.resident_rows())
        if self.tracer.enabled:
            self.tracer.instant(
                "grammar_attach", track=self._slot_tracks[slot],
                args={"request": seq.handle.request_id,
                      "states": g.n_states, "row": start,
                      "uploaded": bool(upload)})

    def _release_mask(self, seq: _ActiveSeq) -> None:
        """Drop the request's mask-row reference (every slot-freeing
        path — finish, cancel, preempt, stop, crash — comes through
        here; the rows stay CACHED for the next request sharing the
        grammar until pool pressure evicts zero-ref entries)."""
        proc = seq.proc
        if proc is not None and proc.mask_base is not None:
            self.maskpool.release(proc.grammar.key)
            proc.mask_base = None
            ledger_note("mask_row", seq.handle.request_id, -1)
            self._m_mask_rows.set(self.maskpool.resident_rows())

    # -- client side -------------------------------------------------------
    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int, *,
               temperature: float = 0.0, top_k: Optional[int] = None,
               top_p: Optional[float] = None, seed: int = 0,
               eos_id: Optional[int] = None,
               request_id: Optional[str] = None, priority: int = 0,
               stop: Optional[Sequence[Sequence[int]]] = None,
               grammar: Optional[CompiledGrammar] = None,
               repetition_penalty: Optional[float] = None,
               presence_penalty: Optional[float] = None,
               frequency_penalty: Optional[float] = None,
               stream=None,
               fork: Optional[ForkGroup] = None,
               _handle: Optional[DecodeHandle] = None,
               _front: bool = False) -> DecodeHandle:
        """``stop``: multi-token stop sequences (list of token-id lists)
        matched across token boundaries; a match truncates the output
        before the stop sequence and finishes the request
        (``finish_reason="stop"``). ``grammar``: a pre-compiled
        `logitproc.CompiledGrammar` (compiled AHEAD of admission — the
        serving layer caches compiles by content); forbidden tokens get
        probability exactly 0 and the grammar's device mask rows attach
        at admission. ``repetition_penalty`` / ``presence_penalty`` /
        ``frequency_penalty``: host-side probability-row penalties over
        generated-token counts. ``stream``: a `logitproc.TokenStream`
        the scheduler pushes released tokens into as they decode (the
        SSE backing; crash-recovery re-decodes dedupe by token index).

        ``priority``: degradation-ladder shedding order (higher
        survives longer; default 0). ``fork``: best-of-n candidate
        group (`speculative.ForkGroup`, see :meth:`generate_many`) —
        the first submission becomes the primary; follower candidates
        stay queued until the primary's prefill publishes the prompt's
        paged blocks, then restore them copy-on-write. ``_handle``/
        ``_front``: the supervisor's crash-recovery resubmission path —
        reuse the ORIGINAL (reset) handle so the caller blocked in
        ``result()`` never notices the restart, and front-queue
        recovered work so it does not wait behind requests submitted
        after the crash."""
        rid = _handle.request_id if _handle is not None \
            else (request_id or new_request_id())
        if not len(prompt_ids):
            raise ValueError("prompt_ids must be non-empty")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        bad = [int(t) for t in prompt_ids
               if not 0 <= int(t) < self.vocab_size]
        if bad:
            # ids arrive from untrusted JSON (/generate); out-of-range ids
            # would one-hot to silent all-zero rows, decoding confidently
            # from a "no token" input
            raise ValueError(
                f"prompt ids out of range [0, {self.vocab_size}): "
                f"{bad[:5]}")
        needed = len(prompt_ids) + max(max_new_tokens - 1, 0)
        if self.paged:
            # pool-bytes admission: a prompt is rejected only when it
            # cannot fit the WHOLE pool — there is no per-slot stripe to
            # outgrow, so "too long" means more blocks than exist
            # the most it ever holds, and its block table's width (the
            # same number unless its layers recycle pages)
            blocks_needed = max(self._blocks_peak(needed),
                                self._blocks_for(needed))
            if blocks_needed > self.pool.capacity_blocks:
                self._m_rejected.inc()
                self.tracer.instant("reject", req=rid, args={
                    "request_id": rid, "reason": "prompt_too_long",
                    "blocks_needed": blocks_needed,
                    "blocks_available": self.pool.capacity_blocks})
                err = PromptTooLongError(
                    f"prompt ({len(prompt_ids)}) + max_new_tokens "
                    f"({max_new_tokens}) needs {blocks_needed} KV blocks "
                    f"of {self.kv_block} positions but the pool has "
                    f"{self.pool.capacity_blocks}")
                err.blocks_needed = blocks_needed
                err.blocks_available = self.pool.capacity_blocks
                raise err
        elif self._cache_cap is not None:
            if needed > self._cache_cap:
                # rejected up front (HTTP 413 at the serving layer), not
                # admitted to die mid-decode on the attention layer's
                # KV-overflow guard
                self._m_rejected.inc()
                self.tracer.instant("reject", req=rid, args={
                    "request_id": rid, "reason": "prompt_too_long",
                    "needed": needed, "cache": self._cache_cap})
                raise PromptTooLongError(
                    f"prompt ({len(prompt_ids)}) + max_new_tokens "
                    f"({max_new_tokens}) needs a KV cache of {needed} but "
                    f"max_cache_len={self._cache_cap}")
        # the per-request logit pipeline is built HERE — including the
        # supervisor's crash-recovery resubmission, whose kwargs carry
        # the same grammar/stop/penalty spec — so a token-identical
        # re-decode re-observes from a clean pipeline state
        proc = None
        if (grammar is not None or stop or repetition_penalty
                or presence_penalty or frequency_penalty):
            proc = LogitState(self.vocab_size, grammar=grammar, stop=stop,
                              repetition_penalty=repetition_penalty,
                              presence_penalty=presence_penalty,
                              frequency_penalty=frequency_penalty)
            if grammar is not None and _handle is None:
                # _handle set = the supervisor's crash-recovery
                # resubmission of a request already counted once
                self._m_constrained.inc()
        handle = _handle if _handle is not None else DecodeHandle(
            len(prompt_ids), max_new_tokens, request_id=rid,
            priority=priority)
        if stream is not None:
            handle.stream = stream
        seq = _ActiveSeq(handle, prompt_ids, temperature, top_k, top_p,
                         seed, eos_id)
        seq.proc = proc
        if fork is not None:
            fork.bind_primary(handle)
            seq.fork = fork
        with self._cond:
            if not self._running:
                raise RuntimeError("scheduler is not running (call start())")
            if len(self._queue) >= self.max_queue:
                self._m_rejected.inc()
                self.tracer.instant("reject", req=rid, args={
                    "request_id": rid, "reason": "queue_full",
                    "waiting": len(self._queue)})
                raise QueueFullError(
                    f"decode queue full ({self.max_queue} waiting)")
            if _front:
                self._queue.insert(0, seq)
            else:
                self._queue.append(seq)
            self._m_queue_depth.set(len(self._queue))
            # the request's first span opens while the queue lock is
            # still held — the scheduler needs _cond to pop this seq, so
            # its end("queued") can never be sequenced before this begin
            self.tracer.begin("queued", req=rid,
                              args={"prompt_tokens": len(seq.prompt),
                                    "max_new_tokens": max_new_tokens})
            self._cond.notify()
        return handle

    def generate_handle(self, prompt_ids: Sequence[int],
                        max_new_tokens: int,
                        timeout: Optional[float] = 120.0,
                        **kw) -> DecodeHandle:
        """Blocking submit returning the COMPLETED handle (tokens plus
        the request_id and per-phase `timings()` the serving layer echoes
        back). A timed-out wait CANCELS the request (the slot is
        reclaimed at the scheduler's next step instead of decoding to
        max_new_tokens for a caller that already gave up) — the one
        place this contract lives; `generate` and the HTTP `/generate`
        route both come through here."""
        handle = self.submit(prompt_ids, max_new_tokens, **kw)
        try:
            handle.result(timeout)
        except TimeoutError:
            handle.cancel()
            raise
        return handle

    def generate(self, prompt_ids: Sequence[int], max_new_tokens: int,
                 timeout: Optional[float] = 120.0, **kw) -> List[int]:
        """Blocking submit — drop-in for `generate_transformer` greedy."""
        return self.generate_handle(prompt_ids, max_new_tokens,
                                    timeout=timeout, **kw).tokens

    def generate_many(self, prompt_ids: Sequence[int], n: int,
                      max_new_tokens: int,
                      timeout: Optional[float] = 120.0, *, seed: int = 0,
                      **kw) -> List[DecodeHandle]:
        """Best-of-n over ONE prompt: ``n`` candidates submitted as a
        copy-on-write fork group (`speculative.submit_fork_group` — the
        shared submission protocol: seed+i per candidate, partial-
        submit failures cancel the already-submitted, a timeout cancels
        all unfinished). In paged mode the first candidate (the
        primary) prefills the prompt once and publishes its blocks the
        moment its prefill completes; follower candidates restore them
        as zero-copy block-table remaps and copy-on-write only the tail
        block they write — n candidates cost ~one prompt's worth of KV
        instead of n (`decode_forks_total` counts the attaches).
        Candidate 0 reproduces the n=1 output for the same seed
        exactly."""
        from .speculative import await_fork_group, submit_fork_group
        handles = submit_fork_group(self.submit, prompt_ids, n,
                                    max_new_tokens, seed=seed, **kw)
        await_fork_group(handles, timeout)
        return handles

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "DecodeScheduler":
        with self._cond:
            if self._running:
                return self
            self._running = True
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="decode-scheduler")
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._fenced:
            # a fenced engine's handles are DISOWNED (the supervisor
            # requeued them onto a replacement): finishing them here
            # would fail requests another engine is actively serving.
            # Just drop the references; the stuck thread (if any) exits
            # at its next fence check.
            with self._cond:
                self._running = False
                self._queue.clear()
                self._cond.notify_all()
            if self._thread is not None:
                self._thread.join(timeout=1)
                self._thread = None
            # safe lock-free: the loop thread is joined (or, if it is a
            # hung zombie, exits at its fence check without writing)
            for seq in self._slots:  # graftlint: disable=CC004
                if seq is not None:
                    # disown, don't judge: the supervisor requeued this
                    # request onto a replacement engine, and this dead
                    # engine's pool (pins, blocks, mask rows and all)
                    # is garbage-collected wholesale
                    ledger_forget(seq.handle.request_id, _LEDGER_KINDS)
            self._slots = [None] * self.n_slots  # graftlint: disable=CC004
            if self.tier is not None:
                # disowned engine: stop the worker, skip the balance
                # check (the ledger entries were forgotten wholesale)
                self.tier.stop(check=False)
            return
        with self._cond:
            self._running = False
            pending = self._queue[:]
            self._queue.clear()
            self._cond.notify_all()
        for seq in pending:
            seq.handle._finish(RuntimeError("scheduler stopped"))
            self._trace_done("cancel", seq)
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        # a pool-pressure preemption racing the drain above can requeue
        # a slot-resident sequence AFTER _queue was cleared; drain once
        # more now that the scheduler thread (the only other writer) is
        # joined, or that handle would never finish and its caller's
        # result() would block out its full timeout
        with self._cond:
            pending = self._queue[:]
            self._queue.clear()
        for seq in pending:
            seq.handle._finish(RuntimeError("scheduler stopped"))
            self._trace_done("cancel", seq)
        # safe lock-free: the scheduler thread (the only other _slots
        # writer) has been joined above
        for i, seq in enumerate(self._slots):  # graftlint: disable=CC004
            if seq is not None:
                if self.paged:
                    self._release_pool(seq)
                    self._release_slot_blocks(i, seq)
                self._release_mask(seq)
                seq.handle._finish(RuntimeError("scheduler stopped"))
                self._trace_done("cancel", seq, slot=i)
                self._slots[i] = None
                ledger_note("engine_slot", seq.handle.request_id, -1)
        if self.tier is not None:
            # joins the transfer worker and zeroes the tier ledger
            # (host_page / disk_block / directory_entry) before the
            # engine's own balance check below
            self.tier.stop()
        ledger_check_zero("engine.stop", _LEDGER_KINDS)

    # -- scheduler loop ----------------------------------------------------
    def _trace_done(self, outcome: str, seq: _ActiveSeq,
                    slot: Optional[int] = None) -> None:
        """Terminal trace records for one request: close whichever phase
        span is still open (a slot-resident request always has `prefill`
        or `decode` open; a never-admitted one has `queued`), then stamp
        one ``finish``/``cancel`` instant carrying the handle's full
        timing breakdown — the record `request_summaries` scrapes. Call
        AFTER `handle._finish()` so `timings()` sees t_done."""
        h = seq.handle
        rid = h.request_id
        tr = self.tracer
        if not tr.enabled:
            return
        self._close_phase_span(seq)
        tr.instant(outcome, req=rid,
                   args={"request_id": rid, "tokens": len(h.tokens),
                         **({"retries": h.retries} if h.retries else {}),
                         **h.timings()})
        if slot is not None:
            tr.instant("free", track=self._slot_tracks[slot],
                       args={"request": rid})

    def _close_phase_span(self, seq: _ActiveSeq) -> None:
        """End whichever request-track span is open. seq.phase (not the
        handle timestamps) names it: a resumed sequence is back in
        "prefill" with t_first_token long stamped, and one cancelled
        while swapped out has "preempted" open instead of "queued"."""
        h = seq.handle
        rid = h.request_id
        tr = self.tracer
        if seq.phase == "queued":
            tr.end("queued", req=rid)
        elif seq.phase == "prefill":
            tr.end("prefill", req=rid, args={"fed_tokens": seq.fed})
        elif seq.phase == "preempted":
            tr.end("preempted", req=rid)
        else:
            tr.end("decode", req=rid,
                   args={"tokens": len(h.tokens), "iterations": seq.steps})

    def _evict_cancelled(self) -> None:
        for i, seq in enumerate(self._slots):
            if seq is not None and seq.handle.cancelled():
                self._m_cancelled.inc()
                if self.paged:
                    # a cancel during prefill still holds the restored
                    # prefix's trie reference — releasing here is what
                    # keeps refcounts leak-free (nothing is published:
                    # the prompt may be half-written)
                    self._release_pool(seq)
                    self._release_slot_blocks(i, seq)
                # a cancel (incl. the streaming layer's client-
                # disconnect path) releases the grammar mask pin too
                self._release_mask(seq)
                seq.handle.finish_reason = "cancelled"
                seq.handle._finish()  # partial tokens, caller already left
                self._trace_done("cancel", seq, slot=i)
                self._slots[i] = None
                ledger_note("engine_slot", seq.handle.request_id, -1)
                ledger_check_request(seq.handle.request_id,
                                     _LEDGER_KINDS)

    def _pool_can_admit(self, seq: _ActiveSeq,
                        reclaim_memo: List[Optional[int]],
                        pending_blocks: int) -> bool:
        """Paged admission gate: only admit when the pool could actually
        back the prompt's prefill (free + evictable blocks) — admitting
        past that point would just preempt a live slot to make room.
        Always True when no slot is live (eviction alone must then cover
        it: submit() checked the prompt fits the whole pool).
        ``reclaim_memo`` caches the two-trie-walk reclaimable count for
        one _admit pass — nothing mutates the pool under _cond, so one
        walk per pass is exact, not stale. ``pending_blocks`` is what
        this pass's earlier admissions PLUS the already-resident slots'
        not-yet-allocated prefill blocks will claim (chunked prefill
        allocates lazily, at most one chunk per iteration, so a freshly
        admitted prompt's claim lands over the NEXT several passes —
        without the resident debit, admission races ahead of allocation
        and triggers exactly the admit-then-preempt churn this gate
        exists to prevent). Decode-time growth past the prompt is
        deliberately NOT reserved — that tail is what preempt-and-swap
        is for."""
        if not self.paged:
            return True
        if not any(s is not None for s in self._slots):
            return True
        if reclaim_memo[0] is None:
            reclaim_memo[0] = self.pool.reclaimable_blocks()
        return (reclaim_memo[0] - pending_blocks
                >= self._blocks_peak(len(seq.prompt)))

    def _admit(self) -> None:
        admitted: List[Tuple[int, _ActiveSeq]] = []
        tr = self.tracer
        reclaim_memo: List[Optional[int]] = [None]
        pending_blocks = 0  # blocks promised but not yet allocated
        if self.paged:
            # resident slots' outstanding prefill claims (scheduler-
            # thread-only reads, same discipline as _step_once)
            pending_blocks = sum(
                max(0, self._blocks_peak(len(s.prompt)) - s.blocks_held)
                for s in self._slots if s is not None)  # graftlint: disable=CC004
        with self._cond:
            blocked = False
            for i in range(self.n_slots):
                if blocked or self._slots[i] is not None:
                    continue
                qi = 0
                while qi < len(self._queue):
                    seq = self._queue[qi]
                    if seq.handle.cancelled():  # gave up while queued
                        self._queue.pop(qi)
                        self._m_cancelled.inc()
                        seq.handle.finish_reason = "cancelled"
                        seq.handle._finish()
                        self._trace_done("cancel", seq)
                        continue
                    if (self.paged and seq.fork is not None
                            and seq.fork.waiting(seq.handle)):
                        # best-of-n FOLLOWER: stay queued until the
                        # primary's prefill publishes the prompt blocks
                        # this candidate exists to share — admitting it
                        # now would cold-prefill its own copy and defeat
                        # the fork. Bounded wait (one prefill), not
                        # starvation: the gate opens the moment the
                        # primary publishes, finishes, or dies.
                        qi += 1
                        continue
                    if not self._pool_can_admit(seq, reclaim_memo,
                                                pending_blocks):
                        # head-of-line blocking is deliberate: skipping
                        # ahead would starve the (front-requeued)
                        # preempted sequence the gate exists to protect
                        blocked = True
                        break
                    self._queue.pop(qi)
                    self._slots[i] = seq
                    ledger_note("engine_slot", seq.handle.request_id, +1)
                    if self.paged:
                        pending_blocks += self._blocks_peak(len(seq.prompt))
                    if not seq.resumed:
                        self._m_seqs.inc()
                    admitted.append((i, seq))
                    break
            self._m_queue_depth.set(len(self._queue))
            self._m_active.set(sum(s is not None for s in self._slots))
        # device work happens OUTSIDE the condvar: the slot-reset and
        # prefix-restore dispatches (and a cold program's first-call
        # compile, which can take seconds) must not stall every submit()
        # caller blocked on _cond. _slots/_states/pool are scheduler-
        # thread-only, so no lock is needed past the queue handoff.
        for i, seq in admitted:
            h = seq.handle
            rid = h.request_id
            h.t_admitted = time.monotonic()
            if seq.phase == "preempted":
                tr.end("preempted", req=rid)
                tr.instant("resume", track=self._slot_tracks[i],
                           args={"request": rid,
                                 "refeed_tokens": len(seq.prompt)})
            else:
                tr.end("queued", req=rid)
            tr.instant("admit", track=self._slot_tracks[i],
                       args={"request": rid})
            tr.begin("prefix_restore", req=rid)
            self._reset_slot_state(i)
            if self.paged:
                self._try_restore(i, seq)
            # grammar mask upload rides the admission window too (a
            # preempted-and-resumed request re-acquires here — its rows
            # are usually still cached, so this is a refcount bump)
            self._attach_mask(i, seq)
            h.t_restored = time.monotonic()
            tr.end("prefix_restore", req=rid,
                   args={"hit_tokens": seq.fed, "slot": i,
                         **({"remap_blocks": len(seq.block_ids),
                             "kv_copies": 0} if self.paged else {})})
            tr.begin("prefill", req=rid,
                     args={"prompt_tokens": len(seq.prompt),
                           "restored_tokens": seq.fed, "slot": i})
            seq.phase = "prefill"

    def _consume(self, slot: int, seq: _ActiveSeq,
                 probs_row: np.ndarray) -> None:
        """Sample one output token from a next-token distribution row;
        finish + evict on max_new_tokens or EOS. Shared by the decode step
        and the final prefill chunk (whose last-real-token distribution
        yields the first output token). Token-count metrics are NOT
        updated here — the loop flushes one batched `inc(n)` per
        iteration instead of taking the counter lock once per token."""
        proc = seq.proc
        if proc is None:
            tok = sample_logits(probs_row, seq.temperature, seq.top_k,
                                seq.rng, seq.top_p)
        else:
            # penalty-adjust + EXACT host-side grammar mask (forbidden
            # tokens get probability 0 whatever the device mask did),
            # then observe — the pipeline's state advances on emitted
            # tokens only, in emission order
            tok = sample_logits(proc.adjust(probs_row), seq.temperature,
                                seq.top_k, seq.rng, seq.top_p,
                                allow=proc.allow_row())
            proc.advance(tok)
        self._emit(slot, seq, tok)

    def _fork_publish(self, slot: int, seq: _ActiveSeq) -> None:
        """Best-of-n early publish: the fork group's PRIMARY just
        finished prefill — run the SAME `_publish_prompt` ownership
        transfer finish-time publish uses, just earlier, so queued
        sibling candidates restore the prompt blocks as zero-copy
        block-table remaps instead of each re-prefilling. The adopted
        blocks flip to shared in the slot's own bookkeeping (its next
        write into one — there is none before the decode tail — would
        COW), and the slot takes a trie pin so eviction cannot free
        rows it still reads."""
        group = seq.fork
        adopted = self._publish_prompt(slot, seq)
        if adopted:
            for j, bid in enumerate(seq.block_ids):
                if bid in adopted:
                    seq.shared[j] = True
            self._release_pool(seq)
            n_full = len(seq.prompt) // self.pool.block
            _, _, node = self.pool.match(seq.prompt, n_full)
            seq.pool_node = node
            if node is not None:
                ledger_note("trie_pin", seq.handle.request_id, +1)
            if self.tracer.enabled:
                self.tracer.instant(
                    "fork", track=self._slot_tracks[slot],
                    args={"request": seq.handle.request_id,
                          "role": "publish", "blocks": len(adopted),
                          "candidates": group.n})
        group.published = True

    def _emit(self, slot: int, seq: _ActiveSeq, tok: int) -> None:
        """Append one ALREADY-SAMPLED output token to the handle;
        finish + evict on max_new_tokens or EOS. The single emission
        path shared by plain decode (`_consume` samples then emits) and
        the speculative acceptance loop (which sampled while walking
        the verify distributions)."""
        if self._fenced:
            # a fenced thread woke mid-iteration: this handle may
            # already be requeued on the replacement engine — appending
            # a token (or finishing) here would corrupt/duplicate it
            raise _EngineFenced
        h = seq.handle
        if h.done():
            return  # a speculative chain can run past a stop-sequence /
            # grammar finish: the tail tokens were sampled (RNG spent on
            # a finished request — harmless) but must not be appended
        h.tokens.append(tok)
        self._emitted_this_iter += 1
        now = time.monotonic()
        if h.t_first_token is None:
            h.t_first_token = now
            h.steps_to_first_token = seq.steps
            ttft = now - h.t_submit
            self._m_first_token.record(ttft, exemplar=h.request_id)
            if self.tracer.enabled:
                # the request waterfall's TTFT marker (ISSUE 14
                # satellite): right where prefill hands off to decode
                self.tracer.instant(
                    "first_token", req=h.request_id,
                    args={"request_id": h.request_id,
                          "ttft_ms": round(ttft * 1e3, 3)})
        if seq.phase == "prefill":
            # phase boundary on the request track: prompt ingestion is
            # over the moment the first output token exists. Keyed on
            # seq.phase, not t_first_token — a RESUMED sequence re-runs
            # prefill with its first-token timestamp long stamped
            self.tracer.end("prefill", req=h.request_id,
                            args={"steps": seq.steps})
            self.tracer.begin("decode", req=h.request_id)
            seq.phase = "decode"
            if (self.paged and seq.fork is not None
                    and seq.fork.primary_handle is h
                    and not seq.fork.published):
                self._fork_publish(slot, seq)
        proc = seq.proc
        if proc is not None:
            # stop sequences match across token boundaries (Aho-Corasick
            # over the emitted stream — a stop split across speculative
            # bursts still matches); the matched tokens are truncated
            # OFF the output before the handle finishes
            matched = proc.stop_feed(tok)
            if matched:
                del h.tokens[len(h.tokens) - matched:]
                h.finish_reason = "stop"
                self._retire(slot, seq, now)
                return
        if h.stream is not None:
            # streaming release with stop hold-back: tokens that form a
            # live partial stop match are withheld (flushed by the next
            # mismatch, or discarded by the truncation above) so an SSE
            # client never sees half a stop sequence
            safe = len(h.tokens) - (proc.stop_pending
                                    if proc is not None else 0)
            for idx in range(h.stream.sent, safe):
                h.stream.push(idx, h.tokens[idx])
        if (len(h.tokens) >= h.max_new_tokens
                or (seq.eos_id is not None and tok == seq.eos_id)):
            h.finish_reason = ("eos" if seq.eos_id is not None
                               and tok == seq.eos_id else "length")
            self._retire(slot, seq, now)

    def _retire(self, slot: int, seq: _ActiveSeq,
                now: Optional[float] = None) -> None:
        """Finish + evict one slot-resident sequence — max tokens, EOS,
        stop-sequence match, or grammar completion. The single
        retirement path: publish the prompt's blocks for the next
        prefix sharer, drop pool + mask pins, finish the handle (which
        closes its token stream with the terminal event), free the
        slot."""
        if now is None:
            now = time.monotonic()
        h = seq.handle
        if self.paged:
            # retain the prompt's prefill-written blocks for the next
            # request sharing this prefix (pure ownership transfer: the
            # trie adopts the pages in place), then drop our own pin
            adopted = self._publish_prompt(slot, seq)
            self._release_pool(seq)
            self._release_slot_blocks(slot, seq, keep=adopted)
        self._release_mask(seq)
        h._finish()
        self._trace_done("finish", seq, slot=slot)
        self._m_latency.record(now - h.t_submit)
        self._slots[slot] = None
        ledger_note("engine_slot", h.request_id, -1)
        ledger_check_request(h.request_id, _LEDGER_KINDS)

    def _run_prefill_chunk(self) -> Optional[int]:
        """At most one bounded prefill chunk per iteration (round-robin
        over prefilling slots). Returns the chunked slot index, or None."""
        if not self.prefill_buckets:
            return None
        for off in range(self.n_slots):
            i = (self._prefill_next + off) % self.n_slots
            seq = self._slots[i]
            if seq is None or seq.fed >= len(seq.prompt):
                continue
            bucket, n_real = self._pick_chunk(seq)
            if not n_real:
                continue  # no cache headroom: token-by-token fallback
            if self.paged:
                # lazy allocation + COW happen HERE, host-side, before
                # the program runs: every block the chunk really writes
                # is allocated and exclusively owned by dispatch time
                if not self._ensure_blocks(i, seq, seq.written + n_real) \
                        or not self._ensure_writable(i, seq, seq.written):
                    continue  # seq itself was preempted for blocks
            ids = np.zeros((bucket,), np.int32)
            ids[:n_real] = seq.prompt[seq.fed:seq.fed + n_real]
            failpoints.fire("dispatch.prefill")
            if self.tracer.enabled:  # keep tracing-off allocation-free
                self.tracer.begin("prefill_chunk",
                                  track=self._slot_tracks[i],
                                  args={"request": seq.handle.request_id,
                                        "bucket": bucket, "tokens": n_real})
            up = [self._dev_index(i), self._dev_array(ids),
                  self._dev_index(n_real)]
            if self.paged:
                # table bucket covers the PADDED chunk end so the
                # layer's overflow guard never trips on pad lanes
                up.append(self._dev_array(
                    self._table_for(seq.written + bucket)))
            # stamped after the uploads: the jit call alone lies between
            # this instant and the program's start on the device
            self.profiler.count("prefill", bucket)
            probs, self._states = self._jprefill(
                self._params, self._variables, *up, self._states)
            seq.written += n_real  # host pos mirror (spec fixpos)
            if self.speculate and seq.draft_fed == seq.fed \
                    and self._draft_cap is not None \
                    and seq.draft_fed + bucket <= self._draft_cap:
                # piggyback: the DRAFT ingests the same chunk (it must
                # hold the prompt to propose continuations of it) — one
                # extra shallow dispatch per chunk, the speculation tax
                # on TTFT. A restore-jumped sequence is out of sync
                # (draft_fed < fed) and catches up via
                # _run_draft_catchup instead.
                self.profiler.count("draft_prefill", bucket)
                _, self._draft_states = self._jdraft_prefill(
                    self._draft_params, self._draft_variables,
                    self._dev_index(i), self._dev_array(ids),
                    self._dev_index(n_real), self._draft_states)
                seq.draft_fed += n_real
            seq.fed += n_real
            seq.steps += 1
            self._m_prefill_tokens.inc(n_real)
            self._m_prefill_chunk.record(n_real)
            if seq.sampling:  # final chunk: its output is the first token
                prof = self.profiler
                prof.begin("prefill_wait")
                row, routed = self._unpack_counts(
                    host_read(probs, prof.ready))
                prof.begin("accept")
                if routed is not None:
                    row = row[0]
                    self._note_routing(routed, n_real, decode=False)
                self._consume(i, seq, row)
            self.tracer.end("prefill_chunk", track=self._slot_tracks[i])
            self._prefill_next = (i + 1) % self.n_slots
            return i
        return None

    # -- speculative decoding: draft, verify, accept, roll back ------------
    def _spec_ready(self, seq: _ActiveSeq) -> bool:
        """Can this decode-ready slot speculate THIS iteration? Needs
        the draft within lockstep range (lag 1 after a plain accept, 2
        after a fully-accepted round — anything more is mid-catch-up),
        gamma+1 rows of cache headroom on both nets, and at least 2
        tokens still wanted (the last token is cheapest decoded plain)."""
        G = self.speculate
        h = seq.handle
        lag = seq.known_tokens() - seq.draft_fed
        # lag > G would make every lockstep round a catch-up round and
        # send ZERO proposals to the verify — speculate=1's post-full-
        # accept lag-2 state would pay draft+verify+fixpos per single
        # token forever; decoding plain instead grows lag past 2 and
        # _run_draft_catchup resyncs the draft for the next real round
        if not 1 <= lag <= min(2, G):
            return False
        if h.max_new_tokens - len(h.tokens) < 2:
            return False
        if self._cache_cap is not None and \
                seq.written + G + 1 > self._cache_cap:
            return False
        if self._draft_cap is not None and \
                seq.draft_fed + G > self._draft_cap:
            return False
        return True

    def _run_draft_catchup(self) -> Optional[int]:
        """At most one draft catch-up chunk per iteration: a decode-
        phase sequence whose MAIN cache jumped past tokens the draft
        never ingested (prefix restore, preempt-resume) re-feeds the
        gap through the draft's chunk-prefill program — the draft costs
        ~K/N of a forward, so a restored prefix still keeps most of its
        TTFT win. The slot decodes plain until lag re-enters lockstep
        range."""
        if not self.speculate:
            return None
        for i in range(self.n_slots):
            seq = self._slots[i]
            if seq is None or not seq.sampling:
                continue
            lag = seq.known_tokens() - seq.draft_fed
            if lag <= 2:
                continue
            # target full_len - 1: the LAST token is the lockstep
            # round's feed (its draft output is the first proposal)
            n_real = min(lag - 1, self.prefill_chunk)
            bucket = bucket_for(n_real, self.prefill_buckets)
            if self._draft_cap is not None and \
                    seq.draft_fed + bucket > self._draft_cap:
                fitting = [b for b in self.prefill_buckets
                           if seq.draft_fed + b <= self._draft_cap]
                if not fitting:
                    continue  # no draft headroom: stays plain decode
                bucket = fitting[-1]
                n_real = min(n_real, bucket)
            full = seq.full_context()
            ids = np.zeros((bucket,), np.int32)
            ids[:n_real] = full[seq.draft_fed:seq.draft_fed + n_real]
            self.profiler.count("draft_prefill", bucket)
            _, self._draft_states = self._jdraft_prefill(
                self._draft_params, self._draft_variables,
                self._dev_index(i), self._dev_array(ids),
                self._dev_index(n_real), self._draft_states)
            seq.draft_fed += n_real
            return i
        return None

    def _truncate_blocks(self, slot: int, seq: _ActiveSeq) -> int:
        """Paged rollback: pop the slot's table entries that now sit
        wholly beyond the accepted frontier (verify pre-allocated blocks
        through pos+gamma+1; acceptance may have stopped short) and
        return the owned pages to the pool. Shared (trie-owned) blocks
        never extend past the write frontier, but the guard keeps a
        refcount leak structurally impossible. Returns blocks freed."""
        need = self._blocks_for(seq.written)
        freed = owned = 0
        while len(seq.block_ids) > need:
            bid = seq.block_ids.pop()
            sh = seq.shared.pop()
            self._table[slot, len(seq.block_ids)] = SCRATCH_BLOCK
            if not sh:
                self.pool.free_block(bid)
                owned += 1
            freed += 1
        if owned:
            ledger_note("pool_block", seq.handle.request_id, -owned)
        return freed

    def _run_speculation(self, spec: List[Tuple[int, _ActiveSeq]]) -> None:
        """The speculative iteration for every eligible slot at once:

        1. DRAFT — gamma lockstep rounds of the cheap draft step
           (shallow exit / draft net), each round feeding the previous
           round's greedy output; round r < lag feeds catch-up tokens
           the draft hasn't ingested (lag 2 follows a fully-accepted
           round, where the bonus token was never drafted).
        2. VERIFY — ONE multi-token target forward over all chains
           (`[last_token, d_1..d_g]`, padded to gamma+1), every
           position's distribution retained.
        3. ACCEPT — `speculative.accept_tokens` samples each position
           from the TARGET distribution with the sequence's own RNG and
           keeps the longest draft-confirmed prefix (+1 bonus): output
           is token-identical to solo decode by construction.
        4. ROLL BACK — one fixpos program per net steps pos back over
           the rejected tail; paged mode also truncates the block table
           and returns the freed pages.
        """
        G = self.speculate
        tr = self.tracer
        dp, dv = self._draft_params, self._draft_variables
        info = []
        for i, seq in spec:
            known = seq.known_tokens()
            lag = known - seq.draft_fed
            # the lockstep only feeds the trailing lag (<= 2) tokens —
            # an O(lag) tail, never an O(context) copy per iteration
            info.append((i, seq, known, lag, seq.tail_context(lag), []))
        live = np.zeros((self.n_slots,), bool)
        for i, _seq, _k, _l, _t, _p in info:
            live[i] = True
        ldev = self._dev_array(live)
        # grammar composition: per-slot SPECULATIVE DFA state chain —
        # schain[i][j] is the state after proposals[0..j-1], starting
        # from the pipeline's live state (every emitted token already
        # observed). Drives the per-round draft mask, the per-position
        # verify mask, and the host-exact mask on draft argmax rows.
        schain: Dict[int, List[int]] = {}
        use_mask = False
        for i, seq, _k, _l, _t, _p in info:
            p = seq.proc
            if p is not None and p.grammar is not None:
                schain[i] = [p.gstate]
                if self._jdraft_step_m is not None \
                        and p.mask_base is not None:
                    use_mask = True
        for r in range(G):
            ids = np.zeros((self.n_slots,), np.int32)
            for i, seq, known, lag, tail, props in info:
                ids[i] = tail[r] if r < lag else props[r - lag]
            self.profiler.count("draft", 0)
            if use_mask:
                # the draft proposes under the same mask verify applies:
                # each round gathers the chain-state-so-far's mask row
                mstate = np.zeros((self.n_slots,), np.int32)
                for i, seq, _k, _l, _t, _p in info:
                    p = seq.proc
                    if p is not None and p.mask_base is not None:
                        mstate[i] = p.mask_base + schain[i][-1]
                dprobs, self._draft_states = self._jdraft_step_m(
                    dp, dv, self._dev_array(ids), ldev,
                    self._dev_array(mstate), self._masks,
                    self._draft_states)
            else:
                dprobs, self._draft_states = self._jdraft_step(
                    dp, dv, self._dev_array(ids), ldev,
                    self._draft_states)
            rows = host_read(dprobs)
            for i, seq, known, lag, tail, props in info:
                if r >= lag - 1:  # catch-up rounds' outputs are known
                    # rows is host numpy (the host_read above IS the
                    # sanctioned boundary); this int() syncs nothing
                    row = rows[i]
                    if i in schain:
                        # host-exact mask on the proposal argmax (covers
                        # host-only grammars the device never masked):
                        # softmax rows are >= 0, so -1 can never win
                        g = seq.proc.grammar
                        allow = g.allow[schain[i][-1]]
                        row = np.where(allow, row, -1.0)
                        prop = int(row.argmax())  # graftlint: disable=JG006
                        schain[i].append(g.step(schain[i][-1], prop))
                        props.append(prop)
                        continue
                    props.append(int(row.argmax()))  # graftlint: disable=JG006
        # seam BEFORE any span opens (the decode/prefill seam ordering:
        # an injected crash must not strand unclosed B-events)
        failpoints.fire("dispatch.verify")
        ids2 = np.zeros((self.n_slots, G + 1), np.int32)
        for i, seq, known, lag, tail, props in info:
            chain = [tail[-1]] + props
            chain += [chain[-1]] * (G + 1 - len(chain))  # pad lanes
            ids2[i] = chain
            if tr.enabled:
                tr.instant("draft", track=self._slot_tracks[i],
                           args={"request": seq.handle.request_id,
                                 "proposed": len(props)})
                tr.begin("verify", req=seq.handle.request_id,
                         args={"slot": i, "proposed": len(props)})
        mstate2 = None
        if use_mask:
            # position j's mask = the state after proposals[0..j-1]
            # (exactly what the draft proposed under); pad lanes repeat
            # the last state — their rows are never read
            mstate2 = np.zeros((self.n_slots, G + 1), np.int32)
            for i, seq, _k, _l, _t, props in info:
                p = seq.proc
                if p is not None and p.mask_base is not None:
                    chain = schain[i]
                    padded = chain + [chain[-1]] * (G + 1 - len(chain))
                    mstate2[i] = [p.mask_base + s
                                  for s in padded[:G + 1]]
        if self.paged:
            table = self._table_for(max(s.written + G + 1
                                        for _, s, _k, _l, _t, _p in info))
            self.profiler.count("verify", table.shape[1])
            if mstate2 is not None:
                vprobs, self._states = self._jverify_m(
                    self._params, self._variables, self._dev_array(ids2),
                    ldev, self._dev_array(table),
                    self._dev_array(mstate2), self._masks, self._states)
            else:
                vprobs, self._states = self._jverify(
                    self._params, self._variables, self._dev_array(ids2),
                    ldev, self._dev_array(table), self._states)
        else:
            self.profiler.count("verify", 0)
            if mstate2 is not None:
                vprobs, self._states = self._jverify_m(
                    self._params, self._variables, self._dev_array(ids2),
                    ldev, self._dev_array(mstate2), self._masks,
                    self._states)
            else:
                vprobs, self._states = self._jverify(
                    self._params, self._variables, self._dev_array(ids2),
                    ldev, self._states)
        rows2 = host_read(vprobs)
        posv = np.zeros((self.n_slots,), np.int32)
        dposv = np.zeros((self.n_slots,), np.int32)
        mask = np.zeros((self.n_slots,), bool)
        proposed = accepted = 0
        for i, seq, known, lag, tail, props in info:
            h = seq.handle
            remaining = h.max_new_tokens - len(h.tokens)
            emitted, matched = accept_tokens(
                rows2[i], props, seq.temperature, seq.top_k, seq.top_p,
                seq.rng, remaining, seq.eos_id, proc=seq.proc)
            proposed += len(props)
            accepted += matched
            seq.steps += 1
            seq.written += len(emitted)
            seq.draft_fed = known + min(G - lag, matched)
            for tok in emitted:
                self._emit(i, seq, tok)
            freed = 0
            if self.paged and self._slots[i] is seq:
                freed = self._truncate_blocks(i, seq)
            mask[i] = True
            posv[i] = seq.written
            dposv[i] = seq.draft_fed
            if tr.enabled:
                tr.end("verify", req=h.request_id,
                       args={"accepted": len(emitted),
                             "matched": matched})
                if len(emitted) < len(props) + 1:
                    tr.instant(
                        "rollback", track=self._slot_tracks[i],
                        args={"request": h.request_id,
                              "rejected": len(props) + 1 - len(emitted),
                              "blocks_freed": freed})
        mdev = self._dev_array(mask)
        self._states = self._jfixpos(self._states,
                                     self._dev_array(posv), mdev)
        self._draft_states = self._jdraft_fixpos(
            self._draft_states, self._dev_array(dposv), mdev)
        self._m_spec_proposed.inc(proposed)
        if accepted:
            self._m_spec_accepted.inc(accepted)

    # -- KV tiering (kvtier.py, ISSUE 19) ----------------------------------
    def _tier_tick(self) -> None:
        """Per-iteration tier maintenance on the scheduler thread: grant
        the worker its pacing credits, serve pending HBM copydowns
        (peer fetches), integrate promotions the worker staged, and
        upgrade mid-prefill slots onto newly resident blocks. Every
        step is bounded — the decode hot path never waits on a
        transfer; an un-landed promotion just means the slot keeps
        prefilling its cold suffix as today."""
        tier = self.tier
        idle = all(s is None for s in self._slots)
        # idle iterations run at the 10 Hz wake; grant a bigger budget
        # so a backlog drains fast when nobody is decoding
        grant = self._tier_chunk * (8 if idle else 1)
        tier.pace(grant)
        for h in tier.pending_copydowns(4):
            self._tier_copydown(h)
        promoted = False
        for entry, rows in tier.drain_ready(grant):
            promoted = self._integrate_promotion(entry, rows) or promoted
        if promoted:
            self._try_upgrade_slots()

    def _tier_copydown(self, h: str) -> None:
        """Capture an HBM-resident chain block into the host ring (no
        eviction) so /prefix/block can serve it to a peer."""
        tier = self.tier
        info = tier.entry_info(h)
        if info is None:
            return
        prefix, depth = info
        node, ids = self.pool._walk_prefix(list(prefix), depth)
        if len(ids) != depth or node.hash != h:
            return  # no longer resident; waiter times out / uses a tier
        tier.complete_copydown(h, self._tier_capture(node.block_id))

    def _integrate_promotion(self, entry, rows) -> bool:
        """Upload one promoted page row and adopt it into the trie via
        the zero-copy publish path. Any failure — injected fault, no
        free page, parent chain gone — drops the promotion; the prefix
        recomputes cold (correct, just slower)."""
        tier = self.tier
        tokens = list(entry.prefix)
        depth = int(entry.depth)
        node, ids = self.pool._walk_prefix(tokens, depth)
        if len(ids) == depth:
            tier.promotion_done(entry.hash, True)  # already resident
            return False
        if len(ids) != depth - 1:
            tier.promotion_done(entry.hash, False)  # parents not landed
            return False
        bid = self.pool.alloc()
        if bid is None:
            # pool fully referenced: promotion must never preempt live
            # work — drop it, the hot path wins
            tier.promotion_done(entry.hash, False)
            return False
        try:
            dev_rows = {
                lk: {pk: self._dev_array(a) for pk, a in pks.items()}
                for lk, pks in rows.items()}
            self._states = self._jtier_restore(  # graftlint: disable=CC005
                self._states, self._dev_index(bid), dev_rows)
        except Exception:
            self.pool.free_block(bid)
            tier.promotion_done(entry.hash, False)
            raise
        # zero-copy adopt: the trie takes over the freshly-written page
        # (note_resident fires inside, flipping the directory tier)
        self.pool.adopt(tokens, ids + [bid])
        tier.promotion_done(entry.hash, True)
        self._m_tier_promoted.inc()
        if self.tracer.enabled:
            self.tracer.instant(
                "tier_restore", track="scheduler",
                args={"hash": entry.hash[:12], "depth": depth,
                      "block": bid})
        return True

    def _try_upgrade_slots(self) -> None:
        """Re-match mid-prefill slots against the trie after promotions
        landed: a slot whose cold suffix just became resident swaps its
        pin to the deeper node, remaps its table onto the shared
        blocks, and jumps ``pos`` past them — the restore-in-flight
        contract: prefill as usual until the pages land, then skip."""
        B = self.kv_block
        for i, seq in enumerate(self._slots):
            if seq is None or seq.fed >= len(seq.prompt) \
                    or seq.cow_starved:
                continue
            max_hit = len(seq.prompt) // B
            cur = seq.fed // B
            if max_hit <= cur:
                continue
            n2, ids2, node2 = self.pool.match(seq.prompt, max_hit)
            if node2 is None:
                continue
            if n2 * B <= seq.fed:
                self.pool.release(node2)
                continue
            rid = seq.handle.request_id
            if seq.pool_node is not None:
                self.pool.release(seq.pool_node)
                seq.pool_node = None
            else:
                ledger_note("trie_pin", rid, +1)
            seq.pool_node = node2
            freed = 0
            for j in range(cur, n2):
                bid2 = ids2[j]  # host ints from the trie walk
                if j < len(seq.block_ids):
                    if not seq.shared[j] \
                            and seq.block_ids[j] != bid2:
                        self.pool.free_block(seq.block_ids[j])
                        freed += 1
                    seq.block_ids[j] = bid2
                    seq.shared[j] = True
                else:
                    seq.block_ids.append(bid2)
                    seq.shared.append(True)
                self._table[i, j] = ids2[j]  # graftlint: disable=CC005
            if freed:
                ledger_note("pool_block", rid, -freed)
            fed = min(n2 * B, len(seq.prompt) - 1)
            gained = fed - seq.fed
            self._states = self._jsetpos(  # graftlint: disable=CC005
                self._states, self._dev_index(i), self._dev_index(fed))
            seq.fed = fed
            seq.written = fed
            self._m_tier_tokens.inc(gained)
            self._m_prefix_hits.inc()
            if self.tracer.enabled:
                self.tracer.instant(
                    "tier_restore", track=self._slot_tracks[i],
                    args={"request": rid, "tokens": gained,
                          "blocks": n2 - cur})

    def _step_once(self) -> bool:
        """One scheduler iteration (admission + at most one prefill chunk
        + the all-slots decode step). Returns False when it idled.

        Host<->device discipline: the ONLY blocking device reads are the
        two `host_read` calls (next-token distributions — the sampled
        token must reach the host to be fed back); everything else ships
        to device explicitly (`jnp.asarray` of ndarrays, `device_index`).
        Metric counters are flushed once per iteration, not per token."""
        if self._fenced:
            raise _EngineFenced
        failpoints.fire("scheduler.iteration")
        prof = self.profiler
        # a pass with no slot held and nothing queued will idle: the
        # trace gets no sched_iter for it. The unlocked look at _queue is
        # one GIL-atomic truth test, and a submit() that lands after it
        # costs that iteration its admit annotation and nothing else
        prof.iter_begin(annotate=any(s is not None for s in self._slots)
                        or bool(self._queue))  # graftlint: disable=CC005
        self._evict_cancelled()
        if self.tier is not None:
            # pace the tier worker and integrate landed promotions
            # BEFORE admission, so an arriving prompt can match blocks
            # promoted this very iteration. Runs on idle passes too
            # (the 10 Hz idle wake in _loop) so spills/promotions drain
            # while the engine has nothing else to do.
            self._tier_tick()
        self._admit()
        # single-writer: _slots is mutated only by this scheduler thread
        # once start() returns (submit() touches only _queue, under
        # _cond); stop() joins the thread before its own sweep
        active = [(i, s) for i, s in enumerate(self._slots)  # graftlint: disable=CC004
                  if s is not None]
        if not active:
            prof.iter_abandon()
            return False  # idle pass: no phase recorded (a 10 Hz idle
            # wake stamping µs admit phases would swamp the histograms)
        prof.begin("prefill_launch")
        t0 = time.monotonic()
        self._emitted_this_iter = 0
        chunked = self._run_prefill_chunk()
        prof.begin("draft")
        self._run_draft_catchup()
        prof.begin("pool")
        # decode step: every decode-ready slot, plus token-by-token
        # prefill for slots chunked prefill cannot serve (disabled, or
        # no bucket fits the remaining cache headroom). With speculation
        # armed, eligible slots ride the draft+verify path (`spec`)
        # instead of the single-token program; the rest — mid-catch-up,
        # out of gamma+1 headroom, one token from done — decode plain.
        fed: List[Tuple[int, _ActiveSeq]] = []
        spec: List[Tuple[int, _ActiveSeq]] = []
        G = self.speculate
        # oldest-first (same t_submit key as _pick_victim): a
        # pool-pressure preemption always victimizes the LATEST-submitted
        # slot, which is processed last here — so an already-vetted
        # candidate can never lose its blocks to a later one's allocation
        # (its removal would leave a stale fed entry writing into freed
        # pages)
        cands = sorted(active, key=lambda e: e[1].handle.t_submit)
        for i, seq in cands:
            if self._slots[i] is not seq or i == chunked:
                continue  # evicted/preempted above / consumed its turn
            if seq.sampling and seq.proc is not None \
                    and seq.proc.exhausted():
                # the grammar admits nothing more: the structured output
                # is COMPLETE — finish before any dispatch (sampling an
                # all-forbidden row has no meaning)
                seq.handle.finish_reason = "grammar"
                self._retire(i, seq)
                continue
            if not seq.sampling and self.prefill_buckets \
                    and self._pick_chunk(seq)[1]:
                continue  # mid-prefill: waits for its chunk turn
            want = G + 1 if G and seq.sampling and self._spec_ready(seq) \
                else 1
            if self.paged:
                if not self._ensure_blocks(i, seq, seq.written + want) \
                        or not self._ensure_writable(i, seq, seq.written):
                    continue  # seq itself was preempted for blocks
            (spec if want > 1 else fed).append((i, seq))
        prof.begin("decode_launch")
        if fed:
            ids = np.zeros((self.n_slots,), np.int32)
            live = np.zeros((self.n_slots,), bool)
            for i, seq in fed:
                ids[i] = seq.next_input()
                live[i] = True
            # masked dispatch only when a DEVICE-RESIDENT grammar is in
            # the batch: pure unconstrained traffic (and host-only
            # fallback grammars) keeps the original program — the
            # single jitted decode program survives constrained serving
            mstate = None
            if self._masks is not None:
                for i, seq in fed:
                    p = seq.proc
                    if p is not None and p.mask_base is not None:
                        if mstate is None:
                            mstate = np.zeros((self.n_slots,), np.int32)
                        # unconstrained slots stay at row 0 (all zeros)
                        mstate[i] = p.mask_base + p.gstate
            failpoints.fire("dispatch.decode")
            if self.tracer.enabled:  # keep tracing-off allocation-free
                self.tracer.begin("decode_step", track=self._sched_track,
                                  args={"live_slots": len(fed)})
            up = [self._dev_array(ids), self._dev_array(live)]
            if self.paged:
                table = self._table_for(max(s.written + 1
                                            for _, s in fed))
                nb = table.shape[1]
                named = self.n_slots * self._pages_listed(nb)
                self._m_pages_bucket.inc(named)
                self._m_pages_read.inc(sum(
                    self._pages_with_rows(s.written) for _, s in fed)
                    if self._fused_read[nb] else named)
                if self._eva is not None:
                    window, chunk = self._eva
                    at = [s.written for _, s in fed if s.sampling]
                    self._m_eva_rows_exact.inc(
                        sum(t % window + 1 for t in at))
                    self._m_eva_rows_summary.inc(
                        sum(t // window for t in at) * (window // chunk))
                if self._latent:
                    self._m_mla_rows.inc(
                        sum(s.written + 1 for _, s in fed if s.sampling))
                if self._ssm:
                    self._m_ssm_bucket.inc(self.n_slots)
                    self._m_ssm_stepped.inc(self.n_slots)
                up.append(self._dev_array(table))
            else:
                nb = 0
            if mstate is not None:
                up += [self._dev_array(mstate), self._masks]
            # stamped after the uploads: the jit call alone lies between
            # this instant and the program's start on the device
            prof.count("decode", nb)
            probs, new_states = (self._jstep if mstate is None
                                 else self._jstep_m)(
                self._params, self._variables, *up, self._states)
            self._states = new_states
            prof.begin("decode_wait")
            probs, routed = self._unpack_counts(
                host_read(probs, prof.ready))
            prof.begin("accept")
            if routed is not None:
                self._note_routing(routed, len(fed), decode=True)
            for i, seq in fed:
                seq.steps += 1
                seq.written += 1
                was_sampling = seq.sampling
                if seq.fed < len(seq.prompt):
                    seq.fed += 1
                if not was_sampling and not seq.sampling:
                    continue  # still prefilling; output not sampled yet
                self._consume(i, seq, probs[i])
            self.tracer.end("decode_step", track=self._sched_track)
        prof.begin("verify")
        if spec:
            self._run_speculation(spec)
        prof.begin("flush")
        if self._emitted_this_iter:
            self._m_tokens.inc(self._emitted_this_iter)
        self._m_occupancy.record(len(active))
        self._m_step_time.record(time.monotonic() - t0)
        self._trace_compiles()
        prof.iter_end(tokens=self._emitted_this_iter)
        return True

    def _note_routing(self, counts: np.ndarray, tokens: int,
                      decode: bool) -> None:
        """One dispatch's routing counts (`_unpack_counts`) into the
        ``moe_*`` counters: ``tokens`` went through every routed layer. A
        prefill chunk that is not its prompt's last is not read and so not
        counted; the share of experts hit is the decode dispatches'."""
        self._m_moe_routed.inc(tokens * self._moe_top_k * len(self._moe))
        # `counts` is a HOST array (host_read brought it): no device sync
        self._m_moe_held.inc(int(counts.sum()))  # graftlint: disable=JG006
        if decode:
            self._m_moe_slots.inc(counts.size)
            self._m_moe_hit.inc(int((counts > 0).sum()))  # graftlint: disable=JG006
            # the visits the kernel's grid made, by its own function
            self._m_moe_passes.inc(int(tile_visits(  # graftlint: disable=JG006
                counts, row_tile(self.n_slots * self._moe_top_k)).sum()))

    def _trace_compiles(self) -> None:
        """Instant event per NEW XLA program: the per-family jit-cache
        sizes (CompileCounter, the same counters the recompile-budget
        tests assert) are polled once per iteration; growth means this
        iteration paid a compile — stamped on the timeline so a
        seconds-long TTFT outlier is attributable to the bucket that
        compiled under it."""
        if not self.tracer.enabled:
            return
        for fam, n in self._compile_counter.counts().items():
            if n > self._compile_seen.get(fam, 0):
                self._compile_seen[fam] = n
                self.tracer.instant("compile", track=self._sched_track,
                                    args={"family": fam, "programs": n})

    def _loop(self) -> None:
        while True:
            self.heartbeat = time.monotonic()
            with self._cond:
                if not self._running:
                    return  # stop() fails any still-active handles
            guard = (jax.transfer_guard(self._transfer_guard)
                     if self._transfer_guard else contextlib.nullcontext())
            try:
                with guard:
                    stepped = self._step_once()
            except _EngineFenced:
                return  # a supervisor already disowned this engine
            except Exception as e:
                # loop death used to be SILENT: the daemon thread
                # evaporated, the HTTP tier kept admitting, and every
                # in-flight caller blocked out its full timeout. Now the
                # crash is recorded (self.crashed), traced, and either
                # handed to the supervisor (which requeues the in-flight
                # work onto a rebuilt engine) or failed fast
                self._crash(e)
                return
            # single-writer int bump; lock-free readers (the watchdog's
            # warmup-grace check, debug_snapshot) take a GIL-atomic
            # value one iteration stale at worst — the documented
            # diagnostics-read contract
            self.iterations += 1  # graftlint: disable=CC005
            if not stepped:
                # idle pass: decay the rate gauges (iter_end never runs
                # here, and frozen gauges would report the last burst's
                # tokens/s and MFU on an hour-idle engine)
                self.profiler.idle_tick()
                with self._cond:
                    if not self._running:
                        return
                    if not self._queue:
                        with self.profiler.idle():
                            self._cond.wait(timeout=0.1)

    # -- crash / fence / degradation surface (inference/supervisor.py) ----
    def _crash(self, exc: BaseException) -> None:
        """Terminal bookkeeping on the dying loop thread. Supervised
        (`_on_crash` set): handles stay OPEN — the supervisor owns them
        now and will requeue each onto the rebuilt engine (their callers
        never see the crash). Unsupervised: fail every in-flight and
        queued handle fast with EngineCrashedError instead of leaving
        the callers to block out their timeouts against a dead loop."""
        if self._fenced:
            return  # already declared dead and disowned; nothing to own
        self.crashed = exc
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self.tracer.enabled:
            self.tracer.instant(
                "engine_crash", track=self._sched_track,
                args={"error": type(exc).__name__,
                      "detail": str(exc)[:200],
                      "iterations": self.iterations})
        if self._on_crash is not None:
            self._close_request_spans()
            # supervised crash: the handles stay open and the supervisor
            # requeues them — this engine's per-request resource debt
            # dies with its pool, so the ledger disowns it (the
            # replacement engine re-acquires under the same request ids
            # from a clean balance)
            for seq in self._slots:  # graftlint: disable=CC004
                if seq is not None:
                    ledger_forget(seq.handle.request_id, _LEDGER_KINDS)
            with self._cond:
                queued = self._queue[:]
            for seq in queued:
                ledger_forget(seq.handle.request_id, _LEDGER_KINDS)
            self._on_crash(exc)
        else:
            self._fail_all_inflight(EngineCrashedError(
                f"decode engine crashed: {type(exc).__name__}: {exc}"))

    def fence(self) -> None:
        """Disown this engine: a supervisor that declared it dead (hung
        heartbeat) fences it BEFORE requeueing its in-flight work onto a
        replacement — if the stuck loop thread ever wakes, it sees the
        fence at its next iteration boundary (and `_consume` refuses to
        touch handles) and exits instead of double-finishing requests
        the new engine now owns. The residual window — a thread awake
        and past the fence checks at the exact fencing instant — is one
        iteration wide; the supervisor additionally joins the thread
        with a grace timeout before resubmitting.

        The fence flag is DELIBERATELY a lock-free GIL-atomic bool: the
        hung loop thread it must reach may be stuck inside an XLA
        dispatch and can never be required to take a lock to learn it
        was disowned; the one-iteration staleness window is the
        documented contract."""
        self._fenced = True  # graftlint: disable=CC005
        with self._cond:
            self._running = False
            self._cond.notify_all()

    def _fail_all_inflight(self, exc: BaseException) -> None:
        """Fail every queued + slot-resident handle (crash path, loop
        thread — the only other `_slots` writer is this thread)."""
        with self._cond:
            pending = self._queue[:]
            self._queue.clear()
            self._m_queue_depth.set(0)
        for seq in pending:
            seq.handle._finish(exc)
            self._trace_done("cancel", seq)
        for i, seq in enumerate(self._slots):  # graftlint: disable=CC004
            if seq is not None:
                if self.paged:
                    self._release_pool(seq)
                    self._release_slot_blocks(i, seq)
                self._release_mask(seq)
                seq.handle._finish(exc)
                self._trace_done("cancel", seq, slot=i)
                self._slots[i] = None
                ledger_note("engine_slot", seq.handle.request_id, -1)
                ledger_check_request(seq.handle.request_id,
                                     _LEDGER_KINDS)
        self._m_active.set(0)

    def _close_request_spans(self) -> None:
        """Close every in-flight request's open phase span WITHOUT
        finishing its handle (supervised crash: the request lives on —
        the supervisor opens a `recovered` span bridging the gap until
        the resubmission's fresh `queued` begins)."""
        if not self.tracer.enabled:
            return
        with self._cond:
            seqs = self._queue[:]
        seqs += [s for s in self._slots if s is not None]  # graftlint: disable=CC004
        for seq in seqs:
            self._close_phase_span(seq)

    def inflight(self) -> int:
        """Queued + slot-resident request count (the drain condition)."""
        with self._cond:
            n = len(self._queue)
        return n + sum(s is not None for s in self._slots)  # graftlint: disable=CC004

    def queue_depth(self) -> int:
        """Waiting (not yet admitted) request count — the degradation
        ladder's pressure signal."""
        with self._cond:
            return len(self._queue)

    def warmup(self, masks: Optional[bool] = None) -> None:
        """Compile every program family up front by invoking each jitted
        callable once per bucket shape. Nothing observable changes — no
        metrics, no trace records, no pool state, no slot bookkeeping —
        but the carried state IS rebound from every call: the programs
        donate it (the rule at ``_jstep``'s construction), so a result
        thrown away would leave ``_states`` / ``_draft_states`` pointing
        at deleted buffers. The arguments make each program the identity
        on live data (all-masked ``live``, scratch table, chunks of no
        real token, scratch -> scratch copy-on-write and tier round
        trip, ``nomask`` fixpos). ``_jzero(slot0)``, ``_jsetpos(slot0,
        0)`` and, in the contiguous layout, the chunk's padded rows are
        NOT the identity: they write, then zero, slot 0's rows. That is
        harmless only because warm-up runs
        with no slot admitted (construction / recovery / drain-swap
        windows the supervisor owns) and admission zeroes a slot before
        its first use.

        ``masks``: also warm the GRAMMAR-MASKED program variants
        (masked decode/verify/draft + the mask-upload family). Default
        (None) warms them only when grammars are already resident —
        unconstrained serving must not pay the near-2x warmup of a
        family it never dispatches (supervisor rebuilds run this inside
        the recovery window). A deployment expecting constrained
        traffic warms eagerly with ``warmup(masks=True)``; otherwise
        the first constrained dispatch pays one bounded lazy compile
        per family member, exactly like a cold chunk bucket.

        Why this exists: a rebuilt engine's jit caches start empty, and
        first-call compiles block the scheduler loop mid-iteration —
        exactly the heartbeat stall a tight supervisor watchdog reads
        as a hang. The supervisor warms every engine it spawns INSIDE
        the recovery/drain window it already owns, so post-swap traffic
        runs on hot caches and the watchdog judges only real stalls."""
        params, variables = self._params, self._variables
        # args go through the SAME placement helpers as live dispatch
        # (placement is part of the jit cache key: a warmup that placed
        # differently would compile a parallel family and blow budgets)
        ids = self._dev_array(np.zeros((self.n_slots,), np.int32))
        # all-masked: every slot's state transition is frozen in-program
        # (and paged writes redirect to the scratch page), so the
        # rebound state holds what it held
        live = self._dev_array(np.zeros((self.n_slots,), bool))
        slot0 = self._dev_index(0)
        # the warm-up chunks have NO real token (n_real = 0, a traced
        # value: same programs): every lane is padding, so a paged chunk
        # writes zeros to the scratch page and leaves ``pos`` where it
        # was. A real lane of a chunk wider than its table bucket (this
        # sweep of all pairs has them, and they overflow by design)
        # would write NaN rows to the scratch page, which every later
        # step gathers
        no_real = slot0  # the same [0]
        if self.paged:
            for nb in self.table_buckets:
                table = self._dev_array(np.full(
                    (self.n_slots, nb), SCRATCH_BLOCK, np.int32))
                _, self._states = self._jstep(
                    params, variables, ids, live, table, self._states)
            # the FULL budgeted prefill family: one program per (chunk
            # bucket, table bucket) pair — live dispatch selects the
            # table bucket from the slot's DEPTH (`_table_for(written +
            # bucket)`), so a multi-chunk prompt's later chunks use
            # wider tables than its first; warming only the depth-0
            # pair would leave those to compile mid-iteration after a
            # swap, when the watchdog no longer extends warmup grace
            for b in self.prefill_buckets:
                for nb in self.table_buckets:
                    table = self._dev_array(np.full(
                        (self.n_slots, nb), SCRATCH_BLOCK, np.int32))
                    _, self._states = self._jprefill(
                        params, variables, slot0,
                        self._dev_array(np.zeros((b,), np.int32)),
                        no_real, table, self._states)
            self._states = self._jsetpos(self._states, slot0, slot0)
            if self._jsumtab is not None:  # slot 0, block 0 -> scratch
                self._states = self._jsumtab(self._states, slot0, slot0,
                                             slot0)
            self._states = self._jcow(
                self._states, self._dev_index(SCRATCH_BLOCK),
                self._dev_index(SCRATCH_BLOCK))
            if self.tier is not None:
                # tier spill/restore: warm with the scratch row, fed
                # back through np.asarray + _dev_array — the EXACT
                # structure/dtypes/placement the live path uses (worker
                # device-get, scheduler upload), so one program each
                scratch = self._dev_index(SCRATCH_BLOCK)
                dev = self._jtier_spill(self._states, scratch)
                rows = {lk: {pk: self._dev_array(np.asarray(a))
                             for pk, a in pks.items()}
                        for lk, pks in dev.items()}
                self._states = self._jtier_restore(self._states, scratch,
                                                   rows)
        else:
            _, self._states = self._jstep(params, variables, ids, live,
                                          self._states)
            for b in self.prefill_buckets:
                _, self._states = self._jprefill(
                    params, variables, slot0,
                    self._dev_array(np.zeros((b,), np.int32)),
                    no_real, self._states)
        self._states = self._jzero(self._states, slot0)
        if masks is None:
            masks = (self.maskpool is not None
                     and self.maskpool.resident_rows() > 0)
        if masks and self._masks is not None:
            # masked-decode family: one program per table bucket, like
            # decode — a constrained request after a supervisor swap
            # must not pay this compile mid-iteration
            mstate0 = self._dev_array(np.zeros((self.n_slots,), np.int32))
            if self.paged:
                for nb in self.table_buckets:
                    table = self._dev_array(np.full(
                        (self.n_slots, nb), SCRATCH_BLOCK, np.int32))
                    _, self._states = self._jstep_m(
                        params, variables, ids, live, table, mstate0,
                        self._masks, self._states)
            else:
                _, self._states = self._jstep_m(
                    params, variables, ids, live, mstate0, self._masks,
                    self._states)
            if self.maskpool.resident_rows() == 0:
                # upload family (pure writes of zeros = admit-all rows).
                # Guarded: on a warm engine that already holds resident
                # grammar tables, re-zeroing rows [0, bucket) would
                # corrupt them — and those engines compiled the family
                # long ago anyway
                for b in self.mask_buckets:
                    self._masks = self._jmask_upload(
                        self._masks, slot0,
                        self._dev_array(np.zeros(
                            (b, self.vocab_size), np.dtype(self._dtype))))
        if self.speculate:
            # speculation's program family: the multi-token verify (per
            # table bucket in paged mode, like decode), the draft's
            # step/prefill/zero, and both fixpos rollback programs —
            # a rebuilt engine must not pay these compiles under traffic
            ids2 = self._dev_array(
                np.zeros((self.n_slots, self.speculate + 1), np.int32))
            if self.paged:
                for nb in self.table_buckets:
                    table = self._dev_array(np.full(
                        (self.n_slots, nb), SCRATCH_BLOCK, np.int32))
                    _, self._states = self._jverify(
                        params, variables, ids2, live, table, self._states)
            else:
                _, self._states = self._jverify(params, variables, ids2,
                                                live, self._states)
            dp, dv = self._draft_params, self._draft_variables
            _, self._draft_states = self._jdraft_step(
                dp, dv, ids, live, self._draft_states)
            if masks and self._jverify_m is not None:
                # speculation x grammar composition: the masked verify
                # mirrors verify's table bucketing, the masked draft
                # step is a singleton
                mstate0 = self._dev_array(np.zeros((self.n_slots,),
                                                   np.int32))
                mstate2 = self._dev_array(np.zeros(
                    (self.n_slots, self.speculate + 1), np.int32))
                if self.paged:
                    for nb in self.table_buckets:
                        table = self._dev_array(np.full(
                            (self.n_slots, nb), SCRATCH_BLOCK, np.int32))
                        _, self._states = self._jverify_m(
                            params, variables, ids2, live, table, mstate2,
                            self._masks, self._states)
                else:
                    _, self._states = self._jverify_m(
                        params, variables, ids2, live, mstate2,
                        self._masks, self._states)
                _, self._draft_states = self._jdraft_step_m(
                    dp, dv, ids, live, mstate0, self._masks,
                    self._draft_states)
            for b in self.prefill_buckets:
                _, self._draft_states = self._jdraft_prefill(
                    dp, dv, slot0,
                    self._dev_array(np.zeros((b,), np.int32)), no_real,
                    self._draft_states)
            self._draft_states = self._jdraft_zero(self._draft_states,
                                                   slot0)
            posv = self._dev_array(np.zeros((self.n_slots,), np.int32))
            nomask = self._dev_array(np.zeros((self.n_slots,), bool))
            self._states = self._jfixpos(self._states, posv, nomask)
            self._draft_states = self._jdraft_fixpos(
                self._draft_states, posv, nomask)
        if self.paged:
            # the bucket loop above traced every decode program through
            # the paged_decode_attention seam, so the kernel variant is
            # compiled (and, in "auto", autotuned) INSIDE the same
            # per-bucket program family — CompileCounter budgets are
            # unchanged and a supervisor rebuild+warmup never pays a
            # kernel compile under traffic. Refresh the engagement gauge
            # now that every bucket has a verdict.
            self.paged_kernel_status()
        if self.profiler.enabled and not self.profiler.costs:
            # a REBUILT engine (supervisor crash recovery / drain swap
            # over the same net) re-ingests the process-wide cached
            # cost table here for free, so post-recovery traffic gets
            # MFU attribution immediately. The FIRST computation is
            # deliberately lazy (first /debug/engine read, bench, or an
            # explicit attribute_costs()) — tracing the whole program
            # family for cost analysis costs seconds on many-bucket
            # paged engines, and warmup's job is keeping the recovery
            # window tight, not paying optional analysis up front.
            from .profiler import cached_program_costs
            cached = cached_program_costs(self)
            if cached:
                self.profiler.ingest_costs(cached)

    def attribute_costs(self) -> None:
        """Lower every program family through the XLA cost model
        (`profiler.program_costs` — the AOT ``.lower()`` path, which
        never touches the jit call caches, so CompileCounter budgets
        are unaffected) and hand the per-invocation FLOPs/bytes table
        to the step-phase profiler. Computed once per (net, engine
        shape) process-wide; rebuilt engines re-ingest the cached table
        at warmup. Called lazily from :meth:`debug_snapshot`, eagerly
        by the bench and anyone who wants MFU before the first debug
        read. Best-effort: a backend without a cost model just leaves
        MFU at 0, it never breaks serving."""
        if not self.profiler.enabled:
            return
        with self._attr_lock:  # one tracer; losers reuse its table
            if self.profiler.costs or self._attr_failed:
                return
            try:
                self.profiler.ingest_costs(program_costs(self))
            except Exception as e:
                # memoized: /debug/engine is a POLL endpoint, and
                # re-tracing the whole family per poll only to fail
                # again would cost seconds of CPU forever
                self._attr_failed = True
                if self.tracer.enabled:
                    self.tracer.instant(
                        "cost_attribution_skipped",
                        track=self._sched_track,
                        args={"error": type(e).__name__,
                              "detail": str(e)[:200]})

    def paged_kernel_status(self) -> dict:
        """Fused-decode-kernel engagement view (ISSUE 15): the mode
        knob, whether ANY decode table bucket traced through the Pallas
        kernel, and the per-bucket verdict — the kernel's grid variant
        where it engaged, False where the trace fell back to XLA, None
        for buckets not traced yet (warmup() traces every bucket, so a
        warmed engine never shows None). A False verdict is qualified,
        so that "XLA won the race" is not the only reading of it:
        ``refused`` maps a bucket to the compiler's message when every
        kernel candidate RAISED in the autotune probe, ``declined`` says
        why the seam never offered the kernel at all (a sub-float32
        compute dtype), and ``execution`` says whether a kernel that
        does engage is Mosaic-compiled or running in the Pallas
        interpreter. Read-side only: consults the ops/pallas_kernels
        trace-time registries, never triggers a compile or a probe."""
        out = {"mode": self.paged_kernel, "engaged": False,
               "buckets": {}, "refused": {}, "declined": None,
               "execution": None}
        if not self.paged:
            return out
        if self._eva is not None or any(self._fused_read.values()):
            # the T=1 read is `ops.paged_read`, engaged by the layer's
            # static rule and not through the seam's registry (a bucket
            # whose page list is beyond the kernel's SMEM gathers)
            out["buckets"] = {nb: "paged_read" if on else False
                              for nb, on in self._fused_read.items()}
            out["engaged"] = any(self._fused_read.values())
            if out["engaged"]:
                out["execution"] = ("compiled" if jax.default_backend()
                                    == "tpu" else "interpreted")
            self._m_paged_kernel.set(1 if out["engaged"] else 0)
            return out
        from ..ops import helpers as ophelpers
        if (self.paged_kernel == "off"
                or ophelpers.get_helper("paged_decode_attention") is None):
            out["buckets"] = {nb: False for nb in self.table_buckets}
            return out
        from ..ops.pallas_kernels import (autotune_refusals,
                                          kernel_execution,
                                          paged_decode_decisions)
        out["execution"] = kernel_execution()
        if jnp.dtype(self._dtype) != jnp.float32:
            out["declined"] = (f"compute dtype {jnp.dtype(self._dtype).name}"
                               " (the kernel is float32-only)")
        dec = paged_decode_decisions()
        refusals = autotune_refusals()
        # match THIS engine's traces exactly: batch/table/block dims,
        # the per-shard head geometry of its own attention layers,
        # compute dtype, int8-ness, AND its mode — the registry is
        # process-global, and a co-resident engine over different
        # shapes or another mode must not color these verdicts
        dt = jnp.dtype(self._dtype).name
        quant = self.kv_dtype == "int8"
        heads = set()
        for _, impl in self._impl_items():
            if _keeps_pages(impl):
                H = int(impl.conf.n_heads)
                heads.add((impl._kv_heads() // self.tp, H // self.tp,
                           impl._head_dim()))
        for nb in self.table_buckets:
            hits = [v for k, v in dec.items()
                    if k[0] == self.n_slots and k[1] == nb
                    and k[2] == self.kv_block and k[3:6] in heads
                    and k[6] == dt and k[7] == quant
                    and k[8] == self.paged_kernel]
            engaged = [v for v in hits if v]
            out["buckets"][nb] = (engaged[0] if engaged
                                  else (False if hits else None))
            if hits and not engaged:
                # XLA by default, not by victory: every kernel
                # candidate raised ("xla" keys the reference's own
                # failure, which is not a kernel refusal)
                for hk in heads:
                    why = {c: r for c, r in refusals.get(
                        ("paged_decode", self.n_slots, nb, self.kv_block)
                        + hk + (dt, quant), {}).items() if c != "xla"}
                    if why:
                        out["refused"][nb] = "; ".join(
                            f"{c}: {r}" for c, r in sorted(why.items()))
        out["engaged"] = any(bool(v) for v in out["buckets"].values())
        if getattr(self, "_m_paged_kernel", None) is not None:
            self._m_paged_kernel.set(1 if out["engaged"] else 0)
        return out

    def debug_snapshot(self) -> dict:
        """`GET /debug/engine`: one JSON view of the engine's live
        anatomy — slot table, queue, block-pool occupancy + trie stats,
        compile-cache census, speculative acceptance, mesh topology,
        per-family program costs and the rolling MFU/tokens-per-second
        estimates, and the step-phase decomposition.

        Read-side contract: called from HTTP handler threads against
        scheduler-thread-owned state, every read is a GIL-atomic
        ref/scalar load and the view is tolerant of being one iteration
        stale (the same discipline as `inflight()` and the supervisor's
        `status()`); the pool's trie walk is guarded because the
        scheduler may grow the trie mid-iteration."""
        slots = []
        for i, seq in enumerate(list(self._slots)):  # graftlint: disable=CC004,CC005
            if seq is None:
                slots.append(None)
                continue
            h = seq.handle
            slots.append({
                "slot": i, "request_id": h.request_id,
                "phase": seq.phase,
                "prompt_tokens": len(seq.prompt),
                "fed": seq.fed, "written": seq.written,
                "tokens_out": len(h.tokens),
                "max_new_tokens": h.max_new_tokens,
                "blocks": seq.blocks_held,
                "resumed": seq.resumed,
            })
        out = {
            "n_slots": self.n_slots,
            "paged": self.paged,
            "iterations": self.iterations,
            "queue_depth": self.queue_depth(),
            "slots": slots,
            "compile_cache": self._compile_counter.counts(),
            "mesh": {"tp": self.tp},
            "prefill_buckets": list(self.prefill_buckets),
            "chunk_cap": self.chunk_cap,
        }
        if self._ssm:
            # beside the pool's budget, not inside it
            out["slot_state"] = {"layers": len(self._ssm),
                                 "bytes_per_slot": self.slot_state_bytes,
                                 "bytes": self.slot_state_bytes
                                 * self.n_slots}
        if self.maskpool is not None:
            out["grammar_masks"] = self.maskpool.stats()
        if self.paged:
            # fused-kernel plane (ISSUE 15): mode, per-bucket fused-vs-
            # XLA verdicts, and the paged family's autotune decisions
            pk = self.paged_kernel_status()
            try:
                from ..ops.pallas_kernels import autotune_decisions
                pk["autotune"] = {
                    "/".join(map(str, k[1:])): v
                    for k, v in autotune_decisions().items()
                    if k[0] == "paged_decode"}
            except Exception:
                pk["autotune"] = {}
            out["paged_kernel"] = pk
        if self.paged:
            try:
                out["pool"] = self.pool.stats()
            except RuntimeError:
                # trie mutated mid-walk (dict changed size): a refresh
                # one poll later sees a settled view
                out["pool"] = {"error": "pool busy, retry"}
        if self.tier is not None:
            out["tier"] = self.tier.stats()
        if self.speculate:
            out["speculative"] = {
                "gamma": self.speculate,
                "draft_blocks": self.draft_blocks,
                "proposed": self._m_spec_proposed.value,
                "accepted": self._m_spec_accepted.value,
            }
        self.attribute_costs()  # lazy for never-warmed engines
        if self.profiler.enabled:
            out["costs"] = self.profiler.cost_snapshot()
            out["phases"] = self.profiler.decomposition()
        return out

    def shed_queued(self, target_depth: int) -> int:
        """Degradation ladder level >= 1: drop queued (never admitted)
        requests until at most ``target_depth`` wait, lowest priority
        first, newest first within a priority — each failed with
        LoadSheddedError (HTTP 503, retryable). Returns how many were
        shed."""
        shed: List[_ActiveSeq] = []
        with self._cond:
            excess = len(self._queue) - max(0, int(target_depth))
            if excess > 0:
                # sort (priority asc, submit time desc): victims first
                order = sorted(
                    self._queue,
                    key=lambda s: (s.handle.priority,
                                   -s.handle.t_submit))[:excess]
                doomed = set(map(id, order))
                self._queue[:] = [s for s in self._queue
                                  if id(s) not in doomed]
                shed = order
                self._m_queue_depth.set(len(self._queue))
        for seq in shed:
            self._m_rejected.inc()
            seq.handle._finish(LoadSheddedError(
                "request shed by the degradation ladder (queue under "
                "sustained pressure); retry with backoff"))
            self._trace_done("cancel", seq)
        return len(shed)
