"""Performance-attribution & SLO plane for the decode engine (ISSUE 11).

The flight recorder (`inference/trace.py`) answers *"what happened to
request X"*; the metrics registry (`inference/metrics.py`) answers *"how
is the fleet doing"*. Neither answers *"why is the fleet at 31% MFU"* or
*"is p99 burning the SLO"* — the attribution questions a serving stack
must answer continuously, not in a one-off profiling session (the
DeepSpark discipline, arXiv 1602.08191: commodity-cluster monitoring is
always-on, floor-gated overhead). Three pieces:

**Step-phase profiler** (:class:`StepPhaseProfiler`). The scheduler loop
names each phase of an iteration as it BEGINS (:data:`PHASES`: batch
assembly ``admit``, the prefill chunk's ``prefill_launch``, draft
rounds, pool ops + candidate assembly ``pool``, a closed window's pages
going back to the pool ``roll`` (`nested`), ``decode_launch``,
host-side acceptance ``accept``, speculative ``verify``, the
metric/trace ``flush``). Naming rule: a phase in which the scheduler's
thread is blocked until the device is done ends in ``_wait``; a phase
that copies a result device -> host ends in ``_read`` (the two halves of
`analysis.runtime.host_read`). One call site feeds three readers: the
cumulative ``phase_seconds``, the per-phase histograms
(``decode_step_phase_seconds{phase=...}``) and, while a `jax.profiler`
trace is on, a ``sched/<phase>`` annotation on the scheduler thread's
host line inside one ``sched_iter`` step annotation per iteration — the
program's phases on the device trace's own clock. Given the engine's
flight recorder (:meth:`StepPhaseProfiler.attach`), each booked iteration
also leaves one ``sched_iter`` span on the scheduler's track of the ring,
always on: its phases and dispatches as offsets from its begin, and the
thread's CPU seconds outside the ``*_wait`` phases (also summed into the
counter ``sched_host_cpu_seconds_total``). Appends are plain
scheduler-thread float arithmetic on preallocated state (the trace
buffer's lock-free single-writer discipline). What the plane costs on
the chip is in `PERF.md` (section 6, PR 26 and PR 38).

**Cost attribution** (:func:`program_costs` + the profiler's rolling
FLOPs window). At warmup, every compiled program family (decode /
prefill / verify / draft, per bucket, at the engine's actual mesh size)
is lowered through ``.lower(...).cost_analysis()`` (on the TPU, the
compiled program's) — the XLA cost model's FLOPs and bytes-accessed per
invocation. Live dispatch
counts (stamped by the scheduler per dispatch) combine with the table
into derived gauges: ``decode_tokens_per_sec``,
``device_flops_per_sec``, ``device_mfu_estimate`` (against the
device's published bf16 peak from :data:`DEVICE_PEAKS`; a device kind
that table does not list gets no MFU at all), ``device_hbm_gbps`` and
per-family FLOPs shares — exposed on `/metrics`, `/info`, and `GET /debug/engine`.

**SLO monitor** (:class:`SLOMonitor`). Sliding-window p50/p95/p99 per
HTTP route plus **multi-window burn rates** against a configurable
latency objective (`serve --slo-p99-ms`): with a p99 objective the
error budget is 1% of requests over the objective; the burn rate is the
observed violation fraction divided by that budget, evaluated over a
fast (default 60 s) and a slow (default 600 s) window — the standard
SRE multiwindow alert shape, so a one-request blip cannot page and a
slow leak still does. ``burning()`` feeds the PR 7 degradation ladder a
SECOND escalation input (`supervisor.EngineSupervisor(slo=...)`): the
ladder becomes latency-aware, not just queue-pressure-aware, and
de-escalates only when BOTH inputs are calm (no flapping when one input
oscillates around its watermark). Route histograms record exemplars
carrying the ``request_id``, so a Prometheus histogram bucket links
straight back into the flight recorder.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .metrics import MetricsRegistry, default_registry

__all__ = ["StepPhaseProfiler", "SLOMonitor", "program_costs",
           "device_peak_flops", "burn_verdict"]


def burn_verdict(fast: float, slow: float, fast_burn: float = 6.0,
                 slow_burn: float = 3.0) -> Tuple[bool, bool]:
    """(burning, calm) from a (fast, slow) burn-rate pair — THE single
    home of the multiwindow thresholds: burning = both windows over
    their burn thresholds (a fast-only spike or a slow-window leftover
    stays quiet); calm = fast window inside budget (< 1.0), the much
    stricter de-escalation gate, so escalate/de-escalate use hysteresis
    instead of one shared edge. Module-level so the fleet federation
    (`serving/telemetry.py`) applies the SAME verdict to fleet-level
    burn rates that each replica's :class:`SLOMonitor` applies locally
    — the router's SLO-aware admission must not disagree with the
    replicas about what "burning" means."""
    return fast >= fast_burn and slow >= slow_burn, fast < 1.0

# iteration phases, in the order engine._step_once begins them. `*_wait`:
# the scheduler thread is blocked until the device is done; `*_read`: a
# result is copied device -> host. Readers (benchmark/metrics/) go by
# those two suffixes, so a new phase that blocks or copies takes one.
PHASES = ("admit", "prefill_launch", "prefill_wait", "prefill_read",
          "draft", "pool", "roll", "decode_launch", "decode_wait",
          "decode_read", "accept", "verify", "flush")
_READ_OF = {p: p[:-len("_wait")] + "_read" for p in PHASES
            if p.endswith("_wait")}
_WAITS = frozenset(_READ_OF)
_NO_SPAN = contextlib.nullcontext()

# Published per-chip peaks keyed by jax ``device_kind`` — the one table
# MFU and roofline figures divide by. Source: Google Cloud TPU
# documentation, "TPU v5e" system architecture (197 TFLOP/s bf16 on the
# MXU, 819 GB/s HBM per chip). A device kind that is not a key has NO
# peak: its MFU is null, never a default. Host CPUs (the test platform)
# are listed so that the null is a decision, not a lookup miss.
DEVICE_PEAKS: Dict[str, Optional[Dict[str, float]]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
    "cpu": None,
}


# net -> {engine-shape tuple -> cost table}; weak on the net so the
# cache dies with the model (see program_costs)
_COST_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _cost_shape_key(engine) -> tuple:
    # paged_kernel is part of the key: the fused and XLA decode
    # programs have different FLOPs/bytes tables, and two engines over
    # one net may run different modes (the bench's A/B does)
    return (engine.tp, engine.paged, engine.speculate, engine.kv_dtype,
            engine.n_slots, tuple(engine.table_buckets),
            tuple(engine.prefill_buckets),
            getattr(engine, "paged_kernel", None))


def cached_program_costs(engine):
    """The cost table for this (net, engine shape) if some earlier
    engine already computed it, else None — the free path a REBUILT
    engine's warmup takes so a post-recovery engine comes up attributed
    without re-tracing the family inside the recovery window."""
    try:
        per_net = _COST_CACHE.get(engine.net)
    except TypeError:
        return None
    if per_net is None:
        return None
    cached = per_net.get(_cost_shape_key(engine))
    return dict(cached) if cached is not None else None


def device_peak_flops(device_kind: Optional[str] = None
                      ) -> Optional[float]:
    """Published bf16 peak FLOP/s of one device of ``device_kind``
    (default: this process's first device), or None when
    :data:`DEVICE_PEAKS` has no figure for it."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    peaks = DEVICE_PEAKS.get(str(device_kind))
    return peaks["bf16_flops"] if peaks else None


def _cost_of(lowered) -> Dict[str, float]:
    """FLOPs / bytes-accessed of one lowered program via the XLA cost
    model. `Lowered.cost_analysis()` runs HLO-level analysis WITHOUT the
    backend compile (milliseconds) where the client has one; the TPU
    client answers None (seen on a v5e with jaxlib 0.9.0), and there the
    compiled program is asked instead — the same program the engine's
    warm-up already compiled, so with the persistent compile cache on
    (util/compile_cache.py) this is a load, not a second compile.
    Missing keys read 0 (some backends publish partial models).

    ``donated_bytes`` is what the call hands over to be updated in
    place: the bytes of every argument leaf the lowering marks donated
    (global shapes under a mesh). The carried state's bytes for a family
    that returns it (the engine's donation rule); 0 means the family
    copies the state on every invocation. Whether the compiler then
    aliased it is the compiled program's ``alias_size_in_bytes``."""
    import jax
    import numpy as np

    c = lowered.cost_analysis()
    if c is None:
        c = lowered.compile().cost_analysis()
    donated = sum(
        int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
        for a in jax.tree_util.tree_leaves(lowered.args_info) if a.donated)
    return {"flops": float(c.get("flops", 0.0) or 0.0),
            "bytes": float(c.get("bytes accessed", 0.0) or 0.0),
            "donated_bytes": float(donated)}


def program_costs(engine) -> Dict[Tuple[str, int], Dict[str, float]]:
    """Per-invocation FLOPs/bytes for every program family the engine
    dispatches, keyed ``(family, bucket)`` — the SAME keys the scheduler
    stamps per live dispatch (:meth:`StepPhaseProfiler.count`), so the
    rolling FLOPs window is a pure table lookup.

    Families and keys:
      - ``decode``: one entry per table bucket (paged) or ``(decode, 0)``
      - ``prefill``: one entry per chunk bucket (paged programs lowered
        at the SMALLEST table bucket — table width is second-order next
        to the chunk's matmuls, and lowering every (chunk × table) pair
        would double warmup for a rounding error)
      - ``verify`` (speculation): per table bucket / ``0``
      - ``draft`` / ``draft_prefill``: the shallow-exit draft's step and
        chunk programs

    Lowering uses the engine's live-dispatch placements (the
    `sharding.decode_program_hlo` contract), so the numbers are for the
    engine's ACTUAL mesh size. The AOT ``.lower()`` path never touches
    the jit call caches — CompileCounter budgets are unaffected.

    Cached per (net, engine shape): the supervisor rebuilds engines
    from a factory over the SAME net on every crash recovery / drain
    swap, and re-tracing the whole family per restart would tax the
    very recovery window warmup exists to protect. The cache is a
    WeakKeyDictionary on the net — it dies with the model.
    """
    import numpy as np

    from .kvpool import SCRATCH_BLOCK

    shape_key = _cost_shape_key(engine)
    cached = cached_program_costs(engine)
    if cached is not None:
        return cached
    try:
        per_net = _COST_CACHE.setdefault(engine.net, {})
    except TypeError:  # unweakrefable stub net (tests): just recompute
        per_net = None

    out: Dict[Tuple[str, int], Dict[str, float]] = {}
    params, variables = engine._params, engine._variables
    ids = engine._dev_array(np.zeros((engine.n_slots,), np.int32))
    live = engine._dev_array(np.zeros((engine.n_slots,), bool))
    slot0 = engine._dev_index(0)
    one = engine._dev_index(1)

    def table(nb):
        return engine._dev_array(
            np.full((engine.n_slots, nb), SCRATCH_BLOCK, np.int32))

    if engine.paged:
        for nb in engine.table_buckets:
            out[("decode", nb)] = _cost_of(engine._jstep.lower(
                params, variables, ids, live, table(nb), engine._states))
        # name which buckets run the fused Pallas kernel vs the XLA
        # gather (ISSUE 15): the .lower() calls above traced every
        # bucket through the paged_decode_attention seam, so the
        # engagement registry has a verdict per bucket — /debug/engine's
        # cost table carries it as a per-invocation "fused" flag
        try:
            fused = engine.paged_kernel_status()["buckets"]
            for nb in engine.table_buckets:
                out[("decode", nb)]["fused"] = (
                    1.0 if fused.get(nb) else 0.0)
        except Exception:
            pass  # a stub engine without the status surface (tests)
        nb0 = engine.table_buckets[0]
        for b in engine.prefill_buckets:
            cids = engine._dev_array(np.zeros((b,), np.int32))
            out[("prefill", b)] = _cost_of(engine._jprefill.lower(
                params, variables, slot0, cids, one, table(nb0),
                engine._states))
    else:
        out[("decode", 0)] = _cost_of(engine._jstep.lower(
            params, variables, ids, live, engine._states))
        for b in engine.prefill_buckets:
            cids = engine._dev_array(np.zeros((b,), np.int32))
            out[("prefill", b)] = _cost_of(engine._jprefill.lower(
                params, variables, slot0, cids, one, engine._states))
    if engine.speculate:
        ids2 = engine._dev_array(
            np.zeros((engine.n_slots, engine.speculate + 1), np.int32))
        if engine.paged:
            for nb in engine.table_buckets:
                out[("verify", nb)] = _cost_of(engine._jverify.lower(
                    params, variables, ids2, live, table(nb),
                    engine._states))
        else:
            out[("verify", 0)] = _cost_of(engine._jverify.lower(
                params, variables, ids2, live, engine._states))
        dp, dv = engine._draft_params, engine._draft_variables
        out[("draft", 0)] = _cost_of(engine._jdraft_step.lower(
            dp, dv, ids, live, engine._draft_states))
        for b in engine.prefill_buckets:
            cids = engine._dev_array(np.zeros((b,), np.int32))
            out[("draft_prefill", b)] = _cost_of(
                engine._jdraft_prefill.lower(dp, dv, slot0, cids, one,
                                             engine._draft_states))
    if per_net is not None:
        per_net[shape_key] = dict(out)
    return out


class StepPhaseProfiler:
    """Per-iteration phase decomposition + rolling cost attribution.

    Hot-path discipline (the flight recorder's): every method the
    scheduler loop calls is plain float/dict arithmetic on preallocated
    SINGLE-WRITER state — no locks, no allocation beyond one small ring
    entry per iteration, no device work. Cross-thread readers
    (`GET /debug/engine`, the gauges) see GIL-atomic snapshots one
    iteration stale at worst. ``enabled=False`` reduces every call to
    one attribute test and opens no trace annotation.

    A phase is named when it begins: ``iter_begin`` opens ``admit``,
    each :meth:`begin` closes the open phase — its seconds go to
    ``phase_seconds`` and its histogram — and opens the next, and
    ``iter_end`` closes the last. The same boundaries open and close the
    ``sched/<phase>`` trace annotations, so an annotation spans exactly
    the interval whose seconds its phase is given. A phase may open more
    than once in an iteration (``accept`` follows a prompt's last chunk
    and the decode step); its seconds add up.

    The scheduler thread's CPU time (``time.thread_time``) is read where
    the iteration begins and ends and where a ``*_wait`` phase begins and
    ends, so CPU spent inside a blocking wait stays out: what an iteration
    burns outside its waits goes to ``sched_host_cpu_seconds_total``. Set
    beside the same phases' wall time it is not 100 % even in a sound run
    (64-88 % on the v5e's host, PR 38: the ``*_read`` phases sleep in the
    copy, which counts as host wall time), so a held thread (another
    process, the machine, the GIL) is a share well under its cell's usual.
    ``thread_time`` moves in 10 ms ticks on that host: a sum over many
    iterations is sound, one iteration's ``cpu_s`` only where it is long.

    With a recorder attached, :meth:`iter_end` appends one ``sched_iter``
    span at the iteration's close, begin and end records both stamped
    then, in ring order like every other record. The begin's arguments
    are ``end`` (the iteration's length: it began ``end`` seconds before
    the record's time), ``phases`` ``[(phase, offset s)]`` in order from
    that begin (each phase runs to the next one's offset, the last to
    ``end``), ``dispatches`` ``[(family, bucket, offset s)]`` (stamped by
    :meth:`count`, after the uploads and just before the jit call) and
    ``cpu_s``. Two ring appends an iteration; nothing per phase, and
    nothing at all with the recorder disabled or absent.
    """

    def __init__(self, metrics: Optional[MetricsRegistry] = None, *,
                 enabled: bool = True, window: int = 256,
                 gauge_every: int = 16,
                 peak_flops: Optional[float] = None):
        self.enabled = bool(enabled)
        self.metrics = metrics if metrics is not None else default_registry()
        # None = the device has no published peak (DEVICE_PEAKS): the
        # MFU gauge stays unset and the read side reports null
        self.peak_flops: Optional[float] = (
            float(peak_flops) if peak_flops else device_peak_flops())
        self._window = max(8, int(window))
        self._gauge_every = max(1, int(gauge_every))
        # cumulative per-phase seconds (scheduler-thread-only writes;
        # dict preallocated so the hot path never inserts keys)
        self.phase_seconds: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._hists = {
            p: self.metrics.histogram(
                "decode_step_phase_seconds",
                help="scheduler iteration wall time by phase "
                     "(admit=batch assembly, pool=pool ops + candidate "
                     "assembly, accept=host-side token acceptance, "
                     "*_wait=blocked on the device, *_read=device->host "
                     "copy)",
                labels={"phase": p})
            for p in PHASES} if self.enabled else {}
        # rolling ring of per-iteration (ts_end, flops, bytes, tokens):
        # preallocated, single-writer, index = iterations % window — the
        # trace ring's overwrite semantics
        self._ring: List[Optional[tuple]] = [None] * self._window
        self.iterations = 0
        # per-invocation cost table from program_costs(); {} until the
        # engine's warmup ingests it (dispatch counts still accumulate)
        self.costs: Dict[Tuple[str, int], Dict[str, float]] = {}
        self.flops_total = 0.0
        self.bytes_total = 0.0
        self.tokens_total = 0
        # per-family cumulative dispatch/flops tallies (debug snapshot +
        # flops-share gauges)
        self.family_dispatches: Dict[str, int] = {}
        self.family_flops: Dict[str, float] = {}
        # per-iteration scratch, reset by iter_begin: (family, bucket,
        # offset) a dispatch, and with a recorder (phase, offset) a phase
        # begun; both lists are handed whole to the iteration's record
        self._iter_counts: List[Tuple[str, int, float]] = []
        self._marks: Optional[List[Tuple[str, float]]] = None
        self._phase = "admit"  # the open phase
        self._t_phase = 0.0    # when it began
        self._t_iter = 0.0     # when the iteration began
        # CPU seconds outside the waits: accumulated, and where the open
        # stretch began (thread_time); _in_wait = a *_wait phase is open
        self._cpu = 0.0
        self._cpu_mark = 0.0
        self._in_wait = False
        self._tracer = None    # attach(): the engine's enabled recorder
        self._track = "scheduler"
        # the open trace annotations (a TraceMe starts when constructed
        # and records when stopped; both cost well under a microsecond
        # with no trace on)
        self._iter_span: Optional[StepTraceAnnotation] = None
        self._span: Optional[TraceAnnotation] = None
        self._t_gauges = 0.0  # last _refresh_gauges wall time
        if self.enabled:
            m = self.metrics
            self._g_tps = m.gauge(
                "decode_tokens_per_sec",
                help="rolling emitted-token rate over the last "
                     f"{self._window} scheduler iterations")
            self._g_flops = m.gauge(
                "device_flops_per_sec",
                help="rolling attributed device FLOP rate (XLA "
                     "cost_analysis per program family x live dispatch "
                     "counts)")
            self._g_mfu = m.gauge(
                "device_mfu_estimate",
                help="model-FLOPs-utilization estimate: attributed "
                     "FLOP/s over the device's published bf16 peak "
                     "(profiler.DEVICE_PEAKS); never set on a device "
                     "kind the table does not list")
            self._g_hbm = m.gauge(
                "device_hbm_gbps",
                help="rolling attributed memory traffic (cost_analysis "
                     "bytes accessed), GB/s")
            self._g_share: Dict[str, object] = {}
            self._c_cpu = m.counter(
                "sched_host_cpu_seconds_total",
                help="scheduler thread CPU seconds (thread_time) in "
                     "booked iterations outside the *_wait phases; over "
                     "those phases' wall seconds, well under 1 = the "
                     "thread was held off the CPU")

    def attach(self, tracer, track: str) -> None:
        """The engine's flight recorder and its scheduler track: from now
        on each booked iteration leaves one ``sched_iter`` span there.
        A disabled profiler or recorder keeps none."""
        self._tracer = tracer if self.enabled and tracer is not None \
            and tracer.enabled else None
        self._track = track

    # -- hot path (scheduler thread only) ----------------------------------
    def iter_begin(self, annotate: bool = True) -> None:
        """Open an iteration and its first phase, ``admit``.
        ``annotate=False`` is the scheduler's word that this pass will
        most likely find nothing to run: its ``sched_iter`` annotation
        then waits for the first :meth:`begin`, so that an engine idling
        at 10 Hz does not write an empty step into the trace on every
        wake."""
        if not self.enabled:
            return
        if self._iter_counts:
            self._iter_counts.clear()
        self._phase = "admit"
        self._t_phase = self._t_iter = time.monotonic()
        self._cpu = 0.0
        self._in_wait = False
        self._cpu_mark = time.thread_time()
        if self._tracer is not None:
            self._marks = [("admit", 0.0)]
        if annotate:
            self._annotate("admit")

    def _annotate(self, phase: str) -> None:
        if self._iter_span is None:
            self._iter_span = StepTraceAnnotation(
                "sched_iter", step_num=self.iterations)
        self._span = TraceAnnotation("sched/" + phase)

    def begin(self, phase: str) -> None:
        """Close the open phase — everything since it began is its —
        and open ``phase``. Sub-microsecond phases stay out of the
        histograms (they would drown in zeros from phases that did not
        run this iteration) and land only in the decomposition."""
        if not self.enabled:
            return
        now = time.monotonic()
        self._close(now)
        self._phase = phase
        self._t_phase = now
        wait = phase in _WAITS
        if wait is not self._in_wait:     # a wait begins or ends
            c = time.thread_time()
            if wait:
                self._cpu += c - self._cpu_mark
            else:
                self._cpu_mark = c
            self._in_wait = wait
        if self._marks is not None:
            self._marks.append((phase, now - self._t_iter))
        self._annotate(phase)

    @contextlib.contextmanager
    def nested(self, phase: str):
        """Host work that happens INSIDE another phase and is booked under
        its own name (``roll``: a closed window's pages going back to the
        pool, wherever a block is claimed): the open phase is suspended and
        opens again after."""
        outer = self._phase
        self.begin(phase)
        try:
            yield
        finally:
            self.begin(outer)

    def ready(self) -> None:
        """The device is done: the open ``*_wait`` phase ends and its
        ``*_read`` begins. `analysis.runtime.host_read` calls this
        between its wait and its copy."""
        if self.enabled:
            self.begin(_READ_OF[self._phase])

    @staticmethod
    def _stop(span) -> None:
        if span is not None:
            span.__exit__(None, None, None)

    def _close(self, now: float) -> None:
        self._stop(self._span)
        self._span = None
        dt = now - self._t_phase
        self.phase_seconds[self._phase] += dt
        if dt >= 1e-6:
            self._hists[self._phase].record(dt)

    def iter_abandon(self) -> None:
        """The pass found nothing to run: its annotations close, and
        nothing is booked (a 10 Hz idle wake stamping microsecond admit
        phases would swamp the histograms) and nothing is recorded."""
        self._stop(self._span)
        self._stop(self._iter_span)
        self._span = self._iter_span = None
        self._marks = None

    def idle(self):
        """Context manager around the scheduler's idle wait: a
        ``sched/idle`` annotation, so that a device gap with nothing to
        run reads as waiting for a request and not as host work. Books
        no phase."""
        return TraceAnnotation("sched/idle") if self.enabled else _NO_SPAN

    def count(self, family: str, bucket: int) -> None:
        """Stamp one dispatch of ``(family, bucket)`` this iteration, after
        its arguments are on the device and just before the jit call that
        launches it (one list append; costs resolve at iter_end; with a
        recorder, the offset is the lower bound on the program's start
        that a reader of the record aligns by)."""
        if self.enabled:
            self._iter_counts.append(
                (family, bucket, time.monotonic() - self._t_iter
                 if self._tracer is not None else 0.0))

    def iter_end(self, tokens: int = 0) -> None:
        """Close the iteration: resolve this iteration's dispatches
        against the cost table, push one ring entry, book its CPU
        seconds, append its ``sched_iter`` record to an attached
        recorder, and refresh the derived gauges every ``gauge_every``
        iterations."""
        if not self.enabled:
            return
        if not self._in_wait:
            self._cpu += time.thread_time() - self._cpu_mark
        now = time.monotonic()
        counts = self._iter_counts
        if self._marks is not None:
            # at once, so that the record's own time is `now` to a
            # microsecond: a reader takes the begin as its time less end
            self._tracer.begin(
                "sched_iter", track=self._track,
                args={"end": now - self._t_iter, "phases": self._marks,
                      "dispatches": counts, "cpu_s": self._cpu})
            self._tracer.end("sched_iter", track=self._track)
            self._marks = None
            self._iter_counts = []      # the record keeps the old list
        self._close(now)
        self._c_cpu.inc(self._cpu)
        self._stop(self._iter_span)  # the step ends with its last phase
        self._iter_span = None
        flops = bytes_ = 0.0
        for family, bucket, _ in counts:
            c = self.costs.get((family, bucket))
            self.family_dispatches[family] = \
                self.family_dispatches.get(family, 0) + 1
            if c is not None:
                f = c["flops"]
                flops += f
                bytes_ += c["bytes"]
                self.family_flops[family] = \
                    self.family_flops.get(family, 0.0) + f
        self.flops_total += flops
        self.bytes_total += bytes_
        self.tokens_total += tokens
        idx = self.iterations % self._window
        # increment BEFORE the store: a concurrent rates() reader
        # indexes ring[iterations % window] as the oldest entry — with
        # store-then-increment it could grab the entry written
        # microseconds ago (dt ~ 0, rates report ~0 on a busy engine);
        # this order makes its view at worst one entry shorter
        self.iterations += 1
        self._ring[idx] = (
            now, self.flops_total, self.bytes_total, self.tokens_total)
        if self.iterations % self._gauge_every == 0:
            self._refresh_gauges(now)

    def idle_tick(self) -> None:
        """Called from the scheduler's IDLE wait (10 Hz wakeups):
        iter_end never runs on idle passes, so without this the rate
        gauges would freeze at the last busy burst's values forever —
        a Prometheus scrape of an hour-idle engine reporting 2000
        tokens/s. Recomputing against the fixed oldest ring entry
        decays the rates as the window stretches. Throttled to ~1 Hz;
        the idle-path cost is one monotonic read and a compare."""
        if not self.enabled or not self.iterations:
            return
        now = time.monotonic()
        if now - self._t_gauges >= 1.0:
            self._refresh_gauges(now)

    def _refresh_gauges(self, now: float) -> None:
        self._t_gauges = now
        oldest = self._ring[self.iterations % self._window] \
            if self.iterations >= self._window else self._ring[0]
        if oldest is None:
            return
        t0, f0, b0, k0 = oldest
        dt = now - t0
        if dt <= 0:
            return
        self._g_tps.set((self.tokens_total - k0) / dt)
        fps = (self.flops_total - f0) / dt
        self._g_flops.set(fps)
        if self.peak_flops:
            self._g_mfu.set(fps / self.peak_flops)
        self._g_hbm.set((self.bytes_total - b0) / dt / 1e9)
        total_f = sum(self.family_flops.values())
        if total_f > 0:
            for fam, f in self.family_flops.items():
                g = self._g_share.get(fam)
                if g is None:
                    g = self._g_share[fam] = self.metrics.gauge(
                        "program_family_flops_share",
                        help="fraction of attributed device FLOPs by "
                             "program family (cumulative)",
                        labels={"family": fam})
                g.set(f / total_f)

    # -- ingestion / read side ---------------------------------------------
    def ingest_costs(self, costs: Dict[Tuple[str, int],
                                       Dict[str, float]]) -> None:
        """Install the per-invocation cost table (engine.warmup calls
        this with :func:`program_costs`' output). One dict rebind —
        GIL-atomic against the scheduler thread's lookups."""
        self.costs = dict(costs)

    def rates(self) -> Dict[str, float]:
        """Rolling-window rates (the gauges' values, computed fresh)."""
        oldest = None
        if self.iterations:
            oldest = self._ring[self.iterations % self._window] \
                if self.iterations >= self._window else self._ring[0]
        if oldest is None:
            return {"tokens_per_sec": 0.0, "flops_per_sec": 0.0,
                    "mfu_estimate": 0.0 if self.peak_flops else None,
                    "hbm_gbps": 0.0}
        t0, f0, b0, k0 = oldest
        dt = max(1e-9, time.monotonic() - t0)
        fps = (self.flops_total - f0) / dt
        return {
            "tokens_per_sec": round((self.tokens_total - k0) / dt, 3),
            "flops_per_sec": round(fps, 1),
            # null, not a guess, where the device has no published peak
            "mfu_estimate": round(fps / self.peak_flops, 6)
            if self.peak_flops else None,
            "hbm_gbps": round((self.bytes_total - b0) / dt / 1e9, 6),
        }

    def decomposition(self) -> Dict[str, dict]:
        """Cumulative per-phase seconds and shares — where every second
        of scheduler wall time went since construction."""
        totals = dict(self.phase_seconds)  # one-pass copy, atomic items
        whole = sum(totals.values()) or 1.0
        return {p: {"seconds": round(s, 6),
                    "share": round(s / whole, 4)}
                for p, s in totals.items()}

    def cost_snapshot(self) -> dict:
        """The `/debug/engine` ``costs`` block: per-family per-bucket
        invocation costs, cumulative dispatch counts, FLOPs shares, and
        the live rolling rates."""
        costs = dict(self.costs)
        fams = sorted({f for f, _ in costs})
        total_f = sum(self.family_flops.values())
        return {
            "per_invocation": {
                f: {str(b): costs[(f2, b)]
                    for f2, b in sorted(costs) if f2 == f}
                for f in fams},
            "dispatches": dict(self.family_dispatches),
            "family_flops_share": {
                f: round(v / total_f, 4)
                for f, v in sorted(self.family_flops.items())}
            if total_f > 0 else {},
            "peak_flops_per_device": self.peak_flops,
            "peak_note": None if self.peak_flops else (
                "no published peak for this device kind "
                "(profiler.DEVICE_PEAKS): MFU is not computed"),
            **self.rates(),
        }


class SLOMonitor:
    """Sliding-window latency percentiles + multiwindow burn rate per
    HTTP route, against one p99 latency objective.

    ``objective_p99_s``: the target — None tracks percentiles but never
    burns (``burning()`` is False, the ladder input stays cold).
    ``error_budget``: allowed violation fraction (0.01 for a p99
    objective). ``burning()`` requires the burn rate over BOTH windows
    to exceed its threshold — fast-window-only spikes and slow-window
    leftovers both stay quiet, the standard multiwindow page condition.
    ``min_samples``: a window holding fewer samples reads burn 0 — on a
    2-requests-a-minute server one slow request is a 100% violation
    fraction, and without the floor that single blip would walk the
    ladder to full admission rejection.
    ``calm()`` is a stricter de-escalation gate (fast burn under 1.0 =
    currently spending within budget) so escalate/de-escalate use
    hysteresis instead of one shared edge.

    Thread-safe: observations arrive from every HTTP handler thread;
    one small lock guards the per-route deques (same discipline as the
    metrics instruments). ``clock`` is injectable so the burn-rate
    algebra is frozen-clock-testable like the supervisor's watchdog.
    """

    def __init__(self, objective_p99_s: Optional[float] = None, *,
                 error_budget: float = 0.01,
                 fast_window_s: float = 60.0, slow_window_s: float = 600.0,
                 fast_burn: float = 6.0, slow_burn: float = 3.0,
                 min_samples: int = 20, max_samples: int = 4096,
                 metrics: Optional[MetricsRegistry] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.objective_p99_s = (float(objective_p99_s)
                                if objective_p99_s else None)
        self.error_budget = float(error_budget)
        self.fast_window_s = float(fast_window_s)
        self.slow_window_s = float(slow_window_s)
        self.fast_burn = float(fast_burn)
        self.slow_burn = float(slow_burn)
        self.min_samples = int(min_samples)
        self.max_samples = int(max_samples)
        self.metrics = metrics if metrics is not None else default_registry()
        self._clock = clock
        self._lock = threading.Lock()
        # per-route (ts, latency) deques: maxlen bounds memory, expired
        # heads popleft in O(expired) per observe — a list rebuild here
        # would be an O(max_samples) copy under the lock on EVERY
        # request once traffic outlives the slow window
        self._samples: Dict[str, collections.deque] = {}
        self._hists: Dict[str, object] = {}
        self._observed = 0
        m = self.metrics
        self._g_fast = m.gauge(
            "slo_burn_rate_fast",
            help="latency-SLO burn rate over the fast window "
                 "(violation fraction / error budget; 1.0 = spending "
                 "exactly the budget)")
        self._g_slow = m.gauge(
            "slo_burn_rate_slow",
            help="latency-SLO burn rate over the slow window")
        if self.objective_p99_s is not None:
            m.gauge("slo_objective_p99_ms",
                    help="configured p99 latency objective"
                    ).set(self.objective_p99_s * 1e3)
        self._g_p99: Dict[str, object] = {}

    def observe(self, route: str, latency_s: float,
                request_id: Optional[str] = None) -> None:
        """Record one request's end-to-end latency for ``route``. The
        labeled histogram keeps an exemplar carrying ``request_id``, so
        a Prometheus bucket links back into `GET /trace`."""
        now = self._clock()
        latency_s = float(latency_s)
        with self._lock:
            hist = self._hists.get(route)
            if hist is None:
                hist = self._hists[route] = self.metrics.histogram(
                    "http_route_latency_seconds",
                    help="end-to-end HTTP request latency by route "
                         "(exemplars carry the request_id)",
                    labels={"route": route})
            buf = self._samples.get(route)
            if buf is None:
                buf = self._samples[route] = collections.deque(
                    maxlen=self.max_samples)
            buf.append((now, latency_s))
            horizon = now - self.slow_window_s
            while buf and buf[0][0] < horizon:
                buf.popleft()
            self._observed += 1
            n = self._observed
        hist.record(latency_s, exemplar=request_id)
        if n % 16 == 0 or n <= 4:
            self._refresh_gauges(now)

    def _window_samples(self, window_s: float, now: float,
                        route: Optional[str] = None) -> List[float]:
        t0 = now - window_s
        with self._lock:
            bufs = ([self._samples.get(route) or ()]
                    if route is not None
                    else list(self._samples.values()))
            return [lat for buf in bufs for ts, lat in buf if ts >= t0]

    def percentiles(self, route: str,
                    window_s: Optional[float] = None) -> dict:
        """Sliding-window p50/p95/p99 (seconds) for one route."""
        now = self._clock()
        vals = sorted(self._window_samples(
            window_s if window_s is not None else self.slow_window_s,
            now, route))
        if not vals:
            return {"n": 0}

        def q(f):
            return vals[min(len(vals) - 1, int(f * len(vals)))]
        return {"n": len(vals), "p50": round(q(0.50), 6),
                "p95": round(q(0.95), 6), "p99": round(q(0.99), 6)}

    def burn_rates(self, now: Optional[float] = None
                   ) -> Tuple[float, float]:
        """(fast, slow) burn rates across all routes: the fraction of
        windowed requests over the objective, divided by the error
        budget. 0.0 when no objective is set or a window holds fewer
        than ``min_samples`` — a near-empty window's violation fraction
        is statistically meaningless and (at 1-2 samples) would let one
        slow request escalate the ladder to admission rejection."""
        if self.objective_p99_s is None:
            return 0.0, 0.0
        now = self._clock() if now is None else now
        out = []
        for w in (self.fast_window_s, self.slow_window_s):
            vals = self._window_samples(w, now)
            if len(vals) < max(1, self.min_samples):
                out.append(0.0)
                continue
            frac = sum(1 for v in vals if v > self.objective_p99_s) \
                / len(vals)
            out.append(frac / self.error_budget)
        return out[0], out[1]

    def _verdict(self, fast: float, slow: float) -> Tuple[bool, bool]:
        """(burning, calm) from an already-computed burn-rate pair —
        delegates to the module-level :func:`burn_verdict` (shared with
        the fleet federation) at this monitor's thresholds."""
        return burn_verdict(fast, slow, self.fast_burn, self.slow_burn)

    def pressure(self, now: Optional[float] = None) -> Tuple[bool, bool]:
        """(burning, calm) from ONE burn-rate computation — the ladder
        evaluates both every watchdog tick, and each burn_rates() call
        scans every route's sample window under the lock, so the paired
        form halves the per-tick cost versus burning()+calm()."""
        fast, slow = self.burn_rates(now)
        return self._verdict(fast, slow)

    def burning(self, now: Optional[float] = None) -> bool:
        """True when the SLO is burning hot enough to escalate."""
        return self.pressure(now)[0]

    def calm(self, now: Optional[float] = None) -> bool:
        """True when latency is inside budget on the fast window."""
        return self.pressure(now)[1]

    def _refresh_gauges(self, now: float) -> None:
        fast, slow = self.burn_rates(now)
        self._g_fast.set(fast)
        self._g_slow.set(slow)
        with self._lock:
            routes = list(self._samples)
        for route in routes:
            p = self.percentiles(route, self.fast_window_s)
            if not p.get("n"):
                continue
            g = self._g_p99.get(route)
            if g is None:
                g = self._g_p99[route] = self.metrics.gauge(
                    "slo_route_p99_ms",
                    help="fast-window p99 latency by route",
                    labels={"route": route})
            g.set(p["p99"] * 1e3)

    def brief(self) -> dict:
        """The burn-rate headline WITHOUT per-route percentiles — what
        `supervisor.status()` embeds in every `/readyz` body. One
        burn_rates() window scan, no sorting: percentiles sort each
        route's full slow-window buffer, and paying that per liveness
        probe (orchestrators poll readiness constantly) would contend
        the SLO lock against every handler's observe(). The full
        per-route picture stays on `/info` and `/debug/engine`."""
        fast, slow = self.burn_rates()
        return {
            "objective_p99_ms": (round(self.objective_p99_s * 1e3, 3)
                                 if self.objective_p99_s else None),
            "burn_rate_fast": round(fast, 4),
            "burn_rate_slow": round(slow, 4),
            "burning": self._verdict(fast, slow)[0],
        }

    def snapshot(self) -> dict:
        """The `/debug/engine` / `/info` SLO block."""
        now = self._clock()
        fast, slow = self.burn_rates(now)
        with self._lock:
            routes = list(self._samples)
        return {
            "objective_p99_ms": (round(self.objective_p99_s * 1e3, 3)
                                 if self.objective_p99_s else None),
            "burn_rate_fast": round(fast, 4),
            "burn_rate_slow": round(slow, 4),
            # reuse the pair computed above rather than re-scanning
            "burning": self._verdict(fast, slow)[0],
            "routes": {
                r: {k: (round(v * 1e3, 3) if k != "n" else v)
                    for k, v in self.percentiles(r).items()}
                for r in routes},
        }
