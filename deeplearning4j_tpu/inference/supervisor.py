"""Engine supervisor: watchdog, crash recovery, degradation, draining.

Before this module, the serving tier's fault model was "hope": one
uncaught exception in the scheduler loop (or one hung XLA dispatch)
killed every in-flight request silently — the daemon thread evaporated,
the HTTP tier kept admitting traffic into a dead engine, and each
blocked caller discovered the outage only by timing out. DeepSpark
(arXiv 1602.08191) and TensorFlow (arXiv 1605.08695) both treat worker
failure as a first-class design input; this is that treatment for the
decode engine.

The supervisor OWNS the engine (it is built from a ``factory`` so a
dead one can be rebuilt from scratch) and layers four mechanisms on top:

**Watchdog.** The scheduler loop stamps ``engine.heartbeat`` once per
iteration (idle passes included, so staleness means *stuck*, not
*quiet*). The watchdog thread polls it; a heartbeat older than
``hang_timeout_s``, or a recorded ``engine.crashed`` exception (the
loop's new try/except reports instead of evaporating), triggers
recovery.

**Crash recovery.** The dead engine is *fenced* (a hung thread that
later wakes sees the fence and exits rather than double-finishing
requests), a replacement is built by the factory — re-jitting the same
program families, so CompileCounter budgets are unchanged — and every
tracked in-flight request is resubmitted FRONT-of-queue onto it with
its ORIGINAL (reset) handle: the caller blocked in ``result()`` never
observes the restart. Decode is deterministic per request (the seed
reseeds, the prompt re-prefills), so the re-run reproduces exactly the
token sequence the crashed attempt was producing — the same primitive
preempt-and-swap (PR 6) already proved. Consecutive restarts back off
exponentially with seeded jitter; each request carries a retry budget,
and exhaustion fails it with :class:`RetryBudgetExceededError` (the
serving layer's structured 503 carrying the ``request_id``).

**Graceful degradation.** Sustained pressure walks a ladder:
level 1 sheds the lowest-priority queued load (``LoadSheddedError`` →
retryable 503), level 2 additionally halves the prefill chunk cap
(shorter device holds; the smaller pow2 buckets are already compiled),
level 3 rejects new admissions with :class:`AdmissionRejectedError`
(503 + ``Retry-After``). TWO escalation inputs (ISSUE 11): queue depth
against the shed watermark, and — with ``slo=`` a
`profiler.SLOMonitor` — the latency-budget burn rate, so a fleet whose
queue is short but whose p99 is burning the SLO still degrades before
it melts. Easing on BOTH inputs walks back down. The current rung is
the ``degradation_level`` gauge.

**Draining restart** (``/admin/drain``): stop admitting, let in-flight
work finish, swap in a fresh engine, resume — a zero-dropped-request
restart for weight pushes or leak hygiene.

Readiness (`/readyz`) is ``not draining AND not recovering AND
heartbeat fresh``; liveness (`/healthz`) is just "the process answers".
Every transition is traced (``engine_crash`` / ``engine_restart`` /
``degrade`` instants, plus a per-request ``recovered`` span bridging
the crash gap on the request waterfall) and counted
(``engine_restarts_total``, ``requests_recovered_total``,
``serving_ready`` / ``degradation_level`` gauges).

The chaos proof lives in ``tests/test_chaos.py``: every
`inference/failpoints.py` seam armed in turn under concurrent load,
asserting no request lost, none answered twice, and every completion
token-identical to the no-fault run.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from .batcher import QueueFullError
from .engine import DecodeHandle, DecodeScheduler
from .metrics import MetricsRegistry, default_registry
from .trace import FlightRecorder, default_recorder

__all__ = ["EngineSupervisor", "RetryBudgetExceededError",
           "ShuttingDownError", "AdmissionRejectedError"]


class RetryBudgetExceededError(RuntimeError):
    """The request's retry budget ran out across engine restarts: every
    attempt saw the engine die. Carries the ``request_id`` so the
    serving layer's 503 body is actionable, not silent."""

    def __init__(self, request_id: str, attempts: int):
        self.request_id = request_id
        self.attempts = attempts
        super().__init__(
            f"request {request_id} abandoned after {attempts} engine "
            "crash(es): retry budget exhausted")


class ShuttingDownError(RuntimeError):
    """The server is tearing down; in-flight requests are failed FAST
    with this (structured 503) instead of being left to hang against a
    stopped engine."""

    def __init__(self, request_id: Optional[str] = None):
        self.request_id = request_id
        super().__init__("server is shutting down")


class AdmissionRejectedError(RuntimeError):
    """Admission refused by the degradation ladder (level 3) or a drain
    in progress. ``retry_after_s`` feeds the HTTP ``Retry-After``
    header — the client should back off, not hammer."""

    def __init__(self, reason: str, retry_after_s: float):
        self.reason = reason
        self.retry_after_s = float(retry_after_s)
        super().__init__(f"not admitting requests ({reason}); retry "
                         f"after {retry_after_s:g}s")


class _Tracked:
    """One supervised in-flight request: everything needed to replay it
    from scratch on a rebuilt engine."""

    __slots__ = ("prompt", "max_new_tokens", "kwargs", "handle", "attempts",
                 "span_open")

    def __init__(self, prompt: List[int], max_new_tokens: int,
                 kwargs: dict, handle: DecodeHandle):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.kwargs = kwargs
        self.handle = handle
        self.attempts = 1  # submissions so far (first one included)
        # a `recovered` span is open on this request's trace track: a
        # recovery pass that fails mid-way (factory error) and reruns
        # must not open a second unmatched begin per victim
        self.span_open = False


class EngineSupervisor:
    """Wraps a :class:`DecodeScheduler` with watchdog + crash recovery +
    a graceful-degradation ladder + draining restarts.

    ``factory``: zero-arg callable building a CONFIGURED (not started)
    DecodeScheduler — called once at construction and once per
    restart/drain swap. ``hang_timeout_s``: heartbeat staleness that
    declares the loop hung. ``retry_budget``: total submissions allowed
    per request (1 original + budget-1 recoveries... precisely: a
    request is abandoned once its attempt count EXCEEDS the budget).
    ``clock``/``sleep_fn``: injectable time (tests drive the watchdog
    with a frozen clock and zero real sleeps via ``check()``).
    ``watchdog=False`` skips the background thread — tests then call
    :meth:`check` explicitly.
    """

    def __init__(self, factory: Callable[[], DecodeScheduler], *,
                 hang_timeout_s: float = 5.0,
                 warmup_timeout_s: float = 60.0,
                 poll_interval_s: float = 0.05,
                 retry_budget: int = 3,
                 backoff_base_s: float = 0.05,
                 backoff_max_s: float = 2.0,
                 backoff_jitter: float = 0.25,
                 backoff_seed: int = 0,
                 backoff_reset_s: float = 30.0,
                 shed_watermark: float = 0.75,
                 calm_watermark: float = 0.25,
                 ladder_patience: int = 3,
                 retry_after_s: float = 1.0,
                 slo=None,
                 metrics: Optional[MetricsRegistry] = None,
                 tracer: Optional[FlightRecorder] = None,
                 clock: Callable[[], float] = time.monotonic,
                 sleep_fn: Callable[[float], None] = time.sleep,
                 watchdog: bool = True, warm_on_build: bool = True):
        self._factory = factory
        self.hang_timeout_s = float(hang_timeout_s)
        # a FRESH engine's first iteration legitimately stalls the
        # heartbeat for however long XLA takes to compile its program
        # families (a rebuilt engine's jit caches start empty) — judging
        # it by hang_timeout_s would declare a false hang, fence the
        # compiling engine, rebuild, recompile, and churn until every
        # request's retry budget died. Until the engine completes its
        # first iteration (iterations == 0), staleness is judged against
        # this much larger bound instead.
        self.warmup_timeout_s = max(float(warmup_timeout_s),
                                    float(hang_timeout_s))
        self.poll_interval_s = float(poll_interval_s)
        self.retry_budget = int(retry_budget)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_max_s = float(backoff_max_s)
        self.backoff_jitter = float(backoff_jitter)
        self.backoff_reset_s = float(backoff_reset_s)
        self.shed_watermark = float(shed_watermark)
        self.calm_watermark = float(calm_watermark)
        self.ladder_patience = int(ladder_patience)
        self.retry_after_s = float(retry_after_s)
        # latency-SLO escalation input (profiler.SLOMonitor, ISSUE 11):
        # the ladder walks up on sustained queue pressure OR a sustained
        # latency-budget burn, and walks down only when BOTH are calm —
        # two independent inputs, one rung, no flapping when one input
        # oscillates around its watermark while the other holds it up
        self._slo = slo
        self.metrics = metrics if metrics is not None else default_registry()
        self.tracer = tracer if tracer is not None else default_recorder()
        self._clock = clock
        self._sleep = sleep_fn
        # seeded jitter: two replicas restarting off the same crash must
        # not retry in lockstep, but a chaos replay must be exact
        self._backoff_rng = np.random.default_rng(backoff_seed)
        self._lock = threading.RLock()  # engine identity + tracked set
        self._tracked: Dict[str, _Tracked] = {}
        self._stopping = False
        self._draining = False
        self._recovering = False
        self._restart_streak = 0
        self._last_restart: Optional[float] = None
        self._pressure_hits = 0
        self._calm_hits = 0
        self.degradation_level = 0
        self.restarts = 0
        m = self.metrics
        self._m_restarts = m.counter("engine_restarts_total")
        self._m_recovered = m.counter("requests_recovered_total")
        self._m_abandoned = m.counter("requests_abandoned_total")
        self._m_shed = m.counter("requests_shed_total")
        self._g_level = m.gauge("degradation_level")
        self._g_ready = m.gauge("serving_ready")
        self._warm_on_build = bool(warm_on_build)
        self._kick = threading.Event()  # crash callback -> prompt poll
        # under the lock like every other _spawn_engine call site: the
        # watchdog starts below and the degradation/engine state it reads
        # is lock-guarded from the first instant
        with self._lock:
            # the FIRST engine's warm-up failure is fatal: at start-up
            # there is no traffic to protect, and a server that came up
            # over a program the compiler refused would only crash-loop
            # under its first request
            self.engine = self._spawn_engine(strict_warm=True)
        self._g_ready.set(1)
        self._watchdog: Optional[threading.Thread] = None
        if watchdog:
            self._watchdog = threading.Thread(
                target=self._watch, daemon=True, name="engine-supervisor")
            self._watchdog.start()

    # -- engine lifecycle --------------------------------------------------
    def _spawn_engine(self, strict_warm: bool = False) -> DecodeScheduler:
        """Build, hook, start, and WARM a fresh engine. Warming runs one
        synthetic request whose prompt touches every prefill chunk
        bucket plus the decode/admit programs, so the XLA compiles land
        HERE — inside the recovery/drain window the supervisor already
        owns — instead of stalling the heartbeat under live traffic
        right after a swap (a tight watchdog would read that stall as a
        fresh hang and churn restarts until the retry budgets died)."""
        eng = self._factory()
        eng._on_crash = self._note_crash
        self._apply_degradation(eng, self.degradation_level)
        eng.start()
        if self._warm_on_build:
            try:
                self._warm(eng, strict=strict_warm)
            except Exception:  # strict only: leave no loop thread behind
                eng.stop()
                raise
        return eng

    def _warm(self, eng: DecodeScheduler, strict: bool = False) -> None:
        """Program-family warm-up (engine.warmup compiles every
        bucket's program with calls that are the identity on live data
        — no metrics, trace, or pool side effects). On a recovery or drain rebuild a failure
        is traced, never swallowed, and never fatal: an unwarmed engine
        still serves, it just compiles under traffic. ``strict`` (the
        first engine of a supervisor) re-raises instead.

        When any TRACKED in-flight request carries a grammar, the
        masked program families are warmed too (``warmup(masks=True)``)
        — a recovery swap is about to resubmit that constrained
        request, and its masked-decode compile landing mid-iteration on
        the fresh engine would stall the very heartbeat the watchdog
        judges (the false-hang churn warmup exists to prevent).
        Unconstrained rebuilds keep skipping the ~2x masked warm-up."""
        warmup = getattr(eng, "warmup", None)  # stub engines: no-op
        if warmup is None:
            return
        with self._lock:
            masks = any(t.kwargs.get("grammar") is not None
                        for t in self._tracked.values())
        try:
            # the masks kwarg only when needed: stub/legacy engines in
            # the chaos drills expose a zero-arg warmup()
            warmup(masks=True) if masks else warmup()
        except Exception as e:
            if strict:
                raise
            self.tracer.instant("warmup_skipped", track="supervisor",
                                args={"error": type(e).__name__,
                                      "detail": str(e)[:200]})

    def _note_crash(self, exc: BaseException) -> None:
        # runs on the DYING scheduler thread: record nothing here (the
        # engine already stamped .crashed); just wake the watchdog so
        # recovery starts within one poll, not one poll interval
        self._kick.set()

    def _watch(self) -> None:
        while not self._stopping:
            self._kick.wait(timeout=self.poll_interval_s)
            self._kick.clear()
            if self._stopping:
                return
            try:
                self.check()
            except Exception as e:
                # the supervisor is the last line of defense — its own
                # loop must survive anything recovery throws (e.g. a
                # factory failure while the process is dying)
                self.tracer.instant(
                    "supervisor_error", track="supervisor",
                    args={"error": type(e).__name__,
                          "detail": str(e)[:200]})

    def check(self) -> None:
        """One watchdog evaluation: crash/hang detection + the
        degradation ladder. Normally driven by the background thread;
        tests call it directly with an injected frozen clock.

        The whole evaluation holds ``self._lock`` (reentrant — recovery
        re-acquires it): the ladder counters and the engine identity are
        otherwise written by this watchdog thread while ``submit()``
        reads them under the lock, the lockset-empty cross-thread access
        graftlint CC005 flagged."""
        with self._lock:
            if self._stopping or self._draining:
                return
            eng = self.engine
            if eng.crashed is not None:
                self._recover("crash", eng)
                return
            limit = (self.hang_timeout_s if eng.iterations > 0
                     else self.warmup_timeout_s)
            if self._clock() - eng.heartbeat > limit:
                self._recover("hang", eng)
                return
            self._evaluate_ladder(eng)
            self._prune_done()

    # -- crash recovery ----------------------------------------------------
    def _recover(self, reason: str, dead: DecodeScheduler) -> None:
        with self._lock:
            if self.engine is not dead or self._stopping:
                return  # someone else already swapped it
            self._recovering = True
            self._g_ready.set(0)
            try:
                self._recover_locked(reason, dead)
                self._g_ready.set(1)
            finally:
                # a factory/rebuild failure must not leave _recovering
                # latched True (readiness stuck 503 forever on whatever
                # engine a LATER pass does manage to build); the next
                # watchdog poll re-enters and retries
                self._recovering = False

    def _recover_locked(self, reason: str, dead: DecodeScheduler) -> None:
        tr = self.tracer
        tr.instant("engine_crash" if reason == "crash"
                   else "engine_hang", track="supervisor",
                   args={"reason": reason,
                         "error": type(dead.crashed).__name__
                         if dead.crashed else "heartbeat_stale",
                         "iterations": dead.iterations,
                         "inflight": len(self._tracked)})
        # fence FIRST: from here the dead engine's thread (hung, may
        # wake later) can no longer touch any handle; then give it a
        # join grace so the common case (crashed = thread already
        # exiting) is fully quiesced before handles are reused
        dead.fence()
        if dead._thread is not None:
            dead._thread.join(timeout=self.poll_interval_s)
        # sweep the tracked set: done/cancelled requests leave it,
        # survivors get a `recovered` span bridging the outage on
        # their waterfall track
        victims: List[_Tracked] = []
        for rid, t in list(self._tracked.items()):
            h = t.handle
            if h.done():
                del self._tracked[rid]
            elif h.cancelled():
                h._finish()  # caller already gave up; partial tokens
                del self._tracked[rid]
            else:
                victims.append(t)
        victims.sort(key=lambda t: t.handle.t_submit)
        for t in victims:
            if not t.span_open:  # a retried recovery pass must not
                t.span_open = True  # stack a second unmatched begin
                tr.begin("recovered", req=t.handle.request_id,
                         args={"reason": reason,
                               "attempt": t.attempts})
        # bounded exponential backoff + seeded jitter between
        # CONSECUTIVE restarts (a crash loop must not spin-rebuild);
        # the streak resets after a healthy stretch
        now = self._clock()
        if self._last_restart is not None and \
                now - self._last_restart > self.backoff_reset_s:
            self._restart_streak = 0
        delay = min(self.backoff_max_s,
                    self.backoff_base_s * (2 ** self._restart_streak))
        jitter = self._backoff_rng.random()  # host RNG, not a sync
        delay *= 1.0 + self.backoff_jitter * jitter
        self._restart_streak += 1
        self._last_restart = now
        if delay > 0:
            self._sleep(delay)
        # rebuild + warm: the factory re-jits the same program
        # families (same shapes, same buckets — CompileCounter
        # budgets are unchanged), and the degradation rung carries
        # over
        self.engine = self._spawn_engine()
        self.restarts += 1
        self._m_restarts.inc()
        tr.instant("engine_restart", track="supervisor",
                   args={"restart": self.restarts, "reason": reason,
                         "backoff_s": round(delay, 4),
                         "recovering": len(victims)})
        # resubmit FRONT-of-queue, newest first, so the final queue
        # order is oldest-submit-first — recovered work does not
        # wait behind requests that arrived after the crash
        recovered = 0
        for t in reversed(victims):
            h = t.handle
            rid = h.request_id
            if t.attempts >= self.retry_budget:
                self._m_abandoned.inc()
                t.span_open = False
                tr.end("recovered", req=rid,
                       args={"outcome": "retry_budget_exhausted"})
                h._finish(RetryBudgetExceededError(rid, t.attempts))
                del self._tracked[rid]
                continue
            t.attempts += 1
            h._reset_for_retry()
            t.span_open = False
            tr.end("recovered", req=rid)
            try:
                self.engine.submit(t.prompt, t.max_new_tokens,
                                   _handle=h, _front=True, **t.kwargs)
            except QueueFullError as e:
                # a full-queue-and-full-slots crash can leave more
                # victims than the rebuilt queue holds: the
                # overflow must FAIL (retryable 503 via the
                # handle), never hang — and must not abort the
                # remaining resubmissions
                h._finish(e)
                del self._tracked[rid]
                continue
            except RuntimeError:
                # the replacement died before this resubmission
                # landed (a crash-looping engine): leave the
                # request TRACKED — the next recovery pass retries
                # it, and its attempts counter keeps marching
                # toward the budget's structured 503
                continue
            recovered += 1
        if recovered:
            self._m_recovered.inc(recovered)
        self._recovering = False
        self._g_ready.set(1)

# -- degradation ladder ------------------------------------------------
    def _evaluate_ladder(self, eng: DecodeScheduler) -> None:
        """One ladder evaluation over BOTH escalation inputs: queue
        pressure (the fraction of max_queue waiting) and — when an
        `profiler.SLOMonitor` is attached — the latency-budget burn
        rate. Either input hot counts a pressure hit; de-escalation
        needs every input calm (queue at-or-under the calm watermark
        AND latency back inside budget), so a rung held up by latency
        cannot flap just because the queue drained, and vice versa.
        The patience counters debounce both directions unchanged."""
        frac = eng.queue_depth() / max(1, eng.max_queue)
        burning, latency_calm = (
            self._slo.pressure(self._clock())
            if self._slo is not None else (False, True))
        if frac >= self.shed_watermark or burning:
            self._pressure_hits += 1
            self._calm_hits = 0
        elif frac <= self.calm_watermark and latency_calm:
            self._calm_hits += 1
            self._pressure_hits = 0
        else:
            self._pressure_hits = 0
            self._calm_hits = 0
        if self._pressure_hits >= self.ladder_patience \
                and self.degradation_level < 3:
            self._set_level(self.degradation_level + 1,
                            source="latency" if burning
                            and frac < self.shed_watermark else "queue")
            self._pressure_hits = 0
        elif self._calm_hits >= self.ladder_patience \
                and self.degradation_level > 0:
            self._set_level(self.degradation_level - 1)
            self._calm_hits = 0
        if self.degradation_level >= 1:
            shed = eng.shed_queued(eng.max_queue // 2)
            if shed:
                self._m_shed.inc(shed)

    def _set_level(self, level: int, source: str = "queue") -> None:
        self.degradation_level = level
        self._g_level.set(level)
        self._apply_degradation(self.engine, level)
        self.tracer.instant("degrade", track="supervisor",
                            args={"level": level, "input": source})

    @staticmethod
    def _apply_degradation(eng: DecodeScheduler, level: int) -> None:
        """Project a degradation rung onto an engine (also called on
        every rebuild, so a restart under pressure comes up degraded,
        not amnesiac). Takes the rung as a parameter — callers read
        ``degradation_level`` under whatever lock they already hold —
        instead of re-reading shared state lock-free here."""
        eng.chunk_cap = (max(1, eng.prefill_chunk // 2)
                         if level >= 2 else None)

    # -- admission / client side -------------------------------------------
    def submit(self, prompt_ids: Sequence[int], max_new_tokens: int,
               **kw) -> DecodeHandle:
        """Supervised submit: tracked for crash recovery. Raises
        :class:`AdmissionRejectedError` at degradation level 3 or while
        draining (the HTTP tier turns it into 503 + Retry-After)."""
        # the not-running retry window must span at least one full
        # recovery (rebuild + warm-up compiles), or a submit landing
        # mid-restart would error out just before the engine came back
        deadline = self._clock() + max(5.0, 2 * self.backoff_max_s)
        while True:
            with self._lock:
                # admission checks live under the same lock that guards
                # engine swaps / drain transitions, so a request can
                # never slip past a flag mid-flip into a dying engine
                if self._stopping:
                    raise ShuttingDownError()
                if self._draining:
                    raise AdmissionRejectedError(
                        "draining restart in progress",
                        self.retry_after_s)
                if self.degradation_level >= 3:
                    raise AdmissionRejectedError(
                        "degradation ladder level 3 (sustained "
                        "overload)", self.retry_after_s)
                try:
                    handle = self.engine.submit(prompt_ids,
                                                max_new_tokens, **kw)
                except QueueFullError:
                    raise
                except RuntimeError:
                    # engine died between checks (not running): recovery
                    # will swap it — bounded retry, and on expiry a
                    # RETRYABLE 503 with a back-off hint, never a raw
                    # lifecycle error surfaced as a client fault
                    if self._clock() >= deadline:
                        raise AdmissionRejectedError(
                            "engine recovering (crash loop?)",
                            self.retry_after_s)
                    handle = None
                if handle is not None:
                    self._tracked[handle.request_id] = _Tracked(
                        [int(t) for t in prompt_ids], int(max_new_tokens),
                        dict(kw), handle)
                    return handle
            self._kick.set()  # nudge the watchdog at the dead engine
            self._sleep(self.poll_interval_s)

    def generate_handle(self, prompt_ids: Sequence[int],
                        max_new_tokens: int,
                        timeout: Optional[float] = 120.0,
                        **kw) -> DecodeHandle:
        """Blocking supervised generate — the `/generate` entry point.
        Same contract as the engine's: a timed-out wait CANCELS the
        request. The handle leaves the recovery-tracking set on exit
        either way (a caller that got its answer — or gave up — must
        not have its request replayed by a later restart)."""
        handle = self.submit(prompt_ids, max_new_tokens, **kw)
        try:
            handle.result(timeout)
        except TimeoutError:
            handle.cancel()
            raise
        finally:
            self._untrack(handle.request_id)
        return handle

    def generate_many(self, prompt_ids: Sequence[int], n: int,
                      max_new_tokens: int,
                      timeout: Optional[float] = 120.0, *, seed: int = 0,
                      **kw) -> List:
        """Supervised best-of-n (`/generate` with ``n > 1``): the shared
        `speculative.submit_fork_group` protocol over this supervisor's
        tracked submit — every candidate is tracked for crash recovery
        individually (the fork group rides the resubmission kwargs, so
        recovered candidates keep sharing blocks when the rebuilt
        engine re-publishes, and degrade to independent prefills when
        it cannot: correctness never depends on the fork). A partial-
        submit failure or timeout cancels the submitted candidates;
        cancelled handles finish at the engine's next sweep and leave
        the tracking set via `_prune_done`."""
        from .speculative import await_fork_group, submit_fork_group
        handles = submit_fork_group(self.submit, prompt_ids, n,
                                    max_new_tokens, seed=seed, **kw)
        try:
            await_fork_group(handles, timeout, clock=self._clock)
        finally:
            for h in handles:
                self._untrack(h.request_id)
        return handles

    def _untrack(self, request_id: str) -> None:
        with self._lock:
            self._tracked.pop(request_id, None)

    def untrack(self, request_id: str) -> None:
        """Public untrack for callers that drive a `submit()` handle
        themselves instead of blocking in `generate_handle` — the SSE
        streaming path: the HTTP tier drains the handle's TokenStream
        and must drop the recovery-tracking entry when the stream ends
        (completed or client-disconnected), exactly like
        `generate_handle`'s finally does. Until then the request IS
        tracked: an engine crash mid-stream resubmits it and the
        token-identical re-decode resumes the stream seamlessly."""
        self._untrack(request_id)

    def _prune_done(self) -> None:
        """Drop finished requests nobody untracked (fire-and-forget
        `submit()` users) so the tracked set cannot grow unbounded."""
        with self._lock:
            for rid in [rid for rid, t in self._tracked.items()
                        if t.handle.done()]:
                del self._tracked[rid]

    # -- readiness / draining ----------------------------------------------
    @property
    def ready(self) -> bool:
        """`/readyz`: able to take traffic NOW — not stopping, not
        draining, not mid-recovery, engine loop alive and beating.

        Deliberately LOCK-FREE: ``self._lock`` is held for the whole of
        a recovery (backoff sleep + rebuild + warm-up compiles, seconds)
        and a readiness probe must answer "not ready" DURING that
        window, not block until it ends. Every read here is one
        GIL-atomic bool/ref load; a probe racing a flag flip returns the
        verdict from one instant earlier — exactly as correct for a
        poller."""
        if self._stopping or self._draining or self._recovering:  # graftlint: disable=CC005
            return False
        eng = self.engine  # graftlint: disable=CC005 — atomic ref read, see above
        if eng.crashed is not None:
            return False
        limit = (self.hang_timeout_s if eng.iterations > 0
                 else self.warmup_timeout_s)
        return (self._clock() - eng.heartbeat) <= limit

    def status(self) -> dict:
        """The `/readyz` body (and the UI's robustness line). Lock-free
        for the same reason as :attr:`ready` — each field is one
        GIL-atomic scalar/ref read, and a diagnostics snapshot one flag
        flip stale is fine; blocking /readyz on the seconds-long
        recovery lock hold is not."""
        eng = self.engine
        out = {
            "ready": self.ready,
            "draining": self._draining,
            "recovering": self._recovering,
            "degradation_level": self.degradation_level,  # graftlint: disable=CC005
            "restarts": self.restarts,  # graftlint: disable=CC005 — atomic int read, see docstring
            "heartbeat_age_s": round(self._clock() - eng.heartbeat, 3),
            "inflight": len(self._tracked),  # graftlint: disable=CC005 — atomic len(), see docstring
        }
        if self._slo is not None:
            # the BRIEF form: /readyz is polled constantly, and the
            # full snapshot sorts every route's window per call — the
            # per-route percentiles live on /info and /debug/engine
            out["slo"] = self._slo.brief()
        return out

    def drain(self, timeout: Optional[float] = None,
              poll_s: float = 0.02) -> bool:
        """Draining restart: stop admitting (readiness flips false),
        let in-flight work finish, swap in a fresh engine, resume.
        Returns False if ``timeout`` expired with work still in flight
        (admission resumes on the OLD engine — nothing was dropped)."""
        with self._lock:
            if self._draining or self._stopping:
                return False
            self._draining = True
            inflight0 = self.engine.inflight()
        self._g_ready.set(0)
        self.tracer.instant("drain_begin", track="supervisor",
                            args={"inflight": inflight0})
        t0 = self._clock()
        try:
            while True:
                with self._lock:
                    # the swap decision and the swap itself share one
                    # lock hold: no submit can slip into the old engine
                    # between "empty" and stop()
                    if self.engine.inflight() == 0 \
                            and not self.engine.crashed:
                        old = self.engine
                        old.stop()
                        self.engine = self._spawn_engine()
                        self.tracer.instant(
                            "drain_swap", track="supervisor",
                            args={"elapsed_s":
                                  round(self._clock() - t0, 3)})
                        return True
                    if self.engine.crashed:
                        # crashed mid-drain: fall back to crash recovery
                        # (it requeues the stragglers), then finish the
                        # drain pass on the fresh engine
                        self._draining = False
                        self._recover("crash", self.engine)
                        self._draining = True
                if timeout is not None and self._clock() - t0 > timeout:
                    return False
                self._sleep(poll_s)
        finally:
            with self._lock:
                self._draining = False
            if not self._stopping:
                self._g_ready.set(1)

    def drain_async(self) -> threading.Thread:
        """`POST /admin/drain`: kick a drain and return immediately
        (clients watch `/readyz` flip)."""
        th = threading.Thread(target=self.drain, daemon=True,
                              name="engine-drain")
        th.start()
        return th

    # -- teardown ----------------------------------------------------------
    def stop(self) -> None:
        """Fail-fast teardown: every tracked in-flight request gets a
        structured :class:`ShuttingDownError` (503 with its request_id)
        instead of hanging against a stopped engine, then the engine
        and watchdog go down."""
        self._stopping = True
        self._kick.set()
        if self._watchdog is not None:
            self._watchdog.join(timeout=5)
            self._watchdog = None
        with self._lock:
            for rid, t in list(self._tracked.items()):
                if not t.handle.done():
                    t.handle._finish(ShuttingDownError(rid))
            self._tracked.clear()
            self._g_ready.set(0)
            self.engine.stop()
