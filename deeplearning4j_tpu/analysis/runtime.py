"""Runtime audit harness: the dynamic half of graftlint.

Static rules state the invariants; these helpers make test runs *prove*
them on real executions:

  - :func:`host_read` / :func:`device_index` — the sanctioned
    device<->host boundaries for hot-loop code. ``host_read`` is the ONE
    place the decode/prefill scheduler is allowed to block on a
    device->host sync (the sampled-token readback); it re-allows
    transfers locally so the surrounding code can run under
    ``jax.transfer_guard("disallow")``. ``device_index`` ships a host
    scalar to device as an explicit 1-element int32 array (scalar feeds
    are *implicit* transfers under the guard; 1-d np arrays are
    explicit).
  - :func:`device_residency` — process-wide ``jax.transfer_guard`` fixture
    for tests: any implicit transfer anywhere (every thread) raises.
  - :class:`CompileCounter` — asserts jit-program-count budgets over
    named jitted callables (the generalized recompile guard; budgets for
    the decode scheduler come from :meth:`CompileCounter.for_scheduler`).
  - :func:`lock_audit` / :class:`LockAuditor` — instruments
    ``threading.Lock/RLock/Condition`` so real acquisition orders are
    recorded (edges: lock A held while acquiring lock B, keyed by each
    lock's allocation site), and :func:`crosscheck_lock_order` joins the
    observed edges against the static lock graph
    (``concurrency_rules.build_lock_graph``) and rejects any combined
    cycle.
"""
from __future__ import annotations

import contextlib
import sys
import threading
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# NOTE: no module-level import of the AST rule machinery — the serving hot
# path imports this module for host_read/device_index, and must not drag
# the linter in with it; crosscheck_lock_order imports lazily.

_PKG = "deeplearning4j_tpu"


# -- sanctioned transfer boundaries ---------------------------------------
def host_read(x, ready: Optional[Callable[[], None]] = None) -> np.ndarray:
    """Blocking device->host read, declared. Hot-loop code must funnel its
    (few, deliberate) host reads through here: graftlint rule JG006 flags
    any other sync in scheduler-loop code, and under
    ``jax.transfer_guard("disallow")`` this is the allow-listed boundary
    that still passes.

    The read is a wait for the device, then a copy. A caller that wants
    the two told apart passes ``ready``, which is called between them
    (`StepPhaseProfiler.ready`: the ``*_wait`` phase ends, the ``*_read``
    phase begins). The copy is queued behind the computation BEFORE the
    wait, as a bare ``np.asarray`` queues it: waiting first and asking
    for the copy only then costs a host round trip per read (0.6 ms an
    iteration on a v5e, `PERF.md` section 6, PR 26). Still one transfer."""
    with jax.transfer_guard("allow"):
        if ready is not None:
            x.copy_to_host_async()
            jax.block_until_ready(x)
            ready()
        return np.asarray(x)


def device_index(v: int) -> jax.Array:
    """A host scalar as an EXPLICIT host->device transfer: 1-element
    int32 array (``jnp.asarray`` of a >=1-d numpy array is explicit under
    the transfer guard; bare Python/numpy scalars are implicit and fail
    under "disallow"). Traced consumers index ``[0]``."""
    return jnp.asarray(np.asarray([v], np.int32))


@contextlib.contextmanager
def device_residency(level: str = "disallow"):
    """Process-wide transfer-guard fixture: while active, implicit
    host<->device transfers raise on EVERY thread (the scheduler/dispatch
    threads included — jax.transfer_guard's context-manager form is
    thread-local, which would silently skip them)."""
    prev = jax.config.jax_transfer_guard
    jax.config.update("jax_transfer_guard", level)
    try:
        yield
    finally:
        jax.config.update("jax_transfer_guard",
                          prev if prev is not None else "allow")


# -- compile budgets -------------------------------------------------------
class CompileCounter:
    """Asserts jit-program-count budgets over named jitted callables.

    Counts are deltas against each callable's compiled-program cache size
    at ``track`` time, so pre-warmed functions start at 0. The budget is
    the *invariant*, not an observation: decode must stay at exactly one
    program no matter the request mix, prefill at one per chunk bucket.
    """

    def __init__(self):
        self._tracked: Dict[str, Tuple[object, Optional[int], int]] = {}

    @staticmethod
    def _cache_size(jitted) -> int:
        size = getattr(jitted, "_cache_size", None)
        if callable(size):
            return int(size())
        raise TypeError(
            f"{jitted!r} exposes no _cache_size(); pass a jax.jit result")

    def track(self, name: str, jitted, budget: Optional[int] = None
              ) -> "CompileCounter":
        self._tracked[name] = (jitted, budget, self._cache_size(jitted))
        return self

    def count(self, name: str) -> int:
        jitted, _, base = self._tracked[name]
        return self._cache_size(jitted) - base

    def counts(self) -> Dict[str, int]:
        return {name: self.count(name) for name in self._tracked}

    def check(self) -> List[str]:
        out = []
        for name, (jitted, budget, base) in self._tracked.items():
            n = self._cache_size(jitted) - base
            if budget is not None and n > budget:
                out.append(
                    f"'{name}' compiled {n} XLA program(s), budget is "
                    f"{budget}: a shape/dtype/static-arg is varying per "
                    "call (recompile storm)")
        return out

    def assert_within_budget(self) -> None:
        problems = self.check()
        if problems:
            raise AssertionError("; ".join(problems))

    @classmethod
    def for_scheduler(cls, scheduler) -> "CompileCounter":
        """Budgets for a DecodeScheduler.

        Contiguous mode: 1 decode program, <=1 prefill program per pow2
        chunk bucket (0 when chunking is off) and 1 slot-reset program.

        Paged mode (engine.paged): block tables are padded to pow2
        bucket widths like every other shape, so decode is <=1 program
        per TABLE bucket, prefill <=1 per (chunk bucket, table bucket)
        pair, plus one pos-set and one COW block-copy program — a FIXED
        family regardless of sequence lengths, slot churn, or pool
        pressure (no per-length recompiles)."""
        c = cls()
        tb = len(getattr(scheduler, "table_buckets", []) or [])
        paged = bool(getattr(scheduler, "paged", False))
        c.track("decode", scheduler._jstep,
                budget=max(1, tb) if paged else 1)
        pf = len(scheduler.prefill_buckets)
        c.track("prefill", scheduler._jprefill,
                budget=pf * max(1, tb) if paged else pf)
        jzero = getattr(scheduler, "_jzero", None)
        if jzero is not None:
            c.track("admit_reset", jzero, budget=1)
        jsetpos = getattr(scheduler, "_jsetpos", None)
        if jsetpos is not None:
            c.track("restore_setpos", jsetpos, budget=1)
        jcow = getattr(scheduler, "_jcow", None)
        if jcow is not None:
            c.track("block_cow", jcow, budget=1)
        jsumtab = getattr(scheduler, "_jsumtab", None)
        if jsumtab is not None:  # a net whose attention recycles pages
            c.track("summary_table", jsumtab, budget=1)
        # KV tiering (ISSUE 19): spill slices and restore writes keep
        # the block index traced — one program each for the whole tier
        # ladder, whatever spills or promotes
        jtspill = getattr(scheduler, "_jtier_spill", None)
        if jtspill is not None:
            c.track("tier_spill", jtspill, budget=1)
        jtrestore = getattr(scheduler, "_jtier_restore", None)
        if jtrestore is not None:
            c.track("tier_restore", jtrestore, budget=1)
        # speculative decoding (ISSUE 10): the verify program mirrors
        # decode's bucketing (<=1 per table bucket, one fixed gamma+1
        # chain width — pow2-gamma callers each get their own engine,
        # so the per-engine family is <=1 per bucket); the draft's
        # step/prefill/zero mirror the main families over the draft
        # state pytree; the two fixpos rollback programs are singletons.
        # All budgets are mesh-size-invariant like the rest.
        jverify = getattr(scheduler, "_jverify", None)
        if jverify is not None:
            c.track("spec_verify", jverify,
                    budget=max(1, tb) if paged else 1)
        jdstep = getattr(scheduler, "_jdraft_step", None)
        if jdstep is not None:
            c.track("draft_decode", jdstep, budget=1)
        jdprefill = getattr(scheduler, "_jdraft_prefill", None)
        if jdprefill is not None:
            c.track("draft_prefill", jdprefill, budget=pf)
        jdzero = getattr(scheduler, "_jdraft_zero", None)
        if jdzero is not None:
            c.track("draft_reset", jdzero, budget=1)
        jfixpos = getattr(scheduler, "_jfixpos", None)
        if jfixpos is not None:
            c.track("spec_fixpos", jfixpos, budget=1)
        jdfixpos = getattr(scheduler, "_jdraft_fixpos", None)
        if jdfixpos is not None:
            c.track("draft_fixpos", jdfixpos, budget=1)
        # grammar-constrained decoding (ISSUE 14): the masked decode /
        # verify / draft-step variants add one mask-gather + add to the
        # corresponding base program, so they inherit its bucketing —
        # at most one masked-decode family member per table bucket, one
        # masked draft step — and the mask UPLOAD program (admission
        # path, never per-token) is <=1 per pow2 mask-row bucket. Zero
        # per-request recompiles: grammar size is absorbed by the
        # bucketed upload and the fixed [mask_rows, vocab] table shape.
        jstep_m = getattr(scheduler, "_jstep_m", None)
        if jstep_m is not None:
            c.track("masked_decode", jstep_m,
                    budget=max(1, tb) if paged else 1)
        jverify_m = getattr(scheduler, "_jverify_m", None)
        if jverify_m is not None:
            c.track("masked_verify", jverify_m,
                    budget=max(1, tb) if paged else 1)
        jdstep_m = getattr(scheduler, "_jdraft_step_m", None)
        if jdstep_m is not None:
            c.track("masked_draft", jdstep_m, budget=1)
        jmask_up = getattr(scheduler, "_jmask_upload", None)
        if jmask_up is not None:
            c.track("mask_upload", jmask_up,
                    budget=len(getattr(scheduler, "mask_buckets", []) or []))
        return c


# -- instrumented locks ----------------------------------------------------
def _creation_site() -> Tuple[str, int]:
    """(relpath, line) of the frame that allocated the lock, skipping
    stdlib threading/queue internals and this module."""
    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename
        base = Path(fn).name
        if base not in ("threading.py", "queue.py", "runtime.py") and \
                "importlib" not in fn:
            parts = Path(fn).parts
            if _PKG in parts:
                rel = "/".join(parts[parts.index(_PKG):])
            else:  # same scheme as core._relpath so sites join cleanly
                rel = "/".join(parts[-2:]) if len(parts) >= 2 else base
            return rel, f.f_lineno
        f = f.f_back
    return "<unknown>", 0


class LockAuditor:
    """Collects real lock-acquisition-order edges while active.

    Edges are keyed by each lock's allocation site (relpath, line) — the
    same key the static analyzer records for ``self._x = threading.Lock()``
    definitions, so observed orders join against the static graph
    directly. Per-thread held stacks are thread-local; the global edge map
    is guarded by a REAL (uninstrumented) lock created before patching.
    """

    def __init__(self):
        self._real_lock_ctor = threading.Lock
        self._guard = threading.Lock()
        self._tls = threading.local()
        # (site_a, site_b) -> count: a was held when b was acquired
        self.edges: Dict[Tuple[Tuple[str, int], Tuple[str, int]], int] = {}
        self.sites: Set[Tuple[str, int]] = set()

    def _held(self) -> list:
        held = getattr(self._tls, "held", None)
        if held is None:
            held = self._tls.held = []
        return held

    def on_acquire(self, lock) -> None:
        held = self._held()
        # RLock/Condition re-entry: the lock is already ours, so locks
        # above it on the stack were acquired AFTER it — recording
        # (top -> lock) here would invert the true order and fabricate a
        # deadlock cycle out of legal reentrant code
        reentry = any(h is lock for h in held)
        if held and not reentry and held[-1] is not lock:
            a, b = held[-1]._graftlint_site, lock._graftlint_site
            if a != b:
                with self._guard:
                    self.edges[(a, b)] = self.edges.get((a, b), 0) + 1
        held.append(lock)

    def on_release(self, lock) -> None:
        held = self._held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] is lock:
                del held[i]
                break

    def observed_edges(self) -> Set[Tuple[Tuple[str, int],
                                          Tuple[str, int]]]:
        with self._guard:
            return set(self.edges)


class _AuditedLock:
    """Wraps a real Lock/RLock; reports acquire/release to the auditor."""

    def __init__(self, auditor: LockAuditor, inner):
        self._auditor = auditor
        self._inner = inner
        self._graftlint_site = _creation_site()
        auditor.sites.add(self._graftlint_site)

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        got = self._inner.acquire(blocking, timeout)
        if got:
            self._auditor.on_acquire(self)
        return got

    def release(self) -> None:
        self._auditor.on_release(self)
        self._inner.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self) -> bool:
        return self._inner.locked()

    def __getattr__(self, name):  # _at_fork_reinit and friends
        return getattr(self._inner, name)


class _AuditedCondition(threading.Condition):
    """Real Condition semantics (native _release_save/_is_owned — no
    probe-acquire noise), with acquire/release/wait reported."""

    def __init__(self, auditor: LockAuditor, lock=None):
        real = lock._inner if isinstance(lock, _AuditedLock) else lock
        super().__init__(real)
        self._graftlint_auditor = auditor
        self._graftlint_site = _creation_site()
        auditor.sites.add(self._graftlint_site)

    def __enter__(self):
        r = super().__enter__()
        self._graftlint_auditor.on_acquire(self)
        return r

    def __exit__(self, *exc):
        self._graftlint_auditor.on_release(self)
        return super().__exit__(*exc)

    def acquire(self, *a):
        got = super().acquire(*a)
        if got:
            self._graftlint_auditor.on_acquire(self)
        return got

    def release(self):
        self._graftlint_auditor.on_release(self)
        super().release()

    def wait(self, timeout=None):
        # wait releases the lock while blocked: mirror that in the held
        # stack so edges recorded by OTHER acquisitions stay truthful
        self._graftlint_auditor.on_release(self)
        try:
            return super().wait(timeout)
        finally:
            self._graftlint_auditor.on_acquire(self)

    def wait_for(self, predicate, timeout=None):
        self._graftlint_auditor.on_release(self)
        try:
            return super().wait_for(predicate, timeout)
        finally:
            self._graftlint_auditor.on_acquire(self)


@contextlib.contextmanager
def lock_audit(auditor: Optional[LockAuditor] = None):
    """Patch threading's lock constructors so every lock allocated inside
    the context is instrumented; yields the LockAuditor. Locks created
    BEFORE entry keep their real, unobserved implementations — construct
    the objects under audit inside the context.

    ``auditor``: a LockAuditor (sub)instance to drive — the runtime race
    checker (`analysis.races.race_audit`) passes one whose
    acquire/release hooks additionally merge vector clocks, so the SAME
    instrumented-lock machinery feeds both the lock-order cross-check
    and the happens-before partial order."""
    auditor = LockAuditor() if auditor is None else auditor
    real_lock, real_rlock = threading.Lock, threading.RLock
    real_cond = threading.Condition

    def make_lock():
        return _AuditedLock(auditor, real_lock())

    def make_rlock():
        return _AuditedLock(auditor, real_rlock())

    def make_cond(lock=None):
        # a bare Condition() must get a REAL inner RLock, not the
        # patched constructor: _AuditedCondition's own overrides are the
        # instrumentation point, and letting Condition.__init__ call the
        # patched RLock() would double-wrap every condvar operation
        # (Python-level acquire + __getattr__ fallbacks for
        # _is_owned/_release_save on the wrapper — measured ~6x the
        # native cost on the decode hot loop) while contributing only
        # self-edges to the order graph
        return _AuditedCondition(auditor,
                                 real_rlock() if lock is None else lock)

    threading.Lock = make_lock
    threading.RLock = make_rlock
    threading.Condition = make_cond
    try:
        yield auditor
    finally:
        threading.Lock = real_lock
        threading.RLock = real_rlock
        threading.Condition = real_cond


def crosscheck_lock_order(observed_edges, graph
                          ) -> Tuple[List[str], List[Tuple[str, str]]]:
    """Join runtime acquisition orders against the static lock graph.

    Returns (violations, unmodeled_edges): violations are combined-graph
    cycles (an observed order contradicting the static order, or a cycle
    the static pass alone missed); unmodeled edges are observed orders
    between statically-known locks the AST pass didn't predict — not an
    error (the static pass is one-level inter-procedural), but the
    watchlist for deepening it. ``graph`` is a
    ``concurrency_rules.LockGraph``.
    """
    from .concurrency_rules import find_cycle
    site_to_id = graph.by_site()
    mapped: Set[Tuple[str, str]] = set()
    for a, b in observed_edges:
        ia, ib = site_to_id.get(tuple(a)), site_to_id.get(tuple(b))
        if ia and ib and ia != ib:
            mapped.add((ia, ib))
    combined = mapped | graph.edge_set
    violations: List[str] = []
    cycle = find_cycle(combined)
    if cycle is not None:
        observed_part = [e for e in zip(cycle, cycle[1:]) if e in mapped]
        violations.append(
            "lock-order cycle in static+observed graph: "
            + " -> ".join(cycle)
            + (f" (runtime-observed edges: {observed_part})"
               if observed_part else ""))
    unmodeled = sorted(e for e in mapped if e not in graph.edge_set)
    return violations, unmodeled


# -- resource ledger (graftleak's runtime half) ----------------------------
# The static lifecycle pass (`analysis/lifecycle.py`) proves the acquire/
# release pairing on paths the AST can see; this ledger proves it on the
# paths a real run actually takes. The engine, kv pool users, mask pool
# users, journal, and fork-group code plant `ledger_note(kind, key, ±1)`
# seams at every acquire/release/transfer site the static registry
# models, keyed by request id. Balances are asserted zero at request end
# (`ledger_check_request`) and at engine/router stop
# (`ledger_check_zero`), and the observed kinds are cross-checked
# against the static registry (`crosscheck_ledger`) — a runtime acquire
# of a kind the static pass does not model FAILS the audit, the same
# discipline as `crosscheck_lock_order`.
#
# Disarmed cost is one module-level dict emptiness test per seam, the
# exact `failpoints.fire()` fast-path shape — safe to leave in the
# production hot loop. Everything else runs under locks.

_LEDGERS: Dict[int, "ResourceLedger"] = {}
_ledgers_lock = threading.Lock()


class ResourceLedger:
    """Balance sheet of (resource kind, request key) acquisitions.

    ``note`` never raises on the noting thread (a broken balance must
    not crash the scheduler mid-request) — violations accumulate and
    the owning test calls :meth:`assert_clean` at the end.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._balances: Dict[Tuple[str, str], int] = {}
        self._kinds: Dict[str, List[int]] = {}  # kind -> [acquires, releases]
        self.violations: List[str] = []
        self._reported: Set[Tuple[str, str]] = set()

    def note(self, kind: str, key: str, delta: int) -> None:
        with self._lock:
            k = (kind, str(key))
            c = self._kinds.setdefault(kind, [0, 0])
            if delta > 0:
                c[0] += delta
            else:
                c[1] += -delta
            bal = self._balances.get(k, 0) + int(delta)
            if bal == 0:
                self._balances.pop(k, None)
                return
            self._balances[k] = bal
            if bal < 0 and k not in self._reported:
                self._reported.add(k)
                self.violations.append(
                    f"over-release: {kind} for request {key!r} went to "
                    f"{bal} (released more than acquired)")

    def check_request(self, key: str, kinds=None) -> None:
        """Request-end invariant: every kind's balance for ``key`` is
        zero. Nonzero balances are recorded (and cleared, so an engine
        stop does not re-report the same debt) as violations.
        ``kinds``: restrict the judgment to the caller's OWN kinds —
        the engine retiring a request must not judge the router's
        still-open journal record for the same request id."""
        key = str(key)
        with self._lock:
            bad = [(k, b) for k, b in self._balances.items()
                   if k[1] == key and (kinds is None or k[0] in kinds)]
            for k, b in bad:
                self._balances.pop(k, None)
                if k not in self._reported:
                    self._reported.add(k)
                    self.violations.append(
                        f"leak at request end: {k[0]} balance {b:+d} "
                        f"for request {key!r}")

    def check_zero(self, scope: str, kinds=None) -> None:
        """Stop-time invariant (engine.stop / router.close): nothing is
        left acquired anywhere. ``kinds`` scopes the judgment like
        :meth:`check_request` (an engine stop judges engine kinds; a
        router close judges its journal records)."""
        with self._lock:
            for k, b in sorted(self._balances.items()):
                if kinds is not None and k[0] not in kinds:
                    continue
                self._balances.pop(k, None)
                if k not in self._reported:
                    self._reported.add(k)
                    self.violations.append(
                        f"leak at {scope}: {k[0]} balance {b:+d} for "
                        f"request {k[1]!r}")

    def forget(self, key: str, kinds=None) -> None:
        """Disown one request's balances WITHOUT judging them — the
        fenced-engine path: a supervisor declared the engine dead and
        requeued the request onto a replacement; the dead engine's pool
        (and every block/pin in it) is garbage-collected wholesale, so
        its per-request debt is not a leak. ``kinds`` scopes the
        disowning like :meth:`check_request`."""
        key = str(key)
        with self._lock:
            for k in [k for k in self._balances
                      if k[1] == key and (kinds is None or k[0] in kinds)]:
                self._balances.pop(k, None)

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {
                "balances": {f"{k}:{key}": b for (k, key), b
                             in sorted(self._balances.items())},
                "kinds": {k: {"acquires": c[0], "releases": c[1]}
                          for k, c in sorted(self._kinds.items())},
            }

    def observed_kinds(self) -> Set[str]:
        with self._lock:
            return set(self._kinds)

    def assert_clean(self) -> None:
        """Final gate for tests: zero balances AND zero recorded
        violations, with the whole charge sheet in the message."""
        with self._lock:
            self.violations.extend(
                f"unchecked residue: {k[0]} balance {b:+d} for request "
                f"{k[1]!r}" for k, b in sorted(self._balances.items()))
            self._balances.clear()
            charges = list(self.violations)
        if charges:
            raise AssertionError(
                "resource ledger is not balanced:\n  "
                + "\n  ".join(charges))


def ledger_note(kind: str, key: str, delta: int) -> None:
    """The seam call. Disarmed: one dict emptiness test, nothing else
    (the failpoints.fire fast-path discipline — GIL-atomic read; a note
    racing an arm either sees it or misses that one event, and tests
    arm the ledger before starting the engine)."""
    if not _LEDGERS:  # graftlint: disable=CC005
        return
    with _ledgers_lock:
        ledgers = list(_LEDGERS.values())
    for led in ledgers:
        led.note(kind, key, delta)


def ledger_check_request(key: str, kinds=None) -> None:
    """Request-end seam (engine retire/evict/fail paths)."""
    if not _LEDGERS:  # graftlint: disable=CC005
        return
    with _ledgers_lock:
        ledgers = list(_LEDGERS.values())
    for led in ledgers:
        led.check_request(key, kinds)


def ledger_check_zero(scope: str, kinds=None) -> None:
    """Stop-time seam (engine.stop / router.close)."""
    if not _LEDGERS:  # graftlint: disable=CC005
        return
    with _ledgers_lock:
        ledgers = list(_LEDGERS.values())
    for led in ledgers:
        led.check_zero(scope, kinds)


def ledger_forget(key: str, kinds=None) -> None:
    """Fence/crash-recovery seam: disown a request's balances."""
    if not _LEDGERS:  # graftlint: disable=CC005
        return
    with _ledgers_lock:
        ledgers = list(_LEDGERS.values())
    for led in ledgers:
        led.forget(key, kinds)


@contextlib.contextmanager
def resource_ledger(crosscheck: bool = True):
    """Arm a ResourceLedger for the duration of the context and yield
    it. On exit the ledger is disarmed and (by default) cross-checked
    against the static registry — violations accumulate on the ledger;
    call ``led.assert_clean()`` to judge them."""
    led = ResourceLedger()
    with _ledgers_lock:
        _LEDGERS[id(led)] = led
    try:
        yield led
    finally:
        with _ledgers_lock:
            _LEDGERS.pop(id(led), None)
        if crosscheck:
            violations, _unmodeled = crosscheck_ledger(led)
            led.violations.extend(violations)


def crosscheck_ledger(ledger: ResourceLedger
                      ) -> Tuple[List[str], List[str]]:
    """Join the runtime-observed resource kinds against the static
    lifecycle registry (lazy import — hot-path modules import this
    module, and must not drag the AST machinery in).

    Returns (violations, silent_kinds): a kind the runtime observed
    that the static registry does not model is a VIOLATION (an
    unmodeled acquire site — the static pass is blind to it, so the
    two-sided guarantee is broken); a registered kind the run never
    exercised is merely reported as silent (workloads differ)."""
    from .lifecycle import registry_kinds
    known = registry_kinds()
    observed = ledger.observed_kinds()
    violations = [
        f"unmodeled resource kind {k!r}: runtime seams note it, but "
        f"the static lifecycle registry does not model it"
        for k in sorted(observed - known)]
    silent = sorted(known - observed)
    return violations, silent
