"""graftleak: static resource-lifecycle analysis (LC001-LC004).

The serving stack's dominant hand-fixed bug class is the resource
lifecycle leak: a cancel path that forgets the trie pin, a stream
disconnect that strands a slot's pool blocks, a journal accept whose
error path never writes the terminal record. Each one was found by a
failing chaos test *after* it shipped. This pass makes the acquire/
release discipline machine-checked, the same two-sided shape as
`races.py`: a static pass here, a cross-checked runtime ledger in
`runtime.py` (`resource_ledger` — every lifecycle seam the engine and
router plant notes into it, and the observed resource kinds are
cross-checked against THIS module's registry, so an acquire site the
static pass does not model fails the audit instead of hiding).

The static pass is a **path-sensitive intraprocedural walk** over each
function's statements — branches, loops (bounded unrolling), early
returns, `continue`/`break`, `try`/`except`/`finally`, and exception
exits from explicit `raise` — driven by the declarative
:data:`REGISTRY` of the repo's real resource kinds:

  trie pins       ``KVPool.match`` -> ``release`` (engine slot pins)
  pool blocks     ``alloc`` -> ``free_block``; ownership transfers out
                  via ``adopt`` (publish/COW)
  mask rows       ``MaskPool.acquire`` -> ``release``/``evict``
  journal records ``accept`` -> exactly one terminal ``finish``/``fail``
  engine slots    admit -> free (index stores; runtime-ledger tracked)
  fork-group refs bind/attach -> handle finish (runtime-ledger tracked)
  streams/sockets ``urlopen`` -> ``close`` (with-statement counts)

Rules:

  LC001  acquire-escapes-scope-unreleased: some path out of the
         function (return, fall-off, or raise) still holds an acquired
         handle, with no paired release, no ``finally`` that releases,
         and no modeled ownership transfer.
  LC002  possible-double-release: a release is reachable twice for the
         same handle with no first-finisher guard (the
         ``if x is not None: release(x); x = None`` idiom) in between.
  LC003  acquired-handle-stored-lock-free outside the owner set: the
         handle lands in an attribute the cleanup path does NOT walk,
         with no lock held — the cleanup sweep will never find it.
  LC004  accept-without-terminal: an exactly-once pair (journal
         ``accept``) has an exit path with neither a terminal
         ``finish``/``fail`` nor a modeled hand-off.

**Transfer semantics** (what discharges an obligation): releasing it;
storing the handle into a registered owner attribute (the structure
the cleanup path walks); returning it (the caller now owns it);
passing it as a bare positional argument to another call (hand-off —
`_dispatch_stream(handler, rid, ...)` owns the journal contract from
there); passing it into a registry ``transfer`` method (``adopt``);
or acquiring it under a ``with`` (the context manager releases).

**Blind spots** (documented, deliberate — see docs/static_analysis.md):
the pass is intraprocedural, so an obligation handed to a helper is
trusted, not followed; calls are assumed non-raising (exception edges
come from explicit ``raise`` statements, plus every ``except`` handler
being analyzed against the state at each point of its ``try`` body);
and index-store resources (engine slots, fork refs) have no
call-shaped acquire for the AST to see — the runtime ledger covers
those, which is why the two sides cross-check.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from .core import Finding, ModuleInfo, Rule, dotted_name

__all__ = ["ResourceSpec", "REGISTRY", "registry_kinds", "RULES"]


# ---------------------------------------------------------------------------
# the declarative ownership registry (shared with runtime.resource_ledger)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ResourceSpec:
    """One resource kind's lifecycle vocabulary.

    ``receivers`` gates matches: the call's receiver (the dotted name
    before the method, last component, leading underscores stripped)
    must contain one of the fragments — this is what keeps
    ``re.match`` / ``lock.acquire`` / ``lock.release`` out of the
    trie-pin and mask-row kinds. Empty receivers = bare-callable match
    on the dotted name's last component (``urlopen``).

    ``owners``: attribute names the cleanup path walks — storing the
    handle there IS the transfer that discharges the obligation
    (``seq.pool_node``, ``seq.block_ids``, ``proc.mask_base``).

    ``ledger_only``: no call-shaped acquire exists for the static pass
    (slots are index stores, fork refs release at handle finish) — the
    kind is registered for the runtime ledger and the crosscheck, and
    the static walk skips it.
    """

    kind: str
    acquire: Tuple[str, ...] = ()
    release: Tuple[str, ...] = ()
    transfer: Tuple[str, ...] = ()
    owners: Tuple[str, ...] = ()
    receivers: Tuple[str, ...] = ()
    terminal: Tuple[str, ...] = ()   # exactly-once terminal methods
    exactly_once: bool = False
    release_on_handle: bool = False  # handle.close() vs pool.release(h)
    ledger_only: bool = False
    doc: str = ""


REGISTRY: Tuple[ResourceSpec, ...] = (
    ResourceSpec(
        kind="trie_pin",
        acquire=("match",), release=("release",),
        owners=("pool_node",), receivers=("pool", "trie"),
        doc="KVPool.match pins the deepest hit node (node.lock += 1); "
            "the pin is dropped by KVPool.release via the engine's "
            "single _release_pool path."),
    ResourceSpec(
        kind="pool_block",
        acquire=("alloc",), release=("free_block",),
        transfer=("adopt",),
        owners=("block_ids",), receivers=("pool",),
        doc="KVPool.alloc claims one page; free_block returns it; "
            "adopt transfers ownership to the trie at publish "
            "(the caller must NOT free adopted ids)."),
    ResourceSpec(
        kind="mask_row",
        acquire=("acquire",), release=("release", "evict"),
        owners=("mask_base",), receivers=("maskpool", "mask_pool", "masks"),
        doc="MaskPool.acquire refs a grammar's device mask rows; "
            "release drops the ref (rows stay cached until evict)."),
    ResourceSpec(
        kind="journal_record",
        acquire=("accept",), terminal=("finish", "fail"),
        receivers=("journal",), exactly_once=True,
        doc="RequestJournal.accept opens a durable record that MUST "
            "reach exactly one terminal finish/fail, or replay wedges "
            "on it forever."),
    ResourceSpec(
        kind="engine_slot",
        receivers=("slots",), ledger_only=True,
        doc="Slot occupancy is an index store (_slots[i] = seq), "
            "invisible to the call-shaped static pass — tracked by "
            "the runtime ledger at admit/free."),
    ResourceSpec(
        kind="fork_ref",
        receivers=("fork", "group"), ledger_only=True,
        doc="Fork-group membership releases at handle finish, not via "
            "a paired call — tracked by the runtime ledger across "
            "submit_fork_group/await_fork_group."),
    ResourceSpec(
        kind="stream",
        acquire=("urlopen",), release=("close",),
        release_on_handle=True,
        doc="HTTP/socket response bodies must be closed on every path "
            "(a with-statement counts); an unclosed SSE body strands "
            "the replica-side cancel-on-disconnect."),
    ResourceSpec(
        kind="host_page",
        receivers=("tier", "host"), ledger_only=True,
        doc="One spilled KV block resident in the TierManager host "
            "ring — acquired by the worker's host insert, released on "
            "LRU demotion/drop/stop; keyed by chain hash and balanced "
            "by the runtime ledger through spill→restore→free."),
    ResourceSpec(
        kind="disk_block",
        receivers=("tier", "disk"), ledger_only=True,
        doc="One CRC-framed block file in the TierManager disk store "
            "— acquired at host-overflow demotion, released on disk "
            "eviction or stop (files persist; the ledger models "
            "in-process ownership only)."),
    ResourceSpec(
        kind="directory_entry",
        receivers=("tier", "directory"), ledger_only=True,
        doc="One chain hash tracked in the prefix directory (any "
            "tier) — acquired at note_resident/insert_fetched, "
            "released when the block falls off the bottom tier."),
)


def registry_kinds() -> set:
    """Every registered kind name — the runtime crosscheck's model."""
    return {s.kind for s in REGISTRY}


_STATIC_SPECS = tuple(s for s in REGISTRY if not s.ledger_only)

# receiver fragments that mark a with-item as a lock (LC003's "stored
# lock-free" judgment) — the same vocabulary concurrency_rules uses
_LOCKISH = ("lock", "cond", "mutex", "sem")


def _receiver_matches(recv_last: str, spec: ResourceSpec) -> bool:
    if not spec.receivers:
        return True
    name = recv_last.lstrip("_").lower()
    return any(frag in name for frag in spec.receivers)


def _split_call(call: ast.Call) -> Tuple[str, str]:
    """(receiver-last-component, method) for ``a.b.pool.match(...)`` ->
    ("pool", "match"); a bare call ``urlopen(...)`` / dotted function
    ``urllib.request.urlopen(...)`` -> ("", last-component)."""
    fn = call.func
    if isinstance(fn, ast.Attribute):
        recv = fn.value
        if isinstance(recv, (ast.Name, ast.Attribute)):
            d = dotted_name(recv)
            last = d.rsplit(".", 1)[-1] if d else ""
            return last, fn.attr
        return "", fn.attr
    d = dotted_name(fn)
    return "", d.rsplit(".", 1)[-1] if d else ""


def _classify(call: ast.Call) -> List[Tuple[str, ResourceSpec]]:
    """Every (role, spec) this call plays: role in acquire | release |
    transfer | terminal. A method name can match several kinds
    (``maskpool`` contains both the mask_row and trie_pin receiver
    fragments) — each role resolves to the single spec whose receiver
    fragment matches MOST SPECIFICALLY (longest fragment wins), so one
    call never plays the same role for two kinds. Empty-receiver specs
    (``urlopen``/``close``) match at the lowest specificity."""
    recv, meth = _split_call(call)
    name = recv.lstrip("_").lower()
    best: Dict[str, Tuple[int, ResourceSpec]] = {}

    def consider(role: str, spec: ResourceSpec, score: int) -> None:
        cur = best.get(role)
        if cur is None or score > cur[0]:
            best[role] = (score, spec)

    for spec in _STATIC_SPECS:
        if spec.receivers:
            if not recv:
                continue  # provider-shaped kinds need a receiver
            matched = [f for f in spec.receivers if f in name]
            if not matched:
                continue
            score = max(len(f) for f in matched)
        else:
            # bare-callable (urlopen) and handle-released (X.close)
            # kinds: matched on the method name alone, the receiver —
            # if any — IS the handle, judged against tracked state
            score = 0
        if meth in spec.acquire:
            consider("acquire", spec, score)
        if meth in spec.release:
            consider("release", spec, score)
        if meth in spec.transfer:
            consider("transfer", spec, score)
        if meth in spec.terminal:
            consider("terminal", spec, score)
    return [(role, spec) for role, (_, spec) in best.items()]


def _attr_path(node) -> str:
    """'seq.pool_node' for an Attribute chain rooted at a Name, '' if
    the root is anything else (a call, a subscript)."""
    return dotted_name(node) if isinstance(node, ast.Attribute) else ""


# ---------------------------------------------------------------------------
# abstract state: tracked handles along one path
# ---------------------------------------------------------------------------

_HELD = "held"
_RELEASED = "released"
_NONE = "none"        # provably no resource behind the name
_NOTNONE = "notnone"  # refinement fact: the name tested not-None on
                      # this path (correlates repeated `if x is not
                      # None:` guards — the journal accept/terminal
                      # pairs both sit under the same test)
_UNKNOWN = "unknown"  # release-site pseudo handle (never acquired here)


class _H:
    """One tracked handle (or release-site pseudo handle) on one path."""

    __slots__ = ("hid", "spec", "status", "node", "names", "pending")

    def __init__(self, hid: str, spec: ResourceSpec, status: str,
                 node, names: frozenset, pending: bool):
        self.hid = hid
        self.spec = spec
        self.status = status
        self.node = node          # acquire site (finding anchor)
        self.names = names        # alias names bound to this handle
        self.pending = pending    # carries an LC001/LC004 obligation

    def clone(self) -> "_H":
        return _H(self.hid, self.spec, self.status, self.node,
                  self.names, self.pending)


class _State:
    """Handle map for one path. Cheap to clone; merged by signature."""

    __slots__ = ("handles",)

    def __init__(self, handles: Optional[Dict[str, _H]] = None):
        self.handles: Dict[str, _H] = handles or {}

    def clone(self) -> "_State":
        return _State({k: h.clone() for k, h in self.handles.items()})

    def sig(self) -> tuple:
        return tuple(sorted((k, h.status, h.pending)
                            for k, h in self.handles.items()))

    def by_name(self, name: str) -> Optional[_H]:
        for h in self.handles.values():
            if name in h.names:
                return h
        return None

    def unbind(self, name: str) -> None:
        """A fresh assignment to ``name`` detaches it from any handle
        (the handle itself keeps its obligation under its other
        aliases, or anonymously)."""
        for h in self.handles.values():
            if name in h.names:
                h.names = h.names - {name}


@dataclass
class _Exit:
    kind: str            # "return" | "raise" | "break" | "continue" | "off"
    node: object
    state: _State


def _merge(states: List[_State], cap: int = 160) -> List[_State]:
    seen, out = set(), []
    for s in states:
        k = s.sig()
        if k not in seen:
            seen.add(k)
            out.append(s)
        if len(out) >= cap:
            break
    return out


# ---------------------------------------------------------------------------
# the path walker
# ---------------------------------------------------------------------------

class _FnWalk:
    """Path-sensitive walk of one function body."""

    def __init__(self, mod: ModuleInfo, func, findings: List[Finding],
                 own_methods: frozenset):
        self.mod = mod
        self.func = func
        self.findings = findings
        self.own_methods = own_methods  # enclosing class defines these
        self.lock_depth = 0
        self.reported: set = set()  # (rule, site-key) dedup

    # -- finding emission --------------------------------------------------

    def _emit(self, rule: str, node, message: str, key) -> None:
        if (rule, key) in self.reported:
            return
        self.reported.add((rule, key))
        self.findings.append(self.mod.finding(rule, node, message))

    # -- entry -------------------------------------------------------------

    def run(self) -> None:
        outs, exits = self._block(self.func.body, [_State()])
        for s in outs:
            self._check_exit(s, self.func, "falls off the end")
        for e in exits:
            if e.kind == "return":
                self._check_exit(e.state, e.node, "returns")
            elif e.kind == "raise":
                self._check_exit(e.state, e.node, "raises")

    def _check_exit(self, state: _State, node, how: str) -> None:
        fname = self.func.name
        for h in state.handles.values():
            if not h.pending or h.status != _HELD:
                continue
            if h.spec.exactly_once:
                self._emit(
                    "LC004", h.node,
                    f"{h.spec.kind} accepted here has an exit path "
                    f"('{fname}' {how}) with no terminal "
                    f"{'/'.join(h.spec.terminal)} and no hand-off",
                    h.hid)
            else:
                self._emit(
                    "LC001", h.node,
                    f"{h.spec.kind} acquired here escapes '{fname}' "
                    f"unreleased (path {how} with no release, "
                    f"transfer, or owner-attribute store)",
                    h.hid)

    # -- block/statement dispatch -----------------------------------------

    def _block(self, stmts, states: List[_State]
               ) -> Tuple[List[_State], List[_Exit]]:
        exits: List[_Exit] = []
        cur = states
        for st in stmts:
            if not cur:
                break
            cur, ex = self._stmt(st, cur)
            exits.extend(ex)
            cur = _merge(cur)
        return cur, exits

    def _stmt(self, st, states: List[_State]
              ) -> Tuple[List[_State], List[_Exit]]:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            return states, []  # analyzed separately
        if isinstance(st, ast.If):
            return self._if(st, states)
        if isinstance(st, (ast.While, ast.For, ast.AsyncFor)):
            return self._loop(st, states)
        if isinstance(st, ast.Try):
            return self._try(st, states)
        if isinstance(st, (ast.With, ast.AsyncWith)):
            return self._with(st, states)
        if isinstance(st, ast.Return):
            states = [self._flat(st, s, returning=st.value) for s in states]
            return [], [_Exit("return", st, s) for s in states]
        if isinstance(st, ast.Raise):
            states = [self._flat(st, s) for s in states]
            return [], [_Exit("raise", st, s) for s in states]
        if isinstance(st, ast.Break):
            return [], [_Exit("break", st, s) for s in states]
        if isinstance(st, ast.Continue):
            return [], [_Exit("continue", st, s) for s in states]
        # flat statement: Assign / AugAssign / AnnAssign / Expr / ...
        return [self._flat(st, s) for s in states], []

    # -- branches ----------------------------------------------------------

    def _if(self, st: ast.If, states: List[_State]):
        t_states, f_states = [], []
        for s in states:
            t, f = self._refine(st.test, s)
            if t is not None:
                t_states.append(t)
            if f is not None:
                f_states.append(f)
        t_out, t_ex = self._block(st.body, t_states)
        f_out, f_ex = (self._block(st.orelse, f_states) if st.orelse
                       else (f_states, []))
        return _merge(t_out + f_out), t_ex + f_ex

    def _refine(self, test, s: _State
                ) -> Tuple[Optional[_State], Optional[_State]]:
        """(state-if-true, state-if-false); None = branch infeasible.
        Understands ``x is None`` / ``x is not None`` / bare ``x`` /
        ``not x`` over handle names and owner-attribute paths — enough
        to recognize the first-finisher guard idiom."""
        name, positive = self._none_test(test)
        if name is None:
            return s.clone(), s.clone()
        # positive=True: test is "x is not None"-shaped (truthy = bound)
        h = s.by_name(name)
        if h is None:
            t, f = s.clone(), s.clone()
            # learn from the refinement on BOTH sides: the None side
            # kills later infeasible releases, the not-None side keeps
            # a later identical guard correlated (the journal accept
            # and its terminal both sit under `if self.journal is not
            # None:` — without this fact the second guard invents an
            # infeasible journal-vanished path)
            (f if positive else t).handles[f"~{name}"] = _H(
                f"~{name}", _STATIC_SPECS[0], _NONE, test,
                frozenset([name]), False)
            (t if positive else f).handles[f"~{name}"] = _H(
                f"~{name}", _STATIC_SPECS[0], _NOTNONE, test,
                frozenset([name]), False)
            return t, f
        if h.status == _NONE:
            return (None, s.clone()) if positive else (s.clone(), None)
        if h.status == _NOTNONE:
            return (s.clone(), None) if positive else (None, s.clone())
        t, f = s.clone(), s.clone()
        fh = f.by_name(name) if positive else t.by_name(name)
        if fh is not None:
            fh.status = _NONE
            fh.pending = False
        return t, f

    @staticmethod
    def _none_test(test) -> Tuple[Optional[str], bool]:
        """(name, positive) where positive means the TRUE branch has
        the name bound/not-None. Returns (None, _) when the test shape
        is not understood."""
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            name, pos = _FnWalk._none_test(test.operand)
            return name, (not pos if name is not None else pos)
        if isinstance(test, ast.Compare) and len(test.ops) == 1 \
                and isinstance(test.comparators[0], ast.Constant) \
                and test.comparators[0].value is None:
            tgt = test.left
            name = (tgt.id if isinstance(tgt, ast.Name)
                    else _attr_path(tgt))
            if not name:
                return None, True
            if isinstance(test.ops[0], ast.Is):
                return name, False
            if isinstance(test.ops[0], ast.IsNot):
                return name, True
            return None, True
        if isinstance(test, ast.Name):
            return test.id, True
        if isinstance(test, ast.Attribute):
            p = _attr_path(test)
            return (p or None), True
        return None, True

    # -- loops -------------------------------------------------------------

    def _loop(self, st, states: List[_State]):
        infinite = (isinstance(st, ast.While)
                    and isinstance(st.test, ast.Constant)
                    and bool(st.test.value))
        out: List[_State] = [] if infinite else [s.clone() for s in states]
        exits: List[_Exit] = []
        cur = states
        for _ in range(2):  # bounded unroll: catches cross-iteration
            # double releases and acquire-per-iteration leaks
            if not cur:
                break
            if isinstance(st, (ast.For, ast.AsyncFor)):
                cur = [self._assign_target(st.target, None, s, st)
                       for s in cur]
            body_out, body_ex = self._block(st.body, cur)
            nxt = list(body_out)
            for e in body_ex:
                if e.kind == "break":
                    out.append(e.state)
                elif e.kind == "continue":
                    nxt.append(e.state)
                else:
                    exits.append(e)
            cur = _merge(nxt)
        if not infinite:
            out.extend(cur)  # loop condition eventually false
        if st.orelse:
            out, else_ex = self._block(st.orelse, _merge(out))
            exits.extend(else_ex)
        return _merge(out), exits

    # -- try/except/finally ------------------------------------------------

    def _try(self, st: ast.Try, states: List[_State]):
        handler_pool: List[_State] = [s.clone() for s in states]
        cur = states
        body_exits: List[_Exit] = []
        for sub in st.body:
            if not cur:
                break
            cur, ex = self._stmt(sub, cur)
            body_exits.extend(ex)
            cur = _merge(cur)
            # an exception may occur at any point in the try body: the
            # state right after each statement feeds the handlers too.
            # Handles whose acquire SITE lies inside this statement are
            # stripped from the exceptional edge — an acquire that
            # raises acquired nothing (its failure mode is the
            # pre-state, which is already in the pool). Keyed by source
            # span, not handle identity, so a loop-unrolled re-acquire
            # (same site id, second iteration) is stripped too.
            lo = getattr(sub, "lineno", None)
            hi = getattr(sub, "end_lineno", lo) or lo
            for s in cur:
                snap = s.clone()
                for hid, h in list(snap.handles.items()):
                    ln = getattr(h.node, "lineno", None)
                    if (h.status == _HELD and ln is not None
                            and lo is not None and lo <= ln <= hi):
                        del snap.handles[hid]
                handler_pool.append(snap)
        out: List[_State] = []
        exits: List[_Exit] = []
        raised_in = [e for e in body_exits if e.kind == "raise"]
        passed = [e for e in body_exits if e.kind != "raise"]
        if st.handlers:
            handler_pool.extend(e.state for e in raised_in)
            handler_pool = _merge(handler_pool)
            for h in st.handlers:
                entry = [s.clone() for s in handler_pool]
                if h.name:  # `except E as e:` rebinds e fresh
                    for s in entry:
                        s.unbind(h.name)
                h_out, h_ex = self._block(h.body, entry)
                out.extend(h_out)
                exits.extend(h_ex)
        else:
            exits.extend(raised_in)
        if st.orelse and cur:
            cur, else_ex = self._block(st.orelse, cur)
            exits.extend(else_ex)
        out.extend(cur)
        exits.extend(passed)
        if st.finalbody:
            fin_out, fin_ex = self._block(st.finalbody, _merge(out))
            out = fin_out
            exits = [e for e in exits]  # each exit flows through finally
            routed: List[_Exit] = list(fin_ex)
            for e in exits:
                f_out, f_ex = self._block(st.finalbody, [e.state])
                routed.extend(f_ex)
                routed.extend(_Exit(e.kind, e.node, s) for s in f_out)
            exits = routed
        return _merge(out), exits

    # -- with --------------------------------------------------------------

    def _with(self, st, states: List[_State]):
        locks = 0
        for item in st.items:
            ce = item.context_expr
            if isinstance(ce, ast.Call):
                for role, spec in _classify(ce):
                    if role == "acquire":
                        # context-managed acquire: released at exit by
                        # construction — bind the as-name with NO
                        # pending obligation so releases inside still
                        # resolve to it
                        states = [self._bind_acquire(
                            ce, spec, item.optional_vars, s,
                            pending=False) for s in states]
                        break
            last = dotted_name(ce if not isinstance(ce, ast.Call)
                               else ce.func).rsplit(".", 1)[-1]
            if any(f in last.lstrip("_").lower() for f in _LOCKISH):
                locks += 1
        self.lock_depth += locks
        out, exits = self._block(st.body, states)
        self.lock_depth -= locks
        return out, exits

    # -- flat statements ---------------------------------------------------

    def _flat(self, st, state: _State, returning=None) -> _State:
        """Apply one non-branching statement: releases, transfers,
        terminals, escapes, acquires, and binding/unbinding."""
        s = state.clone()
        calls = [n for n in ast.walk(st) if isinstance(n, ast.Call)]
        acquires: List[Tuple[ast.Call, ResourceSpec]] = []
        for call in calls:
            for role, spec in _classify(call):
                if role == "acquire":
                    if self._is_own_method(call, spec):
                        continue
                    acquires.append((call, spec))
                elif role == "release":
                    self._apply_release(call, spec, s)
                elif role == "transfer":
                    self._apply_transfer(call, s)
                elif role == "terminal":
                    self._apply_terminal(spec, s)
        # hand-off escape: a tracked name passed as a bare positional
        # argument to any call transfers the obligation to the callee
        for call in calls:
            for arg in call.args:
                if isinstance(arg, ast.Name):
                    h = s.by_name(arg.id)
                    if h is not None and h.status == _HELD:
                        h.pending = False
        # binding
        if isinstance(st, ast.Assign):
            self._apply_assign(st.targets, st.value, acquires, s, st)
        elif isinstance(st, ast.AnnAssign) and st.value is not None:
            self._apply_assign([st.target], st.value, acquires, s, st)
        else:
            for call, spec in acquires:
                self._new_handle(call, spec, s, frozenset())
        if returning is not None:
            # returning the handle transfers it to the caller
            for n in ast.walk(returning):
                if isinstance(n, ast.Name):
                    h = s.by_name(n.id)
                    if h is not None:
                        h.pending = False
            for h in s.handles.values():
                if h.node is not None and any(
                        h.node is c for c in ast.walk(returning)):
                    h.pending = False
        return s

    def _is_own_method(self, call: ast.Call, spec: ResourceSpec) -> bool:
        """`self.match(...)` inside the class that DEFINES match is the
        resource implementation, not a client — skip it. (In practice
        the receiver gate already drops bare-`self` receivers; this
        guards fixture classes named e.g. FakePool calling their own
        acquire.)"""
        recv, meth = _split_call(call)
        return meth in self.own_methods and recv in ("self", "cls")

    # acquire binding ------------------------------------------------------

    def _new_handle(self, call: ast.Call, spec: ResourceSpec,
                    s: _State, names: frozenset) -> _H:
        # deterministic per acquire SITE (not per path): every path
        # through one site shares the finding key, so a leak reports
        # once; a loop's re-acquire overwrites the same slot
        hid = (f"{spec.kind}@{getattr(call, 'lineno', 0)}:"
               f"{getattr(call, 'col_offset', 0)}")
        if spec.exactly_once and not names and call.args \
                and isinstance(call.args[0], ast.Name):
            # bind the exactly-once key (journal.accept(rid, ...)) so
            # passing `rid` onward positionally counts as the hand-off
            names = frozenset([call.args[0].id])
        for n in names:
            s.unbind(n)
        h = _H(hid, spec, _HELD, call, names, pending=True)
        s.handles[hid] = h
        return h

    def _bind_acquire(self, call: ast.Call, spec: ResourceSpec,
                      optional_vars, s: _State, pending: bool) -> _State:
        s = s.clone()
        names = frozenset()
        if isinstance(optional_vars, ast.Name):
            names = frozenset([optional_vars.id])
        h = self._new_handle(call, spec, s, names)
        h.pending = pending
        return s

    def _apply_assign(self, targets, value, acquires, s: _State, st):
        """Bind acquire results (aliasing every tuple-unpack target),
        handle `x = None` guards resets, owner-attribute stores, and
        LC003 lock-free stores outside the owner set."""
        # value-side acquires bound to the targets
        bound = False
        for call, spec in acquires:
            if value is call or (isinstance(value, ast.Tuple)
                                 and any(e is call for e in value.elts)):
                names = set()
                attr_store = None
                for t in targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
                    elif isinstance(t, ast.Tuple):
                        names.update(e.id for e in t.elts
                                     if isinstance(e, ast.Name))
                    elif isinstance(t, ast.Attribute):
                        attr_store = t
                h = self._new_handle(call, spec, s, frozenset(names))
                if attr_store is not None:
                    self._store_to_attr(h, attr_store, s)
                bound = True
            else:
                self._new_handle(call, spec, s, frozenset())
                bound = True
        if bound:
            return
        # x = None: first-finisher guard reset; x = <expr>: rebind
        for t in targets:
            if isinstance(t, ast.Name) or isinstance(t, ast.Attribute):
                name = (t.id if isinstance(t, ast.Name)
                        else _attr_path(t))
                if not name:
                    continue
                if isinstance(value, ast.Constant) and value.value is None:
                    h = s.by_name(name)
                    if h is not None:
                        h.status = _NONE
                        h.pending = False
                    else:
                        s.handles[f"~{name}"] = _H(
                            f"~{name}", _STATIC_SPECS[0], _NONE, st,
                            frozenset([name]), False)
                elif isinstance(value, ast.Name):
                    # alias or owner-store of an existing handle
                    h = s.by_name(value.id)
                    if h is not None:
                        if isinstance(t, ast.Attribute):
                            self._store_to_attr(h, t, s)
                        else:
                            s.unbind(t.id)
                            h.names = h.names | {t.id}
                    else:
                        s.unbind(name)
                else:
                    s.unbind(name)

    def _store_to_attr(self, h: _H, target: ast.Attribute,
                       s: _State) -> None:
        attr = target.attr
        if h.status != _HELD:
            return
        if attr in h.spec.owners:
            h.pending = False  # transferred into the cleanup-walked owner
            return
        if self.lock_depth == 0 and h.spec.owners:
            self._emit(
                "LC003", target,
                f"{h.spec.kind} handle stored lock-free to attribute "
                f"'{attr}', which is outside the owner set "
                f"{list(h.spec.owners)} the cleanup path walks",
                (getattr(target, "lineno", 0), attr))
        # stored on an object: the intraprocedural obligation ends
        # either way (object lifetime owns it now — documented blind
        # spot; LC003 above is the alarm for the lock-free case)
        h.pending = False

    # release / transfer / terminal ---------------------------------------

    def _apply_release(self, call: ast.Call, spec: ResourceSpec,
                       s: _State) -> None:
        target = None
        if spec.release_on_handle:
            fn = call.func
            if isinstance(fn, ast.Attribute):
                target = fn.value
        elif call.args:
            target = call.args[0]
        name = None
        if isinstance(target, ast.Name):
            name = target.id
        elif isinstance(target, ast.Attribute):
            name = _attr_path(target)
        if name:
            h = s.by_name(name)
            if spec.release_on_handle and (h is None or h.spec is not spec):
                # handle-released kinds (file/socket close) are
                # idempotent by contract and `close` is a common method
                # name (`os.close(fd)` receiver is the os MODULE):
                # only a receiver we tracked from its acquire counts,
                # and double-close is never reported
                return
            if h is None:
                hid = f"~rel:{spec.kind}:{name}"
                s.handles[hid] = _H(hid, spec, _RELEASED, call,
                                    frozenset([name]), False)
                return
            if h.status == _RELEASED and spec.release_on_handle:
                return
            if h.status == _RELEASED:
                self._emit(
                    "LC002", call,
                    f"possible double-release of {h.spec.kind} handle "
                    f"'{name}' — already released on this path with no "
                    f"first-finisher guard (`if x is not None: "
                    f"release; x = None`) in between",
                    getattr(call, "lineno", 0))
                return
            if h.status == _NONE:
                return  # infeasible under the guard refinement
            h.status = _RELEASED
            h.pending = False
            return
        # untargetable arg (literal, call result): provider-level
        # release — discharge every held handle of this kind
        for h in s.handles.values():
            if h.spec.kind == spec.kind and h.status == _HELD:
                h.status = _RELEASED
                h.pending = False

    def _apply_transfer(self, call: ast.Call, s: _State) -> None:
        """adopt: any tracked handle named ANYWHERE in the args
        (including inside list literals / slices) moves to the pool."""
        for arg in list(call.args) + [kw.value for kw in call.keywords]:
            for n in ast.walk(arg):
                if isinstance(n, ast.Name):
                    h = s.by_name(n.id)
                    if h is not None and h.status == _HELD:
                        h.pending = False

    def _apply_terminal(self, spec: ResourceSpec, s: _State) -> None:
        for h in s.handles.values():
            if h.spec.kind == spec.kind and h.spec.exactly_once:
                h.status = _RELEASED
                h.pending = False

    # target helper for For loops -----------------------------------------

    def _assign_target(self, target, value, s: _State, st) -> _State:
        s = s.clone()
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                s.unbind(n.id)
        return s


# ---------------------------------------------------------------------------
# per-module analysis, cached once and shared by the four rules
# ---------------------------------------------------------------------------

_QUICK_NAMES = frozenset(
    m for spec in _STATIC_SPECS
    for m in spec.acquire + spec.release + spec.transfer + spec.terminal)


def _module_findings(mod: ModuleInfo) -> List[Finding]:
    cached = getattr(mod, "_graftleak_findings", None)
    if cached is not None:
        return cached
    findings: List[Finding] = []
    # map each function to the method names its enclosing class defines
    class_methods: Dict[int, frozenset] = {}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ClassDef):
            meths = frozenset(
                n.name for n in node.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)))
            for n in node.body:
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    class_methods[id(n)] = meths
    for node in ast.walk(mod.tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # cheap pre-gate: skip functions that never name a registry
        # method (the overwhelming majority of the package)
        wanted = False
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr in _QUICK_NAMES:
                wanted = True
                break
            if isinstance(sub, ast.Name) and sub.id in _QUICK_NAMES:
                wanted = True
                break
        if not wanted:
            continue
        _FnWalk(mod, node, findings,
                class_methods.get(id(node), frozenset())).run()
    mod._graftleak_findings = findings
    return findings


class _LifecycleRule(Rule):
    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        return [f for f in _module_findings(mod) if f.rule == self.id]


class LifecycleLeak(_LifecycleRule):
    id = "LC001"
    name = "acquire-escapes-scope-unreleased"
    description = ("An acquired resource handle reaches a function exit "
                   "(return, fall-off, raise) with no paired release, "
                   "finally, or modeled ownership transfer.")


class LifecycleDoubleRelease(_LifecycleRule):
    id = "LC002"
    name = "possible-double-release"
    description = ("The same handle's release is reachable twice on one "
                   "path with no first-finisher guard in between.")


class LifecycleUnguardedStore(_LifecycleRule):
    id = "LC003"
    name = "handle-stored-lock-free-outside-owners"
    description = ("An acquired handle is stored, with no lock held, "
                   "into an attribute outside the registered owner set "
                   "the cleanup path walks.")


class LifecycleAcceptNoTerminal(_LifecycleRule):
    id = "LC004"
    name = "accept-without-terminal"
    description = ("A journal-style exactly-once pair has an exit path "
                   "with neither a terminal finish/fail nor a hand-off.")


RULES = (LifecycleLeak, LifecycleDoubleRelease, LifecycleUnguardedStore,
         LifecycleAcceptNoTerminal)
