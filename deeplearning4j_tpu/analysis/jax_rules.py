"""graftlint JAX rule pack: trace-safety and compile-discipline rules.

What counts as *traced code* (per module, AST only):

  - functions decorated with ``jax.jit`` / ``jax.pmap`` (bare, dotted, or
    through ``functools.partial``);
  - functions passed to ``jax.jit(...)`` / ``jax.pmap(...)`` anywhere in
    the module (the repo's dominant idiom: ``self._jstep =
    jax.jit(self._step_fn)``), by bare name or ``self.<method>``;
  - inner functions handed to ``jax.lax.scan`` / ``cond`` / ``while_loop``
    / ``fori_loop`` / ``jax.vmap`` / ``jax.grad`` and friends;
  - transitively: functions a traced function calls by bare name or
    ``self.<method>`` within the same module (fixpoint), because tracing
    inlines them.

Inside traced code, a light forward **taint** pass marks values derived
from the function's parameters (tracers at run time). Structural probes
(`isinstance`, `len`, `type`, `.shape`/`.ndim`/`.dtype`) launder taint —
they are static under trace and branching on them is fine.

Rules:
  JG001 host-sync-in-jit       float()/int()/.item()/np.asarray on a
                               traced value inside traced code
  JG002 tracer-branch          Python if/while/assert on a traced value
  JG003 jit-mutable-global     traced code reading a mutable module global
  JG004 jit-missing-statics    jit site without static_argnums/-names whose
                               wrapped function takes shape-like scalars
  JG005 impure-in-jit          time.*()/RNG calls inside traced code
  JG006 host-sync-in-hot-loop  blocking device reads inside scheduler-loop
                               (thread-target) code outside the sanctioned
                               host_read() boundary
  JG007 swallowed-exception-in-thread
                               bare/overbroad except inside Thread-target
                               call graphs that neither re-raises nor uses
                               the caught exception — the bug class that
                               hides scheduler-loop death
"""
from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, ModuleInfo, Rule
from .core import dotted_name as _dotted

_TRACERS = {"jit", "pmap"}
# transform name -> positional indexes of the function argument(s) it
# traces: cond takes (pred, true_fn, false_fn), while_loop
# (cond_fn, body_fn, init), fori_loop (lo, hi, body) — seeding args[0]
# for those would trace the predicate/bound instead of the body
_FN_ARG_TRANSFORMS = {"jit": (0,), "pmap": (0,), "vmap": (0,),
                      "grad": (0,), "value_and_grad": (0,),
                      "checkpoint": (0,), "remat": (0,), "scan": (0,),
                      "cond": (1, 2), "while_loop": (0, 1),
                      "fori_loop": (2,), "custom_jvp": (0,),
                      "custom_vjp": (0,)}
# jnp/jax calls that return static Python values (dtype/shape metadata),
# never tracers — branching on them is fine
_STATIC_JAX_FNS = {"issubdtype", "isdtype", "result_type", "promote_types",
                   "dtype", "shape", "ndim", "size", "iinfo", "finfo",
                   "canonicalize_dtype", "tree_structure", "tree_leaves",
                   "process_count", "process_index", "device_count",
                   "local_device_count"}
# attribute probes that are static under trace (shape metadata, not data)
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "name", "aval",
                 "sharding", "weak_type"}
# builtins that inspect structure, not values — they launder taint
_SANITIZERS = {"isinstance", "len", "type", "hasattr", "getattr", "id",
               "repr", "str", "callable", "issubclass", "enumerate",
               "range", "zip",
               # the engine's one probe of a state entry's STRUCTURE
               # (`isinstance(st, dict) and "k_pages" in st`, by name)
               "_is_paged"}
_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_NUMPY_NAMES = {"np", "numpy"}
_STATIC_PARAM_RE = re.compile(
    r"(^|_)(n|num|size|shape|dim|dims|axis|axes|len|length|count|vocab|"
    r"chunk|bucket|slots|steps|width|height|depth|rank)(_|$)")


class _FnIndex:
    """Per-module function index: defs, call edges, traced set."""

    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        # key: (class_name or None, fn_name) -> def nodes (overloads rare)
        self.defs: Dict[Tuple[Optional[str], str], List[ast.AST]] = {}
        self.lambdas: List[ast.Lambda] = []
        self._collect_defs(mod.tree, None)
        self.traced: Set[int] = set()  # id(def node)
        # id(def node) -> param names that receive traced values. Seeds
        # (the jit/scan signatures themselves) taint every param; callees
        # reached by propagation taint only the params actually FED a
        # tainted argument at some traced call site — a transitively
        # traced helper's `train=False` mode flag stays untainted, so
        # branching on it is not a JG002 tracer-branch.
        self.param_taint: Dict[int, Set[str]] = {}
        self._seed_traced()
        self._propagate()

    def _collect_defs(self, node, cls):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                self._collect_defs(child, child.name)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.defs.setdefault((cls, child.name), []).append(child)
                # nested defs keep the class context of their method
                self._collect_defs(child, cls)
            else:
                self._collect_defs(child, cls)

    def _resolve(self, cls: Optional[str], fn_node: ast.AST,
                 target) -> List[ast.AST]:
        """Def nodes a callable expression might mean: bare name ->
        same-module function (any class scope, nearest first); self.m ->
        method m of the enclosing class."""
        if isinstance(target, ast.Name):
            out = self.defs.get((cls, target.id), [])
            if not out:
                out = self.defs.get((None, target.id), [])
            return out
        if isinstance(target, ast.Attribute) and \
                isinstance(target.value, ast.Name) and \
                target.value.id == "self" and cls is not None:
            return self.defs.get((cls, target.attr), [])
        return []

    def _seed_traced(self) -> None:
        # decorators
        for (cls, _), nodes in self.defs.items():
            for node in nodes:
                for dec in getattr(node, "decorator_list", []):
                    d = _dotted(dec)
                    if d and d.split(".")[-1] in _TRACERS:
                        self.traced.add(id(node))
                    elif isinstance(dec, ast.Call):
                        df = _dotted(dec.func)
                        last = df.split(".")[-1] if df else ""
                        if last in _TRACERS:
                            self.traced.add(id(node))
                        elif last == "partial" and any(
                                _dotted(a).split(".")[-1] in _TRACERS
                                for a in dec.args):
                            self.traced.add(id(node))
        # call sites: jax.jit(f) / lax.scan(body, ...) / lax.cond(p, t, f)
        for cls, scope, call in self._calls():
            if not isinstance(call, ast.Call) or not call.args:
                continue
            last = _dotted(call.func).split(".")[-1]
            for pos in _FN_ARG_TRANSFORMS.get(last, ()):
                if pos >= len(call.args):
                    continue
                cand = call.args[pos]
                for target in self._resolve(cls, scope, cand):
                    self.traced.add(id(target))
                if isinstance(cand, ast.Lambda):
                    self.traced.add(id(cand))

    def _calls(self):
        """(enclosing class name, enclosing def node or None, Call node)
        for every call in the module."""
        def walk(node, cls, fn):
            for child in ast.iter_child_nodes(node):
                ncls, nfn = cls, fn
                if isinstance(child, ast.ClassDef):
                    ncls = child.name
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    nfn = child
                if isinstance(child, ast.Call):
                    yield cls, fn, child
                yield from walk(child, ncls, nfn)
        yield from walk(self.mod.tree, None, None)

    @staticmethod
    def _param_names(fn_node) -> List[str]:
        args = fn_node.args
        return [a.arg for a in (list(args.posonlyargs) + list(args.args))
                if a.arg != "self"]

    def _propagate(self) -> None:
        """Tracing inlines callees: a function called from traced code by
        bare name or self.<m> (same module) is traced too — with only the
        params that receive tainted arguments themselves tainted.
        Worklist fixpoint (taint sets grow monotonically)."""
        id2 = {}
        for (cls, _), nodes in self.defs.items():
            for n in nodes:
                id2[id(n)] = (cls, n)
        for nid in self.traced:  # seeds: the whole signature is traced
            if nid in id2:
                self.param_taint[nid] = set(self._param_names(id2[nid][1]))
        work = list(self.traced)
        while work:
            nid = work.pop()
            if nid not in id2:
                continue
            cls, node = id2[nid]
            taint = _Taint(node, seed=self.param_taint.get(nid))
            taint.run(node)
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                # tree_map inlines its function over (traced) leaves —
                # but only traces it when the CALLER is already traced,
                # which is why it is handled here and not as a seed
                if _dotted(call.func).split(".")[-1] == "tree_map" and \
                        call.args:
                    for target in self._resolve(cls, node, call.args[0]):
                        tid = id(target)
                        allp = set(self._param_names(target))
                        if tid not in self.traced or \
                                not allp <= self.param_taint.get(tid,
                                                                 set()):
                            self.traced.add(tid)
                            self.param_taint[tid] = \
                                self.param_taint.get(tid, set()) | allp
                            work.append(tid)
                    continue
                for target in self._resolve(cls, node, call.func):
                    tid = id(target)
                    params = self._param_names(target)
                    fed: Set[str] = set()
                    for i, arg in enumerate(call.args):
                        if i < len(params) and taint.is_tainted(arg):
                            fed.add(params[i])
                    for kw in call.keywords:
                        if kw.arg and taint.is_tainted(kw.value):
                            fed.add(kw.arg)
                    before = self.param_taint.get(tid)
                    if tid not in self.traced or \
                            (before is not None and not fed <= before):
                        self.traced.add(tid)
                        self.param_taint[tid] = (before or set()) | fed
                        work.append(tid)

    def taint_for(self, fn_node) -> "_Taint":
        """A taint pass seeded with this function's traced params (all of
        them for seeds/unknowns, the fed subset for propagated callees)."""
        t = _Taint(fn_node, seed=self.param_taint.get(id(fn_node)))
        t.run(fn_node)
        return t

    def traced_defs(self) -> List[Tuple[Optional[str], ast.AST]]:
        out = []
        for (cls, _), nodes in self.defs.items():
            for n in nodes:
                if id(n) in self.traced:
                    out.append((cls, n))
        seen = set()
        uniq = []
        for cls, n in out:
            if id(n) not in seen:
                seen.add(id(n))
                uniq.append((cls, n))
        return uniq


class _Taint(ast.NodeVisitor):
    """Single forward pass over one traced function body: which local
    names (transitively) derive from the function's parameters."""

    def __init__(self, fn_node, seed: Optional[Set[str]] = None):
        self.tainted: Set[str] = set()
        args = fn_node.args
        if seed is not None:
            self.tainted.update(seed)
            return
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)):
            if a.arg != "self":
                self.tainted.add(a.arg)
        for extra in (args.vararg, args.kwarg):
            if extra is not None:
                self.tainted.add(extra.arg)

    def is_tainted(self, node) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False  # shape metadata is static under trace
            return self.is_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value)
        if isinstance(node, ast.Call):
            d = _dotted(node.func)
            head = d.split(".")[0] if d else ""
            last = d.split(".")[-1] if d else ""
            if last in _SANITIZERS or head in _SANITIZERS:
                return False
            if head in {"jnp", "jax"}:  # device ops yield tracers
                if last in _STATIC_JAX_FNS:
                    return False  # metadata probes are static under trace
                if any(self.is_tainted(a) for a in node.args):
                    return True
                return last not in {"tree_map", "transfer_guard"}
            if isinstance(node.func, ast.Attribute) and \
                    self.is_tainted(node.func.value):
                return True  # method of a tainted object (x.sum(), .items())
            return any(self.is_tainted(a) for a in node.args) or \
                any(self.is_tainted(k.value) for k in node.keywords)
        if isinstance(node, (ast.BinOp,)):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.is_tainted(v) for v in node.values)
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.In, ast.NotIn, ast.Is, ast.IsNot))
                   for op in node.ops):
                # `"pos" in state_dict` probes pytree STRUCTURE and
                # `x is None` probes the Python object — both static
                # under trace. (A true `x in traced_array` slips through;
                # acceptable miss.)
                return False
            return self.is_tainted(node.left) or \
                any(self.is_tainted(c) for c in node.comparators)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(v is not None and self.is_tainted(v)
                       for v in node.values)
        if isinstance(node, ast.IfExp):
            return self.is_tainted(node.body) or \
                self.is_tainted(node.orelse)
        if isinstance(node, ast.Starred):
            return self.is_tainted(node.value)
        return False

    def _mark_target(self, target) -> None:
        if isinstance(target, ast.Name):
            self.tainted.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self._mark_target(e)
        elif isinstance(target, ast.Starred):
            self._mark_target(target.value)

    def run(self, fn_node) -> None:
        """Statement-order pass; good enough for lint (no loop fixpoint)."""
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign) and self.is_tainted(node.value):
                for t in node.targets:
                    self._mark_target(t)
            elif isinstance(node, ast.AugAssign) and \
                    (self.is_tainted(node.value)
                     or self.is_tainted(node.target)):
                self._mark_target(node.target)
            elif isinstance(node, ast.AnnAssign) and node.value is not None \
                    and self.is_tainted(node.value):
                self._mark_target(node.target)
            elif isinstance(node, ast.For) and self.is_tainted(node.iter):
                self._mark_target(node.target)
            elif isinstance(node, ast.comprehension) and \
                    self.is_tainted(node.iter):
                self._mark_target(node.target)
            elif isinstance(node, ast.withitem) and \
                    node.optional_vars is not None and \
                    self.is_tainted(node.context_expr):
                self._mark_target(node.optional_vars)


def _own_statements(fn_node):
    """Walk fn_node's body but do not descend into nested defs/lambdas
    (they are analyzed as their own traced scopes when relevant)."""
    stack = list(ast.iter_child_nodes(fn_node))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


class _JaxRule(Rule):
    """Shared per-module scaffolding: the function index is computed once
    per ModuleInfo and cached on it (every rule in the pack reuses it)."""

    def index(self, mod: ModuleInfo) -> _FnIndex:
        idx = getattr(mod, "_graftlint_fn_index", None)
        if idx is None:
            idx = _FnIndex(mod)
            mod._graftlint_fn_index = idx
        return idx


class HostSyncInJit(_JaxRule):
    id = "JG001"
    name = "host-sync-in-jit"
    description = ("float()/int()/.item()/np.asarray on a traced value "
                   "inside jit-traced code forces a device sync per trace "
                   "or a ConcretizationError")

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        out = []
        idx = self.index(mod)
        for cls, fn in idx.traced_defs():
            taint = idx.taint_for(fn)
            for node in _own_statements(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                last = d.split(".")[-1] if d else ""
                head = d.split(".")[0] if d else ""
                bad = None
                if isinstance(node.func, ast.Name) and \
                        node.func.id in _SYNC_BUILTINS and node.args and \
                        taint.is_tainted(node.args[0]):
                    bad = f"{node.func.id}() on a traced value"
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _SYNC_METHODS and \
                        taint.is_tainted(node.func.value):
                    # checked via the raw attr (not _dotted) so chained
                    # receivers like x.sum().item() are still seen
                    bad = f".{node.func.attr}() on a traced value"
                elif head in _NUMPY_NAMES and \
                        last in {"asarray", "array", "copy"} and node.args \
                        and taint.is_tainted(node.args[0]):
                    bad = f"{d}() on a traced value"
                if bad:
                    out.append(mod.finding(
                        self.id, node,
                        f"{bad} inside jit-traced code: this either "
                        "blocks on a host sync or raises under trace; "
                        "keep the value on device (jnp) or hoist the "
                        "read out of the traced function"))
        return out


class TracerBranch(_JaxRule):
    id = "JG002"
    name = "tracer-branch"
    description = ("Python if/while/assert on a traced value inside "
                   "jit-traced code — control flow must use lax.cond/"
                   "select/where, or the argument must be static")

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        out = []
        idx = self.index(mod)
        for cls, fn in idx.traced_defs():
            taint = idx.taint_for(fn)
            for node in _own_statements(fn):
                test = None
                kind = None
                if isinstance(node, ast.If):
                    test, kind = node.test, "if"
                elif isinstance(node, ast.While):
                    test, kind = node.test, "while"
                elif isinstance(node, ast.Assert):
                    test, kind = node.test, "assert"
                elif isinstance(node, ast.IfExp):
                    test, kind = node.test, "conditional expression"
                if test is not None and taint.is_tainted(test):
                    out.append(mod.finding(
                        self.id, node,
                        f"Python {kind} on a traced value: under jit this "
                        "raises TracerBoolConversionError (or silently "
                        "bakes one branch in); use jnp.where/lax.cond or "
                        "mark the argument static"))
        return out


class JitMutableGlobal(_JaxRule):
    id = "JG003"
    name = "jit-mutable-global"
    description = ("jit-traced code reading a mutable module global: the "
                   "first trace bakes the value in, later mutations are "
                   "silently ignored")

    def _mutable_globals(self, mod: ModuleInfo) -> Set[str]:
        counts: Dict[str, int] = {}
        mutable: Set[str] = set()
        for node in mod.tree.body:
            targets = []
            if isinstance(node, ast.Assign):
                targets = [t for t in node.targets if isinstance(t, ast.Name)]
                value = node.value
            elif isinstance(node, ast.AnnAssign) and \
                    isinstance(node.target, ast.Name):
                targets, value = [node.target], node.value
            else:
                continue
            for t in targets:
                counts[t.id] = counts.get(t.id, 0) + 1
                if isinstance(value, (ast.List, ast.Dict, ast.Set)) or (
                        isinstance(value, ast.Call)
                        and _dotted(value.func) in
                        {"list", "dict", "set", "bytearray", "defaultdict",
                         "collections.defaultdict"}):
                    mutable.add(t.id)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Global):
                mutable.update(node.names)
        mutable.update(n for n, c in counts.items() if c > 1)
        return mutable

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        mutable = self._mutable_globals(mod)
        if not mutable:
            return []
        out = []
        for cls, fn in self.index(mod).traced_defs():
            local: Set[str] = set()
            args = fn.args
            for a in (list(args.posonlyargs) + list(args.args)
                      + list(args.kwonlyargs)):
                local.add(a.arg)
            for node in _own_statements(fn):
                if isinstance(node, ast.Assign):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            local.add(t.id)
            reported = set()
            for node in _own_statements(fn):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in mutable and node.id not in local and \
                        node.id not in reported:
                    reported.add(node.id)
                    out.append(mod.finding(
                        self.id, node,
                        f"traced code closes over mutable module global "
                        f"'{node.id}': jit captures it at first trace; "
                        "later mutations never reach the compiled "
                        "program — pass it as an argument instead"))
        return out


class JitMissingStatics(_JaxRule):
    id = "JG004"
    name = "jit-missing-statics"
    description = ("jit site without static_argnums/static_argnames whose "
                   "wrapped function takes shape-like scalar parameters — "
                   "each distinct value recompiles or traces as dynamic")

    def _check_site(self, mod, idx, cls, scope, call_or_dec, fn_node,
                    site_node) -> Optional[Finding]:
        suspicious = []
        args = fn_node.args
        for a in (list(args.posonlyargs) + list(args.args)
                  + list(args.kwonlyargs)):
            if a.arg == "self":
                continue
            if _STATIC_PARAM_RE.search(a.arg):
                suspicious.append(a.arg)
        if not suspicious:
            return None
        return mod.finding(
            self.id, site_node,
            f"jax.jit of '{fn_node.name}' declares no static_argnums/"
            f"static_argnames but parameter(s) {suspicious} look like "
            "Python scalars/shapes: traced they force every call "
            "through dynamic ops, static-by-accident they recompile "
            "per value — declare them explicitly either way")

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        idx = self.index(mod)
        out = []
        # decorator sites
        for (cls, _), nodes in idx.defs.items():
            for node in nodes:
                for dec in node.decorator_list:
                    d = _dotted(dec)
                    if d and d.split(".")[-1] in _TRACERS:
                        f = self._check_site(mod, idx, cls, node, dec, node,
                                             node)
                        if f:
                            out.append(f)
                    elif isinstance(dec, ast.Call):
                        df = _dotted(dec.func).split(".")[-1]
                        inner = [a for a in dec.args
                                 if _dotted(a).split(".")[-1] in _TRACERS]
                        is_jit = df in _TRACERS or (df == "partial"
                                                    and inner)
                        if is_jit and not any(
                                k.arg in ("static_argnums",
                                          "static_argnames")
                                for k in dec.keywords):
                            f = self._check_site(mod, idx, cls, node, dec,
                                                 node, node)
                            if f:
                                out.append(f)
        # call sites: jax.jit(fn, ...)
        for cls, scope, call in idx._calls():
            d = _dotted(call.func)
            if not d or d.split(".")[-1] not in _TRACERS or not call.args:
                continue
            if any(k.arg in ("static_argnums", "static_argnames")
                   for k in call.keywords):
                continue
            for fn_node in idx._resolve(cls, scope, call.args[0]):
                f = self._check_site(mod, idx, cls, scope, call, fn_node,
                                     call)
                if f:
                    out.append(f)
        return out


class ImpureInJit(_JaxRule):
    id = "JG005"
    name = "impure-in-jit"
    description = ("time/RNG calls inside jit-traced code run once at "
                   "trace time and are baked into the program as "
                   "constants")

    _IMPURE = {"time.time", "time.monotonic", "time.perf_counter",
               "time.time_ns", "time.monotonic_ns", "datetime.now",
               "datetime.datetime.now", "np.random.seed", "random.seed",
               "random.random", "random.randint", "random.randrange",
               "random.choice", "random.shuffle", "random.uniform"}

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        out = []
        for cls, fn in self.index(mod).traced_defs():
            for node in _own_statements(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                impure = d in self._IMPURE or \
                    d.startswith("np.random.") or \
                    d.startswith("numpy.random.")
                if impure:
                    out.append(mod.finding(
                        self.id, node,
                        f"'{d}' inside jit-traced code executes once at "
                        "trace time and becomes a compiled-in constant — "
                        "every later call replays the same value; pass "
                        "times/keys in as arguments (jax.random for "
                        "randomness)"))
        return out


def thread_spawn_sites(idx: _FnIndex
                       ) -> List[Tuple[Optional[str], Optional[ast.AST],
                                       ast.AST]]:
    """(enclosing class, spawning def node, target def node) for every
    ``threading.Thread/Timer(target=...)`` call in the module — the seed
    set shared by JG006/JG007's hot-loop walker and the CC005/CC006
    lockset race pass (analysis.races)."""
    out = []
    for cls, scope, call in idx._calls():
        d = _dotted(call.func)
        if not d or d.split(".")[-1] not in ("Thread", "Timer"):
            continue
        for kw in call.keywords:
            if kw.arg != "target":
                continue
            for target in idx._resolve(cls, scope, kw.value):
                out.append((cls, scope, target))
    return out


def _thread_target_functions(idx: _FnIndex
                             ) -> List[Tuple[Optional[str], ast.AST]]:
    """Thread-target functions plus everything they call in-module: the
    code that runs on a dispatcher/scheduler thread's loop. Shared by
    JG006 (host syncs stall the loop) and JG007 (swallowed exceptions
    hide the loop's death)."""
    seeds: Set[int] = {id(t) for _, _, t in thread_spawn_sites(idx)}
    if not seeds:
        return []
    id2 = {}
    for (cls, _), nodes in idx.defs.items():
        for n in nodes:
            id2[id(n)] = (cls, n)
    hot = set(seeds)
    changed = True
    while changed:
        changed = False
        for nid in list(hot):
            if nid not in id2:
                continue
            cls, node = id2[nid]
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                for target in idx._resolve(cls, node, call.func):
                    if id(target) not in hot:
                        hot.add(id(target))
                        changed = True
    return [id2[n] for n in hot if n in id2]


class HostSyncInHotLoop(_JaxRule):
    id = "JG006"
    name = "host-sync-in-hot-loop"
    description = ("blocking device read (np.asarray/float/.item) inside "
                   "scheduler-loop code outside the sanctioned host_read "
                   "boundary stalls the dispatch thread")

    # analysis.runtime.host_read is the declared device->host boundary:
    # it is not in any sync pattern below, so routing a read through it
    # is exactly what clears the finding

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        idx = self.index(mod)
        out = []
        for cls, fn in _thread_target_functions(idx):
            for node in _own_statements(fn):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                last = d.split(".")[-1] if d else ""
                head = d.split(".")[0] if d else ""
                bad = None
                host_prep = (ast.List, ast.Tuple, ast.Dict, ast.ListComp,
                             ast.Constant, ast.GeneratorExp)
                if head in _NUMPY_NAMES and last in {"asarray", "array"} \
                        and node.args and not isinstance(node.args[0],
                                                         host_prep):
                    # np.asarray on a literal/comprehension is host-side
                    # data prep, not a device readback
                    bad = f"{d}()"
                elif isinstance(node.func, ast.Name) and \
                        node.func.id in {"float", "int"} and node.args \
                        and isinstance(node.args[0],
                                       (ast.Call, ast.Subscript)):
                    # float()/int() of a call/index result in a hot loop
                    # is the classic one-scalar-at-a-time device read
                    # (plain-name args skew host-side: times, counters)
                    bad = f"{node.func.id}()"
                elif last in {"block_until_ready"}:
                    bad = f".{last}()"
                elif d == "jax.device_get":
                    bad = d
                elif last == "item" and isinstance(node.func,
                                                   ast.Attribute):
                    bad = ".item()"
                if bad:
                    out.append(mod.finding(
                        self.id, node,
                        f"{bad} in scheduler-loop code blocks the "
                        "dispatch thread on a device sync; route the "
                        "read through analysis.runtime.host_read (the "
                        "allow-listed boundary) or move it off the hot "
                        "path"))
        return out


class SwallowedExceptionInThread(_JaxRule):
    id = "JG007"
    name = "swallowed-exception-in-thread"
    description = ("bare/overbroad except swallowing exceptions inside "
                   "Thread-target call graphs hides loop death: the "
                   "thread keeps 'running' (or dies silently) while "
                   "every in-flight request hangs")

    # an exception is considered HANDLED (not swallowed) when the
    # handler re-raises, or binds the exception and actually uses it
    # (fails a future with it, records it, wraps it); a handler that
    # catches everything and uses nothing is the bug class that turned
    # scheduler-loop death into silent request hangs
    _BROAD = {"Exception", "BaseException"}

    def _is_broad(self, type_node) -> bool:
        if type_node is None:
            return True  # bare `except:`
        if isinstance(type_node, ast.Tuple):
            return any(self._is_broad(e) for e in type_node.elts)
        return _dotted(type_node).split(".")[-1] in self._BROAD

    def _swallows(self, handler: ast.ExceptHandler) -> bool:
        for node in ast.walk(handler):
            if isinstance(node, ast.Raise):
                return False  # re-raises (bare or wrapped)
        if handler.name:
            for node in ast.walk(handler):
                if isinstance(node, ast.Name) and node.id == handler.name \
                        and isinstance(node.ctx, ast.Load):
                    return False  # the exception is consumed somewhere
        return True

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        idx = self.index(mod)
        out = []
        for cls, fn in _thread_target_functions(idx):
            for node in _own_statements(fn):
                if not isinstance(node, ast.ExceptHandler):
                    continue
                if self._is_broad(node.type) and self._swallows(node):
                    what = ("bare 'except:'" if node.type is None else
                            f"'except {_dotted(node.type) or '...'}'")
                    out.append(mod.finding(
                        self.id, node,
                        f"{what} in Thread-target code swallows the "
                        "exception without re-raising or recording it — "
                        "a dying scheduler/dispatcher loop becomes a "
                        "silent hang for every in-flight request; "
                        "re-raise, fail the owning futures/handles with "
                        "the error, or record it for a supervisor"))
        return out


RULES = [HostSyncInJit, TracerBranch, JitMutableGlobal, JitMissingStatics,
         ImpureInJit, HostSyncInHotLoop, SwallowedExceptionInThread]
