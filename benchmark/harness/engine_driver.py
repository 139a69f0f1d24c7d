"""Drives the system under test: `DecodeScheduler.submit`, in this process.

This is the call `/generate` makes (`serving/server.py:_decoder_factory`
builds the engine with the same keyword arguments). The only module of the
harness that imports the program; a family's `graph.py` is the other place
in the benchmark that does."""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from .loadgen import Request

class StampSink:
    """Duck-types `logitproc.TokenStream` for `submit(stream=...)`: the
    scheduler pushes each released token; the sink stamps its arrival."""

    def __init__(self, stamps: List[float]):
        self.sent = 0
        self._stamps = stamps

    def push(self, index: int, tok: int) -> None:
        if index < self.sent:
            return
        self.sent = index + 1
        self._stamps.append(time.monotonic())

    def close(self, handle, error=None) -> None:
        now = time.monotonic()
        for _ in range(self.sent, len(handle.tokens)):
            self._stamps.append(now)
        self.sent = len(handle.tokens)


def build_net(family, cfg: dict, params: dict, dtype: str):
    """The configuration's graph, as its family builds it, with the
    harness's weights installed the way
    `model_serializer.restore_computation_graph` installs a checkpoint's
    — minus `init()`, which would first draw a second set of weights."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    net = ComputationGraph(family.graph.build_conf(cfg, dtype))
    tree = family.graph.graph_tree(params)
    jdt = jnp.dtype(dtype)
    for name, impl in net._impls.items():
        want = jax.eval_shape(lambda i=impl: i.init_params(
            jax.random.PRNGKey(0), jdt))
        got = tree.get(name, {})
        if {k: v.shape for k, v in want.items()} != \
                {k: tuple(v.shape) for k, v in got.items()}:
            raise ValueError(f"weights for layer {name!r} do not match the "
                             f"graph: {want} vs "
                             f"{ {k: v.shape for k, v in got.items()} }")
        net.variables[name] = impl.init_variables(jdt)
        net.updater_state[name] = {}
    net.params = tree
    net._initialized = True
    return net


def build_engine(net, vocab: int, geometry: dict):
    """The workload file's `engine` block goes to `DecodeScheduler` whole: a
    key it does not know is its own TypeError, here at set-up, with the
    key's name."""
    from deeplearning4j_tpu.inference.engine import DecodeScheduler
    from deeplearning4j_tpu.inference.metrics import MetricsRegistry
    from deeplearning4j_tpu.inference.trace import FlightRecorder

    eng = DecodeScheduler(net, vocab, metrics=MetricsRegistry(),
                          tracer=FlightRecorder(capacity=1 << 17), **geometry)
    if not eng.paged:
        raise RuntimeError("the cell's geometry did not engage the paged "
                           "KV pool; the cell measures the paged path")
    return eng.start()


# -- which programs a request reaches (mirrors the engine's bucket rules) ---

def _bucket(n: int, buckets: List[int]) -> int:
    return next(b for b in buckets if b >= n)


def programs_of(prompt: int, out: int, eng_facts: dict) -> set:
    """(family, chunk bucket, table bucket) a solo request dispatches."""
    blk, chunk = eng_facts["kv_block"], eng_facts["prefill_chunk"]
    cb, tb = eng_facts["prefill_buckets"], eng_facts["table_buckets"]
    progs, fed = set(), 0
    while fed < prompt:
        n = min(prompt - fed, chunk)
        b = _bucket(n, cb)
        progs.add(("prefill", b, _bucket(-(-(fed + b) // blk), tb)))
        fed += n
    for depth in range(prompt + 1, prompt + out):
        progs.add(("decode", 0, _bucket(-(-depth // blk), tb)))
    return progs


def warm_plan(limits: dict, eng_facts: dict) -> List[tuple]:
    """A small set of (prompt, out) requests that reaches every program any
    request within the mix's length limits can reach: seed-independent, so
    every seed compiles (or loads) the same family and no other."""
    blk, tb = eng_facts["kv_block"], eng_facts["table_buckets"]
    by_len = {p: programs_of(p, 2, eng_facts)
              for p in range(limits["prompt_min"], limits["prompt_max"] + 1)}
    need = set().union(*by_len.values())
    lo = _bucket(-(-(limits["prompt_min"] + 1) // blk), tb)
    hi = _bucket(-(-limits["total_max"] // blk), tb)
    need |= {("decode", 0, b) for b in tb if lo <= b <= hi}
    plan, have = [], set()
    while True:                       # greedy cover by prompt length
        p, got = max(by_len.items(), key=lambda kv: len(kv[1] - have))
        if not got - have:
            break
        plan.append((p, 2))
        have |= got
    for fam, _, b in sorted(need - have):
        # a decode bucket deeper than any prompt: decode up to its first depth
        depth = (b // 2) * blk + 1
        p = min(limits["prompt_max"], depth - 1)
        plan.append((p, depth - p + 1))
        have |= programs_of(*plan[-1], eng_facts)
    if not need <= have:
        raise RuntimeError(f"warm plan misses programs {sorted(need - have)}")
    return plan


def engine_facts(eng) -> dict:
    return {"kv_block": eng.kv_block, "prefill_chunk": eng.prefill_chunk,
            "prefill_buckets": list(eng.prefill_buckets),
            "table_buckets": list(eng.table_buckets),
            "n_slots": eng.n_slots,
            "capacity_blocks": eng.pool.capacity_blocks}


def warm(eng, limits: dict, vocab: int, seed: int) -> dict:
    """Dispatch every shape the mix can reach, through `submit`, one request
    at a time, before the window. Counts as set-up."""
    facts = engine_facts(eng)
    plan = warm_plan(limits, facts)
    rng = np.random.default_rng(int(seed) ^ 0x5EED)
    for p, out in plan:
        ids = rng.integers(0, vocab, p).tolist()
        eng.submit(ids, out).result(timeout=1100)
    return {"requests": len(plan),
            "prompt_tokens": sum(p for p, _ in plan)}


# -- the measured window ----------------------------------------------------

class _SideThread:
    """Runs the window's timed callbacks (the trace's start and stop) off the
    generator's thread: stopping a trace takes seconds, and the generator
    must not be late for it."""

    def __init__(self, hooks: list):
        self._hooks = hooks
        self._thread: Optional[threading.Thread] = None

    def start(self, t0: float) -> None:
        if self._hooks:
            self._thread = threading.Thread(target=self._run, args=(t0,),
                                            name="bench-trace", daemon=True)
            self._thread.start()

    def _run(self, t0: float) -> None:
        for offset, fn in self._hooks:
            time.sleep(max(0.0, t0 + offset - time.monotonic()))
            fn()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join(timeout=120)
            if self._thread.is_alive():
                raise RuntimeError("the trace did not stop within 120 s")


def run_window(eng, requests: List[Request], seconds: float,
               drain_s: float = 60.0,
               at: Optional[Dict[float, Callable[[], None]]] = None) -> dict:
    """Open loop: each request is sent when it is due, whether or not earlier
    ones finished. `at` maps window offsets (s) to callbacks run by this
    thread between sends (the trace's start and stop). Returns the window's
    counters; per-request stamps land on the Request objects. Under
    `counters` is the difference over the window of every counter of the
    engine's `MetricsRegistry` (`snapshot()`, twice), under `spans` every
    begin record of the program's ring for the window: a reader of a new
    counter or span needs no edit here."""
    from deeplearning4j_tpu.analysis.runtime import CompileCounter

    prof = eng.profiler
    compiles = CompileCounter.for_scheduler(eng)
    side = _SideThread(sorted((at or {}).items()))
    snap0 = {"phase_seconds": dict(prof.phase_seconds),
             "iterations": prof.iterations,
             "dispatches": dict(prof.family_dispatches),
             "counters": eng.metrics.snapshot()["counters"]}
    t0 = time.monotonic()
    side.start(t0)

    def idle_until(t: float) -> None:
        while True:
            now = time.monotonic()
            if now >= t:
                return
            time.sleep(min(t - now, 0.05))

    for r in requests:
        r.t_due = t0 + r.due_s
        idle_until(r.t_due)
        r.t_sent = time.monotonic()
        try:
            r.handle = eng.submit(r.prompt, r.out_tokens,
                                  temperature=r.temperature,
                                  seed=r.index, stream=StampSink(r.stamps))
        except Exception as e:  # a refused request is a failed request
            r.error = f"{type(e).__name__}: {e}"
    idle_until(t0 + seconds)
    snap1 = {"phase_seconds": dict(prof.phase_seconds),
             "iterations": prof.iterations,
             "dispatches": dict(prof.family_dispatches),
             "counters": eng.metrics.snapshot()["counters"]}
    deadline = t0 + seconds + drain_s
    for r in requests:
        if r.handle is None:
            continue
        try:
            r.handle.result(timeout=max(0.0, deadline - time.monotonic()))
        except Exception as e:
            r.error = f"{type(e).__name__}: {e}"
            r.handle.cancel()
    t_end = time.monotonic()
    side.join()
    counters = {k: v - snap0["counters"].get(k, 0)
                for k, v in snap1["counters"].items()}
    return {
        "t0": t0, "seconds": seconds, "drained_s": t_end - (t0 + seconds),
        "phase_seconds": {k: snap1["phase_seconds"][k] - v
                          for k, v in snap0["phase_seconds"].items()},
        "iterations": snap1["iterations"] - snap0["iterations"],
        "dispatches": {k: v - snap0["dispatches"].get(k, 0)
                       for k, v in snap1["dispatches"].items()},
        "counters": counters,
        "preempted": counters.get("decode_preempted_total", 0),
        "compiles": compiles.counts(),
        "capacity_blocks": eng.pool.capacity_blocks,
        "pool_live_max": eng.metrics.gauge("kv_pool_blocks_live").max,
        "spans": spans_between(eng, t0, t_end),
    }


def spans_between(eng, t_lo: float, t_hi: float) -> List[dict]:
    """Every span of the program's own that its ring still holds for the
    interval: the begin records, which carry the arguments, with their name
    and track, on the host's monotonic clock (among them today `queued`,
    `decode`, `prefill_chunk` with its request, bucket and tokens, and
    `decode_step` with its live slots)."""
    t_ref = eng.tracer.clock()["trace_t0"]
    out = []
    for e in eng.tracer.events():
        if e["ph"] == "B":
            t = e["ts"] + t_ref
            if t_lo <= t <= t_hi:
                out.append({**e.get("args", {}), "name": e["name"],
                            "track": e["track"], "t": t})
    return out


def request_rows(requests: List[Request]) -> List[dict]:
    rows = []
    for r in requests:
        h = r.handle
        rows.append({
            "index": r.index, "due": r.t_due, "sent": r.t_sent,
            "id": getattr(h, "request_id", None),
            "admitted": getattr(h, "t_admitted", None),
            "first": getattr(h, "t_first_token", None),
            "done": getattr(h, "t_done", None) if h is not None and h.done()
            else None,
            "prompt_len": len(r.prompt), "out_len": r.out_tokens,
            "tokens": list(h.tokens) if h is not None else [],
            "stamps": list(r.stamps), "error": r.error,
        })
    return rows
