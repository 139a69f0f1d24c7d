"""The scheduler iteration on the profiler's clock (ISSUE 26).

Three things are held here. (i) A `jax.profiler` trace of a toy paged
engine, taken with the benchmark harness's options, carries the
scheduler's phases as `sched/<phase>` annotations inside one `sched_iter`
per iteration, on one host line beside the `PjitFunction(...)` launches.
(ii) The phases partition the iteration: their seconds add up to its wall
time, the wait for the device and the copy to the host are told apart, and
a disarmed profiler books and annotates nothing. (iii) The names that the
benchmark's accepted readers match — the two paged programs' module names
and the ring's dispatch spans — are pinned, so that a refactor cannot null
a roofline in silence. (iv) Each booked iteration leaves one `sched_iter`
record on the flight recorder's ring (ISSUE 38) from which the benchmark's
readers rebuild its phases and dispatches, and the scheduler thread's CPU
seconds outside the waits go to `sched_host_cpu_seconds_total`.
"""
import glob
import re
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from deeplearning4j_tpu.inference import (DecodeScheduler, MetricsRegistry,
                                          StepPhaseProfiler)
from deeplearning4j_tpu.inference import profiler as profiler_mod
from deeplearning4j_tpu.inference.kvpool import SCRATCH_BLOCK
from deeplearning4j_tpu.inference.trace import FlightRecorder
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph

from benchmark.harness import engine_driver

V = 13
CHUNK = 16


@pytest.fixture(scope="module")
def eng():
    # wide enough that an iteration lasts milliseconds: the microseconds
    # between one annotation's end and the next one's start must stay a
    # small share of it
    conf = transformer_lm(vocab_size=V, d_model=128, n_heads=2, n_blocks=4,
                          rope=True)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = 96
    e = DecodeScheduler(ComputationGraph(conf).init(), V, n_slots=2,
                        prefill_chunk=CHUNK, kv_pool_mb=4.0, kv_block=8,
                        metrics=MetricsRegistry(),
                        tracer=FlightRecorder(8192)).start()
    assert e.paged
    e.generate(_prompt(0, 40), 4, timeout=300)      # compiles, outside
    yield e
    e.stop()


def _prompt(salt, n):
    # distinct first tokens: no prefix of an earlier prompt to restore
    return [(salt + 3 * i) % (V - 1) + 1 for i in range(n)]


def _within_a_time_limit(fn, seconds):
    out = {}
    t = threading.Thread(target=lambda: out.update(value=fn()), daemon=True)
    t.start()
    t.join(timeout=seconds)
    assert not t.is_alive(), f"not done after {seconds} s"
    return out["value"]


# ------------------------------------------------- (i) the profiler trace --
def _trace_one_request(eng, trace_dir):
    opts = jax.profiler.ProfileOptions()     # benchmark/harness/runner.py's
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    it0 = eng.profiler.iterations
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        time.sleep(0.25)                     # nothing to run: sched/idle
        # long enough that one descheduling of the thread between two
        # annotations is a small share of the traced iterations
        eng.generate(_prompt(1, 40), 40, timeout=120)
        time.sleep(0.25)
    finally:
        jax.profiler.stop_trace()
    return it0, eng.profiler.iterations


def test_trace_carries_the_phases_on_the_schedulers_line(eng, tmp_path):
    it0, it1 = _within_a_time_limit(
        lambda: _trace_one_request(eng, str(tmp_path)), 240)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    lines = [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for e in line.events]
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines]
    sched = [evs for evs in lines
             if any(n.startswith("sched") for _, _, n in evs)]
    assert len(sched) == 1, "the phases belong to one thread's line"
    evs = sorted(sched[0])
    iters = [(s, e) for s, e, n in evs if n == "sched_iter"]
    phases = [(s, e, n) for s, e, n in evs if n.startswith("sched/")]
    assert len(iters) == it1 - it0 >= 5      # one step per iteration
    assert {n for _, _, n in phases} >= {
        "sched/" + p for p in ("admit", "prefill_launch", "prefill_wait",
                               "prefill_read", "pool", "decode_launch",
                               "decode_wait", "decode_read", "accept",
                               "flush")}
    covered = 0
    for s, e in iters:
        kids = [p for p in phases if s <= p[0] < e]
        assert kids[0][2] == "sched/admit" and kids[-1][2] == "sched/flush"
        assert all(k[1] <= e for k in kids)
        assert all(a[1] <= b[0] for a, b in zip(kids, kids[1:])), \
            "phases of one iteration do not overlap"
        covered += sum(k[1] - k[0] for k in kids)
    assert covered >= 0.95 * sum(e - s for s, e in iters)
    # every launch of the decode program lies inside a decode_launch
    launches = [(s, e) for s, e, n in evs
                if n == "PjitFunction(_step_paged_fn)"]
    spans = [(s, e) for s, e, n in phases if n == "sched/decode_launch"]
    assert launches and all(any(a <= s and e <= b for a, b in spans)
                            for s, e in launches)
    # with nothing to run the thread says so, outside any iteration
    idle = [(s, e) for s, e, n in phases if n == "sched/idle"]
    assert idle and not any(a <= s < b for s, _ in idle for a, b in iters)


# ------------------------------------------ (ii) phases partition the lap --
class _Ticks:
    """A clock that advances one millisecond per reading; the thread's CPU
    clock stands still."""

    def __init__(self):
        self.now = 0.0

    def monotonic(self):
        self.now += 0.001
        return self.now

    def thread_time(self):
        return 0.0


def test_phases_partition_the_iteration(monkeypatch):
    clock = _Ticks()
    monkeypatch.setattr(profiler_mod, "time", clock)
    prof = StepPhaseProfiler(MetricsRegistry())
    assert set(prof.phase_seconds) == set(profiler_mod.PHASES)
    walls = 0.0
    for _ in range(3):
        prof.iter_begin()
        t_begin = clock.now
        for phase in ("prefill_launch", "prefill_wait"):
            prof.begin(phase)
        prof.ready()                         # prefill_wait -> prefill_read
        for phase in ("accept", "draft", "pool"):
            prof.begin(phase)
        with prof.nested("roll"):            # pool -> roll -> pool again
            pass
        for phase in ("decode_launch", "decode_wait"):
            prof.begin(phase)
        prof.ready()                         # decode_wait -> decode_read
        for phase in ("accept", "verify", "flush"):
            prof.begin(phase)
        prof.iter_end(tokens=1)
        walls += clock.now - t_begin         # iter_end read the clock once
    ph = prof.phase_seconds
    assert sum(ph.values()) == pytest.approx(walls, rel=1e-9)
    twice = ("accept", "pool")                       # twice an iteration
    assert all(ph[p] == pytest.approx(0.006) for p in twice)
    assert all(ph[p] == pytest.approx(0.003) for p in ph if p not in twice)
    # the benchmark's three readers go by the two suffixes
    assert {p for p in ph if p.endswith("_wait")} == {"prefill_wait",
                                                      "decode_wait"}
    assert {p for p in ph if p.endswith("_read")} == {"prefill_read",
                                                      "decode_read"}
    hists = prof.metrics.snapshot()["histograms"]
    assert hists['decode_step_phase_seconds{phase="decode_read"}'][
        "count"] == 3


def test_only_a_prompts_last_chunk_waits_and_reads(eng):
    prof, begun = eng.profiler, []
    real = prof.begin
    prof.begin = lambda phase: (begun.append(phase), real(phase))
    n0 = len(eng.tracer.events())
    try:
        eng.generate(_prompt(2, 40), 4, timeout=120)
        time.sleep(0.2)                       # the last iteration ends
    finally:
        del prof.begin
    new = [e for e in eng.tracer.events()[n0:] if e["ph"] == "B"]
    chunks = [e["args"]["tokens"] for e in new if e["name"] == "prefill_chunk"]
    steps = [e for e in new if e["name"] == "decode_step"]
    assert chunks == [16, 16, 8]
    # three chunks launched, one waited for and read; its sample is accept
    assert begun.count("prefill_wait") == begun.count("prefill_read") == 1
    i = begun.index("prefill_wait")
    assert begun[i - 1:i + 3] == ["prefill_launch", "prefill_wait",
                                  "prefill_read", "accept"]
    # every decode step is launched, waited for, read and accepted
    assert len(steps) == 3 == begun.count("decode_wait")
    j = begun.index("decode_wait")
    assert begun[j - 1:j + 3] == ["decode_launch", "decode_wait",
                                  "decode_read", "accept"]
    # an iteration begins with its chunk's launch; none is left unbooked
    assert begun.count("prefill_launch") == begun.count("flush") >= 6


class _Span:
    """Stands in for the profiler's annotations and records those of the
    test's own thread. The patch is the module's, so the module-scoped
    `eng`'s live scheduler thread comes through here too: it idles every
    0.1 s, and its `sched/idle` is not this test's."""
    made, stopped, thread = [], [], None

    def __init__(self, name, **kwargs):
        self.name = name
        self.mine = threading.get_ident() == _Span.thread
        if self.mine:
            _Span.made.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        if self.mine:
            _Span.stopped.append(self.name)


@pytest.fixture
def spans(monkeypatch):
    _Span.made, _Span.stopped = [], []
    _Span.thread = threading.get_ident()
    monkeypatch.setattr(profiler_mod, "TraceAnnotation", _Span)
    monkeypatch.setattr(profiler_mod, "StepTraceAnnotation", _Span)
    return _Span


def _one_pass(prof):
    prof.iter_begin()
    prof.begin("decode_wait")
    prof.ready()
    prof.begin("accept")
    prof.count("decode", 0)
    prof.iter_end(tokens=5)
    with prof.idle():
        pass


def test_a_disarmed_profiler_books_nothing_and_opens_no_annotation(spans):
    prof = StepPhaseProfiler(MetricsRegistry(), enabled=False)
    _one_pass(prof)
    prof.iter_begin()
    prof.iter_abandon()
    assert spans.made == [] and spans.stopped == []
    assert not any(prof.phase_seconds.values()) and prof.iterations == 0


def test_an_armed_profiler_annotates_what_it_books(spans):
    prof = StepPhaseProfiler(MetricsRegistry())
    _one_pass(prof)
    assert spans.made == ["sched_iter", "sched/admit", "sched/decode_wait",
                          "sched/decode_read", "sched/accept", "sched/idle"]
    assert sorted(spans.stopped) == sorted(spans.made)
    assert spans.stopped.index("sched/accept") \
        < spans.stopped.index("sched_iter")       # a phase ends in its step
    # a pass that found nothing to run: closed, not booked
    before = dict(prof.phase_seconds)
    prof.iter_begin()
    prof.iter_abandon()
    assert spans.made[-2:] == spans.stopped[-2:][::-1] == ["sched_iter",
                                                           "sched/admit"]
    assert prof.phase_seconds == before and prof.iterations == 1
    # a pass expected to idle writes no step, unless it runs after all
    n = len(spans.made)
    prof.iter_begin(annotate=False)
    prof.iter_abandon()
    assert len(spans.made) == n
    prof.iter_begin(annotate=False)
    prof.begin("prefill_launch")
    prof.iter_end()
    assert spans.made[n:] == ["sched_iter", "sched/prefill_launch"]


# ----------------------------------- (iii) names the benchmark's readers match
def test_paged_program_names_are_what_the_rooflines_match(eng):
    nb = eng.table_buckets[0]
    table = eng._dev_array(np.full((eng.n_slots, nb), SCRATCH_BLOCK,
                                   np.int32))
    step = eng._jstep.lower(
        eng._params, eng._variables,
        eng._dev_array(np.zeros((eng.n_slots,), np.int32)),
        eng._dev_array(np.zeros((eng.n_slots,), bool)), table, eng._states)
    chunk = eng._jprefill.lower(
        eng._params, eng._variables, eng._dev_index(0),
        eng._dev_array(np.zeros((eng.prefill_buckets[0],), np.int32)),
        eng._dev_index(1), table, eng._states)
    names = [re.search(r"module @(\S+)", low.as_text()).group(1)
             for low in (step, chunk)]
    # benchmark/metrics/decode_step_roofline.py, prefill_chunk_roofline.py
    assert names == ["jit__step_paged_fn", "jit__prefill_paged_fn"]
    assert eng._thread.name == "decode-scheduler"


def test_ring_carries_the_dispatch_spans_the_benchmark_reads(eng):
    n0 = len(eng.tracer.events())
    h = eng.submit(_prompt(3, 24), 3)
    h.result(timeout=120)
    new = [e for e in eng.tracer.events()[n0:] if e["ph"] == "B"]
    steps = [e for e in new if e["name"] == "decode_step"]
    chunks = [e for e in new if e["name"] == "prefill_chunk"]
    # engine_driver.spans_between / harness/facts.chunks read these keys
    assert steps and all(e["args"]["live_slots"] == 1 for e in steps)
    assert [(e["args"]["bucket"], e["args"]["tokens"]) for e in chunks] \
        == [(16, 16), (16, 8)]
    assert all(e["args"]["request"] == h.request_id for e in chunks)
    # no record per phase: one `sched_iter` an iteration carries them
    assert not any(e["name"] in profiler_mod.PHASES
                   or e["name"].startswith("sched/") for e in new)


# ------------------------------------- (iv) the iteration on the ring ------
def _sched_iters(eng, t_lo, t_hi):
    """What a benchmark reader gets: `engine_driver.spans_between`'s begin
    records, arguments merged in."""
    return [s for s in engine_driver.spans_between(eng, t_lo, t_hi)
            if s["name"] == "sched_iter"]


def _snap(eng):
    prof = eng.profiler
    return (dict(prof.phase_seconds), prof.iterations,
            dict(prof.family_dispatches),
            eng.metrics.snapshot()["counters"]["sched_host_cpu_seconds_total"])


def test_each_booked_iteration_leaves_one_record_that_rebuilds_it(eng):
    time.sleep(0.25)                          # between iterations
    ph0, it0, d0, cpu0 = _snap(eng)
    t_lo = time.monotonic()
    eng.generate(_prompt(4, 40), 6, timeout=120)
    time.sleep(0.35)                          # three idle wakes, no record
    t_hi = time.monotonic()
    ph1, it1, d1, cpu1 = _snap(eng)
    recs = _sched_iters(eng, t_lo, t_hi)
    n = it1 - it0
    assert n >= 6 and len(recs) == n          # one a booked iteration
    assert all(s["track"] == eng._sched_track for s in recs)
    # the phases, each to the next one's offset and the last to `end`, add
    # up per phase to what the profiler booked
    rebuilt = dict.fromkeys(ph0, 0.0)
    for s in recs:
        offs = [off for _, off in s["phases"]] + [s["end"]]
        assert s["phases"][0] == ("admit", 0.0)
        assert offs == sorted(offs)
        for (name, off), nxt in zip(s["phases"], offs[1:]):
            rebuilt[name] += nxt - off
    for name in ph0:
        assert abs(rebuilt[name] - (ph1[name] - ph0[name])) <= 1e-6 * n
    # every dispatch is there with its bucket, inside its iteration
    disp = [d for s in recs for d in s["dispatches"]]
    assert sum(1 for f, _, _ in disp if f == "decode") \
        == d1["decode"] - d0["decode"] >= 5
    assert sum(1 for f, _, _ in disp if f == "prefill") \
        == d1["prefill"] - d0["prefill"] == 3
    assert {b for f, b, _ in disp if f == "decode"} \
        <= set(eng.table_buckets)
    assert {b for f, b, _ in disp if f == "prefill"} \
        <= set(eng.prefill_buckets)
    assert all(0 <= off <= s["end"] for s in recs
               for _, _, off in s["dispatches"])
    # CPU seconds outside the waits: no more than their wall seconds
    wall = sum(ph1[k] - ph0[k] for k in ph0 if not k.endswith("_wait"))
    assert 0 <= cpu1 - cpu0 <= wall + 1e-4 * n
    assert cpu1 - cpu0 == pytest.approx(sum(s["cpu_s"] for s in recs))
    # written at the close, in ring order: the record's time is the
    # iteration's end, after every record of its own, and its end record
    # sits beside it, so the Chrome export stays nested
    evs = [e for e in eng.tracer.events()
           if e["track"] == eng._sched_track]
    for i, e in enumerate(evs):
        if e["name"] == "sched_iter" and e["ph"] == "B":
            assert (evs[i + 1]["ph"], evs[i + 1]["name"]) \
                == ("E", "sched_iter")
    seqs = [e["seq"] for e in eng.tracer.events()]
    assert seqs == sorted(seqs)
    chrome = [e for e in eng.tracer.chrome_trace()["traceEvents"]
              if e.get("name") in ("sched_iter", "decode_step")]
    depth = 0
    for e in chrome:
        depth += {"B": 1, "E": -1}.get(e["ph"], 0)
        assert 0 <= depth <= 1, "a span opened inside another"


def test_a_tail_of_the_serving_ring_misses_nothing(eng):
    """`TraceAggregator` tails the engine's ring by cursor while it serves:
    the `sched_iter` records, written at the close in ring order, leave no
    hole that reads as a `ring_dropped`."""
    from deeplearning4j_tpu.serving.telemetry import TraceAggregator
    agg = TraceAggregator([], client_recorder=eng.tracer)
    agg.sync_clocks()
    agg.poll()                                # up to now
    src = agg._sources[0]
    seq0 = src.cursor
    hs = [eng.submit(_prompt(20 + k, 24 + 4 * k), 5) for k in range(3)]
    while not all(h.done() for h in hs):
        agg.poll()
        time.sleep(0.002)
    for h in hs:
        h.result(timeout=120)
    agg.poll()
    assert agg.stats()["dropped_total"] == 0
    assert not any(e["name"] == "ring_dropped" for e in src.events)
    got = [e["seq"] for e in src.events if e["seq"] >= seq0]
    assert got == list(range(seq0, src.cursor))   # every record, once
    assert sum(e["name"] == "sched_iter" for e in src.events) >= 5


def test_no_record_without_an_enabled_recorder():
    off = FlightRecorder(64, enabled=False)
    prof = StepPhaseProfiler(MetricsRegistry())
    prof.attach(off, "scheduler")
    _one_pass(prof)
    assert prof.iterations == 1 and prof._marks is None
    assert off.events() == [] and set(off._buf) == {None}
    # an armed recorder gets the two records of the same pass
    on = FlightRecorder(64)
    prof.attach(on, "scheduler")
    _one_pass(prof)
    prof.iter_begin()
    prof.iter_abandon()                      # an idle wake writes nothing
    assert [(e["ph"], e["name"]) for e in on.events()] == [
        ("B", "sched_iter"), ("E", "sched_iter")]
    args = on.events()[0]["args"]
    assert [p for p, _ in args["phases"]] == [
        "admit", "decode_wait", "decode_read", "accept"]
    assert [(f, b) for f, b, _ in args["dispatches"]] == [("decode", 0)]


def test_a_disarmed_profiler_never_reads_the_cpu_clock(monkeypatch):
    class _NoCpu:
        monotonic = staticmethod(time.monotonic)

        @staticmethod
        def thread_time():
            raise AssertionError("thread_time read by a disarmed profiler")

    monkeypatch.setattr(profiler_mod, "time", _NoCpu)
    prof = StepPhaseProfiler(MetricsRegistry(), enabled=False)
    prof.attach(FlightRecorder(64), "scheduler")
    _one_pass(prof)
    assert prof._tracer is None and prof.iterations == 0


def test_cpu_outside_the_waits_is_what_is_booked(monkeypatch):
    """thread_time is read at the iteration's begin and end and where a
    wait begins and ends; what the thread burns inside a wait stays out."""
    class _Cpu(_Ticks):
        cpu = 0.0

        def thread_time(self):
            self.cpu += 0.010
            return self.cpu

    clock = _Cpu()
    monkeypatch.setattr(profiler_mod, "time", clock)
    m = MetricsRegistry()
    prof = StepPhaseProfiler(m)
    prof.iter_begin()                    # cpu 0.01
    prof.begin("decode_launch")
    prof.begin("decode_wait")            # 0.02: 0.01 outside
    prof.ready()                         # 0.03
    prof.begin("accept")
    prof.iter_end()                      # 0.04: 0.01 outside
    assert m.snapshot()["counters"]["sched_host_cpu_seconds_total"] \
        == pytest.approx(0.02)
