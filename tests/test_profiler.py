"""Performance-attribution & SLO plane tests (ISSUE 11).

Four layers, smallest first: the SLOMonitor's burn-rate algebra on a
FROZEN injectable clock (zero real sleeps, the test_supervisor.py
discipline); the SLO→degradation-ladder path on stub engines —
escalation on an injected latency burn with queue pressure untouched,
persistence across an engine restart, and the two inputs composing
without flapping; the three-way metrics exposition parity (JSON /
legacy text / real Prometheus with HELP, TYPE, labels, buckets, and
request-id exemplars); and the step-phase profiler + cost attribution
on a real tiny engine and over HTTP (`GET /metrics?format=prometheus`,
`GET /debug/engine`, the `/trace?since=` cursor).
"""
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.inference import (DecodeScheduler, EngineSupervisor,
                                          MetricsRegistry, SLOMonitor,
                                          StepPhaseProfiler, program_costs)
from deeplearning4j_tpu.inference.trace import FlightRecorder
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph

V = 13


def _lm(cache=96):
    conf = transformer_lm(vocab_size=V, d_model=16, n_heads=2, n_blocks=2,
                          rope=True)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()


class FakeClock:
    def __init__(self):
        self.now = 1000.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


class StubEngine:
    """The EngineSupervisor-facing surface with settable vitals (the
    test_supervisor.py stub, queue depth included)."""

    def __init__(self, clock):
        self._clock = clock
        self.heartbeat = clock()
        self.iterations = 1
        self.crashed = None
        self.fenced = False
        self.stopped = False
        self.prefill_chunk = 64
        self.chunk_cap = None
        self.max_queue = 64
        self._queue_depth = 0
        self.shed_calls = []
        self._thread = None
        self._on_crash = None

    def fence(self):
        self.fenced = True

    def stop(self):
        self.stopped = True

    def start(self):
        return self

    def inflight(self):
        return self._queue_depth

    def queue_depth(self):
        return self._queue_depth

    def shed_queued(self, target):
        self.shed_calls.append(target)
        return 0

    def submit(self, prompt, max_new_tokens, **kw):
        from deeplearning4j_tpu.inference.engine import DecodeHandle
        return kw.get("_handle") or DecodeHandle(len(prompt),
                                                 max_new_tokens)


def _sup(clock, slo=None, **kw):
    spawned = []

    def factory():
        eng = StubEngine(clock)
        spawned.append(eng)
        return eng

    sup = EngineSupervisor(factory, clock=clock, sleep_fn=clock.sleep,
                           watchdog=False, warm_on_build=False, slo=slo,
                           metrics=MetricsRegistry(),
                           tracer=FlightRecorder(1024), **kw)
    return sup, spawned


# ------------------------------------------------------- SLOMonitor unit --
def test_slo_percentiles_and_burn_rates_frozen_clock():
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.1, metrics=MetricsRegistry(),
                     clock=clock)
    for i in range(100):
        slo.observe("/generate", 0.01 + 0.0001 * i, request_id=f"r{i:03d}")
    p = slo.percentiles("/generate")
    assert p["n"] == 100
    assert 0.01 <= p["p50"] <= p["p95"] <= p["p99"] <= 0.02
    fast, slow = slo.burn_rates()
    assert fast == 0.0 and slow == 0.0  # everything inside the objective
    assert not slo.burning() and slo.calm()
    # now a 100%-violation stretch: burn = violation fraction / budget
    for i in range(100):
        slo.observe("/generate", 0.5, request_id=f"b{i:03d}")
    fast, slow = slo.burn_rates()
    assert fast == pytest.approx(50.0)  # 50% over / 1% budget
    assert slow == pytest.approx(50.0)
    assert slo.burning() and not slo.calm()


def test_slo_fast_window_recovers_before_slow():
    """Multiwindow semantics: after the burn stops, the fast window goes
    calm while the slow window still remembers — burning() (which needs
    BOTH) flips off, calm() (fast-only) flips on: hysteresis, not one
    shared edge."""
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.1, fast_window_s=60,
                     slow_window_s=600, metrics=MetricsRegistry(),
                     clock=clock)
    for _ in range(50):
        slo.observe("/generate", 1.0)
    assert slo.burning()
    clock.now += 120  # the bad minute ages out of the fast window only
    for _ in range(50):
        slo.observe("/generate", 0.01)
    fast, slow = slo.burn_rates()
    assert fast == 0.0
    assert slow == pytest.approx(50.0)  # old violations still in window
    assert not slo.burning() and slo.calm()


def test_slo_without_objective_never_burns():
    clock = FakeClock()
    slo = SLOMonitor(metrics=MetricsRegistry(), clock=clock)
    for _ in range(64):
        slo.observe("/predict", 99.0)
    assert slo.burn_rates() == (0.0, 0.0)
    assert not slo.burning() and slo.calm()
    assert slo.percentiles("/predict")["n"] == 64


def test_slo_window_pruning_bounds_memory():
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.1, slow_window_s=100,
                     max_samples=64, metrics=MetricsRegistry(),
                     clock=clock)
    for i in range(500):
        clock.now += 1.0
        slo.observe("/generate", 0.01)
    with slo._lock:
        n = len(slo._samples["/generate"])
    assert n <= 64


# --------------------------------------------------- SLO -> ladder path --
def test_latency_burn_escalates_ladder_with_queue_untouched():
    """The acceptance-criterion path: an injected latency burn walks the
    ladder up while queue depth stays 0 — the ladder is latency-aware,
    not just queue-pressure-aware."""
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.1, metrics=MetricsRegistry(),
                     clock=clock)
    sup, spawned = _sup(clock, slo=slo, ladder_patience=2)
    try:
        eng = spawned[0]
        assert eng.queue_depth() == 0
        for _ in range(40):
            slo.observe("/generate", 2.0)  # sustained burn
        for _ in range(4):
            clock.now += 0.1
            eng.heartbeat = clock()
            sup.check()
        assert sup.degradation_level >= 1
        assert eng.queue_depth() == 0  # queue pressure never involved
        # level >= 1 sheds queued load (a no-op on an empty queue, but
        # the rung must drive the engine hook)
        assert eng.shed_calls
    finally:
        sup.stop()


def test_ladder_deescalates_when_latency_calms():
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.1, fast_window_s=60,
                     slow_window_s=120, metrics=MetricsRegistry(),
                     clock=clock)
    sup, spawned = _sup(clock, slo=slo, ladder_patience=2)
    try:
        eng = spawned[0]
        for _ in range(40):
            slo.observe("/generate", 2.0)
        for _ in range(4):
            clock.now += 0.1
            eng.heartbeat = clock()
            sup.check()
        assert sup.degradation_level >= 1
        clock.now += 200  # every violation ages out of both windows
        for _ in range(20):
            slo.observe("/generate", 0.01)
        for _ in range(2 * sup.degradation_level + 2):
            clock.now += 0.1
            eng.heartbeat = clock()
            sup.check()
        assert sup.degradation_level == 0
    finally:
        sup.stop()


def test_degradation_level_survives_restart_with_latency_input():
    """A rung reached via the latency input persists across a crash
    recovery: the rebuilt engine comes up degraded, not amnesiac."""
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.1, metrics=MetricsRegistry(),
                     clock=clock)
    sup, spawned = _sup(clock, slo=slo, ladder_patience=1)
    try:
        eng = spawned[0]
        for _ in range(40):
            slo.observe("/generate", 2.0)
        for _ in range(4):  # walk up to level 2 (chunk-cap rung)
            clock.now += 0.1
            eng.heartbeat = clock()
            sup.check()
        assert sup.degradation_level >= 2
        level = sup.degradation_level
        eng.crashed = RuntimeError("boom")
        sup.check()  # crash recovery spawns a replacement
        assert len(spawned) == 2
        assert sup.degradation_level == level
        # the rung was PROJECTED onto the rebuilt engine
        assert spawned[1].chunk_cap == spawned[1].prefill_chunk // 2
    finally:
        sup.stop()


def test_queue_and_latency_inputs_compose_without_flapping():
    """One input calm must not de-escalate a rung the other holds up:
    queue drains while latency still burns -> the level STAYS; latency
    calms while the queue is loaded -> the level STAYS; both calm ->
    down it comes."""
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.1, fast_window_s=60,
                     slow_window_s=120, metrics=MetricsRegistry(),
                     clock=clock)
    sup, spawned = _sup(clock, slo=slo, ladder_patience=2)
    try:
        eng = spawned[0]
        for _ in range(40):
            slo.observe("/generate", 2.0)  # latency hot, queue empty
        for _ in range(4):
            clock.now += 0.1
            eng.heartbeat = clock()
            sup.check()
        level = sup.degradation_level
        assert level >= 1
        # queue stays empty (calm side), latency keeps burning: many
        # more checks must not walk the rung down (no flapping)
        for _ in range(10):
            clock.now += 0.1
            eng.heartbeat = clock()
            slo.observe("/generate", 2.0)  # keep the burn fresh
            sup.check()
        assert sup.degradation_level >= level
        # now latency calms but the QUEUE fills: still no de-escalation
        clock.now += 200
        for _ in range(20):
            slo.observe("/generate", 0.01)
        eng._queue_depth = eng.max_queue  # pressure side takes over
        lvl = sup.degradation_level
        for _ in range(3):
            clock.now += 0.1
            eng.heartbeat = clock()
            sup.check()
        assert sup.degradation_level >= lvl
        # both calm -> the ladder walks down
        eng._queue_depth = 0
        for _ in range(4 * sup.degradation_level + 4):
            clock.now += 0.1
            eng.heartbeat = clock()
            sup.check()
        assert sup.degradation_level == 0
    finally:
        sup.stop()


def test_supervisor_status_carries_slo_snapshot():
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.25, metrics=MetricsRegistry(),
                     clock=clock)
    sup, _ = _sup(clock, slo=slo)
    try:
        slo.observe("/generate", 0.01, request_id="r1")
        st = sup.status()
        # status() carries the BRIEF (burn-rate headline, no per-route
        # percentiles — /readyz is polled constantly); the full
        # per-route snapshot lives on /info and /debug/engine
        assert st["slo"]["objective_p99_ms"] == 250.0
        assert "burn_rate_fast" in st["slo"]
        assert "routes" not in st["slo"]
        assert "/generate" in slo.snapshot()["routes"]
    finally:
        sup.stop()


# ------------------------------------------------- exposition parity -----
def _parity_registry():
    m = MetricsRegistry()
    m.counter("reqs_total", help="requests served").inc(5)
    g = m.gauge("queue_depth", help="waiting requests")
    g.set(9)
    g.set(2)
    h = m.histogram("lat_sec", help="latency")
    h.record(0.01)
    h.record(0.2, exemplar="r000042")
    m.histogram("phase_sec", help="per-phase",
                labels={"phase": "decode"}).record(0.03)
    m.ratio("hit_rate", m.counter("hits"), m.counter("lookups"),
            help="hit fraction")
    return m


def test_three_expositions_agree_on_names_and_values():
    """The satellite invariant: JSON snapshot, legacy text, and the
    Prometheus renderer expose the SAME series names and values."""
    m = _parity_registry()
    snap = m.snapshot()
    text = m.render_text()
    prom = m.render_prometheus()
    # counters/gauges: same key, same value, everywhere
    for key, v in snap["counters"].items():
        assert f"{key} {v}" in text
        assert f"{key} {v}" in prom
    for key, gv in snap["gauges"].items():
        assert f"{key} {gv['value']}" in text
        assert f"{key} {gv['value']}" in prom
    for name, v in snap["ratios"].items():
        assert f"{name} {v}" in text
        assert f"{name} {v}" in prom
    # histograms: count parity across all three (sum too, when set)
    for key, hs in snap["histograms"].items():
        base = key.split("{", 1)[0]
        suffix = key[len(base):]
        assert f"{base}_count{suffix} {hs['count']}" in text
        assert f"{base}_count{suffix} {hs['count']}" in prom
        if hs.get("count"):
            assert f"{base}_sum{suffix} {hs['sum']}" in text
            assert f"{base}_sum{suffix} {hs['sum']}" in prom


def test_help_text_lands_in_all_three_expositions():
    m = _parity_registry()
    assert m.snapshot()["help"]["reqs_total"] == "requests served"
    assert "# HELP reqs_total requests served" in m.render_text()
    # OpenMetrics: a counter FAMILY strips the _total suffix in its
    # HELP/TYPE lines (samples keep the full name)
    prom = m.render_prometheus()
    assert "# HELP reqs requests served" in prom
    assert "# TYPE reqs counter" in prom
    assert "reqs_total 5" in prom
    # the 0.0.4 form keeps the full name in TYPE (legacy convention)
    plain = m.render_prometheus(openmetrics=False)
    assert "# TYPE reqs_total counter" in plain
    # help is registered once per family, first non-empty wins
    m.counter("reqs_total", help="different text")
    assert m.snapshot()["help"]["reqs_total"] == "requests served"


def test_prometheus_renderer_buckets_labels_exemplars():
    m = _parity_registry()
    prom = m.render_prometheus()
    assert "# TYPE lat_sec histogram" in prom
    assert "# TYPE reqs counter" in prom  # OM family: _total stripped
    # cumulative buckets end at +Inf == _count
    inf_lines = [line for line in prom.splitlines()
                 if line.startswith('lat_sec_bucket{le="+Inf"}')]
    assert inf_lines and inf_lines[0].split()[1] == "2"
    # label support: the labeled series keeps its labels in the bucket
    assert 'phase_sec_bucket{phase="decode",le=' in prom
    # the exemplar carries the request id (OpenMetrics form), and the
    # exposition ends with the required '# EOF' terminator
    ex = [line for line in prom.splitlines() if "request_id=" in line]
    assert ex and 'request_id="r000042"' in ex[0]
    assert prom.rstrip().endswith("# EOF")
    # buckets are cumulative and non-decreasing
    cums = [int(line.split(" ")[1]) for line in prom.splitlines()
            if line.startswith("lat_sec_bucket")]
    assert cums == sorted(cums)
    # the legacy 0.0.4 form omits exemplars and the EOF terminator
    plain = m.render_prometheus(openmetrics=False)
    assert "request_id=" not in plain and "# EOF" not in plain
    assert "# TYPE lat_sec histogram" in plain


def test_exemplar_label_values_are_escaped():
    """The exemplar label is the CLIENT-controlled request id (the
    X-Request-Id header survives into it): quotes/backslashes/newlines
    must not corrupt the exposition."""
    m = MetricsRegistry()
    h = m.histogram("lat_sec")
    h.record(0.01, exemplar='evil"id\\with\nnewline')
    prom = m.render_prometheus()
    ex = [line for line in prom.splitlines() if "request_id=" in line]
    assert ex, prom
    assert 'request_id="evil\\"id\\\\with\\nnewline"' in ex[0]
    assert "\n" not in ex[0]  # the newline was escaped, not emitted


def test_labeled_series_coexist_with_unlabeled():
    m = MetricsRegistry()
    a = m.histogram("x_sec", labels={"phase": "a"})
    b = m.histogram("x_sec", labels={"phase": "b"})
    assert a is not b
    assert a is m.histogram("x_sec", labels={"phase": "a"})
    a.record(1.0)
    b.record(2.0)
    snap = m.snapshot()["histograms"]
    assert snap['x_sec{phase="a"}']["count"] == 1
    assert snap['x_sec{phase="b"}']["count"] == 1


# ----------------------------------------- step-phase profiler + costs ----
def test_step_phase_profiler_unit():
    m = MetricsRegistry()
    prof = StepPhaseProfiler(m, gauge_every=1)
    prof.ingest_costs({("decode", 0): {"flops": 100.0, "bytes": 10.0},
                       ("prefill", 16): {"flops": 1000.0, "bytes": 50.0}})
    for _ in range(4):
        prof.iter_begin()
        prof.begin("prefill_launch")
        prof.count("prefill", 16)
        prof.begin("decode_launch")
        prof.count("decode", 0)
        prof.begin("decode_wait")
        prof.ready()
        prof.begin("accept")
        prof.iter_end(tokens=2)
    dec = prof.decomposition()
    assert set(dec) == set(
        ("admit", "prefill_launch", "prefill_wait", "prefill_read",
         "draft", "pool", "roll", "decode_launch", "decode_wait",
         "decode_read", "accept", "verify", "flush"))
    assert abs(sum(p["share"] for p in dec.values()) - 1.0) < 0.01
    assert prof.family_dispatches == {"decode": 4, "prefill": 4}
    assert prof.flops_total == pytest.approx(4 * 1100.0)
    assert prof.tokens_total == 8
    snap = prof.cost_snapshot()
    assert snap["family_flops_share"]["prefill"] == pytest.approx(
        1000 / 1100, abs=1e-3)
    assert m.snapshot()["gauges"]["decode_tokens_per_sec"]["value"] > 0


def test_disabled_profiler_is_inert():
    m = MetricsRegistry()
    prof = StepPhaseProfiler(m, enabled=False)
    prof.iter_begin()
    prof.begin("decode_wait")
    prof.ready()
    prof.count("decode", 0)
    prof.iter_end(tokens=5)
    assert prof.iterations == 0 and prof.tokens_total == 0
    assert not any(prof.phase_seconds.values())
    assert "decode_tokens_per_sec" not in m.snapshot()["gauges"]


@pytest.fixture(scope="module")
def lm_net():
    return _lm()


def test_engine_cost_attribution_and_debug_snapshot(lm_net):
    m = MetricsRegistry()
    eng = DecodeScheduler(lm_net, V, n_slots=2, prefill_chunk=16,
                          metrics=m, tracer=FlightRecorder(2048)).start()
    try:
        eng.attribute_costs()
        assert eng.profiler.costs, "attribute_costs must fill the table"
        for key, c in eng.profiler.costs.items():
            assert c["flops"] > 0, key
            assert c["bytes"] > 0, key
        eng.generate(list(range(1, 11)) * 2, 6, timeout=120)
        snap = eng.debug_snapshot()
        # the acceptance-criterion fields: per-family FLOPs/bytes from
        # cost_analysis + live MFU / tokens-per-second estimates
        costs = snap["costs"]
        assert costs["per_invocation"]["decode"]
        assert costs["tokens_per_sec"] > 0
        # the CPU has no published peak (profiler.DEVICE_PEAKS): MFU is
        # null and says why, never a figure against a made-up peak
        assert costs["mfu_estimate"] is None
        assert costs["peak_flops_per_device"] is None
        assert "no published peak" in costs["peak_note"]
        assert costs["dispatches"]["decode"] >= 1
        assert snap["phases"]["decode_wait"]["seconds"] > 0
        assert snap["compile_cache"]["decode"] >= 0
        assert snap["mesh"]["tp"] == 1
        assert snap["slots"][0] is None  # finished -> freed
        # phase histograms landed as labeled series
        hists = m.snapshot()["histograms"]
        assert 'decode_step_phase_seconds{phase="decode_launch"}' in hists
        assert hists['decode_step_phase_seconds{phase="decode_launch"}'][
            "count"] > 0
    finally:
        eng.stop()
    # a REBUILT engine over the same net (the supervisor's crash-
    # recovery path) re-ingests the cached cost table at warmup — free,
    # no re-tracing inside the recovery window
    eng2 = DecodeScheduler(lm_net, V, n_slots=2, prefill_chunk=16,
                           metrics=MetricsRegistry(),
                           tracer=FlightRecorder(256))
    assert not eng2.profiler.costs
    eng2.warmup()
    assert eng2.profiler.costs == eng.profiler.costs


def test_program_costs_paged_covers_table_buckets(lm_net):
    eng = DecodeScheduler(lm_net, V, n_slots=2, prefill_chunk=16,
                          kv_pool_mb=1.0, kv_block=8,
                          metrics=MetricsRegistry(),
                          tracer=FlightRecorder(1024))
    assert eng.paged
    costs = program_costs(eng)
    decode_keys = sorted(b for f, b in costs if f == "decode")
    assert decode_keys == sorted(eng.table_buckets)
    prefill_keys = sorted(b for f, b in costs if f == "prefill")
    assert prefill_keys == sorted(eng.prefill_buckets)
    # the engine's donation rule: both families hand the carried state
    # over to be updated in place — at least the page arrays' bytes
    page_bytes = sum(a.nbytes for st in eng._states.values()
                     for k, a in st.items() if k.endswith("_pages"))
    assert page_bytes > 0
    for (fam, b), c in costs.items():
        assert c["donated_bytes"] >= page_bytes, (fam, b)


# ------------------------------------------------------------ HTTP layer --
def _post(base, path, payload):
    req = urllib.request.Request(
        base + path, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req).read())


def test_http_prometheus_debug_engine_and_trace_cursor(lm_net):
    from deeplearning4j_tpu.serving import InferenceServer
    srv = InferenceServer(net=lm_net, decode_vocab=V, decode_slots=2,
                          prefill_chunk=16, slo_p99_ms=30000.0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        out = _post(base, "/generate", {"prompt": list(range(1, 9)),
                                        "max_new_tokens": 3})
        rid = out["request_id"]
        # -- /metrics?format=prometheus: HELP/TYPE/labels + an exemplar
        #    carrying a real request_id (the acceptance criterion)
        prom = urllib.request.urlopen(
            base + "/metrics?format=prometheus").read().decode()
        # OM counter family name strips _total; the sample keeps it
        assert "# TYPE decode_tokens counter" in prom
        assert "decode_tokens_total " in prom
        assert "# TYPE http_route_latency_seconds histogram" in prom
        assert "# HELP http_route_latency_seconds" in prom
        assert 'http_route_latency_seconds_bucket{route="/generate"' \
            in prom
        assert f'request_id="{rid}"' in prom
        # explicit ?format=prometheus is the OpenMetrics form: exemplars
        # legal, '# EOF' terminator, openmetrics content type
        assert prom.rstrip().endswith("# EOF")
        # content negotiation: an Accept: text/plain scrape (a legacy
        # Prometheus scraper) gets the same families WITHOUT exemplars —
        # the 0.0.4 parser rejects the '#' marker after a sample value
        req = urllib.request.Request(base + "/metrics",
                                     headers={"Accept": "text/plain"})
        resp = urllib.request.urlopen(req)
        via_accept = resp.read().decode()
        assert "version=0.0.4" in resp.headers.get("Content-Type", "")
        assert "# TYPE decode_tokens_total counter" in via_accept
        assert "request_id=" not in via_accept
        assert not via_accept.rstrip().endswith("# EOF")
        # an OpenMetrics Accept gets exemplars + the openmetrics type
        req = urllib.request.Request(
            base + "/metrics",
            headers={"Accept": "application/openmetrics-text"})
        resp = urllib.request.urlopen(req)
        assert "openmetrics-text" in resp.headers.get("Content-Type", "")
        assert f'request_id="{rid}"' in resp.read().decode()
        # the default (no format, no Accept) stays JSON
        snap = json.loads(urllib.request.urlopen(
            base + "/metrics").read())
        assert "counters" in snap and "help" in snap
        # -- /debug/engine: slot table + costs + supervisor + SLO
        dbg = json.loads(urllib.request.urlopen(
            base + "/debug/engine").read())
        assert dbg["n_slots"] == 2
        assert len(dbg["slots"]) == 2
        assert dbg["costs"]["per_invocation"]["decode"]
        assert dbg["costs"]["tokens_per_sec"] >= 0
        assert "mfu_estimate" in dbg["costs"]
        assert dbg["compile_cache"]
        assert dbg["supervisor"]["slo"]["objective_p99_ms"] == 30000.0
        assert "/generate" in dbg["slo"]["routes"]
        # -- /trace?since= cursor: the second poll returns only what was
        #    recorded after the first (here: nothing)
        t1 = json.loads(urllib.request.urlopen(
            base + "/trace").read())
        assert t1["next_cursor"] == t1["total_recorded"] > 0
        t2 = json.loads(urllib.request.urlopen(
            base + f"/trace?since={t1['next_cursor']}").read())
        assert t2["events"] == []
        _post(base, "/generate", {"prompt": list(range(1, 9)),
                                  "max_new_tokens": 2})
        t3 = json.loads(urllib.request.urlopen(
            base + f"/trace?since={t1['next_cursor']}").read())
        assert t3["events"]
        assert all(e["seq"] >= t1["next_cursor"] for e in t3["events"])
        assert t3["next_cursor"] > t1["next_cursor"]
        # /info carries the SLO + profiler headline
        info = json.loads(urllib.request.urlopen(base + "/info").read())
        assert info["slo"]["objective_p99_ms"] == 30000.0
        assert "tokens_per_sec" in info["profiler"]
    finally:
        srv.stop()


def test_http_debug_engine_404_without_decoder():
    from deeplearning4j_tpu.serving import InferenceServer
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    b = NeuralNetConfiguration.builder().seed(1).learning_rate(0.01).list()
    b.layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
    b.layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                        loss="mcxent"))
    net = MultiLayerNetwork(b.build()).init()
    srv = InferenceServer(net=net).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/engine")
        assert e.value.code == 404
    finally:
        srv.stop()


def test_tracer_export_cursor_api():
    rec = FlightRecorder(64)
    for i in range(10):
        rec.instant(f"e{i}", track="t")
    first = rec.export()
    assert first["next_cursor"] == 10
    assert len(first["events"]) == 10
    nothing = rec.export(since=first["next_cursor"])
    assert nothing["events"] == []
    rec.instant("late", track="t")
    tail = rec.export(since=first["next_cursor"])
    assert [e["name"] for e in tail["events"]] == ["late"]
    assert tail["next_cursor"] == 11


def test_trace_cursor_survives_ring_wraparound():
    rec = FlightRecorder(8)
    for i in range(20):
        rec.instant(f"e{i}", track="t")
    snap = rec.export(since=5)
    # seqs 0..11 were overwritten; the filter returns survivors >= 5,
    # which is just the newest 8 — and dropped tells the poller the gap
    assert all(e["seq"] >= 12 for e in snap["events"])
    assert snap["dropped"] == 12
    assert snap["next_cursor"] == 20


# ------------------------------------------- load-test client aggregation --
def test_load_test_client_timing_summary():
    """ISSUE 11 satellite: the load generator aggregates per-response
    ``timings`` into a client-side p50/p95/p99 + phase table, the
    cross-check for the server-side SLO numbers."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                           / "examples"))
    import serving_load_test as slt
    results = [{"timings": {"queue_ms": 1.0, "restore_ms": 0.5,
                            "prefill_ms": 10.0, "decode_ms": 40.0,
                            "total_ms": 51.5}} for _ in range(50)]
    results.append({"timings": {"queue_ms": 100.0, "restore_ms": 0.0,
                                "prefill_ms": 10.0, "decode_ms": 40.0,
                                "total_ms": 150.0}})
    s = slt.summarize_timings(results)
    assert s["n"] == 51
    assert s["total_ms"]["p50"] == 51.5
    assert s["total_ms"]["p99"] == 150.0  # the one outlier
    shares = sum(ph["share"] for ph in s["phases"].values())
    assert abs(shares - 1.0) < 0.02  # phases sum to total by construction
    assert s["phases"]["decode_ms"]["mean"] == 40.0
    slt.print_timing_table(s)  # smoke: the table renders
    assert slt.summarize_timings([]) is None


def test_trace_cursor_with_limit_pages_forward_without_skipping():
    """?since + ?limit is forward pagination: each page keeps the OLDEST
    N unseen events and next_cursor resumes right after the last
    returned one — a burst larger than the page size is delivered in
    full across polls, never silently skipped."""
    rec = FlightRecorder(256)
    for i in range(30):
        rec.instant(f"e{i}", track="t")
    seen, cur = [], 1  # start tailing from seq 1
    for _ in range(10):
        page = rec.export(since=cur, limit=7)
        if not page["events"]:
            break
        seen.extend(e["seq"] for e in page["events"])
        cur = page["next_cursor"]
    assert seen == list(range(1, 30))  # every event once, in order
    assert cur == 30


def test_single_slow_request_cannot_burn_on_low_traffic():
    """min_samples floor: a near-empty window's violation fraction is
    meaningless — one 300ms request on a 2-req/min server must NOT walk
    the ladder to admission rejection."""
    clock = FakeClock()
    slo = SLOMonitor(objective_p99_s=0.25, metrics=MetricsRegistry(),
                     clock=clock)
    slo.observe("/generate", 0.3)  # one violation, window of one
    assert slo.burn_rates() == (0.0, 0.0)
    assert not slo.burning() and slo.calm()
    # a real sustained burn (>= min_samples violations) still fires
    for _ in range(slo.min_samples):
        slo.observe("/generate", 0.3)
    assert slo.burning()


def test_trace_cursor_zero_is_a_real_cursor():
    """since=0 (the documented initial cursor) must page forward from
    the oldest event, not fall back to newest-N limit semantics."""
    rec = FlightRecorder(256)
    for i in range(30):
        rec.instant(f"e{i}", track="t")
    page = rec.export(since=0, limit=7)
    assert [e["seq"] for e in page["events"]] == list(range(7))
    assert page["next_cursor"] == 7


def test_idle_tick_decays_rate_gauges():
    """iter_end never runs on idle scheduler passes; idle_tick must keep
    refreshing the rate gauges so an idle engine's tokens/s decays
    instead of freezing at the last burst's value."""
    import time as _time
    m = MetricsRegistry()
    prof = StepPhaseProfiler(m, gauge_every=1)
    for _ in range(3):
        prof.iter_begin()
        prof.begin("decode_launch")
        prof.iter_end(tokens=100)
    busy = m.snapshot()["gauges"]["decode_tokens_per_sec"]["value"]
    assert busy > 0
    prof._t_gauges = 0.0  # bypass the 1 Hz throttle for the test
    _time.sleep(0.05)
    prof.idle_tick()
    idle = m.snapshot()["gauges"]["decode_tokens_per_sec"]["value"]
    assert idle < busy  # window stretched, rate decayed
