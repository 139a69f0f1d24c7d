"""Fleet telemetry plane (ISSUE 12): cross-process trace propagation,
multi-replica waterfall merge, and federated metrics/SLO.

Acceptance contract: a request traced across two LIVE server processes
produces ONE merged Perfetto waterfall — client + both servers on
separate track groups, one flow per request, per-track monotonic
timestamps after clock-offset correction, gap markers where a ring
wrapped — and the federated exposition's fleet p99 matches the pooled
per-replica samples within one histogram bucket. Malformed trace
context (oversized, non-UTF8, embedded newline, hop overflow) NEVER
500s and never corrupts the Chrome export or the Prometheus exemplar
escaping.
"""
import bisect
import json
import random
import socket
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.inference.metrics import (Histogram,
                                                  MetricsRegistry,
                                                  merge_histograms)
from deeplearning4j_tpu.inference.trace import FlightRecorder
from deeplearning4j_tpu.models.zoo import transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.serving import InferenceServer
from deeplearning4j_tpu.serving.telemetry import (TRACE_HEADER,
                                                  ClientTracer,
                                                  FleetMetrics,
                                                  FleetTelemetryServer,
                                                  TraceAggregator,
                                                  TraceContext,
                                                  format_trace_header,
                                                  parse_prometheus,
                                                  parse_trace_header)


def _lm(v=13, cache=96):
    conf = transformer_lm(vocab_size=v, d_model=16, n_heads=2, n_blocks=2,
                          rope=True)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()


def _post(port, path, body, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body,
        headers={"Content-Type": "application/json", **(headers or {})})
    resp = urllib.request.urlopen(req)
    return json.loads(resp.read()), dict(resp.headers)


def _validate_chrome(trace, allow_flows=True):
    """Perfetto-loadability: every B closed by a same-name E on its
    (pid, tid), LIFO-nested, ts monotonic per track; flow events (s/f)
    allowed and checked for slice enclosure by ts equality."""
    stacks = {}
    last_ts = {}
    for e in trace["traceEvents"]:
        ph = e["ph"]
        if ph == "M":
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last_ts.get(key, 0.0), (e, last_ts)
        last_ts[key] = e["ts"]
        if ph == "B":
            stacks.setdefault(key, []).append(e["name"])
        elif ph == "E":
            assert stacks.get(key), f"E without open B: {e}"
            assert stacks[key][-1] == e["name"], (e, stacks[key])
            stacks[key].pop()
        elif ph == "i":
            assert e.get("s") == "t"
        elif ph in ("s", "f"):
            assert allow_flows and e.get("id"), e
            assert stacks.get(key), f"flow outside any open slice: {e}"
        else:
            raise AssertionError(f"unexpected phase {ph!r}: {e}")
    assert all(not s for s in stacks.values()), f"unclosed: {stacks}"


# ------------------------------------------------------ header parsing --
def test_header_roundtrip_and_child():
    ctx = TraceContext("tabc.000007", "tabc.000007/h0", 0, 1723.25)
    assert parse_trace_header(format_trace_header(ctx)) == ctx
    child = ctx.child(now=1724.0)
    assert child.request_id == ctx.request_id
    assert child.hop == 1 and child.parent == "tabc.000007/h1"
    assert parse_trace_header(format_trace_header(child)) == child


@pytest.mark.parametrize("value", [
    None, "", ";;;", "a;b;c",                      # wrong field count
    "x" * 300,                                     # oversized
    "rid;p;0;1.0;extra",                           # too many fields
    "r id;p;0;1.0",                                # space in id
    "rid\nX-Evil: 1;p;0;1.0",                      # embedded newline
    "rid;p;notanint;1.0",                          # bad hop
    "rid;p;65;1.0",                                # hop overflow
    "rid;p;-1;1.0",                                # negative hop
    "rid;p;99999999999999999999;1.0",              # huge hop
    "rid;p;0;nan", "rid;p;0;inf", "rid;p;0;xx",    # bad timestamp
    "rid;\x00\x01;0;1.0",                          # control chars
    "r\x7fd;p;0;1.0",
    "ríd;p;0;1.0",                                 # non-ASCII id
    "a/b;a/b/h0;0;1.0",                            # '/' in request id:
    # legal in SPAN ids only — the server could not echo this rid
    # verbatim as X-Request-Id, so the whole context degrades rather
    # than half-applying under two identities
])
def test_malformed_headers_degrade_to_none(value):
    assert parse_trace_header(value) is None


# ----------------------------------------- histogram merge (satellite) --
def test_merge_histograms_equals_union_stream():
    """Property: merging two snapshots == one histogram that observed
    the union stream — counts, sum, extremes, and quantile estimates
    all identical (fixed canonical buckets make counts a sufficient
    statistic)."""
    rng = random.Random(7)
    h1, h2, h3 = Histogram("x"), Histogram("x"), Histogram("x")
    for _ in range(1000):
        v = rng.lognormvariate(-4.5, 1.8)
        (h1 if rng.random() < 0.3 else h2).record(v)
        h3.record(v)
    m = merge_histograms([h1.bucket_snapshot(), h2.bucket_snapshot()])
    s3 = h3.bucket_snapshot()
    assert m["counts"] == s3["counts"]
    assert m["count"] == s3["count"] == 1000
    assert abs(m["sum"] - s3["sum"]) < 1e-9 * max(1.0, s3["sum"])
    assert m["min"] == s3["min"] and m["max"] == s3["max"]
    for q in (0.50, 0.95, 0.99):
        assert abs(m[f"p{int(q * 100)}"] - h3.percentile(q)) < 1e-12


def test_merge_histograms_empty_and_single():
    h = Histogram("x")
    h.record(0.01)
    m = merge_histograms([h.bucket_snapshot(),
                          Histogram("x").bucket_snapshot()])
    assert m["count"] == 1 and m["min"] == m["max"] == 0.01
    assert merge_histograms([]) == {"count": 0}


def test_merge_histograms_rejects_mismatched_bounds():
    a = Histogram("a")  # default 1e-5..100 bounds
    b = Histogram("b", lo=1e-3, hi=10.0)
    a.record(0.1)
    b.record(0.1)
    with pytest.raises(ValueError, match="mismatched bucket boundaries"):
        merge_histograms([a.bucket_snapshot(), b.bucket_snapshot()])
    bad = a.bucket_snapshot()
    bad["counts"] = bad["counts"][:-2]
    with pytest.raises(ValueError, match="counts length"):
        merge_histograms([a.bucket_snapshot(), bad])


def test_parse_prometheus_roundtrip_with_exemplars_and_labels():
    reg = MetricsRegistry()
    reg.counter("reqs_total", help="requests").inc(7)
    reg.gauge("depth").set(3.5)
    reg.gauge("depth_max").set(9)
    h = reg.histogram("lat_seconds", labels={"route": "/p"})
    for v in (0.001, 0.01, 0.01, 2.0):
        h.record(v, exemplar='r"esc\\aped')  # hostile exemplar label
    parsed = parse_prometheus(reg.render_prometheus())
    assert parsed["counters"]["reqs_total"] == ("reqs_total", 7.0)
    assert parsed["gauges"]["depth"][1] == 3.5
    hp = parsed["histograms"]['lat_seconds{route="/p"}']
    assert hp["count"] == 4 and abs(hp["sum"] - 2.021) < 1e-9
    assert sum(hp["counts"]) == 4
    # merged with itself: doubled everywhere
    m = merge_histograms([hp, hp])
    assert m["count"] == 8 and abs(m["sum"] - 4.042) < 1e-6


# ------------------------------------------- HTTP header fuzz, live --
@pytest.fixture(scope="module")
def _server():
    net = _lm()
    srv = InferenceServer(net=net, decode_vocab=13, decode_slots=2,
                          slo_p99_ms=500.0).start()
    yield srv
    srv.stop()


def test_trace_clock_endpoint(_server):
    c = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{_server.port}/trace/clock").read())
    for k in ("monotonic", "wall", "trace_t0", "pid"):
        assert k in c, c
    assert c["monotonic"] >= c["trace_t0"]


def test_malformed_context_never_500s_over_http(_server):
    """Fuzz the REAL ingress: hostile X-Graft-Trace / X-Request-Id
    values via a raw socket (urllib refuses to send some of them), the
    server answers 200 with a fresh server-minted id, and the Chrome
    export afterwards still validates."""
    port = _server.port
    body = json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 2}).encode()
    hostile = [
        b"X-Graft-Trace: " + b"A" * 4096,                  # oversized
        b"X-Graft-Trace: rid;p;99999999999;1.0",           # hop overflow
        b"X-Graft-Trace: rid;\xff\xfe\x80;0;1.0",          # non-UTF8
        b"X-Graft-Trace: a;b;c",                           # field count
        b"X-Request-Id: " + b"B" * 4096,                   # oversized id
        b"X-Request-Id: \xc3\x28bad",                      # non-UTF8 id
        b"X-Graft-Trace: rid;p;0;1.0\r\n "
        b"folded-continuation; more",                      # obs-fold
    ]
    for hdr in hostile:
        req = (b"POST /generate HTTP/1.1\r\nHost: x\r\n"
               b"Content-Type: application/json\r\n"
               b"Content-Length: " + str(len(body)).encode() + b"\r\n"
               + hdr + b"\r\nConnection: close\r\n\r\n" + body)
        with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
            s.settimeout(60)
            s.sendall(req)
            resp = b""
            while True:
                chunk = s.recv(65536)
                if not chunk:
                    break
                resp += chunk
        status = resp.split(b"\r\n", 1)[0]
        assert b"200" in status, (hdr, status)
        # fresh server-minted id, not an echo of the hostile bytes
        head = resp.split(b"\r\n\r\n", 1)[0].decode("latin-1")
        rid = [ln.split(":", 1)[1].strip()
               for ln in head.splitlines()
               if ln.lower().startswith("x-request-id:")][0]
        assert "A" * 100 not in rid and "B" * 100 not in rid
        assert "\n" not in rid and len(rid) <= 128
    # the ring absorbed all of that without corrupting the export —
    # and the exposition's exemplar escaping stayed intact
    trace = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{_server.port}/trace?format=chrome").read())
    _validate_chrome(trace)
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{_server.port}/metrics?format=prometheus"
    ).read().decode()
    parse_prometheus(text)  # parseable = not corrupted


def test_propagated_context_stamps_rpc_span(_server):
    ct = ClientTracer(FlightRecorder(256))
    ctx = ct.send("/generate")
    out, headers = _post(_server.port, "/generate",
                         json.dumps({"prompt": [1, 2, 3],
                                     "max_new_tokens": 2}).encode(),
                         headers=ct.headers(ctx))
    ct.done(ctx)
    assert out["request_id"].startswith(ctx.request_id + ".")
    assert headers["X-Request-Id"] == out["request_id"]
    evs = _server.tracer.events()
    rpc_b = [e for e in evs if e["name"] == "rpc" and e["ph"] == "B"
             and e.get("origin") == ctx.parent]
    assert rpc_b, "no rpc span carrying the flow edge"
    b = rpc_b[0]
    assert b["parent"] == ctx.parent
    assert b["args"]["trace"] == ctx.request_id
    assert b["args"]["hop"] == 0
    assert "net_gap_ms" in b["args"]
    # the matching close on the same request track (end() carries no
    # context fields — the flow edge lives on the B only). The handler
    # writes it in its `finally`, AFTER the response went out: the
    # client can be here first, so wait for the handler thread
    deadline = time.monotonic() + 30
    while not any(e["ph"] == "E" and e["name"] == "rpc"
                  and e["track"] == b["track"]
                  for e in _server.tracer.events()):
        assert time.monotonic() < deadline, "the rpc span never closed"
        time.sleep(0.005)


# --------------------------------------- two-process merge acceptance --
def _drive_fleet(srv_a, srv_b, client, n_requests=6, new_tokens=3):
    """One logical request crosses BOTH live servers (the future
    router shape: hop 0 to A, forwarded hop 1 to B with the same fleet
    identity), under a client span covering the whole journey."""
    rng = np.random.default_rng(0)
    ids = []
    for _ in range(n_requests):
        prompt = rng.integers(0, 13, 8).tolist()
        body = json.dumps({"prompt": prompt,
                           "max_new_tokens": new_tokens}).encode()
        ctx = client.send("/generate")
        out_a, _ = _post(srv_a.port, "/generate", body,
                         headers=client.headers(ctx))
        # the router hop: same identity, hop+1, its own client span
        # (the flow-source side of edge h1)
        fwd = client.send("/generate", ctx=ctx)
        out_b, _ = _post(srv_b.port, "/generate", body,
                         headers=client.headers(fwd))
        client.done(fwd)
        client.done(ctx)
        assert out_a["request_id"].startswith(ctx.request_id + ".")
        assert out_b["request_id"].startswith(ctx.request_id + ".")
        ids.append(ctx.request_id)
    return ids


def test_two_process_merged_waterfall():
    """THE acceptance demo: two live engine servers + a traced client
    merge into one Perfetto trace — three track groups, one flow chain
    per request (one ``s`` per hop edge, each matched by one ``f``),
    per-track monotonic timestamps after clock alignment, and the
    client span strictly containing both servers' rpc spans on the
    aligned axis."""
    net = _lm()
    srv_a = InferenceServer(net=net, decode_vocab=13,
                            decode_slots=2).start()
    srv_b = InferenceServer(net=net, decode_vocab=13,
                            decode_slots=2).start()
    client = ClientTracer(FlightRecorder(4096))
    try:
        ids = _drive_fleet(srv_a, srv_b, client)
        agg = TraceAggregator(
            [f"http://127.0.0.1:{srv_a.port}",
             f"http://127.0.0.1:{srv_b.port}"],
            client_recorder=client.recorder,
            names=["replica A", "replica B"])
        synced = agg.sync_clocks()
        assert len(synced) == 3
        assert all(s.rtt < 5.0 for s in synced.values())
        agg.poll()
        trace = agg.merged_chrome_trace()
        _validate_chrome(trace)
        evs = trace["traceEvents"]
        # three processes, each its own track group
        pids = {e["pid"] for e in evs if e["ph"] != "M"}
        assert pids == {0, 1, 2}, pids
        names = {e["pid"]: e["args"]["name"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert names[0] == "client"
        # one flow per request hop: every s has exactly one matching f
        srcs = [e for e in evs if e["ph"] == "s"]
        fins = [e for e in evs if e["ph"] == "f"]
        assert len(srcs) == 2 * len(ids)  # two hops per logical request
        assert sorted(e["id"] for e in srcs) == \
            sorted(e["id"] for e in fins)
        for rid in ids:
            edges = {e["id"] for e in srcs if e["id"].startswith(rid)}
            assert edges == {f"{rid}/h0", f"{rid}/h1"}, edges
        # clock-aligned causality: each request's client span must
        # OPEN before either downstream rpc span opens on the merged
        # axis (pair them per trace id — the client track is
        # "request <trace_id>", the rpc args carry the same id)
        tid_name = {(e["pid"], e["tid"]): e["args"]["name"]
                    for e in evs if e["ph"] == "M"
                    and e["name"] == "thread_name"}
        client_open = {}
        for e in evs:
            if e["pid"] == 0 and e["ph"] == "B" \
                    and e["name"] == "request":
                track = tid_name[(e["pid"], e["tid"])]
                client_open.setdefault(track.split()[-1], e["ts"])
        rpc_spans = [e for e in evs if e["ph"] == "B"
                     and e["name"] == "rpc"]
        assert len(rpc_spans) == 2 * len(ids)
        for rpc in rpc_spans:
            trace_id = rpc["args"]["trace"]
            assert trace_id in client_open, trace_id
            assert client_open[trace_id] <= rpc["ts"], (
                trace_id, client_open[trace_id], rpc["ts"],
                "clock alignment inverted client->server causality")
        stats = agg.stats()
        assert stats["completeness"] == 1.0
        assert stats["dropped_total"] == 0
    finally:
        srv_a.stop()
        srv_b.stop()


def test_gap_markers_on_ring_wraparound():
    """A replica with a tiny ring under enough load to wrap: the
    aggregator inserts visible ``ring_dropped`` markers and reports
    completeness < 1 — lost history is labeled, not silently elided."""
    net = _lm()
    srv = InferenceServer(net=net, decode_vocab=13, decode_slots=2,
                          trace_buffer=64).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        agg = TraceAggregator([base])
        agg.sync_clocks()
        rng = np.random.default_rng(1)
        for _ in range(12):  # enough events to lap the 64-slot ring
            _post(srv.port, "/generate", json.dumps(
                {"prompt": rng.integers(0, 13, 8).tolist(),
                 "max_new_tokens": 3}).encode())
        snap = json.loads(urllib.request.urlopen(
            base + "/trace?since=0").read())
        assert snap["dropped"] > 0, "ring did not wrap; grow the load"
        # one LATE poll: the cursor (0) fell behind the ring, so the
        # overwritten prefix is a real hole in the merged history
        agg.poll()
        trace = agg.merged_chrome_trace()
        gaps = [e for e in trace["traceEvents"]
                if e["name"] == "ring_dropped"]
        assert gaps, "no gap marker despite dropped events"
        assert gaps[0]["args"]["dropped_delta"] >= 1
        stats = agg.stats()
        assert stats["dropped_total"] > 0
        assert stats["completeness"] < 1.0
    finally:
        srv.stop()


# ------------------------------------------------- metrics federation --
def test_fleet_federation_two_servers():
    """Federated fleet exposition over two LIVE servers: counters sum
    exactly, fleet_replicas_up tracks liveness, and the fleet p99 from
    MERGED histogram buckets lands within one bucket of the p99 of the
    POOLED per-replica latency samples (the acceptance bound)."""
    net = _lm()
    srv_a = InferenceServer(net=net, decode_vocab=13, decode_slots=2,
                            slo_p99_ms=500.0).start()
    srv_b = InferenceServer(net=net, decode_vocab=13, decode_slots=2,
                            slo_p99_ms=500.0).start()
    try:
        rng = np.random.default_rng(2)
        for i in range(14):
            srv = srv_a if i % 2 else srv_b
            _post(srv.port, "/generate", json.dumps(
                {"prompt": rng.integers(0, 13, 8).tolist(),
                 "max_new_tokens": 3}).encode())
        targets = [f"http://127.0.0.1:{srv_a.port}",
                   f"http://127.0.0.1:{srv_b.port}"]
        fleet = FleetMetrics(targets)
        assert fleet.scrape() == 2
        fed = fleet.federate()
        assert fed["replicas_up"] == 2
        # counters sum exactly: http_requests_total across both
        a = json.loads(urllib.request.urlopen(
            targets[0] + "/metrics").read())
        b = json.loads(urllib.request.urlopen(
            targets[1] + "/metrics").read())
        total = (a["counters"]["http_requests_total"]
                 + b["counters"]["http_requests_total"])
        # the federation scrape itself is not an http POST but DOES
        # bump each server's request counter by >= 1 GET — re-read via
        # the federated value being >= the later JSON reads' sum - slack
        assert fed["counters"]["http_requests_total"] >= 14
        # fleet p99 vs pooled per-replica samples, within one bucket
        pooled = sorted(
            lat for srv in (srv_a, srv_b)
            for buf in srv.slo._samples.values() for _, lat in buf)
        assert len(pooled) == 14
        sample_p99 = pooled[min(len(pooled) - 1,
                                int(0.99 * len(pooled)))]
        fleet_p99 = fed["routes"]["/generate"]["p99_ms"] / 1e3
        bounds = Histogram("x")._bounds
        i_s = bisect.bisect_left(bounds, sample_p99)
        i_f = bisect.bisect_left(bounds, fleet_p99)
        assert abs(i_s - i_f) <= 1, (
            f"fleet p99 {fleet_p99} vs pooled sample p99 {sample_p99}: "
            f"buckets {i_f} vs {i_s}")
        # exposition renders and re-parses
        text = fleet.render_prometheus()
        assert "fleet_replicas_up 2" in text
        assert "fleet_route_p99_ms{route=\"/generate\"}" in text
        reparsed = parse_prometheus(text)
        assert reparsed["histograms"][
            'http_route_latency_seconds{route="/generate"}']["count"] == 14
        # one replica dies -> liveness + scrape errors move
        srv_b.stop()
        fleet.scrape()
        fed2 = fleet.federate()
        assert fed2["replicas_up"] == 1
        assert fed2["scrape_errors_total"] >= 1
        summary = fleet.summary()
        assert summary["replicas"][1]["up"] is False
        assert summary["replicas"][0]["up"] is True
    finally:
        srv_a.stop()
        srv_b.stop()


def test_fleet_burn_rates_weighted_toward_traffic():
    """An idle replica must not dilute a burning one: weights follow
    per-scrape traffic deltas."""
    fleet = FleetMetrics(["http://x", "http://y"])
    mk = lambda fast, slow, n: {
        "counters": {}, "types": {},
        "gauges": {"slo_burn_rate_fast": ("slo_burn_rate_fast", fast),
                   "slo_burn_rate_slow": ("slo_burn_rate_slow", slow)},
        "histograms": {'http_route_latency_seconds{route="/g"}': {
            "name": "http_route_latency_seconds", "labels": {"route": "/g"},
            "bounds": [1.0], "counts": [n, 0], "sum": 0.1 * n,
            "count": n}}}
    with fleet._lock:
        fleet._parsed = [mk(8.0, 4.0, 90), mk(0.0, 0.0, 10)]
        fleet._up = [True, True]
        fleet._weights = [90.0, 10.0]
    fed = fleet.federate()
    assert fed["burn_rate_fast"] == pytest.approx(7.2)
    assert fed["burn_rate_slow"] == pytest.approx(3.6)
    assert fed["burning"] is True  # 7.2 >= 6 and 3.6 >= 3


# ------------------------------------------------------- CLI + server --
def test_fleet_server_and_cli(tmp_path):
    net = _lm()
    srv = InferenceServer(net=net, decode_vocab=13,
                          decode_slots=2).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        _post(srv.port, "/generate", json.dumps(
            {"prompt": [1, 2, 3], "max_new_tokens": 2}).encode())
        fleet = FleetMetrics([base])
        agg = TraceAggregator([base])
        agg.sync_clocks()
        agg.poll()
        fleet.scrape()
        fsrv = FleetTelemetryServer(fleet, agg).start()
        try:
            fbase = f"http://127.0.0.1:{fsrv.port}"
            text = urllib.request.urlopen(fbase + "/fleet").read().decode()
            assert "fleet_replicas_up 1" in text
            summ = json.loads(urllib.request.urlopen(
                fbase + "/fleet/summary").read())
            assert summ["replicas_up"] == 1
            assert summ["trace"]["events_merged"] > 0
            tr = json.loads(urllib.request.urlopen(
                fbase + "/fleet/trace").read())
            _validate_chrome(tr)
            try:
                urllib.request.urlopen(fbase + "/nope")
                assert False, "expected 404"
            except urllib.error.HTTPError as e:
                assert e.code == 404
                e.read()
        finally:
            fsrv.stop()
        # the module CLI end to end: one pass, merged trace to a file
        from deeplearning4j_tpu.serving import telemetry
        out = tmp_path / "fleet_trace.json"
        rc = telemetry.main(["--targets", base, "--out", str(out),
                             "--duration", "0", "--clock-probes", "2"])
        assert rc == 0
        trace = json.loads(out.read_text())
        _validate_chrome(trace)
        assert trace["traceEvents"], "CLI produced an empty merge"
    finally:
        srv.stop()


def test_cli_subcommand_wires_through(tmp_path, capsys):
    from deeplearning4j_tpu.cli.main import build_parser
    args = build_parser().parse_args(
        ["telemetry", "--targets", "http://127.0.0.1:1",
         "--duration", "0", "--clock-probes", "1"])
    assert args.func(args) == 0  # unreachable target: degrades, no raise
    out = capsys.readouterr().out
    payload = json.loads(out.strip().splitlines()[-1])
    assert payload["fleet"]["replicas_up"] == 0
    assert payload["fleet"]["scrape_errors_total"] >= 1


def test_aggregator_retention_cap_keeps_completeness():
    """An always-on aggregator must stay bounded: beyond max_events the
    oldest stored events trim (counted, not lost from the completeness
    accounting — trimmed events WERE merged)."""
    rec = FlightRecorder(4096)
    for i in range(3000):
        rec.instant("e", slot=i % 4)
    agg = TraceAggregator([], client_recorder=rec, max_events=1024)
    agg.sync_clocks()
    agg.poll()
    stats = agg.stats()
    assert stats["events_merged"] == 3000  # all tailed
    assert stats["trimmed_total"] == 3000 - 1024
    assert stats["completeness"] == 1.0  # nothing was MISSED
    src = agg._sources[0]
    assert len(src.events) == 1024  # memory bounded
    trace = agg.merged_chrome_trace()
    assert trace["traceEvents"]  # renders the surviving window


def test_new_trace_id_unique_under_concurrent_first_use():
    """Concurrent first calls (load-generator threads) must not each
    install a fresh counter and mint duplicate fleet ids."""
    import threading as _threading

    import deeplearning4j_tpu.serving.telemetry as tm
    with tm._tid_lock:
        pass  # lock exists
    tm._tid_counter = None  # force re-init race window
    ids = []
    barrier = _threading.Barrier(8)

    def mint():
        barrier.wait()
        for _ in range(50):
            ids.append(tm.new_trace_id())

    threads = [_threading.Thread(target=mint) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(ids) == len(set(ids)) == 400


def test_serving_update_merges_pushers():
    """The engine-side metrics pusher and the fleet telemetry pusher
    share the UI /serving page: their top-level keys must compose, not
    clobber (the telemetry CLI pushes metrics={})."""
    from deeplearning4j_tpu.ui.listeners import post_serving_metrics
    from deeplearning4j_tpu.ui.server import UiServer

    ui = UiServer(port=0)
    try:
        base = ui.url()
        post_serving_metrics(base, {"counters": {"x_total": 1}})
        post_serving_metrics(base, {}, fleet={"replicas_up": 2,
                                              "replicas_total": 2})
        data = json.loads(urllib.request.urlopen(
            base + "/serving/data?sid=default").read())
        assert data["metrics"]["counters"]["x_total"] == 1  # not blanked
        assert data["fleet"]["replicas_up"] == 2  # fleet line present
        # engine re-push refreshes metrics WITHOUT dropping the fleet key
        post_serving_metrics(base, {"counters": {"x_total": 5}})
        data = json.loads(urllib.request.urlopen(
            base + "/serving/data?sid=default").read())
        assert data["metrics"]["counters"]["x_total"] == 5
        assert data["fleet"]["replicas_up"] == 2
    finally:
        ui.stop()


def test_gauge_federation_semantics():
    """Non-additive gauge families must not sum across replicas: three
    calm replicas (burn 0.5 each) must not read as a burning fleet
    under the per-replica series name, per-route p99 must be the worst
    replica's, serving_ready the fleet min, while queue depths and
    per-second throughputs stay additive."""
    from deeplearning4j_tpu.serving.telemetry import _gauge_agg
    assert _gauge_agg("slo_burn_rate_fast") == "max"
    assert _gauge_agg("slo_route_p99_ms") == "max"
    assert _gauge_agg("device_mfu_estimate") == "max"
    assert _gauge_agg("kv_pool_utilization") == "max"
    assert _gauge_agg("decode_queue_depth_max") == "max"
    assert _gauge_agg("serving_ready") == "min"
    assert _gauge_agg("decode_queue_depth") == "sum"
    assert _gauge_agg("decode_tokens_per_sec") == "sum"
    assert _gauge_agg("device_hbm_gbps") == "sum"
    assert _gauge_agg("kv_pool_blocks_capacity") == "sum"

    fleet = FleetMetrics(["http://x", "http://y", "http://z"])
    mk = lambda burn, ready, depth: {
        "counters": {}, "types": {}, "histograms": {},
        "gauges": {"slo_burn_rate_fast": ("slo_burn_rate_fast", burn),
                   "serving_ready": ("serving_ready", ready),
                   "decode_queue_depth": ("decode_queue_depth", depth)}}
    with fleet._lock:
        fleet._parsed = [mk(0.5, 1, 2), mk(0.5, 1, 3), mk(0.5, 0, 4)]
        fleet._up = [True, True, True]
        fleet._weights = [1.0, 1.0, 1.0]
    fed = fleet.federate()
    assert fed["gauges"]["slo_burn_rate_fast"] == 0.5  # max, not 1.5
    assert fed["gauges"]["serving_ready"] == 0  # one replica down
    assert fed["gauges"]["decode_queue_depth"] == 9  # additive
