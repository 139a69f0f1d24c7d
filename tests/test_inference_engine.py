"""Production inference engine: continuous micro-batching, decode
scheduling, SLO metrics (ISSUE 1 tentpole).

The acceptance contract: under concurrent load the batched path aggregates
requests (mean batch occupancy > 1), beats the lock-serialized path on
requests/sec, honors per-request deadlines without dying, and returns
bit-identical outputs to the unbatched path; the decode scheduler
interleaves sequences of different lengths and matches solo greedy
decoding token-for-token.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.fetchers import load_iris_dataset
from deeplearning4j_tpu.inference import (DecodeScheduler, MetricsRegistry,
                                          MicroBatcher, QueueFullError,
                                          RequestTimeoutError)
from deeplearning4j_tpu.models.sampling import generate_transformer
from deeplearning4j_tpu.models.zoo import mlp_iris, transformer_lm
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def _trained_iris_net(steps=10):
    iris = load_iris_dataset()
    net = MultiLayerNetwork(mlp_iris()).init()
    for _ in range(steps):
        net.fit_batch(iris.features, iris.labels)
    return net, iris


def _post(port, path, body):
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=body,
                                 headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req).read())


# ---------------------------------------------------------------- metrics --
def test_histogram_percentiles():
    m = MetricsRegistry()
    h = m.histogram("lat")
    for v in np.linspace(0.001, 0.1, 1000):
        h.record(float(v))
    assert h.count == 1000
    # log-bucket interpolation: estimates within a bucket width of truth
    assert 0.03 < h.percentile(0.5) < 0.08
    assert 0.08 < h.percentile(0.95) <= 0.1
    snap = h.snapshot()
    assert snap["count"] == 1000 and snap["p50"] <= snap["p95"] <= snap["p99"]
    assert snap["min"] == pytest.approx(0.001)
    assert snap["max"] == pytest.approx(0.1)


class _CountingLock:
    """Lock proxy counting acquisitions (context-manager uses only)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.acquisitions = 0

    def __enter__(self):
        self._lock.acquire()
        self.acquisitions += 1
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False


def test_histogram_snapshot_is_one_atomic_lock_acquisition():
    """Regression for the graftlint CC004 finding: snapshot() used to
    read count/sum under the lock but min/max lock-free and re-acquire
    per percentile — a scrape racing record() could pair a count from one
    instant with quantiles from another (e.g. a count-1 snapshot whose
    p99 was not its only sample). The whole snapshot (and each
    percentile) must derive from ONE locked copy of the state."""
    m = MetricsRegistry()
    h = m.histogram("atomic")
    for v in (0.002, 0.02, 0.2):
        h.record(v)
    counter = _CountingLock()
    h._lock = counter
    snap = h.snapshot()
    assert counter.acquisitions == 1, \
        "snapshot must take the instrument lock exactly once"
    assert snap["count"] == 3
    assert snap["min"] <= snap["p50"] <= snap["p95"] <= snap["p99"] \
        <= snap["max"]
    counter.acquisitions = 0
    h.percentile(0.5)
    assert counter.acquisitions == 1


def test_histogram_snapshot_consistent_under_concurrent_records():
    """Hammer: a writer records values from a fixed set while snapshots
    stream; every snapshot must be internally consistent (ordered
    quantiles inside [min, max], mean inside [min, max], sum/mean/count
    agreeing) — torn multi-lock snapshots break these invariants."""
    m = MetricsRegistry()
    h = m.histogram("hammer")
    stop = threading.Event()

    def writer():
        vals = (0.001, 0.005, 0.05, 0.5)
        i = 0
        while not stop.is_set():
            h.record(vals[i % 4])
            i += 1

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 2.0
        checked = 0
        while time.monotonic() < deadline:
            snap = h.snapshot()
            if not snap.get("count"):
                continue
            checked += 1
            assert snap["min"] <= snap["p50"] <= snap["p95"] \
                <= snap["p99"] <= snap["max"]
            assert snap["min"] <= snap["mean"] <= snap["max"]
            # snapshot rounds to 6 decimals; compare within that grain
            assert snap["mean"] == pytest.approx(
                snap["sum"] / snap["count"], abs=2e-6)
        assert checked > 50
    finally:
        stop.set()
        t.join(timeout=10)


def test_registry_snapshot_and_text():
    m = MetricsRegistry()
    m.counter("reqs").inc(3)
    m.gauge("depth").set(7)
    m.histogram("lat").record(0.01)
    snap = m.snapshot()
    assert snap["counters"]["reqs"] == 3
    assert snap["gauges"]["depth"]["value"] == 7
    assert snap["histograms"]["lat"]["count"] == 1
    text = m.render_text()
    # fractional quantile labels, the Prometheus summary convention
    assert "reqs 3" in text and 'lat{quantile="0.5"}' in text


def test_metrics_post_to_ui_serving_page():
    """`post_serving_metrics` feeds the training UI's /serving view."""
    from deeplearning4j_tpu.ui.listeners import post_serving_metrics
    from deeplearning4j_tpu.ui.server import UiServer
    ui = UiServer(port=0)
    try:
        m = MetricsRegistry()
        m.counter("predict_requests_total").inc(12)
        m.histogram("predict_latency_sec").record(0.02)
        url = f"http://127.0.0.1:{ui.port}"
        post_serving_metrics(url, m, session_id="s1")
        page = urllib.request.urlopen(url + "/serving").read().decode()
        assert "Serving SLO metrics" in page
        data = json.loads(urllib.request.urlopen(
            url + "/serving/data?sid=s1").read())
        assert data["metrics"]["counters"]["predict_requests_total"] == 12
        assert data["metrics"]["histograms"]["predict_latency_sec"]["count"] == 1
    finally:
        ui.stop()


# ---------------------------------------------------------------- batcher --
def test_batcher_aggregates_and_scatters():
    seen = []

    def fwd(a):
        seen.append(a.shape[0])
        return a * 2.0

    b = MicroBatcher(fwd, max_batch=16, batch_window_s=0.05).start()
    try:
        futs = [b.submit(np.full((2, 3), i, np.float32)) for i in range(4)]
        outs = [f.result(10) for f in futs]
        for i, o in enumerate(outs):
            np.testing.assert_array_equal(o, np.full((2, 3), 2.0 * i))
        # 8 rows from 4 requests collated into one bucketed forward
        assert seen == [8]
        assert b.metrics.histogram("batcher_batch_occupancy").mean == 4
    finally:
        b.stop()


def test_batcher_bucketed_padding():
    shapes = []

    def fwd(a):
        shapes.append(a.shape[0])
        return a

    b = MicroBatcher(fwd, max_batch=32, batch_window_s=0.0).start()
    try:
        np.testing.assert_array_equal(
            b.predict(np.ones((5, 2), np.float32)),
            np.ones((5, 2), np.float32))
        assert shapes == [8]  # 5 rows pad to the 8-bucket, result unpadded
    finally:
        b.stop()


def test_batcher_backpressure_and_deadline():
    release = threading.Event()

    def slow_fwd(a):
        release.wait(10)
        return a

    b = MicroBatcher(slow_fwd, max_batch=4, max_queue=2,
                     batch_window_s=0.0).start()
    try:
        first = b.submit(np.zeros((1, 2), np.float32))  # occupies dispatcher
        time.sleep(0.1)
        b.submit(np.zeros((1, 2), np.float32))
        b.submit(np.zeros((1, 2), np.float32))
        with pytest.raises(QueueFullError):
            b.submit(np.zeros((1, 2), np.float32))
        assert b.metrics.counter("batcher_rejected_total").value == 1
        # expired-deadline request fails without being dispatched
        with pytest.raises((QueueFullError, RequestTimeoutError)):
            b.predict(np.zeros((1, 2), np.float32), timeout_s=0.0)
        release.set()
        assert first.result(10).shape == (1, 2)
    finally:
        release.set()
        b.stop()


def test_batcher_model_error_fails_request_not_dispatcher():
    calls = {"n": 0}

    def flaky(a):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return a

    b = MicroBatcher(flaky, batch_window_s=0.0).start()
    try:
        with pytest.raises(RuntimeError, match="boom"):
            b.predict(np.zeros((1, 2), np.float32))
        # dispatcher survived; next request succeeds
        assert b.predict(np.zeros((1, 2), np.float32)).shape == (1, 2)
    finally:
        b.stop()


# ------------------------------------------------- batched serving (HTTP) --
def test_server_batched_matches_unbatched_bit_identical():
    net, iris = _trained_iris_net()
    from deeplearning4j_tpu.serving import InferenceServer
    sb = InferenceServer(net=net, batching=True, batch_window_ms=2.0).start()
    su = InferenceServer(net=net, batching=False).start()
    try:
        body = json.dumps({"data": iris.features[:9].tolist()}).encode()
        ob = _post(sb.port, "/predict", body)
        ou = _post(su.port, "/predict", body)
        assert ob["predictions"] == ou["predictions"]  # bit-identical JSON
        assert ob["classes"] == ou["classes"]
    finally:
        sb.stop()
        su.stop()


def test_server_concurrent_load_batches_and_reports_metrics():
    net, iris = _trained_iris_net()
    from deeplearning4j_tpu.serving import InferenceServer
    srv = InferenceServer(net=net, batching=True, batch_window_ms=10.0).start()
    try:
        body = json.dumps({"data": iris.features[:4].tolist()}).encode()
        expect = _post(srv.port, "/predict", body)  # warm the jit caches
        results, errors = [], []

        def client():
            try:
                for _ in range(6):
                    results.append(_post(srv.port, "/predict", body))
            except Exception as e:  # pragma: no cover - diagnostic
                errors.append(e)

        threads = [threading.Thread(target=client) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert len(results) == 48
        for r in results:  # batching must not mix rows across requests
            assert r["predictions"] == expect["predictions"]
        m = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics").read())
        occ = m["histograms"]["predict_batch_occupancy"]
        lat = m["histograms"]["predict_latency_sec"]
        assert occ["count"] > 0 and occ["mean"] > 1.0, occ
        assert lat["count"] >= 48 and lat["p99"] > 0, lat
        assert m["gauges"]["predict_queue_depth"]["max"] >= 1
        assert m["counters"]["predict_requests_total"] >= 49
    finally:
        srv.stop()


def test_server_deadline_expires_server_stays_up():
    net, iris = _trained_iris_net(steps=2)
    from deeplearning4j_tpu.serving import InferenceServer
    srv = InferenceServer(net=net, batching=True, batch_window_ms=5.0).start()
    try:
        body = json.dumps({"data": iris.features[:2].tolist()}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, "/predict?timeout_ms=0", body)
        assert ei.value.code == 504
        # server alive, timeout counted, normal requests still served
        ok = _post(srv.port, "/predict", body)
        assert len(ok["classes"]) == 2
        m = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics").read())
        assert m["counters"]["predict_timeouts_total"] >= 1
    finally:
        srv.stop()


def _serving_mlp(n_in=64, hidden=512, n_out=10):
    """A model big enough that the forward (not HTTP plumbing) dominates —
    the regime batching exists for. The iris MLP is so small that the
    batch window costs more than the aggregation saves."""
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    b = NeuralNetConfiguration.builder().seed(1).learning_rate(0.01).list()
    b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
    b.layer(DenseLayer(n_in=hidden, n_out=hidden, activation="relu"))
    b.layer(OutputLayer(n_in=hidden, n_out=n_out, activation="softmax",
                        loss="mcxent"))
    return MultiLayerNetwork(b.build()).init()


def test_server_batched_path_aggregates_concurrent_clients():
    """8 concurrent clients: the batched path really aggregates (mean
    occupancy of a dispatched batch above 1: the count its throughput
    rests on) and answers what the lock-serialized path answers. Which
    of the two serves more requests a second is a speed statement: no
    CPU timing under a loaded test host decides it (ROADMAP Design 8)."""
    from deeplearning4j_tpu.serving import InferenceServer
    net = _serving_mlp()
    rng = np.random.default_rng(0)
    body = json.dumps(
        {"data": rng.standard_normal((8, 64)).tolist()}).encode()

    def drive(server, n_threads=8, reqs_each=20):
        outs = [None] * n_threads

        def client(k):
            for _ in range(reqs_each):
                outs[k] = _post(server.port, "/predict", body)

        ts = [threading.Thread(target=client, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return outs

    sb = InferenceServer(net=net, batching=True, batch_window_ms=1.0,
                         max_batch=64).start()
    try:
        batched = drive(sb)
        occ = sb.metrics.histogram("predict_batch_occupancy").mean
    finally:
        sb.stop()
    su = InferenceServer(net=net, batching=False).start()
    try:
        serial = drive(su)
    finally:
        su.stop()
    assert occ > 1.0, f"no aggregation happened (occupancy {occ})"
    want = np.asarray(serial[0]["predictions"])
    assert want.shape[0] == 8
    for out in batched + serial:
        np.testing.assert_allclose(np.asarray(out["predictions"]), want,
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- decode scheduler --
def _lm(v=13, cache=48, rope=False):
    conf = transformer_lm(vocab_size=v, d_model=16, n_heads=2, n_blocks=2,
                          rope=rope)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()


def test_decode_scheduler_matches_solo_greedy():
    """Sequences of different lengths interleaved through fewer slots than
    sequences must each reproduce solo cached greedy decoding exactly."""
    V = 13
    net = _lm(V)
    prompts = [[1, 2, 3], [5], [7, 8, 9, 10, 2], [4, 6], [11, 0, 3, 2]]
    n_new = [6, 4, 3, 7, 5]
    solo = [generate_transformer(net, p, n, V, use_cache=True)
            for p, n in zip(prompts, n_new)]
    # transfer_guard="disallow" locks in device residency of the decode
    # step: any implicit host<->device transfer in the hot loop raises
    # (the sampled-token readback goes through the allow-listed
    # analysis.runtime.host_read boundary)
    eng = DecodeScheduler(net, V, n_slots=2,
                          transfer_guard="disallow").start()
    try:
        handles = [eng.submit(p, n) for p, n in zip(prompts, n_new)]
        got = [h.result(120) for h in handles]
    finally:
        eng.stop()
    assert got == solo
    # 5 sequences through 2 slots: continuous admission really interleaved
    assert eng.metrics.counter("decode_sequences_total").value == 5
    assert eng.metrics.counter("decode_tokens_total").value == sum(n_new)
    assert eng.metrics.histogram("decode_slot_occupancy").mean > 1.0


def test_decode_scheduler_rope_per_slot_positions():
    """RoPE decode depends on absolute positions — per-slot position
    vectors must rotate each slot at its own depth."""
    V = 13
    net = _lm(V, rope=True)
    prompts = [[1, 2, 3, 4], [5], [7, 8]]
    solo = [generate_transformer(net, p, 5, V, use_cache=True)
            for p in prompts]
    eng = DecodeScheduler(net, V, n_slots=2).start()
    try:
        got = [h.result(120) for h in
               [eng.submit(p, 5) for p in prompts]]
    finally:
        eng.stop()
    assert got == solo


def test_decode_scheduler_eos_and_admission_guard():
    V = 13
    net = _lm(V, cache=16)
    eng = DecodeScheduler(net, V, n_slots=2).start()
    try:
        # cache-capacity admission check fails fast, nothing is queued
        with pytest.raises(ValueError, match="max_cache_len"):
            eng.submit(list(range(10)), 10)
        # EOS stops a sequence early: use greedy's first token as the EOS
        first = generate_transformer(net, [3, 1], 1, V, use_cache=True)[0]
        toks = eng.submit([3, 1], 8, eos_id=first).result(120)
        assert toks == [first]
    finally:
        eng.stop()


def test_decode_scheduler_recurrent_net():
    """The engine also schedules recurrent MultiLayerNetworks (h/c slot
    rows instead of a KV cache) — admit zeroes the slot's state rows."""
    from deeplearning4j_tpu.models.sampling import generate_rnn
    from deeplearning4j_tpu.models.zoo import char_rnn_lstm
    V = 11
    rnn = MultiLayerNetwork(char_rnn_lstm(vocab_size=V, hidden=16)).init()
    prompts = [[1, 2], [3], [4, 5, 6]]
    solo = [generate_rnn(rnn, p, 5, V) for p in prompts]
    eng = DecodeScheduler(rnn, V, n_slots=2).start()
    try:
        got = [h.result(120) for h in [eng.submit(p, 5) for p in prompts]]
    finally:
        eng.stop()
    assert got == solo


def test_decode_scheduler_slot_reuse_is_clean():
    """A slot that served a long sequence must not leak state into the
    next occupant (stale KV beyond the new position is causally masked)."""
    V = 13
    net = _lm(V)
    solo = generate_transformer(net, [2, 4], 5, V, use_cache=True)
    eng = DecodeScheduler(net, V, n_slots=1).start()
    try:
        eng.submit([7, 8, 9, 10, 2, 6, 1], 8).result(120)  # pollute the slot
        assert eng.submit([2, 4], 5).result(120) == solo
    finally:
        eng.stop()
