"""Load-generate against the batched inference server (ISSUE 1 + 5).

Starts an `InferenceServer` (continuous micro-batching ON), drives it with
N closed-loop HTTP client threads, then prints the SLO picture straight
from `GET /metrics`: requests/sec, mean batch occupancy, queue depth
high-water mark, and p50/p95/p99 end-to-end latency. Run `--compare` to
also measure the lock-serialized fallback on the same model (the
pre-batching serving path) and print the speedup.

`--generate` drives the continuous-batching decode scheduler instead
(`POST /generate` on a small transformer LM): each response's per-phase
``timings`` breakdown is printed as a waterfall line, the run ends with
a CLIENT-side p50/p95/p99 + phase-breakdown table (ISSUE 11: the
independent cross-check for the server's SLO monitor — the two measure
the same requests at opposite ends of the socket). Every request also
carries a propagated ``X-Graft-Trace`` context and a client-side span
(ISSUE 12), so `--trace-out FILE` now writes the MERGED two-process
Chrome trace (client + server track groups, clock-aligned, one flow
arrow per request) via `serving.telemetry.TraceAggregator` — open it
at https://ui.perfetto.dev to read the network/queue gap between the
tiers straight off the waterfall; the report prints the same gap as
client-observed minus server-observed latency.

Chaos-compatible (ISSUE 7): the HTTP client retries connection-refused
and 5xx responses with capped exponential backoff and honors 503
``Retry-After`` hints, so a run against a server under failpoint
injection or a draining restart rides the outage out instead of
aborting; per-request retry counts (and server-side engine-restart
recoveries, the ``retries`` field in /generate responses) are reported
at the end.

Sharded serving (ISSUE 9): `--generate --mesh N` runs the decode engine
tensor-parallel over an N-device mesh (heads/FFN sharded over the `tp`
axis, paged KV pool head-sharded with a PER-DEVICE byte budget) and
reports tokens/s — the reproducible-from-the-example form of
`bench.py`'s `sharded_decode` row. On CPU the flag forces
`--xla_force_host_platform_device_count=N` for you.

Fleet serving (ISSUE 13): `--fleet N` spawns a prefix-affine
`serving/router.py` front-end plus N engine replica PROCESSES and
drives `/generate` through the router — reporting req/s, client p99,
the durable-journal ledger (accepted/finished/lost), and the fleet
prefix-cache hit rate that affinity routing protects.

    python examples/serving_load_test.py            # batched only
    python examples/serving_load_test.py --compare  # batched vs serialized
    python examples/serving_load_test.py --generate --trace-out trace.json
    python examples/serving_load_test.py --generate --mesh 4
    python examples/serving_load_test.py --fleet 2
"""
import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

def _mesh_arg(argv):
    """The --mesh value, handling both '--mesh N' and '--mesh=N' (None
    when absent or malformed — argparse reports the error later)."""
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--mesh="):
            return a.split("=", 1)[1]
    return None


_n = _mesh_arg(sys.argv[1:])
if _n and _n.isdigit():
    # must happen BEFORE jax initializes (the imports below pull it in):
    # N virtual host devices so the tp mesh exists on plain CPU. Unlike
    # conftest.py/bench.py (which only fill an ABSENT flag), a smaller
    # pre-existing count is REPLACED — the user asked for exactly N
    _flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
              if "xla_force_host_platform_device_count" not in f]
    _flags.append(f"--xla_force_host_platform_device_count={_n}")
    os.environ["XLA_FLAGS"] = " ".join(_flags)

import numpy as np

from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.serving import InferenceServer


def _make_net(n_in=64, hidden=256, n_out=10):
    b = NeuralNetConfiguration.builder().seed(1).learning_rate(0.01).list()
    b.layer(DenseLayer(n_in=n_in, n_out=hidden, activation="relu"))
    b.layer(DenseLayer(n_in=hidden, n_out=hidden, activation="relu"))
    b.layer(OutputLayer(n_in=hidden, n_out=n_out, activation="softmax",
                        loss="mcxent"))
    return MultiLayerNetwork(b.build()).init()


# retry policy for chaos / draining-restart runs: the server may answer
# 5xx (engine recovering, degradation ladder, injected HTTP fault) or
# refuse the connection entirely for a moment — the load generator must
# ride that out, not abort the run. 4xx (client errors) never retry.
_MAX_RETRIES = 8
_BACKOFF_BASE_S = 0.05
_BACKOFF_CAP_S = 2.0


def _post(port, path, body, retries=None, headers=None):
    """POST with capped exponential backoff on connection-refused/5xx.
    Honors a 503's ``Retry-After`` header (the degradation ladder's
    explicit back-off hint) over the computed delay. Returns the parsed
    JSON; when a ``retries`` list is passed, the number of retries this
    request needed is appended to it (the per-request retry record).
    ``headers`` rides extra request headers (the propagated
    ``X-Graft-Trace`` context in --generate mode)."""
    attempt = 0
    while True:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=body,
            headers={"Content-Type": "application/json",
                     **(headers or {})})
        try:
            out = json.loads(urllib.request.urlopen(req).read())
            if retries is not None:
                retries.append(attempt)
            return out
        except urllib.error.HTTPError as e:
            if e.code < 500 and e.code != 503:
                raise  # a client error will not improve with retries
            delay = min(_BACKOFF_CAP_S, _BACKOFF_BASE_S * (2 ** attempt))
            ra = e.headers.get("Retry-After") if e.headers else None
            if ra:
                try:
                    delay = max(delay, float(ra))
                except ValueError:
                    pass
            e.read()  # drain so the connection can be reused
        except urllib.error.URLError:
            # connection refused/reset: the server is mid-restart
            delay = min(_BACKOFF_CAP_S, _BACKOFF_BASE_S * (2 ** attempt))
        attempt += 1
        if attempt > _MAX_RETRIES:
            raise RuntimeError(
                f"{path}: gave up after {_MAX_RETRIES} retries")
        time.sleep(delay)


def _post_stream(port, path, body, headers=None):
    """POST a ``stream=true`` /generate and consume the SSE response
    (ISSUE 14). Returns the TERMINAL event dict augmented with the
    client-observed ``ttft_ms`` (send -> first token event on the wire
    — the real thing the server's `generate_first_token_seconds`
    histogram approximates from inside) and ``client_ms``, plus the
    per-event token list for the token-identity cross-check."""
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    t0 = time.perf_counter()
    conn.request("POST", path, body,
                 {"Content-Type": "application/json", **(headers or {})})
    resp = conn.getresponse()
    if resp.status != 200:
        raise urllib.error.HTTPError(path, resp.status, resp.reason,
                                     resp.headers, resp)
    buf = b""
    ttft = None
    done = None
    toks = []
    while True:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            line, buf = buf.split(b"\n\n", 1)
            if not line.startswith(b"data: "):
                continue
            evt = json.loads(line[len(b"data: "):])
            if evt.get("done"):
                done = evt
            elif "token" in evt:
                if ttft is None:
                    ttft = (time.perf_counter() - t0) * 1e3
                toks.append(evt["token"])
    conn.close()
    if done is None:
        raise RuntimeError(f"{path}: stream ended without a terminal "
                           "event")
    done["streamed_tokens"] = toks
    done["client_ms"] = (time.perf_counter() - t0) * 1e3
    done["ttft_ms"] = ttft if ttft is not None else done["client_ms"]
    return done


def summarize_timings(results):
    """Client-side SLO aggregation over the per-response ``timings``
    every `/generate` answer carries (ISSUE 11 satellite): end-to-end
    p50/p95/p99 plus a per-phase breakdown (queue/restore/prefill/
    decode, mean and p99 each) computed from what the CLIENT observed —
    the independent cross-check for the server's own SLO monitor
    (`GET /metrics` `slo_route_p99_ms`, `/debug/engine`): the two are
    measured at different ends of the socket, so they must broadly
    agree, and a divergence localizes the gap to the HTTP layer."""
    timings = [r["timings"] for r in results if r.get("timings")]
    if not timings:
        return None

    def pct(vals, q):
        vals = sorted(vals)
        return vals[min(len(vals) - 1, int(q * len(vals)))]

    totals = [t["total_ms"] for t in timings]
    out = {"n": len(timings),
           "total_ms": {"p50": round(pct(totals, 0.50), 3),
                        "p95": round(pct(totals, 0.95), 3),
                        "p99": round(pct(totals, 0.99), 3)},
           "phases": {}}
    for ph in ("queue_ms", "restore_ms", "prefill_ms", "decode_ms"):
        vals = [t.get(ph, 0.0) for t in timings]
        out["phases"][ph] = {
            "mean": round(sum(vals) / len(vals), 3),
            "p99": round(pct(vals, 0.99), 3),
            "share": round(sum(vals) / max(1e-9, sum(totals)), 4)}
    # TTFT (ISSUE 14 satellite): client-measured when the run streamed
    # (wall time to the first SSE token event), otherwise derived from
    # the server timings (queue+restore+prefill ends exactly at the
    # first token by construction)
    ttfts = []
    client_measured = False
    for r in results:
        if r.get("ttft_ms") is not None:
            ttfts.append(r["ttft_ms"])
            client_measured = True
        elif r.get("timings"):
            t = r["timings"]
            ttfts.append(t.get("queue_ms", 0.0) + t.get("restore_ms", 0.0)
                         + t.get("prefill_ms", 0.0))
    if ttfts:
        out["ttft_ms"] = {"p50": round(pct(ttfts, 0.50), 3),
                          "p95": round(pct(ttfts, 0.95), 3),
                          "p99": round(pct(ttfts, 0.99), 3),
                          "source": ("client" if client_measured
                                     else "server")}
    return out


def print_timing_table(summary):
    """The end-of-run client-side latency table."""
    if not summary:
        return
    t = summary["total_ms"]
    print(f"client SLO: n={summary['n']}  total p50 {t['p50']:.1f}ms  "
          f"p95 {t['p95']:.1f}ms  p99 {t['p99']:.1f}ms")
    print("  phase      mean_ms    p99_ms   share")
    for ph, s in summary["phases"].items():
        print(f"  {ph:<10} {s['mean']:8.1f} {s['p99']:9.1f}   "
              f"{100 * s['share']:5.1f}%")
    ttft = summary.get("ttft_ms")
    if ttft:
        print(f"  first_token ({ttft['source']}): p50 {ttft['p50']:.1f}ms"
              f"  p95 {ttft['p95']:.1f}ms  p99 {ttft['p99']:.1f}ms")


def _drive(server, n_threads, reqs_each, body):
    _post(server.port, "/predict", body)  # warm the jitted buckets
    errors = []
    retry_counts = []  # per-request attempts beyond the first
    t0 = time.perf_counter()

    def client():
        for _ in range(reqs_each):
            try:
                _post(server.port, "/predict", body, retries=retry_counts)
            except Exception as e:  # keep driving; report at the end
                errors.append(repr(e))

    threads = [threading.Thread(target=client) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    return n_threads * reqs_each / elapsed, errors, retry_counts


def _make_lm(vocab=32, cache=96):
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    # 4 KV heads so --mesh 2/4 can shard the cache by head
    conf = transformer_lm(vocab_size=vocab, d_model=32, n_heads=4,
                          n_blocks=2, rope=True)
    for vert in conf.vertices.values():
        layer = getattr(vert, "layer", None)
        if layer is not None and hasattr(layer, "max_cache_len"):
            layer.max_cache_len = cache
    return ComputationGraph(conf).init()



def zipf_prompts(n, vocab, prompt_len, k_users, s=1.1, prefix_len=None,
                 seed=0):
    """Deterministic zipf-distributed prompt mix (ISSUE 19): ``k_users``
    "users" each own a fixed shared prefix; every request is its user's
    prefix plus a fresh random suffix, with users drawn rank-weighted
    ~ 1/rank**s. Hot users repeat their prefix constantly, cold users
    barely ever — the canonical serving distribution for prefix-cache
    and KV-tiering experiments (same generator bench.py kv_tiering
    uses, so load-test numbers and bench numbers describe one mix)."""
    rng = np.random.default_rng(seed)
    if prefix_len is None:
        prefix_len = (prompt_len * 2) // 3
    prefix_len = max(1, min(int(prefix_len), prompt_len - 1))
    prefixes = [rng.integers(0, vocab, prefix_len).tolist()
                for _ in range(max(1, int(k_users)))]
    w = 1.0 / np.power(np.arange(1, len(prefixes) + 1, dtype=np.float64),
                       float(s))
    w /= w.sum()
    users = rng.choice(len(prefixes), size=int(n), p=w)
    return [prefixes[u]
            + rng.integers(0, vocab, prompt_len - prefix_len).tolist()
            for u in users]


def main_generate(n_threads=4, reqs_each=4, prompt_len=48, new_tokens=12,
                  trace_out=None, mesh=0, stream=False, verbose=True,
                  zipf=0, zipf_s=1.1, prefix_len=None,
                  host_cache_mb=0.0):
    """Drive POST /generate and show where each request's time went.
    Served from the paged KV pool (its trie is the prefix cache);
    ``mesh`` > 1: tensor-parallel decode over that many devices, the
    pool's budget per device.

    Fleet telemetry (ISSUE 12): every request carries a propagated
    ``X-Graft-Trace`` context and records a CLIENT-side span (send ->
    first-byte -> done) into a local FlightRecorder; at the end the
    `serving.telemetry.TraceAggregator` clock-aligns and merges the
    client and server rings into ONE Perfetto trace (``--trace-out``
    now writes the merged two-process waterfall, flow arrows included),
    and the report shows client-observed vs server-observed latency —
    the network/queue gap between the tiers."""
    from deeplearning4j_tpu.inference.trace import FlightRecorder
    from deeplearning4j_tpu.serving.telemetry import (ClientTracer,
                                                      TraceAggregator)

    vocab = 32
    net = _make_lm(vocab, cache=prompt_len + new_tokens)
    tp = mesh if mesh and mesh > 1 else 0
    # one device: a deliberately tight HBM budget, so that with
    # --host-cache-mb the host ring actually absorbs evictions
    kw = dict(kv_pool_mb=4.0 if tp else 1.0, decode_tp=tp,
              host_cache_mb=host_cache_mb or 0.0)
    srv = InferenceServer(net=net, decode_vocab=vocab, decode_slots=4,
                          prefill_chunk=16, kv_block=8, **kw).start()
    rng = np.random.default_rng(0)
    results, errors, retry_counts = [], [], []
    ctracer = ClientTracer(FlightRecorder(8192))
    # prompts pre-built on the main thread (numpy Generators are not
    # thread-safe); a few repeats so the prefix cache has something to hit
    n_prompts = max(1, n_threads * reqs_each // 2)
    prompts = (zipf_prompts(n_prompts, vocab, prompt_len, zipf, s=zipf_s,
                            prefix_len=prefix_len, seed=0)
               if zipf else
               [rng.integers(0, vocab, prompt_len).tolist()
                for _ in range(n_prompts)])
    bodies = [json.dumps(
        {"prompt": p, "max_new_tokens": new_tokens,
         **({"stream": True} if stream else {})}).encode()
        for p in prompts]

    def client(k):
        for i in range(reqs_each):
            # global index: threads walk DIFFERENT slices of the prompt
            # set, so each prompt is sent ~twice across the run (the
            # prefix-cache repeat mix)
            try:
                ctx = ctracer.send("/generate")
                t_send = time.perf_counter()
                body = bodies[(k * reqs_each + i) % len(bodies)]
                if stream:
                    # SSE mode (ISSUE 14): consume the token events as
                    # they arrive — ttft_ms is the real wire-level
                    # time-to-first-token the phase table reports
                    r = _post_stream(srv.port, "/generate", body,
                                     headers=ctracer.headers(ctx))
                else:
                    r = _post(srv.port, "/generate", body,
                              retries=retry_counts,
                              headers=ctracer.headers(ctx))
                    r["client_ms"] = (time.perf_counter() - t_send) * 1e3
                ctracer.done(ctx, args={
                    "request_id": r.get("request_id"),
                    "client_ms": round(r["client_ms"], 3)})
                results.append(r)
            except Exception as e:
                errors.append(repr(e))

    try:
        # warm the program families so the timed run is compile-free
        _post(srv.port, "/generate", json.dumps(
            {"prompt": rng.integers(0, vocab, prompt_len).tolist(),
             "max_new_tokens": 2}).encode())
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        # merge the client ring with the server's over HTTP — the same
        # aggregator path a real fleet runs (clock handshake included)
        agg = TraceAggregator([f"http://127.0.0.1:{srv.port}"],
                              client_recorder=ctracer.recorder)
        agg.sync_clocks()
        agg.poll()
        merge_stats = agg.stats()
        if trace_out:
            trace = agg.merged_chrome_trace()
            with open(trace_out, "w") as fh:
                json.dump(trace, fh)
        tp_used = getattr(srv._decoder, "tp", 1)  # before stop() drops it
        tier_census = (srv._decoder.tier.stats()
                       if getattr(srv._decoder, "tier", None) is not None
                       else None)
    finally:
        srv.stop()
    assert not errors, errors
    if verbose:
        tok_s = len(results) * new_tokens / elapsed
        retried = sum(1 for n in retry_counts if n)
        if mesh and mesh > 1:
            # report the engine's ACTUAL tp (the scheduler disables
            # sharding with a warning when heads don't divide) — same
            # honesty contract as the CLI banner
            if tp_used > 1:
                print(f"mesh:       tensor-parallel over {tp_used} "
                      "devices (tp axis), paged KV pool head-sharded, "
                      "per-device budget")
            else:
                print(f"mesh:       --mesh {mesh} requested but sharding "
                      "is DISABLED (see the engine warning above); "
                      "single-device numbers follow")
        print(f"generate:   {len(results)} requests, {tok_s:8.1f} tokens/s"
              + (" [SSE streamed]" if stream else "")
              + (f"  (HTTP retries: {sum(retry_counts)} across {retried} "
                 f"request(s), max {max(retry_counts)})"
                 if retried else ""))
        recov = [r for r in results if r.get("retries")]
        if recov:  # server-side crash recoveries (engine restarts)
            print(f"recovered:  {len(recov)} request(s) survived an "
                  "engine restart transparently")
        for r in results[-6:]:  # waterfall: where each request's time went
            t = r["timings"]
            print(f"  {r['request_id']}  total {t['total_ms']:7.1f}ms = "
                  f"queue {t['queue_ms']:.1f} + restore {t['restore_ms']:.1f}"
                  f" + prefill {t['prefill_ms']:.1f} + decode "
                  f"{t['decode_ms']:.1f}")
        # client-side percentile + phase table (cross-check against the
        # server's SLO monitor: GET /metrics slo_route_p99_ms)
        if tier_census is not None:
            h, d = tier_census["host"], tier_census["disk"]
            print(f"kv tiers:   host {h['blocks']} blocks "
                  f"({h['bytes'] / 1e6:.2f}MB of "
                  f"{h['budget_bytes'] / 1e6:.0f}MB), disk "
                  f"{d['blocks']} blocks, directory "
                  f"{tier_census['directory_entries']} entries")
        print_timing_table(summarize_timings(results))
        # client-observed vs server-observed latency: the difference is
        # the HTTP/network/accept-queue gap BETWEEN the tiers — exactly
        # what the merged waterfall's client->server flow arrow spans
        gaps = sorted(r["client_ms"] - r["timings"]["total_ms"]
                      for r in results
                      if "client_ms" in r and r.get("timings"))
        if gaps:
            print(f"tier gap:   client-observed minus server-observed "
                  f"latency: mean {sum(gaps) / len(gaps):.2f}ms  "
                  f"p99 {gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))]:.2f}ms "
                  f"(network + accept queue)")
        print(f"merged:     {merge_stats['events_merged']} events from "
              f"{len(merge_stats['sources'])} processes "
              f"(completeness {merge_stats['completeness']})")
        if trace_out:
            n = len(trace.get("traceEvents", []))
            print(f"trace:      {n} merged events -> {trace_out} "
                  "(client + server waterfall; open at "
                  "https://ui.perfetto.dev)")
    return results


def main_fleet(n_replicas=2, n_threads=4, reqs_each=8, prompt_len=48,
               new_tokens=8, verbose=True):
    """Fleet mode (ISSUE 13): spawn a prefix-affine router + N engine
    replica PROCESSES (each a supervised `serving/replica.py`
    subprocess over the same seeded LM), drive `/generate` through the
    router with a repeated-prompt mix, and report req/s, client-side
    p50/p95/p99, the journal ledger, and the FLEET prefix-cache hit
    rate — the number affinity routing exists to protect: repeats of a
    prompt land on the replica that already holds its blocks, so the
    fleet's hit rate matches a single replica's instead of dividing by
    N (`bench.py fleet_router` floor-gates the same invariant).

        python examples/serving_load_test.py --fleet 2
    """
    import tempfile

    from deeplearning4j_tpu.serving.replica import (ReplicaProcess,
                                                    ReplicaSupervisor,
                                                    lm_spec_argv)
    from deeplearning4j_tpu.serving.router import FleetRouter

    vocab = 32
    wd = tempfile.mkdtemp(prefix="dl4j-fleet-")
    argv = lm_spec_argv(vocab=vocab, d_model=32, n_heads=4, n_blocks=2,
                        cache=prompt_len + new_tokens + 16) + [
        "--slots", "4", "--prefill-chunk", "16",
        "--kv-pool-mb", "0.5", "--kv-block", "8"]
    print(f"spawning {n_replicas} replica process(es) + router "
          "(each replica pays a JAX import + warmup)...")
    sup = ReplicaSupervisor(
        [ReplicaProcess(argv, name=f"r{i}", workdir=wd)
         for i in range(n_replicas)])
    router = FleetRouter(supervisor=sup, quorum=n_replicas, kv_block=8,
                         journal_path=os.path.join(wd, "journal.log"),
                         scrape_interval_s=0.5).start()
    rng = np.random.default_rng(0)
    # two passes over one distinct-prompt set: pass 1 prefills cold and
    # publishes, pass 2 repeats — the repeat must land on the replica
    # already holding the blocks (two concurrent sends of the SAME
    # prompt would race each other cold before the first publish, which
    # measures scheduling luck, not routing)
    bodies = [json.dumps(
        {"prompt": rng.integers(0, vocab, prompt_len).tolist(),
         "max_new_tokens": new_tokens}).encode()
        for _ in range(max(1, n_threads * reqs_each // 2))]
    results, errors, retry_counts = [], [], []

    def client(k):
        for i in range(k, len(bodies), n_threads):
            try:
                t0 = time.perf_counter()
                r = _post(router.port, "/generate", bodies[i],
                          retries=retry_counts)
                r["client_ms"] = (time.perf_counter() - t0) * 1e3
                results.append(r)
            except Exception as e:
                errors.append(repr(e))

    def run_pass():
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def replica_counter(url, name):
        m = json.loads(urllib.request.urlopen(
            url + "/metrics", timeout=10).read())
        return float(m["counters"].get(name, 0.0))

    try:
        # warm each replica's program families off the timed path
        for _name, url in sup.ready_replicas():
            _post(int(url.rsplit(":", 1)[1]), "/generate", json.dumps(
                {"prompt": rng.integers(0, vocab, prompt_len).tolist(),
                 "max_new_tokens": 2}).encode())
        # hit-rate baseline AFTER warmup: the warmup prompts are
        # guaranteed misses and must not dilute the measured rate
        base = {url: (replica_counter(url,
                                      "prefix_cache_hit_tokens_total"),
                      replica_counter(
                          url, "prefix_cache_lookup_tokens_total"))
                for _name, url in sup.ready_replicas()}
        t0 = time.perf_counter()
        run_pass()   # cold: prefill + publish
        run_pass()   # warm: every prompt repeats, affinity-routed
        elapsed = time.perf_counter() - t0
        hit = lookup = 0.0
        for _name, url in sup.ready_replicas():
            h0, l0 = base.get(url, (0.0, 0.0))
            hit += replica_counter(
                url, "prefix_cache_hit_tokens_total") - h0
            lookup += replica_counter(
                url, "prefix_cache_lookup_tokens_total") - l0
        journal = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{router.port}/router/journal",
            timeout=10).read())
        ready_n = sup.ready_count()
    finally:
        router.stop(stop_replicas=True)
    assert not errors, errors
    if verbose:
        by_rep = {}
        for r in results:
            rep = (r.get("router") or {}).get("replica", "?")
            by_rep[rep] = by_rep.get(rep, 0) + 1
        retried = sum(1 for c in retry_counts if c)
        print(f"fleet:      {ready_n}/{n_replicas} replicas ready, "
              f"{len(results)} requests -> {len(results) / elapsed:6.1f} "
              f"req/s  (per-replica load {by_rep}"
              + (f", HTTP retries {sum(retry_counts)}" if retried else "")
              + ")")
        print(f"hit rate:   fleet prefix-cache "
              f"{hit / max(1.0, lookup):.3f} "
              f"({hit:.0f}/{lookup:.0f} tokens) — affinity keeps "
              "repeats on the replica that holds their blocks")
        print(f"journal:    {journal['accepted_total']} accepted, "
              f"{journal['finished_total']} finished, "
              f"{journal['failed_total']} failed, "
              f"{journal['duplicate_finishes_suppressed']} dup-"
              "suppressed")
        if tier_census is not None:
            h, d = tier_census["host"], tier_census["disk"]
            print(f"kv tiers:   host {h['blocks']} blocks "
                  f"({h['bytes'] / 1e6:.2f}MB of "
                  f"{h['budget_bytes'] / 1e6:.0f}MB), disk "
                  f"{d['blocks']} blocks, directory "
                  f"{tier_census['directory_entries']} entries")
        print_timing_table(summarize_timings(results))
        lost = journal["accepted_total"] - journal["finished_total"] \
            - journal["failed_total"]
        print(f"lost:       {lost} (accepted with no terminal record)")
    return results


def main(n_threads=8, reqs_each=10, rows=8, compare=False, verbose=True):
    net = _make_net()
    rng = np.random.default_rng(0)
    body = json.dumps(
        {"data": rng.standard_normal((rows, 64)).tolist()}).encode()

    srv = InferenceServer(net=net, batching=True, batch_window_ms=1.0,
                          max_batch=64).start()
    try:
        rps, errors, retry_counts = _drive(srv, n_threads, reqs_each, body)
        metrics = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics").read())
    finally:
        srv.stop()
    occ = metrics["histograms"]["predict_batch_occupancy"].get("mean", 0)
    lat = metrics["histograms"]["predict_latency_sec"]
    if verbose:
        retried = sum(1 for n in retry_counts if n)
        print(f"batched:    {rps:8.1f} req/s  "
              f"(occupancy {occ:.2f}, queue-depth max "
              f"{metrics['gauges']['predict_queue_depth']['max']:.0f}, "
              f"errors {len(errors)}, retried requests {retried})")
        if lat.get("count"):
            print(f"latency:    p50 {lat['p50'] * 1e3:.2f}ms  "
                  f"p95 {lat['p95'] * 1e3:.2f}ms  "
                  f"p99 {lat['p99'] * 1e3:.2f}ms")
    if compare:
        srv = InferenceServer(net=net, batching=False).start()
        try:
            serial_rps, _, _ = _drive(srv, n_threads, reqs_each, body)
        finally:
            srv.stop()
        if verbose:
            print(f"serialized: {serial_rps:8.1f} req/s  "
                  f"-> batching speedup {rps / serial_rps:.2f}x")
    assert not errors, errors
    assert occ >= 1.0
    return occ


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--threads", type=int, default=8)
    ap.add_argument("--requests", type=int, default=10,
                    help="requests per client thread")
    ap.add_argument("--rows", type=int, default=8, help="rows per request")
    ap.add_argument("--compare", action="store_true",
                    help="also measure the lock-serialized fallback")
    ap.add_argument("--generate", action="store_true",
                    help="drive POST /generate (decode scheduler) and "
                         "print per-request timing waterfalls")
    ap.add_argument("--trace-out", default=None,
                    help="with --generate: dump the flight recorder as "
                         "Chrome trace-event JSON (Perfetto-loadable)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="with --generate: shard the decode engine "
                         "tensor-parallel over N devices (forces an "
                         "N-device virtual CPU mesh when needed) and "
                         "report tokens/s")
    ap.add_argument("--zipf", type=int, default=0,
                    help="with --generate: draw prompts as a "
                         "zipf-distributed mix over K users' shared "
                         "prefixes (hot users repeat; exercises the "
                         "prefix cache / KV tiers) instead of uniform "
                         "~2x repeats")
    ap.add_argument("--zipf-s", type=float, default=1.1,
                    help="zipf skew exponent (higher = hotter head)")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="shared-prefix tokens per zipf user "
                         "(default: 2/3 of the prompt)")
    ap.add_argument("--host-cache-mb", type=float, default=0.0,
                    help="with --generate: serve from a paged pool "
                         "with hierarchical KV tiering (host ring of "
                         "this budget) and print the tier census")
    ap.add_argument("--stream", action="store_true",
                    help="with --generate: request SSE token streams "
                         "and report client-measured TTFT in the phase "
                         "table")
    ap.add_argument("--fleet", type=int, default=0,
                    help="spawn a prefix-affine fleet router + N engine "
                         "replica PROCESSES and drive /generate through "
                         "it; reports req/s, p99, and the fleet "
                         "prefix-cache hit rate")
    a = ap.parse_args()
    if a.fleet:
        main_fleet(n_replicas=a.fleet, n_threads=a.threads,
                   reqs_each=a.requests)
    elif a.generate:
        main_generate(n_threads=a.threads, reqs_each=a.requests,
                      trace_out=a.trace_out, mesh=a.mesh,
                      stream=a.stream, zipf=a.zipf, zipf_s=a.zipf_s,
                      prefix_len=a.prefix_len,
                      host_cache_mb=a.host_cache_mb)
    else:
        main(n_threads=a.threads, reqs_each=a.requests, rows=a.rows,
             compare=a.compare)
