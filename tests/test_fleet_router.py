"""Fleet router suite (ISSUE 13): prefix-affine routing, quorum
readiness, Retry-After propagation, the durable request journal, and
the cross-process chaos invariants:

  - **replica SIGKILL mid-decode**: every client request still
    completes (router failover + fleet supervisor respawn), outputs
    token-identical to the no-fault run, journal shows exactly one
    terminal record per accepted request;
  - **router SIGKILL mid-journal**: a real router subprocess is killed
    while a request sits between journal-accept and replica dispatch
    (the ``router.dispatch`` hang seam, armed via ``DL4J_FAILPOINTS``
    in the child env — the documented cross-process arming path); the
    restarted router replays exactly the unfinished request, once,
    token-identically;
  - the runtime happens-before checker watches the router's shared
    state through concurrent HTTP load and reports zero violations.

The expensive fixtures (engine replicas are real subprocesses that pay
a JAX import + warmup each) are module-scoped and shared.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from deeplearning4j_tpu.inference import MetricsRegistry
from deeplearning4j_tpu.serving.durable import DurableLogConsumer
from deeplearning4j_tpu.serving.replica import (ReplicaProcess,
                                                ReplicaSupervisor,
                                                lm_spec_argv)
from deeplearning4j_tpu.serving.router import (FleetRouter, NoReplicaError,
                                               ReplicaEndpoint,
                                               affinity_key, pick_replica)

V = 13
KV_BLOCK = 8
NEW_TOKENS = 8
N_CLIENTS = 4


def _replica_argv():
    return lm_spec_argv(vocab=V, d_model=16, n_heads=2, n_blocks=2,
                        cache=96) + [
        "--slots", "2", "--prefill-chunk", "16",
        "--kv-pool-mb", "0.125", "--kv-block", str(KV_BLOCK),
        "--hang-timeout", "5", "--retry-budget", "6"]


def _post_retry(port, path, body, timeout=120, max_retries=12,
                headers=None):
    """The chaos client (same shape as tests/test_chaos.py): rides 5xx
    and connection-refused windows with capped backoff, honors
    Retry-After; a request is lost only if even this gives up."""
    attempt = 0
    while True:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=body,
            headers={"Content-Type": "application/json",
                     **(headers or {})})
        try:
            return json.loads(urllib.request.urlopen(req, timeout=timeout)
                              .read())
        except urllib.error.HTTPError as e:
            if e.code < 500 and e.code != 503:
                raise
            delay = min(1.0, 0.05 * (2 ** attempt))
            ra = e.headers.get("Retry-After") if e.headers else None
            if ra:
                delay = max(delay, float(ra))
            e.read()
        except (urllib.error.URLError, OSError):
            delay = min(1.0, 0.05 * (2 ** attempt))
        attempt += 1
        if attempt > max_retries:
            raise RuntimeError(f"request lost: {max_retries} retries "
                               "exhausted")
        time.sleep(delay)


def _mk_prompts(n=8, repeat=2):
    """n distinct prompts, each occurring `repeat` times (the affinity /
    prefix-cache mix), all greedy for cross-replica token identity."""
    rng = np.random.default_rng(7)
    distinct = [[int(t) for t in rng.integers(0, V,
                                              int(rng.integers(12, 40)))]
                for _ in range(n)]
    return [p for p in distinct for _ in range(repeat)]


def _drive(port, prompts, max_new=NEW_TOKENS):
    out = [None] * len(prompts)
    errors = []

    def client(k):
        for i in range(k, len(prompts), N_CLIENTS):
            body = json.dumps({"prompt": prompts[i],
                               "max_new_tokens": max_new}).encode()
            try:
                out[i] = _post_retry(port, "/generate", body)
            except Exception as e:  # noqa: BLE001 - the lost-request record
                errors.append((i, repr(e)))

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(N_CLIENTS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, f"requests lost: {errors}"
    return out


def _replica_finish_counts(url):
    """request_id -> finish-instant count from a replica's flight
    recorder (the answered-twice detector, same audit as
    tests/test_chaos.py but over HTTP)."""
    snap = json.loads(urllib.request.urlopen(
        url + "/trace", timeout=10).read())
    counts = {}
    for ev in snap.get("events", []):
        if ev.get("ph") == "i" and ev.get("name") == "finish":
            rid = (ev.get("args") or {}).get("request_id")
            if rid:
                counts[rid] = counts.get(rid, 0) + 1
    return counts


def _journal_audit(path):
    """(accept rids, finish counts per rid, fail counts per rid) read
    from offset 0 with a throwaway cursor group."""
    c = DurableLogConsumer(path, group=f"audit{time.monotonic_ns()}")
    accepts, finishes, fails = [], {}, {}
    recs = []
    while True:
        batch = c.poll(256)
        if not batch:
            break
        recs += batch
    for r in recs:
        if r["t"] == "accept":
            accepts.append(r["rid"])
        elif r["t"] == "finish":
            finishes[r["rid"]] = finishes.get(r["rid"], 0) + 1
        elif r["t"] == "fail":
            fails[r["rid"]] = fails.get(r["rid"], 0) + 1
    os.unlink(c.cursor_path) if os.path.exists(c.cursor_path) else None
    return accepts, finishes, fails


# ---------------------------------------------------------------------------
# pure units: affinity + rendezvous
# ---------------------------------------------------------------------------

def test_affinity_key_is_block_aligned():
    a = affinity_key(list(range(20)), kv_block=8)
    b = affinity_key(list(range(8)) + [99] * 12, kv_block=8)
    assert a == b, "keys must ignore tokens past the first aligned block"
    assert affinity_key(list(range(20)), 8) != affinity_key(
        [1] + list(range(1, 20)), 8)
    # short prompts key on their full run (not all on the empty prefix)
    assert affinity_key([1, 2, 3], 8) != affinity_key([4, 5], 8)
    # affinity_blocks widens the covered prefix: a divergence in the
    # second block separates keys at affinity_blocks=2, not at 1
    c = affinity_key(list(range(32)), 8, affinity_blocks=2)
    d = affinity_key(list(range(8)) + [99] * 24, 8, affinity_blocks=2)
    assert c != d
    assert affinity_key(list(range(32)), 8) == affinity_key(
        list(range(8)) + [99] * 24, 8)


def test_replica_endpoint_parses_portless_urls():
    assert ReplicaEndpoint("http://replica-a.internal", "a").port == 80
    assert ReplicaEndpoint("https://replica-b.internal", "b").port == 443
    assert ReplicaEndpoint("http://10.0.0.1:8080/v1", "c").port == 8080
    assert ReplicaEndpoint("127.0.0.1:9999", "d").port == 9999


def test_rendezvous_is_deterministic_and_minimal_reshuffle():
    cands = [(f"r{i}", f"u{i}") for i in range(4)]
    keys = [affinity_key([i, i + 1, i + 2] * 5, 4) for i in range(64)]
    owner = {k: pick_replica(k, cands) for k in keys}
    assert owner == {k: pick_replica(k, cands) for k in keys}
    # keys spread over more than one replica
    assert len({o[0] for o in owner.values()}) > 1
    # drop r1: ONLY r1's keys move
    survivors = [c for c in cands if c[0] != "r1"]
    for k, o in owner.items():
        if o[0] != "r1":
            assert pick_replica(k, survivors) == o
    with pytest.raises(NoReplicaError):
        pick_replica(b"x", [])


# ---------------------------------------------------------------------------
# stub-replica units: quorum readiness + Retry-After propagation
# ---------------------------------------------------------------------------

class _StubReplica:
    """A fake replica: scripted /readyz and /generate answers — the
    protocol-shape tests need no engine."""

    def __init__(self, ready=True, generate=None):
        self.ready = ready
        # generate: (status, body_dict, extra_headers)
        self.generate = generate or (200, {"tokens": [1, 2]}, {})
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _send(self, code, body, headers=None):
                raw = json.dumps(body).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(raw)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(raw)

            def do_GET(self):
                if self.path.startswith("/readyz"):
                    self._send(200 if stub.ready else 503,
                               {"ready": stub.ready})
                elif self.path.startswith("/metrics"):
                    self._send(200, {})
                else:
                    self._send(404, {})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                code, body, headers = stub.generate
                self._send(code, body, headers)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.thread = threading.Thread(target=self.httpd.serve_forever,
                                       daemon=True)
        self.thread.start()

    @property
    def url(self):
        return f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_readyz_aggregates_quorum(tmp_path):
    up, down = _StubReplica(ready=True), _StubReplica(ready=False)
    sup = ReplicaSupervisor(
        [ReplicaEndpoint(up.url, "up"), ReplicaEndpoint(down.url, "down")],
        poll_interval_s=0.05, metrics=MetricsRegistry())
    # wait=False: a quorum fleet must come up with a minority down
    sup.start(wait=False)
    # startup_wait_s=0: observe the below-quorum state immediately
    # instead of waiting for a quorum that will not come
    router = FleetRouter(supervisor=sup, quorum=2,
                         journal_path=str(tmp_path / "j.log"),
                         scrape_interval_s=0.05,
                         startup_wait_s=0).start()
    try:
        ok, body = router.ready()
        assert not ok and body["replicas_ready"] == 1
        assert body["reason"].startswith("quorum")
        # the per-replica block names which replica is down
        assert body["replicas"]["down"]["ready"] is False
        assert body["replicas"]["up"]["ready"] is True
        # HTTP surface agrees
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{router.port}/readyz", timeout=10)
            code = 200
        except urllib.error.HTTPError as e:
            code = e.code
            e.read()
        assert code == 503
        # quorum satisfied once the second replica comes up
        down.ready = True
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and not router.ready()[0]:
            time.sleep(0.05)
        assert router.ready()[0]
    finally:
        router.stop(stop_replicas=False)
        sup.stop()
        up.stop()
        down.stop()


def test_replica_503_retry_after_propagates_unchanged(tmp_path):
    busy = _StubReplica(
        ready=True,
        generate=(503, {"error": "not_admitting", "retry_after_s": 7.0},
                  {"Retry-After": "7"}))
    sup = ReplicaSupervisor([ReplicaEndpoint(busy.url, "busy")],
                            poll_interval_s=0.05,
                            metrics=MetricsRegistry())
    router = FleetRouter(supervisor=sup, quorum=1,
                         journal_path=str(tmp_path / "j.log"),
                         scrape_interval_s=0.05).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/generate",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        e = ei.value
        body = json.loads(e.read().decode())
        assert e.code == 503
        # the header AND the replica's body pass through unchanged
        assert e.headers.get("Retry-After") == "7"
        assert body["error"] == "not_admitting"
        assert body["retry_after_s"] == 7.0
        # terminal in the journal: the client saw the answer, a restart
        # must not replay it
        accepts, finishes, fails = _journal_audit(str(tmp_path / "j.log"))
        assert len(accepts) == 1 and not finishes
        assert fails[accepts[0]] == 1
    finally:
        router.stop(stop_replicas=False)
        sup.stop()
        busy.stop()


def test_replica_504_is_terminal_not_failed_over(tmp_path):
    """A replica's 504 (its own timeout-cancel) must propagate as 504
    and journal terminal — failing over would re-run a request whose
    deadline budget is already spent, on every surviving replica."""
    slow = _StubReplica(ready=True,
                        generate=(504, {"error": "deadline exceeded"},
                                  {}))
    ok_rep = _StubReplica(ready=True)
    sup = ReplicaSupervisor(
        [ReplicaEndpoint(slow.url, "slow"),
         ReplicaEndpoint(ok_rep.url, "ok")],
        poll_interval_s=0.05, metrics=MetricsRegistry())
    router = FleetRouter(supervisor=sup, quorum=1,
                         journal_path=str(tmp_path / "j.log"),
                         scrape_interval_s=0.05).start()
    try:
        # find a prompt whose affinity lands on the slow stub, so the
        # 504 path is the one exercised deterministically
        prompt = [1, 2, 3]
        for seed in range(64):
            prompt = [seed, seed + 1, seed + 2]
            from deeplearning4j_tpu.serving.router import (affinity_key,
                                                           pick_replica)
            cands = sorted((n, u) for n, u in sup.ready_replicas())
            if pick_replica(affinity_key(prompt, 16), cands)[0] == "slow":
                break
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/generate",
            data=json.dumps({"prompt": prompt,
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 504
        assert json.loads(ei.value.read())["error"] == "deadline exceeded"
        accepts, finishes, fails = _journal_audit(str(tmp_path / "j.log"))
        assert len(accepts) == 1 and not finishes
        assert fails[accepts[0]] == 1  # terminal: no replay after crash
    finally:
        router.stop(stop_replicas=False)
        sup.stop()
        slow.stop()
        ok_rep.stop()


def test_journal_replay_balances_resource_ledger(tmp_path):
    """Crash -> replay under the armed resource ledger (graftleak): a
    predecessor's journal holds an accept with no terminal record (it
    died mid-dispatch). The next incarnation's recover() inherits the
    open obligation (+1 on its ledger), the replay's finish settles it,
    and a live request's accept/finish pair balances too — journal
    records leak exactly never, across the crash boundary included."""
    from deeplearning4j_tpu.analysis import resource_ledger
    from deeplearning4j_tpu.serving.router import RequestJournal

    jpath = str(tmp_path / "j.log")
    # the crashed incarnation: accept journaled, no terminal record.
    # (Built BEFORE arming, exactly like a dead process's file.)
    j = RequestJournal(jpath)
    j.accept("req-inherited", {"prompt": [1, 2, 3], "max_new_tokens": 2})
    j.close()

    ok_rep = _StubReplica(ready=True)
    with resource_ledger() as led:
        sup = ReplicaSupervisor([ReplicaEndpoint(ok_rep.url, "r0")],
                                poll_interval_s=0.05,
                                metrics=MetricsRegistry())
        router = FleetRouter(supervisor=sup, quorum=1, journal_path=jpath,
                             scrape_interval_s=0.05).start()
        try:
            body = json.dumps({"prompt": [4, 5, 6],
                               "max_new_tokens": 2}).encode()
            live = _post_retry(router.port, "/generate", body)
            assert live.get("tokens") is not None
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                with router._lock:
                    if router.replayed_total >= 1:
                        break
                time.sleep(0.05)
            with router._lock:
                assert router.replayed_total == 1
        finally:
            router.stop(stop_replicas=False)
            sup.stop()
    ok_rep.stop()
    accepts, finishes, fails = _journal_audit(jpath)
    assert finishes.get("req-inherited") == 1 and not fails
    snap = led.snapshot()
    # both the inherited and the live record were noted and settled
    assert snap["kinds"]["journal_record"]["acquires"] >= 2
    led.assert_clean()


def test_burning_fleet_rejects_with_retry_after(tmp_path):
    ok_rep = _StubReplica(ready=True)
    sup = ReplicaSupervisor([ReplicaEndpoint(ok_rep.url, "r0")],
                            poll_interval_s=0.05,
                            metrics=MetricsRegistry())
    router = FleetRouter(supervisor=sup, quorum=1,
                         journal_path=str(tmp_path / "j.log"),
                         scrape_interval_s=3600).start()
    try:
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_new_tokens": 2}).encode()
        # healthy fleet: admitted
        out = _post_retry(router.port, "/generate", body)
        assert out["tokens"] == [1, 2]
        # force the federated verdict to burning (the scrape loop is
        # parked at a 1h interval so it cannot overwrite the injection)
        with router._lock:
            router._admission = {"burning": True, "fast": 9.0,
                                 "slow": 4.0, "replicas_up": 1}
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After") is not None
        rej = json.loads(ei.value.read().decode())
        assert rej["error"] == "fleet_burning"
        # a rejected-at-admission request is never journaled: nothing
        # to replay for work that was never accepted
        accepts, _f, _x = _journal_audit(str(tmp_path / "j.log"))
        assert len(accepts) == 1
        # calm again: admitted again
        with router._lock:
            router._admission = {"burning": False, "fast": 0.0,
                                 "slow": 0.0, "replicas_up": 1}
        assert _post_retry(router.port, "/generate",
                           body)["tokens"] == [1, 2]
    finally:
        router.stop(stop_replicas=False)
        sup.stop()
        ok_rep.stop()


# ---------------------------------------------------------------------------
# the real fleet (module-scoped subprocess replicas)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """2 engine replica subprocesses under one fleet supervisor, shared
    by the integration tests (each spawn pays a JAX import + warmup)."""
    wd = str(tmp_path_factory.mktemp("fleet"))
    reps = [ReplicaProcess(_replica_argv(), name=f"r{i}", workdir=wd)
            for i in range(2)]
    sup = ReplicaSupervisor(reps, poll_interval_s=0.2,
                            backoff_base_s=0.05, backoff_max_s=1.0,
                            metrics=MetricsRegistry())
    sup.start()
    yield wd, sup
    sup.stop()


def _await_replicas(sup, n, deadline_s=180):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if sup.ready_count() >= n:
            return
        time.sleep(0.1)
    raise AssertionError(f"fleet never reached {n} ready replicas: "
                         f"{sup.states()}")


@pytest.fixture(scope="module")
def reference(fleet):
    """No-fault run through a throwaway router: the token-identity
    baseline, plus the affinity map (prompt index -> replica name)."""
    wd, sup = fleet
    _await_replicas(sup, 2)
    router = FleetRouter(supervisor=sup, quorum=2, kv_block=KV_BLOCK,
                         journal_path=os.path.join(wd, "ref.journal"),
                         scrape_interval_s=0.2).start()
    try:
        prompts = _mk_prompts()
        outs = _drive(router.port, prompts)
        return (prompts, [o["tokens"] for o in outs],
                [o["router"]["replica"] for o in outs])
    finally:
        router.stop(stop_replicas=False)


@pytest.mark.slow
def test_fleet_token_identical_and_affine(fleet, reference):
    """Clean-fleet invariants: outputs reproduce, every repeat of a
    prompt routes to the SAME replica (affinity engaged), and both
    replicas carry traffic (affinity is not a degenerate all-to-one)."""
    wd, sup = fleet
    prompts, expected, replicas0 = reference
    _await_replicas(sup, 2)
    router = FleetRouter(supervisor=sup, quorum=2, kv_block=KV_BLOCK,
                         journal_path=os.path.join(wd, "clean.journal"),
                         scrape_interval_s=0.2).start()
    try:
        outs = _drive(router.port, prompts)
        assert [o["tokens"] for o in outs] == expected
        by_prompt = {}
        for p, o in zip(prompts, outs):
            by_prompt.setdefault(tuple(p), set()).add(
                o["router"]["replica"])
        assert all(len(s) == 1 for s in by_prompt.values()), \
            f"repeats split across replicas: {by_prompt}"
        assert len({next(iter(s)) for s in by_prompt.values()}) == 2, \
            "affinity degenerated to a single replica"
        # journal: every accept has exactly one finish
        accepts, finishes, fails = _journal_audit(
            os.path.join(wd, "clean.journal"))
        assert len(accepts) == len(prompts) and not fails
        assert all(finishes.get(r, 0) == 1 for r in accepts)
    finally:
        router.stop(stop_replicas=False)


@pytest.mark.slow
def test_replica_sigkill_mid_decode_zero_lost_token_identical(
        fleet, reference):
    """SIGKILL one replica while requests are mid-decode: the router
    fails the in-flight dispatches over to the survivor, the fleet
    supervisor respawns the corpse, no request is lost, none double-
    finishes, and every completion matches the no-fault tokens."""
    wd, sup = fleet
    prompts, expected, _replicas0 = reference
    _await_replicas(sup, 2)
    jpath = os.path.join(wd, "chaos-replica.journal")
    router = FleetRouter(supervisor=sup, quorum=1, kv_block=KV_BLOCK,
                         journal_path=jpath,
                         scrape_interval_s=0.2).start()
    restarts0 = sup.restarts
    try:
        victim = sup.replicas[0]
        outs = [None] * len(prompts)
        errors = []

        def client(k):
            for i in range(k, len(prompts), N_CLIENTS):
                body = json.dumps(
                    {"prompt": prompts[i],
                     "max_new_tokens": NEW_TOKENS}).encode()
                try:
                    outs[i] = _post_retry(router.port, "/generate", body)
                except Exception as e:  # noqa: BLE001
                    errors.append((i, repr(e)))

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(N_CLIENTS)]
        for t in threads:
            t.start()
        time.sleep(0.15)  # let requests reach mid-decode
        victim.kill()
        for t in threads:
            t.join()
        assert not errors, f"requests lost under replica kill: {errors}"
        assert [o["tokens"] for o in outs] == expected
        accepts, finishes, fails = _journal_audit(jpath)
        assert len(accepts) == len(prompts) and not fails
        dup = {r: n for r, n in finishes.items() if n > 1}
        assert not dup, f"double-finished under replica kill: {dup}"
        assert all(finishes.get(r) == 1 for r in accepts)
        # the corpse is respawned (the probe cache can lag the kill by
        # a poll interval — wait for the restart to be OBSERVED, then
        # for the fleet to heal to 2)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and sup.restarts <= restarts0:
            time.sleep(0.1)
        assert sup.restarts > restarts0, \
            f"supervisor never respawned the killed replica: {sup.states()}"
        _await_replicas(sup, 2)
        # flight-recorder finish-count audit on every live replica
        # (fresh ready set — the healed fleet): no engine handle
        # finished twice — the fenced-zombie protection, observed
        # across the process boundary
        audited = 0
        for _name, url in sup.ready_replicas():
            dups = {r: n for r, n in _replica_finish_counts(url).items()
                    if n > 1}
            assert not dups, f"replica {url} double-finished: {dups}"
            audited += 1
        assert audited == 2
    finally:
        router.stop(stop_replicas=False)


def _spawn_router_proc(wd, urls, jpath, tag, failpoints=None):
    announce = os.path.join(wd, f"router.{tag}.json")
    env = dict(os.environ)
    if failpoints:
        env["DL4J_FAILPOINTS"] = ";".join(
            f"{k}={v}" for k, v in failpoints.items())
    else:
        env.pop("DL4J_FAILPOINTS", None)
    log = open(os.path.join(wd, f"router.{tag}.log"), "ab")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "deeplearning4j_tpu.serving.router",
             "--replicas", ",".join(urls), "--journal", jpath,
             "--announce", announce, "--kv-block", str(KV_BLOCK),
             "--quorum", "1", "--scrape-interval", "0.2"],
            stdout=log, stderr=log, env=env)
    finally:
        log.close()
    deadline = time.monotonic() + 120
    port = None
    while port is None:
        assert proc.poll() is None, \
            f"router died: {open(log.name, 'rb').read()[-2000:]}"
        try:
            with open(announce) as fh:
                port = int(json.load(fh)["port"])
        except (OSError, ValueError, KeyError):
            assert time.monotonic() < deadline
            time.sleep(0.05)
    return proc, port


@pytest.mark.slow
def test_router_sigkill_mid_journal_replays_exactly_once(fleet,
                                                         reference):
    """The tentpole invariant: a router subprocess SIGKILLed while a
    request sits between journal-accept and dispatch (the
    ``router.dispatch`` hang seam, armed through DL4J_FAILPOINTS in the
    child environment) loses nothing — the restarted router replays
    exactly the unfinished request, exactly once, and its recovered
    output is token-identical to the no-fault run."""
    wd, sup = fleet
    prompts, expected, _r = reference
    _await_replicas(sup, 2)
    urls = [u for _n, u in sup.ready_replicas()]
    jpath = os.path.join(wd, "chaos-router.journal")
    # requests 1..3 flow; request 4 hangs AFTER its journal append,
    # BEFORE its dispatch — the exact mid-journal crash window
    proc, port = _spawn_router_proc(
        wd, urls, jpath, "a",
        failpoints={"router.dispatch": "hang:30000@n:4"})
    hung_idx = 3  # 4th /generate fire()
    try:
        for i in range(3):
            body = json.dumps({"prompt": prompts[i],
                               "max_new_tokens": NEW_TOKENS}).encode()
            out = _post_retry(port, "/generate", body)
            assert out["tokens"] == expected[i]

        hung_err = []

        def hung_client():
            body = json.dumps({"prompt": prompts[hung_idx],
                               "max_new_tokens": NEW_TOKENS}).encode()
            try:
                _post_retry(port, "/generate", body, timeout=60,
                            max_retries=0)
            except Exception as e:  # noqa: BLE001 - expected: router dies
                hung_err.append(repr(e))

        th = threading.Thread(target=hung_client)
        th.start()
        # wait until the 4th accept is journaled (the hang holds it
        # there), then SIGKILL the router mid-journal
        deadline = time.monotonic() + 30
        while True:
            accepts, finishes, _f = _journal_audit(jpath)
            if len(accepts) >= 4:
                break
            assert time.monotonic() < deadline, \
                f"4th accept never journaled: {accepts}"
            time.sleep(0.05)
        assert sum(finishes.values()) == 3
        hung_rid = accepts[3]
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        th.join(timeout=30)
        assert hung_err, "the hung client should have seen the crash"
    finally:
        if proc.poll() is None:
            proc.kill()
    # restart the router on the SAME journal (no failpoints): replay
    proc2, port2 = _spawn_router_proc(wd, urls, jpath, "b")
    try:
        deadline = time.monotonic() + 60
        while True:
            accepts, finishes, fails = _journal_audit(jpath)
            if finishes.get(hung_rid):
                break
            assert time.monotonic() < deadline, \
                (f"journal replay never finished {hung_rid}: "
                 f"{finishes} {fails}")
            time.sleep(0.1)
        # exactly once, for EVERY accepted request
        assert all(finishes.get(r, 0) == 1 for r in accepts), finishes
        assert not fails
        # recovered output is token-identical to the no-fault run
        c = DurableLogConsumer(jpath, group=f"tok{time.monotonic_ns()}")
        recs = []
        while True:
            batch = c.poll(256)
            if not batch:
                break
            recs += batch
        replayed = [r for r in recs if r["t"] == "finish"
                    and r["rid"] == hung_rid]
        assert len(replayed) == 1 and replayed[0]["replay"] is True
        assert replayed[0]["tokens"] == expected[hung_idx]
        # the journal endpoint reports the replay
        stats = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port2}/router/journal", timeout=10)
            .read())
        assert stats["replayed_total"] == 1
        assert stats["replay_abandoned_total"] == 0
    finally:
        proc2.terminate()
        proc2.wait(timeout=30)


@pytest.mark.slow
def test_failpoints_env_arms_inside_replica_subprocess(fleet, reference):
    """Satellite: DL4J_FAILPOINTS is the way chaos runs arm seams
    INSIDE replica subprocesses — the announce file reports the armed
    seams, the in-replica supervisor recovers the injected crash
    transparently, and the trigger is visible in the replica's own
    /metrics."""
    wd, sup = fleet
    prompts, expected, _r = reference
    rep = ReplicaProcess(_replica_argv(), name="armed", workdir=wd,
                         failpoints={"dispatch.decode": "crash@once"})
    rep.spawn()
    try:
        url = rep.await_ready()
        with open(rep._announce_path()) as fh:
            assert json.load(fh)["failpoints_armed"] == ["dispatch.decode"]
        body = json.dumps({"prompt": prompts[0],
                           "max_new_tokens": NEW_TOKENS}).encode()
        out = _post_retry(rep.port, "/generate", body)
        # the injected crash happened INSIDE the subprocess and its
        # supervisor recovered it token-identically
        assert out["tokens"] == expected[0]
        metrics = json.loads(urllib.request.urlopen(
            url + "/metrics", timeout=10).read())
        assert metrics["counters"]["failpoint_triggers_total"] >= 1
        assert metrics["counters"]["engine_restarts_total"] >= 1
    finally:
        rep.terminate()


@pytest.mark.slow
def test_rolling_drain_keeps_quorum(fleet):
    """POST /admin/drain fans the supervisor's drain protocol across
    the replicas one at a time; with quorum 1 the router stays ready
    throughout and the fleet ends fully ready."""
    wd, sup = fleet
    _await_replicas(sup, 2)
    router = FleetRouter(supervisor=sup, quorum=1, kv_block=KV_BLOCK,
                         journal_path=os.path.join(wd, "drain.journal"),
                         scrape_interval_s=0.2).start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/admin/drain", data=b"{}")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 202
            assert json.loads(resp.read())["status"] == "draining"
        # a second POST while draining must NOT start a second rolling
        # drain (two could take two replicas down at once)
        with urllib.request.urlopen(req, timeout=10) as resp:
            body2 = json.loads(resp.read())
        assert body2["status"] == "already_draining"
        assert router.ready()[1]["draining"] is True
        deadline = time.monotonic() + 120
        saw_unready_replica = False
        while time.monotonic() < deadline:
            ok, body = router.ready()
            assert ok, f"router lost quorum during rolling drain: {body}"
            if body["replicas_ready"] < 2:
                saw_unready_replica = True
            elif saw_unready_replica:
                break  # a drain window was observed and healed
            time.sleep(0.05)
        _await_replicas(sup, 2)
    finally:
        router.stop(stop_replicas=False)


@pytest.mark.slow
def test_race_checker_router_state_zero_violations(fleet, reference):
    """The FastTrack-lite happens-before checker over the router's
    shared state (admission verdict, round-robin cursor, journal
    counters — all lock-disciplined) through concurrent HTTP load:
    zero violations."""
    from deeplearning4j_tpu.analysis.races import race_audit

    wd, sup = fleet
    prompts, expected, _r = reference
    _await_replicas(sup, 2)
    with race_audit() as det:
        router = FleetRouter(supervisor=sup, quorum=2, kv_block=KV_BLOCK,
                             journal_path=os.path.join(wd, "race.journal"),
                             scrape_interval_s=0.05).start()
        det.watch(router, ["_admission", "_rr", "_draining",
                           "_scrape_error"], label="router")
        det.watch(router.journal,
                  ["accepted_total", "finished_total", "failed_total"],
                  label="journal")
        try:
            outs = _drive(router.port, prompts[:8])
            assert [o["tokens"] for o in outs] == expected[:8]
        finally:
            router.stop(stop_replicas=False)
    assert det.violations == [], det.format_violations()
    assert det.tracking
