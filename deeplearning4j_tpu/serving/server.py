"""HTTP model-serving endpoint.

Capability parity with the reference's serving route
(dl4j-streaming/.../routes/DL4jServeRouteBuilder.java: load a serialized
model, vectorize incoming records, emit predictions) — exposed over HTTP
(stdlib ThreadingHTTPServer, same stack as ui/server.py) instead of a
Camel/Kafka route; see streaming.py for the queue-fed variant.

Concurrency model (the TensorFlow-Serving batched-session shape,
arXiv 1605.08695): by default every request is routed through a
`inference.MicroBatcher` — concurrent clients' rows are aggregated into ONE
padded bucketed device batch by a single dispatcher thread, so the model
needs no lock and XLA compiles once per bucket. `batching=False` restores
the original lock-serialized direct path (also the fallback for callers
that need strict FIFO with zero batching delay). SLO telemetry (queue
depth, batch occupancy, time-in-queue, latency percentiles, timeout/reject
counts) lives in a `MetricsRegistry` exported at `GET /metrics`.

Generative serving: pass ``decode_vocab`` (the LM's vocabulary size) and
the server additionally runs a `inference.DecodeScheduler` — slot-based
continuous-batching decode with chunked prefill — behind `POST /generate`.
``prefill_chunk`` is the TTFT / decode-latency knob (`dl4j-tpu serve
--generate --prefill-chunk C`). ``kv_pool_mb``/``kv_block``
(`--kv-pool-mb MB --kv-block B`) switch the decode cache to the PAGED
layout (`inference/kvpool.py`): all slots share one block pool, so slot
capacity is bounded by pool bytes instead of ``slots × max_cache_len``,
prompt prefixes restore as zero-copy block-table remaps, and cold slots
are preempted-and-resumed under pool pressure; the default contiguous
layout carries no prefix cache. The scheduler's metrics (TTFT,
prefill tokens, chunk sizes, prefix hit rate, pool occupancy,
preemptions, cancellations) land in the same registry as the
request-path metrics, so `GET /metrics` and the UI `/serving` page show
the whole hot path. Requests that cannot fit the KV cache are rejected
up front with HTTP 413 (counted in `decode_rejected_total`) instead of
dying mid-decode on the attention layer's overflow guard — contiguous
mode bounds on ``max_cache_len``, paged mode only on the WHOLE pool
(the 413 body then reports ``blocks_needed`` vs ``blocks_available``).
``decode_tp`` (`--tp N`) shards the decode engine tensor-parallel over
an N-device mesh (`inference/sharding.py`): attention heads / FFN
hidden dims split across the ``tp`` axis, the KV pool shards by head
(``kv_pool_mb`` becomes the PER-DEVICE budget — N× the blocks at fixed
per-device HBM), and the mesh topology + per-device pool bytes surface
as ``decode_mesh_devices`` / ``kv_pool_device_bytes`` gauges in
`GET /metrics`, `GET /info`, and the UI `/serving` page.
``paged_kernel`` (`--paged-kernel auto|on|off`, ISSUE 15) picks the
fused Pallas paged-decode kernel vs the XLA gather per decode bucket
("auto" = per-shape autotune, docs/serving.md "Fused decode kernel");
the `paged_kernel_engaged` gauge and the ``paged_kernel`` block of
`GET /debug/engine` report the live verdicts.

Observability (`inference/trace.py`): the server owns a span flight
recorder written from the HTTP layer, batcher, decode scheduler, and KV
pool. Every POST carries an `X-Request-Id` response header (a well-formed
client-supplied id becomes the prefix of a server-uniquified one, so
retries sharing an id never merge onto one trace track), error bodies
quote the id, `/generate`
responses include a per-phase ``timings`` breakdown (queue/restore/
prefill/decode, summing to the end-to-end latency), and `GET /trace`
exports the ring — structured JSON or Chrome trace-event format
(`?format=chrome`, Perfetto-loadable; `python -m
deeplearning4j_tpu.inference.trace dump` fetches it to a file).
Cross-process context (`serving/telemetry.py`): a valid
``X-Graft-Trace`` ingress header (fleet trace id, sender span id, hop
count, send timestamp) makes the request's spans joinable across
processes — the handler records an ``rpc`` span carrying the flow
edge, and the fleet aggregator merges N replicas' rings into one
Perfetto waterfall via the `GET /trace/clock` handshake. A malformed
header of either kind degrades to a fresh server-minted context,
never an error.

Fault tolerance (`inference/supervisor.py`, `inference/failpoints.py`):
the decode engine runs under an EngineSupervisor by default
(``supervise=False`` opts out) — a watchdog consumes the scheduler
loop's per-iteration heartbeat, and a crashed or hung engine is fenced,
rebuilt, and every in-flight request resubmitted onto the replacement
with its original handle and seed (token-identical recovery; bounded
exponential backoff + per-request retry budget, exhaustion -> structured
503 carrying the ``request_id``). Sustained queue pressure walks a
graceful-degradation ladder (shed low-priority queued load -> halve the
prefill chunk -> reject with ``Retry-After``), `POST /admin/drain` does
a zero-dropped-request engine swap, and `GET /healthz` / `GET /readyz`
split liveness from readiness so a load balancer stops routing DURING
recovery and resumes after. Chaos seams (`--failpoint name=spec`, env
``DL4J_FAILPOINTS``, or the opt-in `POST /admin/failpoints`) inject
deterministic crashes/hangs/OOMs for drills; `tests/test_chaos.py`
proves the no-lost-request / token-identity invariants per seam.
See ``docs/robustness.md`` for the failure model and runbook.

Endpoints:
  GET  /health            {"status": "ok", "model": "...", "params": N}
  GET  /healthz           liveness: process answers (always 200)
  GET  /readyz            readiness: 200 while heartbeat fresh AND not
                          draining/recovering, else 503 (+ status body)
  GET  /info              model summary + config JSON + SLO/profiler
                          headline (tokens/s, MFU estimate)
  GET  /metrics           SLO metrics snapshot (?format=prometheus — or
                          an Accept: application/openmetrics-text
                          scrape — for the OpenMetrics exposition with
                          HELP/TYPE, labels, buckets, and request-id
                          exemplars; Accept: text/plain gets the same
                          families as 0.0.4 text, exemplars omitted;
                          ?format=text for the legacy summary text)
  GET  /debug/engine      live engine anatomy: slot table, pool/trie
                          occupancy, compile-cache census, spec
                          acceptance, mesh, per-family FLOPs/bytes from
                          cost_analysis(), MFU/tokens-per-sec estimates,
                          step-phase decomposition, supervisor+SLO state
  GET  /trace/clock       clock-alignment handshake (monotonic + wall +
                          trace_t0): the fleet aggregator
                          (serving/telemetry.py) places this process's
                          trace timestamps on the fleet timeline
  GET  /trace             flight-recorder dump (?limit=N newest events;
                          ?since=CURSOR tails incrementally — pass the
                          previous response's next_cursor;
                          ?format=chrome for Perfetto / chrome://tracing)
  POST /predict           {"data": [[...], ...]}  -> probabilities + argmax
                          (?timeout_ms=N sets the request deadline; an
                          expired request gets HTTP 504, a full queue 503)
  POST /predict/csv       text/plain CSV rows     -> same, via the
                          RecordToDataSetConverter (label column ignored)
  POST /generate          {"prompt": [ids], "max_new_tokens": N,
                          "temperature"/"top_k"/"top_p"/"seed"/"eos_id"?,
                          "stop"/"repetition_penalty"/"presence_penalty"
                          /"frequency_penalty"/"grammar"?}
                          -> {"tokens": [ids], "request_id": "...",
                          "finish_reason": "length|eos|stop|grammar",
                          "timings": {queue_ms, restore_ms, prefill_ms,
                          decode_ms, total_ms}}; 400 unless the server
                          was started with decode_vocab. A ?timeout_ms
                          expiry CANCELS the decode (slot reclaimed) ->
                          HTTP 504; a full decode queue -> HTTP 503; a
                          prompt that cannot fit the KV cache -> HTTP 413.
                          {"stream": true} -> 200 text/event-stream: one
                          `data: {"token", "index"}` event per decoded
                          token, then `data: {"done": true, request_id,
                          tokens, finish_reason, timings}`; a client
                          hangup mid-stream cancels the decode (slot +
                          pins reclaimed, stream_disconnects_total).
                          "grammar" ({"type": "admit_all" | "trie" |
                          "json_schema", ...}) compiles ahead of
                          admission to device token masks — see
                          docs/serving.md "Streaming & constrained
                          decoding"
  POST /admin/drain       draining restart: stop admitting, finish
                          in-flight, swap the engine, resume (202; watch
                          /readyz flip)
  GET/POST /admin/failpoints  chaos control (opt-in failpoint_endpoint):
                          {"name": seam, "spec": "crash@n:3"} arms,
                          spec null disarms, name "*" disarms all
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import select
import socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from typing import Dict, Optional, Tuple, Union
from urllib.parse import parse_qs, urlparse

import numpy as np

from ..inference import (AdmissionRejectedError, DecodeScheduler,
                         EngineSupervisor, GrammarError, MetricsRegistry,
                         MicroBatcher,
                         PromptTooLongError, QueueFullError,
                         RequestTimeoutError, RetryBudgetExceededError,
                         SLOMonitor, ShuttingDownError, TokenStream,
                         admit_all, compile_json_schema, compile_trie,
                         failpoints)
from ..inference.failpoints import InjectedFault
from ..inference.trace import FlightRecorder, new_request_id
from .streaming import RecordToDataSetConverter
from .telemetry import TRACE_HEADER, parse_trace_header

# what a client-supplied X-Request-Id may look like before we echo it
# back into a response HEADER: obs-folded request headers reach
# `self.headers.get()` with embedded CR/LF, and `send_header` writes the
# value verbatim — an unvalidated id is a response-header injection (and
# an unbounded string in every trace record). Anything else gets a
# server-generated id instead.
_REQUEST_ID_RE = re.compile(r"[A-Za-z0-9._:\-]{1,128}")

# bounded grammar-compile cache: compiled AHEAD of admission, shared
# across requests carrying byte-equal grammar specs
_GRAMMAR_CACHE_CAP = 32


def _peer_gone(sock) -> bool:
    """True when the SSE client hung up: the socket is readable and a
    zero-byte MSG_PEEK confirms EOF (an orderly close; an RST raises
    OSError, also caught). Polled between events so a silent disconnect
    is noticed promptly even when the kernel send buffer would have
    absorbed the next token write without raising EPIPE."""
    try:
        readable, _, _ = select.select([sock], [], [], 0)
        if not readable:
            return False
        return sock.recv(1, socket.MSG_PEEK) == b""
    except (OSError, ValueError):
        return True


class InferenceServer:
    def __init__(self, net=None, model_path: Union[str, Path, None] = None,
                 port: int = 0, max_batch: int = 1024,
                 converter: Optional[RecordToDataSetConverter] = None,
                 batching: bool = True, batch_window_ms: float = 2.0,
                 max_queue: int = 256,
                 default_timeout_ms: Optional[float] = None,
                 decode_vocab: Optional[int] = None, decode_slots: int = 4,
                 prefill_chunk: int = 64, decode_queue: int = 64,
                 kv_block: int = 16,
                 kv_pool_mb: float = 0.0, kv_dtype: Optional[str] = None,
                 paged_kernel: str = "auto",
                 host_cache_mb: float = 0.0, disk_cache_mb: float = 0.0,
                 tier_dir: Optional[str] = None,
                 mask_rows: int = 64,
                 decode_tp: int = 0, speculate: int = 0,
                 draft_blocks: int = 0, draft_net=None,
                 metrics: Optional[MetricsRegistry] = None,
                 trace_buffer: int = 8192,
                 tracer: Optional[FlightRecorder] = None,
                 supervise: bool = True, hang_timeout_s: float = 5.0,
                 retry_budget: int = 3,
                 slo_p99_ms: Optional[float] = None,
                 slo: Optional[SLOMonitor] = None,
                 profile: bool = True,
                 decode_transfer_guard: Optional[str] = None,
                 failpoint_endpoint: bool = False):
        if net is None:
            if model_path is None:
                raise ValueError("pass a net or a model_path")
            from ..util.model_serializer import restore_model
            net = restore_model(model_path)  # MLN or ComputationGraph,
            # dispatched on the zip's model_type stamp
        self.net = net
        self.max_batch = max_batch
        self.converter = converter or RecordToDataSetConverter(label_index=None)
        self.batching = batching
        self.batch_window_ms = float(batch_window_ms)
        self.max_queue = int(max_queue)
        self.default_timeout_ms = default_timeout_ms
        self.decode_vocab = decode_vocab
        self.decode_slots = int(decode_slots)
        self.prefill_chunk = int(prefill_chunk)
        self.decode_queue = int(decode_queue)
        self.kv_block = int(kv_block)
        self.kv_pool_mb = float(kv_pool_mb)
        self.kv_dtype = kv_dtype
        # hierarchical KV tiering (ISSUE 19, inference/kvtier.py):
        # host-RAM + disk demotion targets for pool evictions, plus the
        # fleet prefix-directory endpoints below
        self.host_cache_mb = float(host_cache_mb)
        self.disk_cache_mb = float(disk_cache_mb)
        self.tier_dir = tier_dir
        # fused Pallas decode kernel (ISSUE 15): the factory passes the
        # mode through on every (re)build, so crash recovery and
        # draining restarts come back with the same kernel decision —
        # warmup inside the supervisor's recovery window covers the
        # kernel variant, keeping CompileCounter budgets across swaps
        self.paged_kernel = paged_kernel
        # grammar-constrained decoding (ISSUE 14): device mask-table
        # rows; grammar specs in /generate payloads compile ONCE (cache
        # below, keyed by spec bytes) ahead of admission
        self.mask_rows = int(mask_rows)
        self._grammar_cache: Dict[str, object] = {}
        self._grammar_lock = threading.Lock()
        # speculative decoding (ISSUE 10): gamma draft tokens per slot
        # per iteration, verified token-identically by one multi-token
        # target forward; draft = shallow exit over the first
        # `draft_blocks` transformer blocks (or an explicit draft_net)
        self.speculate = int(speculate)
        self.draft_blocks = int(draft_blocks)
        self.draft_net = draft_net
        # tensor-parallel decode (inference/sharding.py): > 1 shards the
        # engine over a tp-device mesh — heads/FFN split, KV pool
        # head-sharded (kv_pool_mb becomes the PER-DEVICE budget), block
        # tables replicated. 0/1 = single-device. The factory passes it
        # through on every (re)build, so crash recovery and draining
        # restarts come back sharded too.
        self.decode_tp = int(decode_tp)
        # fault tolerance (inference/supervisor.py): the decode engine
        # is owned by an EngineSupervisor — watchdog, crash recovery
        # with request requeue, degradation ladder, draining restarts —
        # unless supervise=False restores the bare scheduler
        self.supervise = bool(supervise)
        self.hang_timeout_s = float(hang_timeout_s)
        self.retry_budget = int(retry_budget)
        self.decode_transfer_guard = decode_transfer_guard
        # test-only chaos control plane (POST /admin/failpoints): must
        # be opted into — a production server must not let clients arm
        # crash seams
        self.failpoint_endpoint = bool(failpoint_endpoint)
        self.supervisor: Optional[EngineSupervisor] = None
        self._decoder_direct: Optional[DecodeScheduler] = None
        self._shutting_down = False
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # performance-attribution & SLO plane (inference/profiler.py,
        # ISSUE 11): per-route sliding-window latency percentiles +
        # burn-rate against the --slo-p99-ms objective (None = track
        # percentiles, never burn), fed to the degradation ladder as its
        # second escalation input; profile=False disarms the engine's
        # step-phase profiler (the bench A/B knob)
        self.slo = slo if slo is not None else SLOMonitor(
            objective_p99_s=slo_p99_ms / 1e3 if slo_p99_ms else None,
            metrics=self.metrics)
        self.profile = bool(profile)
        # per-server flight recorder (like the per-server MetricsRegistry:
        # one source of truth this server's `GET /trace` reads back);
        # trace_buffer=0 disables recording entirely (`--trace-buffer 0`)
        self.tracer = tracer if tracer is not None else FlightRecorder(
            trace_buffer, enabled=trace_buffer > 0)
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None
        self._port = port
        self._lock = threading.Lock()  # unbatched path: output() mutates
        # net._jit_cache etc.
        # one batcher per trailing feature signature (each signature is its
        # own family of bucketed XLA programs). Bounded: a client free-form
        # controls the signature via the payload, and each batcher costs a
        # dispatcher thread + compiled programs — beyond the cap, unseen
        # signatures take the lock-serialized path instead of allocating.
        self._batchers: Dict[Tuple, MicroBatcher] = {}
        self._batchers_lock = threading.Lock()
        self.max_signatures = 16
        # streaming observability (ISSUE 14): request/disconnect
        # counters live on the server (the engine owns the TTFT
        # histogram + first_token instant)
        self._m_stream_reqs = self.metrics.counter(
            "stream_requests_total",
            help="/generate requests served as SSE token streams")
        self._m_stream_disconnects = self.metrics.counter(
            "stream_disconnects_total",
            help="SSE clients that hung up mid-stream (decode "
                 "cancelled, slot reclaimed)")
        self._m_grammar_compiles = self.metrics.counter(
            "grammar_compiles_total",
            help="grammar specs compiled (cache misses)")

    @property
    def port(self) -> int:
        return self._httpd.server_address[1] if self._httpd else self._port

    @property
    def _decoder(self) -> Optional[DecodeScheduler]:
        """The LIVE decode scheduler: supervised servers swap engines on
        crash recovery / drain, so this must always resolve through the
        supervisor rather than pinning the first instance."""
        if self.supervisor is not None:
            return self.supervisor.engine
        return self._decoder_direct

    def _tier(self):
        """The live engine's TierManager, or None when tiering is off
        (``host_cache_mb == 0``) or no decode engine is configured."""
        dec = self._decoder
        return getattr(dec, "tier", None) if dec is not None else None

    def _prefix_fetch(self, payload: dict) -> Tuple[int, dict]:
        """POST /prefix/fetch body: pull a block-hash chain from a peer
        replica's ``/prefix/block`` endpoint into the local tier.

        Hashes MUST arrive parent-first (the router sends them in chain
        order): ``insert_fetched`` rejects a child whose parent chain is
        unknown, so a failed parent makes the rest of the chain
        unreachable and we stop rather than burn peer round-trips."""
        tier = self._tier()
        if tier is None:
            return 404, {"error": "KV tiering disabled"}
        peer = payload.get("peer") or ""
        hashes = payload.get("hashes") or []
        if not peer or not isinstance(hashes, list):
            return 400, {"error": "need peer URL and hashes list"}
        import urllib.request
        fetched, skipped, failed = 0, 0, 0
        inserted = []
        for h in hashes:
            h = str(h)
            if tier.holds(h):
                skipped += 1
                continue
            try:
                with urllib.request.urlopen(
                        peer.rstrip("/") + "/prefix/block?hash=" + h,
                        timeout=10.0) as resp:
                    body = resp.read()
            except OSError:
                failed += 1
                break
            if tier.insert_fetched(body) is None:
                failed += 1
                break
            fetched += 1
            inserted.append(h)
        if inserted:
            # warm the pulled chain immediately: the request that
            # triggered this fetch is usually right behind it
            tier.request_restore(inserted)
        return 200, {"fetched": fetched, "skipped": skipped,
                     "failed": failed}

    def _decoder_factory(self) -> DecodeScheduler:
        return DecodeScheduler(
            self.net, self.decode_vocab, n_slots=self.decode_slots,
            max_queue=self.decode_queue,
            prefill_chunk=self.prefill_chunk,
            kv_block=self.kv_block,
            kv_pool_mb=self.kv_pool_mb,
            kv_dtype=self.kv_dtype,
            paged_kernel=self.paged_kernel,
            host_cache_mb=self.host_cache_mb,
            disk_cache_mb=self.disk_cache_mb,
            tier_dir=self.tier_dir,
            mask_rows=self.mask_rows,
            mesh=self.decode_tp if self.decode_tp > 1 else None,
            speculate=self.speculate,
            draft_blocks=self.draft_blocks or None,
            draft_net=self.draft_net,
            transfer_guard=self.decode_transfer_guard,
            profile=self.profile,
            metrics=self.metrics, tracer=self.tracer)

    def ready(self) -> Tuple[bool, dict]:
        """`/readyz` verdict + body. Unsupervised servers are ready
        while not shutting down (there is no watchdog to vouch for the
        engine, and the prediction path has no engine at all)."""
        if self._shutting_down:
            return False, {"ready": False, "reason": "shutting_down"}
        if self.supervisor is not None:
            status = self.supervisor.status()
            return status["ready"], status
        return True, {"ready": True}

    def _net_output(self, arr: np.ndarray) -> np.ndarray:
        """One forward through either facade. ComputationGraph.output
        returns a LIST of output arrays — /predict's contract is one
        prediction tensor, so take the (first) output; without this the
        row-wise batching/scatter would slice the outputs axis."""
        out = self.net.output(arr)
        if isinstance(out, (list, tuple)):
            out = out[0]
        return np.asarray(out)

    def _batcher_for(self, arr: np.ndarray) -> Optional[MicroBatcher]:
        sig = (arr.shape[1:], str(arr.dtype))
        with self._batchers_lock:
            b = self._batchers.get(sig)
            if b is None:
                if len(self._batchers) >= self.max_signatures:
                    return None  # signature-cap overflow: direct path
                b = MicroBatcher(
                    self._net_output,
                    max_batch=self.max_batch, max_queue=self.max_queue,
                    batch_window_s=self.batch_window_ms / 1e3,
                    metrics=self.metrics, tracer=self.tracer,
                    name="predict").start()
                self._batchers[sig] = b
            return b

    def _forward(self, arr: np.ndarray,
                 timeout_ms: Optional[float]) -> np.ndarray:
        if self.batching:
            batcher = self._batcher_for(arr)
            if batcher is not None:
                timeout_s = (timeout_ms / 1e3 if timeout_ms is not None
                             else None)
                return batcher.predict(arr, timeout_s=timeout_s)
        outs = []
        with self._lock:
            for off in range(0, arr.shape[0], self.max_batch):
                outs.append(self._net_output(arr[off:off + self.max_batch]))
        return np.concatenate(outs) if outs else np.zeros((0, 0), np.float32)

    def _predict(self, arr: np.ndarray,
                 timeout_ms: Optional[float] = None) -> dict:
        if timeout_ms is None:
            timeout_ms = self.default_timeout_ms
        out = (self._forward(arr, timeout_ms) if arr.shape[0]
               else np.zeros((0, 0), np.float32))
        return {
            "predictions": out.astype(float).tolist(),
            "classes": np.argmax(out, axis=-1).astype(int).tolist()
            if out.ndim >= 2 and out.shape[-1] > 0 else [],
        }

    def _compile_grammar(self, spec: dict, eos_id: Optional[int]):
        """Compile a /generate ``grammar`` spec (AHEAD of admission —
        the tentpole contract: mask construction never rides the decode
        hot path), cached by spec bytes so a repeated structured-output
        schema compiles once for the whole serving lifetime.

        Spec forms: ``{"type": "admit_all"}`` (the token-identity
        reference), ``{"type": "trie", "sequences": [[ids], ...]}``
        (emit exactly one of the sequences), ``{"type": "json_schema",
        "schema": {...}, "alphabet": "chars-or-token-strings"}`` (the
        alphabet maps token id -> decoded text; see
        logitproc.compile_json_schema for the schema subset)."""
        if not isinstance(spec, dict):
            raise GrammarError("grammar must be an object")
        # digest, not the serialized spec itself: a json_schema spec
        # carries a vocab-length alphabet, and retaining up to 32 full
        # spec strings as dict keys would hold O(32 x vocab) bytes
        # forever (the one canonicalization pass per request stays —
        # content addressing has to read the content)
        key = hashlib.sha1(json.dumps([spec, eos_id],
                                      sort_keys=True).encode()).hexdigest()
        with self._grammar_lock:
            g = self._grammar_cache.get(key)
        if g is not None:
            return g
        typ = spec.get("type")
        if typ == "admit_all":
            g = admit_all(self.decode_vocab)
        elif typ == "trie":
            g = compile_trie(spec.get("sequences") or [],
                             self.decode_vocab, eos_id=eos_id)
        elif typ == "json_schema":
            alphabet = spec.get("alphabet")
            if alphabet is None:
                raise GrammarError(
                    "json_schema grammar needs an 'alphabet' (token id "
                    "-> decoded text)")
            if len(alphabet) != self.decode_vocab:
                raise GrammarError(
                    f"alphabet length {len(alphabet)} != vocab "
                    f"{self.decode_vocab}")
            g = compile_json_schema(spec.get("schema") or {}, alphabet,
                                    eos_id=eos_id)
        else:
            raise GrammarError(
                f"unknown grammar type {typ!r} (admit_all | trie | "
                "json_schema)")
        self._m_grammar_compiles.inc()
        with self._grammar_lock:
            if len(self._grammar_cache) >= _GRAMMAR_CACHE_CAP:
                # bounded: drop the oldest entry (insertion order) — a
                # client cycling unique schemas cannot grow this
                self._grammar_cache.pop(next(iter(self._grammar_cache)))
            self._grammar_cache[key] = g
        return g

    def _decode_kwargs(self, payload: dict) -> dict:
        """The per-request decode kwargs shared by the buffered and
        streaming /generate paths: sampling knobs plus the ISSUE 14
        logit-pipeline spec (stop sequences, penalties, grammar)."""
        kw = {k: payload[k] for k in ("temperature", "top_k", "top_p",
                                      "seed", "eos_id", "priority",
                                      "repetition_penalty",
                                      "presence_penalty",
                                      "frequency_penalty")
              if k in payload}
        stop = payload.get("stop")
        if stop:
            if isinstance(stop[0], (int, float)):
                stop = [stop]  # one bare sequence
            kw["stop"] = [[int(t) for t in s] for s in stop]
        gspec = payload.get("grammar")
        if gspec is not None:
            kw["grammar"] = self._compile_grammar(gspec,
                                                  payload.get("eos_id"))
        return kw

    def _generate(self, payload: dict, timeout_ms: Optional[float],
                  request_id: Optional[str] = None) -> dict:
        gen = (self.supervisor if self.supervisor is not None
               else self._decoder_direct)
        if gen is None:
            raise ValueError("generation is disabled: start the server "
                             "with decode_vocab (CLI: --generate)")
        if timeout_ms is None:
            timeout_ms = self.default_timeout_ms
        kw = self._decode_kwargs(payload)
        prompt = [int(t) for t in payload["prompt"]]
        max_new = int(payload.get("max_new_tokens", 16))
        timeout = timeout_ms / 1e3 if timeout_ms is not None else 120.0
        n = int(payload.get("n", 1))
        if n < 1:
            raise ValueError(f"n must be >= 1, got {n}")
        if n > 1:
            # best-of-n: n candidates over one prompt, submitted as a
            # COW fork group (paged engines share the prompt's blocks —
            # n candidates, ~one prompt's worth of KV). Candidate i
            # samples with seed+i; the client ranks the candidates.
            if n > max(self.decode_slots, 1) * 4:
                raise ValueError(
                    f"n={n} exceeds the candidate cap "
                    f"({max(self.decode_slots, 1) * 4} = 4x decode "
                    "slots)")
            handles = gen.generate_many(prompt, n, max_new,
                                        timeout=timeout,
                                        request_id=request_id, **kw)
            return {
                "tokens": handles[0].tokens,  # n=1-compatible surface
                "candidates": [
                    {"tokens": h.tokens, "request_id": h.request_id,
                     "timings": h.timings()} for h in handles],
                "n": n,
                # the handler's id (the X-Request-Id header): candidate
                # ids derive from it as <id>.cI, so body and header
                # correlate instead of contradicting
                "request_id": request_id or handles[0].request_id,
                "timings": handles[0].timings(),
            }
        # supervised: the supervisor tracks the request for crash
        # recovery (an engine restart resubmits it, same handle, same
        # seed — the client never sees the crash)
        handle = gen.generate_handle(
            prompt, max_new, timeout=timeout,
            request_id=request_id, **kw)
        # the per-request observability payload: the id the client can
        # quote (X-Request-Id carries it too) and the phase breakdown
        # whose four segments sum to the end-to-end latency
        out = {"tokens": handle.tokens, "request_id": handle.request_id,
               "timings": handle.timings()}
        if handle.finish_reason:
            out["finish_reason"] = handle.finish_reason
        if handle.retries:
            out["retries"] = handle.retries  # survived engine crash(es)
        return out

    def _generate_stream(self, handler, payload: dict,
                         timeout_ms: Optional[float], rid: str) -> str:
        """POST /generate with ``"stream": true`` — SSE token emission.

        Writes the response DIRECTLY on ``handler``: one
        ``data: {"token": t, "index": i}`` event per decoded token as
        the scheduler releases it (stop-sequence hold-back applies —
        a client never sees half a stop sequence), then a terminal
        ``data: {"done": true, request_id, tokens, finish_reason,
        timings}`` event. Submit-time failures (413/503/400) raise
        BEFORE any byte is written, so do_POST's ordinary error mapping
        answers them as JSON; once the SSE headers are out, failures are
        reported in-band on a best-effort final event.

        Client disconnects are detected between events (socket EOF
        peek) and on write (EPIPE): the decode is CANCELLED — the slot,
        its paged blocks, the prefix-trie pin, any fork membership, and
        the grammar mask rows are all reclaimed at the scheduler's next
        sweep — and ``stream_disconnects_total`` counts it. Returns
        "ok" | "disconnect" (the SLO plane skips disconnects: the
        client, not the server, ended those)."""
        gen = (self.supervisor if self.supervisor is not None
               else self._decoder_direct)
        if gen is None:
            raise ValueError("generation is disabled: start the server "
                             "with decode_vocab (CLI: --generate)")
        if int(payload.get("n", 1)) != 1:
            raise ValueError("stream=true supports n=1 only (best-of-n "
                             "candidates finish at different times; "
                             "rank buffered candidates instead)")
        if timeout_ms is None:
            timeout_ms = self.default_timeout_ms
        timeout = timeout_ms / 1e3 if timeout_ms is not None else 120.0
        kw = self._decode_kwargs(payload)
        prompt = [int(t) for t in payload["prompt"]]
        max_new = int(payload.get("max_new_tokens", 16))
        stream = TokenStream()
        # everything above (parse errors, grammar compile errors, 413s,
        # queue-full 503s from this submit) raises pre-header: the
        # client gets the same structured JSON errors as buffered mode
        handle = gen.submit(prompt, max_new, request_id=rid,
                            stream=stream, **kw)
        self._m_stream_reqs.inc()
        status = "ok"
        try:
            handler.send_response(200)
            handler.send_header("Content-Type", "text/event-stream")
            handler.send_header("Cache-Control", "no-cache")
            handler.send_header("X-Request-Id", rid)
            handler.end_headers()
            deadline = time.monotonic() + timeout
            conn = handler.connection
            try:
                for evt in stream.events(deadline=deadline):
                    if _peer_gone(conn):
                        raise BrokenPipeError("SSE client hung up")
                    handler.wfile.write(
                        b"data: " + json.dumps(evt).encode() + b"\n\n")
                    handler.wfile.flush()
            except TimeoutError:
                # the request's own deadline (buffered mode's 504):
                # cancel reclaims the slot; the expiry is reported
                # in-band — headers are long gone — but it still counts
                # in http_errors_total exactly like a buffered 504
                handle.cancel()
                self.metrics.counter("http_errors_total").inc()
                self.tracer.instant("reject", track="http", args={
                    "request_id": rid, "reason": "stream_timeout"})
                handler.wfile.write(
                    b"data: " + json.dumps(
                        {"done": True, "request_id": rid,
                         "error": "deadline exceeded",
                         "finish_reason": "timeout"}).encode() + b"\n\n")
                handler.wfile.flush()
        except (BrokenPipeError, ConnectionResetError, OSError):
            # cancel-on-disconnect: the slot (and every pin riding it)
            # is reclaimed at the scheduler's next sweep instead of
            # decoding to max_new_tokens for a client that left
            status = "disconnect"
            handle.cancel()
            self._m_stream_disconnects.inc()
            self.tracer.instant(
                "stream_disconnect", req=rid,
                args={"request_id": rid, "streamed": stream.sent})
        except Exception as e:  # post-header: report in-band, never a
            # second status line into the event stream
            handle.cancel()
            try:
                handler.wfile.write(
                    b"data: " + json.dumps(
                        {"done": True, "request_id": rid,
                         "error": str(e)}).encode() + b"\n\n")
                handler.wfile.flush()
            except OSError:
                status = "disconnect"
        finally:
            if self.supervisor is not None:
                # leave the crash-recovery tracking set exactly like
                # generate_handle's finally: a client that got its
                # stream (or gave up) must not have the request
                # replayed by a later engine restart
                self.supervisor.untrack(rid)
        return status

    def start(self) -> "InferenceServer":
        server = self
        self._shutting_down = False
        failpoints.bind_metrics(self.metrics)
        if self.decode_vocab is not None and self._decoder is None:
            if self.supervise:
                self.supervisor = EngineSupervisor(
                    self._decoder_factory,
                    hang_timeout_s=self.hang_timeout_s,
                    retry_budget=self.retry_budget,
                    slo=self.slo,
                    metrics=self.metrics, tracer=self.tracer)
            else:
                self._decoder_direct = self._decoder_factory().start()
        m_http = self.metrics.counter("http_requests_total")
        m_err = self.metrics.counter("http_errors_total")

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *args):  # quiet
                pass

            def _send(self, obj, code=200, content_type="application/json",
                      request_id=None, headers=None):
                body = (obj if isinstance(obj, bytes)
                        else json.dumps(obj).encode())
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                if request_id:
                    # clients quote this id when reporting a slow/failed
                    # request; it keys straight into GET /trace
                    self.send_header("X-Request-Id", request_id)
                for name, value in (headers or {}).items():
                    self.send_header(name, value)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                m_http.inc()
                url = urlparse(self.path)
                if url.path == "/health":
                    self._send({"status": "ok",
                                "model": type(server.net).__name__,
                                "params": server.net.num_params()})
                elif url.path == "/healthz":
                    # liveness: the process answers. Nothing else — a
                    # crashed engine mid-recovery is still a LIVE
                    # process (restart-looping it would only make the
                    # outage worse); that distinction is /readyz's job
                    self._send({"status": "up"})
                elif url.path == "/readyz":
                    # readiness: able to take traffic NOW (watchdog
                    # heartbeat fresh AND not draining/recovering) —
                    # load balancers route on this, so it flips unready
                    # for the recovery window and back after
                    ok, body = server.ready()
                    self._send(body, 200 if ok else 503)
                elif url.path == "/admin/failpoints":
                    if not server.failpoint_endpoint:
                        return self._send(
                            {"error": "failpoint endpoint disabled "
                             "(start the server with "
                             "failpoint_endpoint=True)"}, 403)
                    self._send({"armed": failpoints.snapshot(),
                                "seams": list(failpoints.SEAMS)})
                elif url.path == "/info":
                    import jax  # mesh topology: visible vs used devices
                    dec = server._decoder
                    devs = jax.devices()
                    body = {"model": type(server.net).__name__,
                            "config": json.loads(server.net.conf.to_json()),
                            "params": server.net.num_params(),
                            "batching": server.batching,
                            # what this process runs on, so a client
                            # (or a parent that must stay off the
                            # chip) can check it over HTTP
                            "platform": devs[0].platform,
                            "device_kind": devs[0].device_kind,
                            "mesh": {"devices": len(devs),
                                     # the chip a fleet parent confined
                                     # this process to (JAX numbers its
                                     # device 0 in every such process)
                                     "visible_chips": os.environ.get(
                                         "TPU_VISIBLE_CHIPS"),
                                     "tp": getattr(dec, "tp", 1)},
                            "slo": server.slo.snapshot()}
                    prof = getattr(dec, "profiler", None)
                    if prof is not None and prof.enabled:
                        # the attribution headline (full detail lives at
                        # GET /debug/engine): rolling tokens/s, MFU
                        # estimate, attributed FLOP/s and HBM traffic
                        body["profiler"] = prof.rates()
                    self._send(body)
                elif url.path == "/metrics":
                    q = parse_qs(url.query)
                    fmt = q.get("format", [""])[0]
                    accept = self.headers.get("Accept", "") or ""
                    if fmt == "text":
                        self._send(server.metrics.render_text().encode(),
                                   content_type="text/plain; version=0.0.4")
                    elif fmt == "prometheus" or (
                            not fmt and "openmetrics" in accept):
                        # explicit ?format=prometheus or an OpenMetrics
                        # scrape: the full exposition WITH exemplars +
                        # '# EOF', under the openmetrics content type
                        # (exemplars are only legal in that format)
                        self._send(
                            server.metrics.render_prometheus().encode(),
                            content_type="application/openmetrics-text; "
                                         "version=1.0.0; charset=utf-8")
                    elif not fmt and "text/plain" in accept:
                        # a legacy text/plain Prometheus scraper: same
                        # families/buckets, exemplars omitted — the
                        # 0.0.4 parser rejects the '#' exemplar marker
                        # after a sample value
                        self._send(
                            server.metrics.render_prometheus(
                                openmetrics=False).encode(),
                            content_type="text/plain; version=0.0.4; "
                                         "charset=utf-8")
                    else:
                        self._send(server.metrics.snapshot())
                elif url.path == "/debug/engine":
                    dec = server._decoder
                    if dec is None:
                        return self._send(
                            {"error": "no decode engine (start the "
                             "server with decode_vocab / --generate)"},
                            404)
                    body = dec.debug_snapshot()
                    if server.supervisor is not None:
                        body["supervisor"] = server.supervisor.status()
                    # the FULL per-route SLO picture (status() embeds
                    # only the burn-rate brief — /readyz must stay
                    # cheap, a debug read need not)
                    body["slo"] = server.slo.snapshot()
                    self._send(body)
                elif url.path == "/trace/clock":
                    # clock-alignment handshake (serving/telemetry.py):
                    # the fleet aggregator brackets this read with its
                    # own wall clock to place this process's trace ts
                    # axis on the fleet timeline to within ±RTT/2
                    self._send({**server.tracer.clock(),
                                "pid": os.getpid()})
                elif url.path == "/trace":
                    q = parse_qs(url.query)
                    try:
                        limit = int(q.get("limit", ["0"])[0]) or None
                        # presence check, not `or None`: ?since=0 is the
                        # documented initial cursor, distinct from no
                        # cursor at all
                        since = (int(q["since"][0]) if "since" in q
                                 else None)
                    except ValueError:
                        return self._send(
                            {"error": "limit/since must be integers"},
                            400)
                    if q.get("format", [""])[0] == "chrome":
                        # Perfetto / chrome://tracing loadable
                        self._send(server.tracer.chrome_trace(limit=limit))
                    else:
                        # ?since=<cursor> tails the ring incrementally:
                        # pass the previous response's next_cursor
                        self._send(server.tracer.snapshot(limit=limit,
                                                          since=since))
                elif url.path == "/prefix/directory":
                    # fleet prefix directory feed (ISSUE 19): the router
                    # tails this incrementally with ?since=<next cursor>;
                    # a cursor gap or since<=0 returns a reset snapshot
                    tier = server._tier()
                    if tier is None:
                        return self._send(
                            {"error": "KV tiering disabled "
                                      "(start with --host-cache-mb)"}, 404)
                    q = parse_qs(url.query)
                    try:
                        since = int(q.get("since", ["0"])[0])
                    except ValueError:
                        return self._send(
                            {"error": "since must be an integer"}, 400)
                    self._send(tier.directory_feed(since))
                elif url.path == "/prefix/block":
                    # peer block pull: serve one spilled KV block as the
                    # raw encode_block() payload (CRC-framed JSON) so a
                    # peer replica can adopt the prefix without
                    # recomputing it
                    tier = server._tier()
                    if tier is None:
                        return self._send(
                            {"error": "KV tiering disabled"}, 404)
                    q = parse_qs(url.query)
                    h = q.get("hash", [""])[0]
                    payload = (tier.get_block_payload(h, timeout=5.0)
                               if h else None)
                    if payload is None:
                        return self._send(
                            {"error": "block not available", "hash": h},
                            404)
                    self._send(payload,
                               content_type="application/octet-stream")
                else:
                    self._send({"error": "not found"}, 404)

            def do_POST(self):
                m_http.inc()
                url = urlparse(self.path)
                q = parse_qs(url.query)
                # every POST gets a request id; a well-formed
                # client-supplied X-Request-Id is kept as the PREFIX of
                # a server-uniquified id (a client retrying with the
                # same id must not merge two live requests onto one
                # trace track — stack-paired B/E spans would garble).
                # The id rides the trace spans, the response header, and
                # every error body — "my request was slow" becomes
                # "request r000123 was slow", greppable in /trace.
                # Cross-process context (serving/telemetry.py): a valid
                # X-Graft-Trace header WINS the identity — its fleet
                # trace id becomes the prefix, so one request keeps one
                # greppable identity across client -> router -> replica.
                # Both headers are length-capped BEFORE any matching and
                # validated against a control-character-free alphabet; a
                # malformed value of either degrades to a fresh
                # server-minted id — never a 500, never an unvalidated
                # byte into trace records or exemplar labels.
                ctx = parse_trace_header(self.headers.get(TRACE_HEADER))
                rid = (ctx.request_id if ctx is not None
                       else (self.headers.get("X-Request-Id") or "")[:256])
                rid = (f"{rid}.{new_request_id()}"
                       if _REQUEST_ID_RE.fullmatch(rid)
                       else new_request_id())
                timeout_ms = None
                if "timeout_ms" in q:
                    try:
                        timeout_ms = float(q["timeout_ms"][0])
                    except ValueError:
                        m_err.inc()
                        return self._send(
                            {"error": "timeout_ms must be a number",
                             "request_id": rid}, 400, request_id=rid)
                n = int(self.headers.get("Content-Length", 0))
                raw = self.rfile.read(n)
                if server._shutting_down:
                    # stop() raced an in-flight POST: fail FAST with a
                    # structured 503 instead of letting the handler run
                    # into half-torn-down components and hang its client
                    m_err.inc()
                    return self._send({"error": "shutting_down",
                                       "request_id": rid}, 503,
                                      request_id=rid)
                t_route = time.monotonic()
                slo_sample = True  # flipped off by fast-reject paths
                if ctx is not None:
                    # server-side half of the cross-process waterfall:
                    # an `rpc` span on the request track wrapping the
                    # handler (closed in the finally below, so error
                    # paths close it too), carrying the flow edge
                    # (origin = the sender's span id, so the merged
                    # Chrome export draws the client->server arrow) and
                    # the sender's send timestamp (net_gap_ms = wire +
                    # accept-queue time between tiers,
                    # clock-skew-bounded)
                    server.tracer.begin(
                        "rpc", req=rid,
                        origin=ctx.parent or ctx.request_id,
                        parent=ctx.parent or ctx.request_id,
                        args={"path": url.path, "hop": ctx.hop,
                              "trace": ctx.request_id,
                              "net_gap_ms": round(
                                  (time.time() - ctx.origin_ts) * 1e3,
                                  3)})
                try:
                    if url.path == "/admin/drain":
                        if server.supervisor is None:
                            return self._send(
                                {"error": "draining needs a supervised "
                                 "decode engine (supervise=True + "
                                 "decode_vocab)", "request_id": rid},
                                400, request_id=rid)
                        server.supervisor.drain_async()
                        return self._send(
                            {"status": "draining", "request_id": rid,
                             **server.supervisor.status()}, 202,
                            request_id=rid)
                    if url.path == "/admin/failpoints":
                        if not server.failpoint_endpoint:
                            return self._send(
                                {"error": "failpoint endpoint disabled",
                                 "request_id": rid}, 403, request_id=rid)
                        payload = json.loads(raw.decode())
                        name = payload["name"]
                        spec = payload.get("spec")
                        if spec:
                            failpoints.arm(name, spec)
                        else:
                            failpoints.disarm(None if name == "*"
                                              else name)
                        return self._send(
                            {"armed": failpoints.snapshot(),
                             "request_id": rid}, request_id=rid)
                    # chaos seam AFTER the /admin/* branches: an armed
                    # http.handler seam must not be able to block its
                    # own HTTP disarm path (control-plane lockout)
                    failpoints.fire("http.handler")
                    if url.path == "/predict/csv":
                        rows = [line.split(",") for line in
                                raw.decode().strip().splitlines() if line.strip()]
                        ds = server.converter.convert(rows)
                        self._send(server._predict(np.asarray(ds.features),
                                                   timeout_ms),
                                   request_id=rid)
                    elif url.path == "/predict":
                        payload = json.loads(raw.decode())
                        arr = np.asarray(payload["data"], np.float32)
                        self._send(server._predict(arr, timeout_ms),
                                   request_id=rid)
                    elif url.path == "/generate":
                        payload = json.loads(raw.decode())
                        if payload.get("stream"):
                            # SSE: _generate_stream writes the response
                            # itself; submit-time errors raise before
                            # any byte and fall through to the JSON
                            # error mapping below
                            outcome = server._generate_stream(
                                self, payload, timeout_ms, rid)
                            if outcome == "disconnect":
                                # the CLIENT ended this one: not an SLO
                                # sample (same dilution argument as the
                                # fast rejects)
                                slo_sample = False
                        else:
                            self._send(server._generate(
                                payload, timeout_ms,
                                request_id=rid), request_id=rid)
                    elif url.path == "/prefix/fetch":
                        # router-directed peer pull (ISSUE 19): fetch a
                        # prefix block chain from the replica that holds
                        # it, adopt into the local tier, queue promotion
                        payload = json.loads(raw.decode())
                        code, body = server._prefix_fetch(payload)
                        body["request_id"] = rid
                        self._send(body, code, request_id=rid)
                    else:
                        self._send({"error": "not found"}, 404,
                                   request_id=rid)
                except PromptTooLongError as e:
                    # the scheduler refuses prompts that cannot fit the
                    # KV cache BEFORE queueing (no slot ever admitted a
                    # request destined to die on the overflow guard);
                    # 413 tells the client the payload itself is the
                    # problem, unlike a retryable 503/504. Paged engines
                    # reject on POOL capacity (the whole budget, not a
                    # per-slot stripe) and the body carries the math
                    body = {"error": f"prompt too long: {e}",
                            "request_id": rid}
                    if getattr(e, "blocks_needed", None) is not None:
                        body["blocks_needed"] = e.blocks_needed
                        body["blocks_available"] = e.blocks_available
                    m_err.inc()
                    slo_sample = False  # client error, ~1ms: not SLO
                    self._send(body, 413, request_id=rid)
                except TimeoutError as e:  # incl. RequestTimeoutError and
                    # decode-scheduler timeouts (the decode is cancelled
                    # by generate() before the error propagates here)
                    m_err.inc()
                    server.tracer.instant("reject", track="http", args={
                        "request_id": rid, "reason": "timeout_504"})
                    self._send({"error": f"deadline exceeded: {e}",
                                "request_id": rid}, 504, request_id=rid)
                except RetryBudgetExceededError as e:
                    # every attempt saw the engine die: a structured 503
                    # naming the request — never silence (the satellite
                    # invariant: exhaustion answers, it does not hang)
                    m_err.inc()
                    server.tracer.instant("reject", track="http", args={
                        "request_id": rid,
                        "reason": "retry_budget_exhausted"})
                    self._send({"error": "retry_budget_exhausted",
                                "detail": str(e), "request_id": rid},
                               503, request_id=rid)
                except ShuttingDownError:
                    m_err.inc()
                    slo_sample = False
                    self._send({"error": "shutting_down",
                                "request_id": rid}, 503, request_id=rid)
                except AdmissionRejectedError as e:
                    # degradation ladder level 3 / draining restart:
                    # Retry-After tells well-behaved clients how long to
                    # back off (examples/serving_load_test.py honors it)
                    m_err.inc()
                    slo_sample = False
                    server.tracer.instant("reject", track="http", args={
                        "request_id": rid, "reason": "degraded_503"})
                    self._send(
                        {"error": "not_admitting", "detail": str(e),
                         "retry_after_s": e.retry_after_s,
                         "request_id": rid}, 503, request_id=rid,
                        headers={"Retry-After":
                                 str(max(1, int(e.retry_after_s)))})
                except QueueFullError as e:
                    # incl. LoadSheddedError (the ladder's own level-1
                    # shedding): fast rejects again
                    m_err.inc()
                    slo_sample = False
                    server.tracer.instant("reject", track="http", args={
                        "request_id": rid, "reason": "backpressure_503"})
                    self._send({"error": f"over capacity: {e}",
                                "request_id": rid}, 503, request_id=rid)
                except InjectedFault as e:
                    # a chaos seam fired in the HTTP layer itself (or an
                    # injected fault escaped a lower layer): a 5xx —
                    # retryable server fault, NOT a 400 client error
                    m_err.inc()
                    self._send({"error": "injected_fault",
                                "seam": e.seam, "request_id": rid}, 500,
                               request_id=rid)
                except Exception as e:  # bad payloads must not kill the server
                    m_err.inc()
                    slo_sample = False  # 400s are client errors served
                    # in ~1ms; sampling them would dilute the burn
                    # signal exactly like the fast-reject 503s above
                    self._send({"error": str(e), "request_id": rid}, 400,
                               request_id=rid)
                finally:
                    if ctx is not None:
                        # close the ingress rpc span: server-observed
                        # end-to-end wall time on the request track
                        server.tracer.end("rpc", req=rid)
                    if slo_sample and url.path in ("/predict",
                                                   "/predict/csv",
                                                   "/generate"):
                        # the SLO plane's input: end-to-end route
                        # latency of requests that were actually
                        # SERVED (timeouts included — a 504 burned the
                        # budget). Fast-reject 503s (shed, admission-
                        # rejected, backpressure, shutdown) are the
                        # LADDER'S OWN OUTPUT: observing their ~1ms
                        # latencies would dilute the violation fraction
                        # and let the mitigation suppress the very burn
                        # signal that triggered it (de-escalate ->
                        # re-burn -> flap). Excluded, recovery probes
                        # itself: with rejects unsampled the fast
                        # window drains, burn reads 0, the ladder steps
                        # down and real traffic re-measures.
                        server.slo.observe(
                            url.path, time.monotonic() - t_route,
                            request_id=rid)

        self._httpd = ThreadingHTTPServer(("127.0.0.1", self._port), Handler)
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        # flag FIRST: handler threads that already passed accept see it
        # and answer a structured 503 ("shutting_down", request_id
        # echoed) instead of racing the teardown below into a hang
        self._shutting_down = True
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        if self.supervisor is not None:
            # fails every tracked in-flight request fast with
            # ShuttingDownError -> the blocked POST handlers respond 503
            self.supervisor.stop()
            self.supervisor = None
        if self._decoder_direct is not None:
            self._decoder_direct.stop()
            self._decoder_direct = None
        with self._batchers_lock:
            batchers = list(self._batchers.values())
            self._batchers.clear()
        for b in batchers:
            b.stop()
