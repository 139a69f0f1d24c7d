"""Command-line interface: train / test / predict.

Parity with the reference `deeplearning4j-cli` (CommandLineInterfaceDriver +
subcommands/Train.java:66 args4j flags :80-108 — -conf properties/JSON,
-input, -model, -output, -type, -runtime local —, Predict, Test).

Usage:
  dl4j-tpu train   --conf net.json --input data.csv --output model.zip
                   [--epochs N] [--batch B] [--label-index I] [--num-classes C]
                   [--runtime local|data-parallel]
  dl4j-tpu test    --model model.zip --input data.csv [--label-index I]
  dl4j-tpu predict --model model.zip --input data.csv [--output preds.csv]
  dl4j-tpu serve   --model model.zip [--port P] [--int8] [--no-batching]
                   [--batch-window-ms MS] [--queue-size N] [--timeout-ms MS]
                   [--trace-buffer N]
                   [--generate [--vocab-size V] [--decode-slots N]
                    [--prefill-chunk C] [--kv-pool-mb MB] [--kv-block B]
                    [--kv-dtype int8] [--paged-kernel auto|on|off]
                    [--host-cache-mb MB] [--disk-cache-mb MB]
                    [--tier-dir DIR]
                    [--mask-rows N] [--speculate GAMMA]
                    [--draft-blocks K] [--tp N]]
                   [--no-supervise] [--hang-timeout S] [--retry-budget N]
                   [--slo-p99-ms MS] [--no-profiler]
                   [--failpoint NAME=SPEC ...] [--failpoint-endpoint]
  dl4j-tpu telemetry --targets http://h:p,http://h:p [--out trace.json]
                   [--serve-port P] [--interval S] [--duration S]
                   [--ui URL]
  dl4j-tpu router  --spawn N --model model.zip [--journal journal.log]
                   [--port P] [--quorum Q] [--kv-block B]
                   [--paged-kernel auto|on|off]
                   [--affinity-blocks K] [--replica-arg ARG ...]
                   [--no-prefix-directory] [--prefix-fetch]
                   | --replicas http://h:p,http://h:p (attach mode)
"""
from __future__ import annotations

import argparse
import json
import signal
import sys
import threading
from pathlib import Path


def _build_iterator(args, num_classes=None):
    from ..datasets.records import CSVRecordReader, RecordReaderDataSetIterator

    reader = CSVRecordReader(skip_lines=args.skip_lines).initialize(args.input)
    return RecordReaderDataSetIterator(
        reader, batch_size=args.batch, label_index=args.label_index,
        num_classes=num_classes or args.num_classes,
        regression=args.regression)


def _load_conf(path):
    from ..nn.conf.config import MultiLayerConfiguration

    return MultiLayerConfiguration.from_json(Path(path).read_text())


def cmd_train(args) -> int:
    from ..nn.multilayer import MultiLayerNetwork
    from ..datasets.iterators import MultipleEpochsIterator
    from ..optimize.listeners import ScoreIterationListener
    from ..util import model_serializer

    conf = _load_conf(args.conf)
    net = MultiLayerNetwork(conf).init()
    net.set_listeners(ScoreIterationListener(args.print_every,
                                             log_fn=lambda m: print(m)))
    iterator = _build_iterator(args)
    if args.epochs > 1:
        iterator = MultipleEpochsIterator(args.epochs, iterator)
    if args.runtime == "data-parallel":
        from ..parallel.trainer import IciDataParallelTrainingMaster
        IciDataParallelTrainingMaster().execute_training(net, iterator)
    else:
        net.fit(iterator)
    model_serializer.write_model(net, args.output)
    print(f"Model saved to {args.output} (final score {net.score_:.6f})")
    return 0


def cmd_test(args) -> int:
    from ..util import model_serializer

    net = model_serializer.restore_multi_layer_network(args.model)
    iterator = _build_iterator(args)
    ev = net.evaluate(iterator)
    print(ev.stats())
    return 0


def cmd_predict(args) -> int:
    import numpy as np
    from ..util import model_serializer

    net = model_serializer.restore_multi_layer_network(args.model)
    iterator = _build_iterator(args)
    preds = []
    for ds in iterator:
        preds.extend(net.predict(ds.features).tolist())
    if args.output:
        Path(args.output).write_text("\n".join(str(p) for p in preds) + "\n")
        print(f"{len(preds)} predictions written to {args.output}")
    else:
        for p in preds:
            print(p)
    return 0


def cmd_serve(args) -> int:
    """Serve a saved model over HTTP (the dl4j-streaming serve-route
    analog, serving/server.py)."""
    import jax

    from ..serving import InferenceServer

    from ..inference import failpoints

    kw = dict(port=args.port, max_batch=args.max_batch,
              batching=not args.no_batching,
              batch_window_ms=args.batch_window_ms,
              max_queue=args.queue_size,
              default_timeout_ms=args.timeout_ms,
              decode_slots=args.decode_slots,
              prefill_chunk=args.prefill_chunk,
              kv_block=args.kv_block,
              kv_pool_mb=args.kv_pool_mb,
              kv_dtype=args.kv_dtype,
              paged_kernel=args.paged_kernel,
              host_cache_mb=args.host_cache_mb,
              disk_cache_mb=args.disk_cache_mb,
              tier_dir=args.tier_dir,
              mask_rows=args.mask_rows,
              decode_tp=args.tp,
              speculate=args.speculate,
              draft_blocks=args.draft_blocks,
              trace_buffer=args.trace_buffer,
              supervise=not args.no_supervise,
              hang_timeout_s=args.hang_timeout,
              retry_budget=args.retry_budget,
              slo_p99_ms=args.slo_p99_ms,
              profile=not args.no_profiler,
              failpoint_endpoint=args.failpoint_endpoint)
    # chaos seams: --failpoint flags, then the environment
    # (DL4J_FAILPOINTS="name=spec;..."), both through the same parser
    # so a typo'd seam or spec fails startup loudly
    armed = []
    for entry in args.failpoint or []:
        name, sep, spec = entry.partition("=")
        if not sep:
            print(f"error: bad --failpoint {entry!r} (want name=spec)",
                  file=sys.stderr)
            return 2
        failpoints.arm(name.strip(), spec.strip())
        armed.append(name.strip())
    armed += failpoints.arm_from_env()
    if getattr(args, "int8", False):
        # artifact must carry calibration (nn/quantization.save_quantized);
        # weight quantization is rebuilt deterministically from the params
        from ..nn.quantization import load_quantized
        net = load_quantized(args.model)
        mode = "int8"
    else:
        # type-dispatching restore: --generate's primary target is a
        # transformer LM ComputationGraph, not just MLN facades
        from ..util.model_serializer import restore_model
        net = restore_model(args.model)
        mode = "float"
    if args.generate:
        if mode == "int8" and not hasattr(net.conf, "vertices"):
            # the decode scheduler drives ComputationGraph decode (KV
            # cache states); a multilayer QuantizedNetwork has neither —
            # quantize the LM with quantize_graph/save_quantized_graph
            print("error: --int8 --generate needs a quantized "
                  "ComputationGraph artifact (nn.quantization."
                  "save_quantized_graph); this zip holds a multilayer "
                  "one", file=sys.stderr)
            return 2
        # the LM's next-token head width IS the vocabulary; --vocab-size
        # only exists for models whose output layer is wider than the
        # token space actually served. An int8 graph clone keeps the
        # float conf, so the inference below works for both modes.
        if args.vocab_size:
            kw["decode_vocab"] = args.vocab_size
        elif hasattr(net.conf, "vertices"):  # ComputationGraph facade
            out = net.conf.network_outputs[0]
            kw["decode_vocab"] = int(net.conf.vertices[out].layer.n_out)
        else:
            kw["decode_vocab"] = int(net.conf.layers[-1].n_out)
    if args.generate and args.kv_pool_mb > 0 and args.paged_kernel != "off":
        # arm ONLY the paged-decode seam BEFORE the engine builds, so
        # the --paged-kernel knob has a kernel registered to dispatch
        # (per-shape autotune keeps XLA wherever the kernel loses;
        # "off" never needs the registration at all). Deliberately NOT
        # the full enable(): that would also reroute /predict forwards
        # and the GQA contraction through the attention helper.
        from ..ops import pallas_kernels
        pallas_kernels.enable_paged_decode()
    server = InferenceServer(net=net, **kw).start()
    batch_mode = ("lock-serialized" if args.no_batching else
                  f"micro-batched, window {args.batch_window_ms}ms, "
                  f"queue {args.queue_size}")
    # report the pool's ACTUAL state, not the flag: the scheduler
    # disables it (with a RuntimeWarning) when the model has no KV cache
    # or the budget cannot fit two blocks
    decoder = getattr(server, "_decoder", None)
    paged_on = bool(getattr(decoder, "paged", False))
    # mesh topology: the ENGINE's actual tp (the scheduler disables
    # sharding with a RuntimeWarning when heads don't divide), not the
    # flag
    tp_on = int(getattr(decoder, "tp", 1))
    if tp_on > 1:
        mesh_mode = (f", tensor-parallel over {tp_on} of "
                     f"{len(jax.devices())} devices (tp axis; KV pool "
                     "head-sharded, per-device budgets)")
    else:
        mesh_mode = ""
    # speculation: report the ENGINE's armed state (disabled with a
    # RuntimeWarning when the model cannot be draft-cut), not the flag
    spec_on = int(getattr(decoder, "speculate", 0))
    if spec_on:
        spec_mode = (f", speculative x{spec_on} (shallow-exit draft, "
                     f"{getattr(decoder, 'draft_blocks', 0)} blocks)")
    else:
        spec_mode = ""
    if paged_on:
        # report the fused-kernel plane's ACTUAL engagement (the warmed
        # engine's per-bucket verdicts), not just the flag
        pk_st = decoder.paged_kernel_status()
        if pk_st["engaged"]:
            verdict = f"fused ({pk_st['execution']})"
        elif pk_st["declined"]:
            verdict = f"declined: {pk_st['declined']}"
        elif pk_st["refused"]:
            # XLA by default, not by victory: say what the compiler said
            verdict = "refused: " + next(iter(
                pk_st["refused"].values()))[:160]
        else:
            verdict = "xla"
        kern = f", decode kernel {pk_st['mode']}/{verdict}"
        kv_mode = (f", paged KV pool {args.kv_pool_mb}MB "
                   f"({decoder.pool.capacity_blocks} blocks of "
                   f"{args.kv_block}"
                   + (", int8 KV" if getattr(decoder, "kv_dtype", None)
                      else "") + ")" + kern
                   + (f", host tier {args.host_cache_mb:g}MB"
                      + (f" + disk {args.disk_cache_mb:g}MB"
                         if args.disk_cache_mb else "")
                      if getattr(decoder, "tier", None) is not None
                      else ""))
    else:
        kv_mode = ", prefix cache OFF"
    slo_mode = (f", SLO p99<={args.slo_p99_ms:g}ms (burn-rate fed to "
                "the degradation ladder)" if args.slo_p99_ms else "")
    prof_mode = ("" if not args.no_profiler
                 else ", profiler OFF (no phase/MFU attribution)")
    mask_on = getattr(decoder, "maskpool", None) is not None
    stream_mode = (", SSE streaming + constrained decoding"
                   + (f" ({args.mask_rows} device mask rows)"
                      if mask_on else " (host-only grammar masks)"))
    # slots and chunk as the ENGINE holds them, not as the flags asked
    gen_mode = (f"; /generate: {getattr(decoder, 'n_slots', 0)} slots, "
                f"prefill chunk "
                f"{max(getattr(decoder, 'prefill_buckets', None) or [1])}"
                + kv_mode
                + stream_mode + spec_mode + mesh_mode
                + (f", supervised (hang timeout {args.hang_timeout}s, "
                   f"retry budget {args.retry_budget})"
                   if not args.no_supervise else ", UNSUPERVISED")
                + slo_mode + prof_mode
                if args.generate else "")
    chaos = (f"; failpoints ARMED: {', '.join(armed)}" if armed else "")
    dev = jax.devices()[0]
    print(f"Serving {args.model} ({mode}, {batch_mode}{gen_mode}{chaos}) "
          f"on {len(jax.devices())} x {dev.platform} '{dev.device_kind}' "
          f"at http://127.0.0.1:{server.port} "
          "(POST /predict, /predict/csv"
          + (", /generate" if args.generate else "")
          + (", /admin/drain" if args.generate and not args.no_supervise
             else "")
          + "; GET /health, /healthz, /readyz, /info, /metrics"
          + (f", /trace[{args.trace_buffer} events]"
             if args.trace_buffer else "") + ")")
    if args.once:  # test hook: start, report, stop
        server.stop()
        return 0
    # SIGINT and SIGTERM both mean "stop cleanly, exit 0". Installed
    # explicitly: a process started with SIGINT ignored (a background
    # job of a non-interactive shell) never sees KeyboardInterrupt.
    stop = threading.Event()
    for sig in (signal.SIGINT, signal.SIGTERM):
        signal.signal(sig, lambda _sig, _frm: stop.set())
    stop.wait()
    server.stop()
    return 0


def cmd_telemetry(args) -> int:
    """Fleet telemetry plane (serving/telemetry.py): tail N replicas'
    flight recorders into one merged Perfetto waterfall and federate
    their /metrics into one fleet exposition."""
    from ..serving import telemetry

    argv = ["--targets", args.targets,
            "--interval", str(args.interval),
            "--clock-probes", str(args.clock_probes)]
    if args.out:
        argv += ["--out", args.out]
    if args.serve_port is not None:
        argv += ["--serve-port", str(args.serve_port)]
    if args.duration is not None:
        argv += ["--duration", str(args.duration)]
    if args.ui:
        argv += ["--ui", args.ui]
    return telemetry.main(argv)


def cmd_router(args) -> int:
    """Fleet front-end (serving/router.py): journaled, prefix-affine
    routing over N replica processes, with quorum readiness and
    SLO-aware admission."""
    from ..serving import router

    argv = []
    if args.replicas:
        argv += ["--replicas", args.replicas]
    if args.spawn:
        argv += ["--spawn", str(args.spawn)]
        rargs = (["--model", args.model] if args.model else [])
        rargs += list(args.replica_arg or [])
        # the = form: a forwarded fragment may itself start with --,
        # which argparse would otherwise read as the next option
        argv += [f"--replica-arg={ra}" for ra in rargs]
    if args.journal:
        argv += ["--journal", args.journal]
    argv += ["--port", str(args.port),
             "--kv-block", str(args.kv_block),
             "--affinity-blocks", str(args.affinity_blocks),
             "--quorum", str(args.quorum)]
    if args.paged_kernel is not None:
        argv += ["--paged-kernel", args.paged_kernel]
    if args.no_admission:
        argv += ["--no-admission"]
    if args.no_prefix_directory:
        argv += ["--no-prefix-directory"]
    if args.prefix_fetch:
        argv += ["--prefix-fetch"]
    return router.main(argv)


def _add_data_args(p: argparse.ArgumentParser):
    p.add_argument("--input", required=True, help="input CSV path")
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--label-index", type=int, default=-1,
                   help="label column (-1 = last)")
    p.add_argument("--num-classes", type=int, default=None)
    p.add_argument("--regression", action="store_true")
    p.add_argument("--skip-lines", type=int, default=0)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        prog="dl4j-tpu",
        description="TPU-native deep learning CLI (train/test/predict)")
    sub = parser.add_subparsers(dest="command", required=True)

    t = sub.add_parser("train", help="train a model from a JSON configuration")
    t.add_argument("--conf", required=True, help="MultiLayerConfiguration JSON")
    t.add_argument("--output", required=True, help="output model zip")
    t.add_argument("--epochs", type=int, default=1)
    t.add_argument("--print-every", type=int, default=10)
    t.add_argument("--runtime", choices=["local", "data-parallel"],
                   default="local")
    _add_data_args(t)
    t.set_defaults(func=cmd_train)

    e = sub.add_parser("test", help="evaluate a saved model")
    e.add_argument("--model", required=True)
    _add_data_args(e)
    e.set_defaults(func=cmd_test)

    p = sub.add_parser("predict", help="predict with a saved model")
    p.add_argument("--model", required=True)
    p.add_argument("--output", default=None)
    _add_data_args(p)
    p.set_defaults(func=cmd_predict)

    s = sub.add_parser("serve", help="serve a saved model over HTTP")
    s.add_argument("--model", required=True)
    s.add_argument("--port", type=int, default=0)
    s.add_argument("--max-batch", type=int, default=1024)
    s.add_argument("--int8", action="store_true",
                   help="serve the int8 quantized program (the model zip "
                        "must come from save_quantized)")
    s.add_argument("--no-batching", action="store_true",
                   help="disable continuous micro-batching (fall back to "
                        "the lock-serialized direct path)")
    s.add_argument("--batch-window-ms", type=float, default=2.0,
                   help="how long the collator waits for more requests "
                        "after the first arrival (latency/occupancy knob)")
    s.add_argument("--queue-size", type=int, default=256,
                   help="bounded request queue; beyond it requests get "
                        "HTTP 503 (backpressure)")
    s.add_argument("--timeout-ms", type=float, default=None,
                   help="default per-request deadline; expired requests "
                        "get HTTP 504 (clients can override per request "
                        "with ?timeout_ms=)")
    s.add_argument("--generate", action="store_true",
                   help="expose POST /generate backed by the continuous-"
                        "batching decode scheduler (chunked prefill)")
    s.add_argument("--vocab-size", type=int, default=None,
                   help="LM vocabulary for /generate (default: inferred "
                        "from the model's output layer width)")
    s.add_argument("--decode-slots", type=int, default=4,
                   help="concurrent decode slots for /generate")
    s.add_argument("--prefill-chunk", type=int, default=64,
                   help="max prompt tokens prefilled per engine step "
                        "(pow2 chunk buckets; TTFT/decode-latency knob; "
                        "<=1 = token-by-token prefill)")
    s.add_argument("--kv-pool-mb", type=float, default=0.0,
                   help="byte budget (MiB) for the PAGED live-decode KV "
                        "pool: all slots share one block pool (capacity "
                        "is pool bytes, not slots x max_cache_len), "
                        "prefix restore is a zero-copy block-table "
                        "remap, and cold slots preempt-and-resume under "
                        "pressure (0 = contiguous per-slot caches, no "
                        "prefix cache)")
    s.add_argument("--tp", type=int, default=0,
                   help="shard the decode engine tensor-parallel over N "
                        "devices (attention heads/FFN split over a 'tp' "
                        "mesh axis, KV pool sharded by head — pool "
                        "budgets become per-device bytes; 0/1 = single "
                        "device; CPU test meshes via XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    s.add_argument("--kv-block", type=int, default=16,
                   help="positions per KV pool block (only full "
                        "blocks of a prompt are shared)")
    s.add_argument("--host-cache-mb", type=float, default=0.0,
                   help="hierarchical KV tiering (paged mode only): "
                        "evicted-but-unreferenced prefix blocks demote "
                        "to an int8-quantized host-RAM ring of this "
                        "byte budget (MiB) instead of vanishing, and "
                        "promote back by zero-copy table remap on the "
                        "next hit (0 = tiering off)")
    s.add_argument("--disk-cache-mb", type=float, default=0.0,
                   help="disk tier below the host ring: blocks the "
                        "host budget evicts land in CRC-framed files "
                        "under --tier-dir (needs --host-cache-mb)")
    s.add_argument("--tier-dir", default=None,
                   help="directory for disk-tier block files (default: "
                        "a fresh tempdir)")
    s.add_argument("--kv-dtype", choices=["int8"], default=None,
                   help="quantize the PAGED KV pool's pages to int8 "
                        "(per-row max-abs scales; less than half the "
                        "bytes per block, so the same --kv-pool-mb "
                        "holds 2x+ the blocks; paged mode only)")
    s.add_argument("--paged-kernel", choices=["auto", "on", "off"],
                   default="auto",
                   help="fused Pallas paged-decode kernel (paged mode "
                        "only): 'auto' lets the per-shape autotune pick "
                        "kernel vs XLA gather per decode bucket, 'on' "
                        "forces the kernel, 'off' pins the XLA gather; "
                        "outputs are token-identical either way "
                        "(docs/serving.md 'Fused decode kernel')")
    s.add_argument("--mask-rows", type=int, default=64,
                   help="device rows of the grammar mask table backing "
                        "constrained decoding (/generate 'grammar': "
                        "JSON-schema / trie DFAs compiled to per-state "
                        "token masks; row 0 reserved admit-all; <=1 "
                        "falls back to host-only masking)")
    s.add_argument("--speculate", type=int, default=0, metavar="GAMMA",
                   help="speculative decoding: draft GAMMA tokens per "
                        "slot per iteration with a shallow-exit draft "
                        "and verify them in one multi-token forward — "
                        "output stays token-identical to GAMMA=0 by "
                        "construction (0 = off)")
    s.add_argument("--draft-blocks", type=int, default=0, metavar="K",
                   help="transformer blocks the self-speculative draft "
                        "runs before early-exiting through the output "
                        "head (default: half the model's blocks)")
    s.add_argument("--trace-buffer", type=int, default=8192,
                   help="span flight-recorder ring capacity (events) "
                        "backing GET /trace and per-request timings; "
                        "0 disables request-lifecycle tracing")
    s.add_argument("--no-supervise", action="store_true",
                   help="run the decode engine WITHOUT the crash-"
                        "recovery supervisor (no watchdog, no engine "
                        "restarts, no /readyz gating, no /admin/drain)")
    s.add_argument("--hang-timeout", type=float, default=5.0,
                   help="watchdog heartbeat staleness (seconds) that "
                        "declares the scheduler loop hung and triggers "
                        "an engine restart; set well above your "
                        "model's worst single-iteration time")
    s.add_argument("--retry-budget", type=int, default=3,
                   help="submissions allowed per request across engine "
                        "crashes before it fails with a structured 503")
    s.add_argument("--slo-p99-ms", type=float, default=None,
                   help="p99 latency objective (ms) for the SLO monitor: "
                        "per-route sliding-window percentiles + fast/"
                        "slow-window burn rates on /metrics, and a "
                        "sustained burn escalates the degradation "
                        "ladder alongside queue pressure (default: "
                        "track percentiles only, never escalate)")
    s.add_argument("--no-profiler", action="store_true",
                   help="disarm the step-phase profiler + cost "
                        "attribution (no per-phase step decomposition, "
                        "no FLOPs/MFU gauges; <=5%% overhead when on, "
                        "bench-gated)")
    s.add_argument("--failpoint", action="append", metavar="NAME=SPEC",
                   help="arm a chaos seam, e.g. "
                        "dispatch.decode=crash@n:3 or "
                        "scheduler.iteration=hang:500@p:0.01:42 "
                        "(repeatable; see inference/failpoints.py)")
    s.add_argument("--failpoint-endpoint", action="store_true",
                   help="TEST ONLY: expose POST /admin/failpoints so "
                        "clients can arm/disarm chaos seams over HTTP")
    s.add_argument("--once", action="store_true",
                   help="start and immediately stop (smoke test)")
    s.set_defaults(func=cmd_serve)

    f = sub.add_parser("telemetry",
                       help="fleet telemetry: merge N replicas' traces "
                            "into one Perfetto waterfall and federate "
                            "their metrics/SLO")
    f.add_argument("--targets", required=True,
                   help="comma-separated replica base URLs")
    f.add_argument("--out", default=None,
                   help="write the merged Perfetto trace here at exit")
    f.add_argument("--serve-port", type=int, default=None,
                   help="expose GET /fleet, /fleet/summary, "
                        "/fleet/trace")
    f.add_argument("--interval", type=float, default=1.0,
                   help="poll/scrape cadence, seconds")
    f.add_argument("--duration", type=float, default=None,
                   help="run this long then exit")
    f.add_argument("--clock-probes", type=int, default=5,
                   help="RTT-bounded /trace/clock probes per replica")
    f.add_argument("--ui", default=None,
                   help="training-UI base URL for the /serving fleet "
                        "line")
    f.set_defaults(func=cmd_telemetry)

    r = sub.add_parser("router",
                       help="fleet front-end: journaled, prefix-affine "
                            "routing over N engine replica processes")
    r.add_argument("--replicas", default=None,
                   help="attach to running replicas (comma-separated "
                        "base URLs)")
    r.add_argument("--spawn", type=int, default=0,
                   help="spawn N replica subprocesses serving --model")
    r.add_argument("--model", default=None,
                   help="model zip every spawned replica serves")
    r.add_argument("--replica-arg", action="append", default=[],
                   help="extra argv forwarded to every spawned replica "
                        "(repeatable; see python -m "
                        "deeplearning4j_tpu.serving.replica --help)")
    r.add_argument("--journal", default=None,
                   help="durable request-journal path (a SIGKILLed "
                        "router replays in-flight requests from it)")
    r.add_argument("--port", type=int, default=0)
    r.add_argument("--quorum", type=int, default=1,
                   help="/readyz answers 200 only with >= this many "
                        "ready replicas")
    r.add_argument("--kv-block", type=int, default=16,
                   help="the replicas' KV block size (the affinity "
                        "hash aligns to it)")
    r.add_argument("--paged-kernel", choices=["auto", "on", "off"],
                   default=None,
                   help="fused-decode-kernel mode forwarded to every "
                        "spawned replica (replicas default to 'auto')")
    r.add_argument("--affinity-blocks", type=int, default=1,
                   help="how many leading prompt blocks the affinity "
                        "hash covers")
    r.add_argument("--no-admission", action="store_true",
                   help="disable SLO-aware admission (route even while "
                        "the fleet burns)")
    r.add_argument("--no-prefix-directory", action="store_true",
                   help="stop tailing replica /prefix/directory feeds "
                        "(affinity-only routing)")
    r.add_argument("--prefix-fetch", action="store_true",
                   help="keep rendezvous placement and have the target "
                        "pull tiered prefix chains from the holding "
                        "peer instead of re-routing to it")
    r.set_defaults(func=cmd_router)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.func not in (cmd_router, cmd_telemetry):
        # every other subcommand compiles; the router and the telemetry
        # collector run no program of their own
        from ..util.compile_cache import enable_compile_cache
        enable_compile_cache()
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
