#!/usr/bin/env python3
"""chip_smoke.py — train -> save -> serve on the TPU, through the entry
points a user calls. The quickest proof that the system still starts on
the chip; it is not a benchmark and prints no rate.

    python3 chip_smoke.py                # one chip; exits 0 only on a TPU
    python3 chip_smoke.py --devices 4    # the four-chip host checks
    python3 chip_smoke.py --rehearse-cpu # tiny CPU rehearsal (tests)

One model, the zoo's decoder-only LM at d512 x 4 blocks, head width 128:

  train   ComputationGraph.fit(iterator), bfloat16, B=32 T=256, then two
          steps at T=8192 with the Pallas attention seam armed (flash
          kernel autotune); save a bfloat16 zip and a float32 zip of the
          same master weights; solo-decode reference tokens.
  serve   `python -m deeplearning4j_tpu.cli.main serve --generate` as its
          own process, over HTTP: health, concurrent /generate, /info,
          /metrics, /debug/engine, SIGINT -> exit 0. Three launches:
          bfloat16 (8 slots x 1024 positions), float32 --paged-kernel on,
          float32 --paged-kernel off (token-compared).
  --devices 4 adds: data-parallel training on a 4-device mesh (loss
          against the one-chip run), `serve --tp 4`, `router --spawn 4`
          with one chip per replica, and `--spawn 5` refused at launch.

ONE PROCESS PER CHIP. This parent imports the standard library only and
never touches JAX; every phase that needs the chip is a child process,
one after another, never two at once (the fleet's replicas each get a
chip of their own). A child's failure prints the tail of its log and
fails the run. Without a TPU every child refuses to start; the CPU
rehearsal exists only behind --rehearse-cpu and labels itself.

Standard output carries one JSON line per phase, then a summary line
(set-up and work seconds per phase, "claim": null), and as its LAST line
exactly {"ok": true, "device": {"platform": ..., "kind": ..., "count":
...}} with the device as JAX reported it to the first child. A failed run
exits non-zero; its last line says "ok": false if a phase had already run
on the device, and without a device nothing is printed at all.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# vocab is a power of two so that next = (5 * tok + 3) % vocab is a
# full-period map: a language the model learns in a few dozen steps,
# which keeps greedy margins far above the dtype's rounding noise.
FULL = dict(vocab=128, d_model=512, n_heads=4, n_blocks=4,
            batch=32, seq=256, scans=4, long_seq=8192,
            slots=8, positions=1024, kernel_positions=256,
            kv_block=16, prefill_chunk=128,
            prompts=(64, 128, 256, 384, 512), kernel_prompts=(64, 96, 160),
            new_tokens=64, solo=2, dp_steps=32)
TINY = dict(vocab=32, d_model=32, n_heads=4, n_blocks=1,
            batch=4, seq=16, scans=2, long_seq=64,
            slots=2, positions=32, kernel_positions=32,
            kv_block=8, prefill_chunk=16,
            prompts=(8, 12, 20), kernel_prompts=(8, 12),
            new_tokens=6, solo=2, dp_steps=4)
SCAN_BATCHES = 16  # nn/graph.py ComputationGraph.scan_batches
# 4-device vs 1-device loss at the same global batch, every step: bfloat16
# compute with another reduction order (the loss falls from ~5 to ~0.01)
DP_LOSS_RTOL, DP_LOSS_ATOL = 0.05, 0.02


class SmokeFailure(Exception):
    pass


def chain(start: int, n: int, vocab: int) -> list:
    out, tok = [], start % vocab
    for _ in range(n):
        out.append(tok)
        tok = (5 * tok + 3) % vocab
    return out


def prompt_for(i: int, length: int, vocab: int) -> list:
    return chain(7 * i + 1, length, vocab)


def pool_mb(cfg: dict, positions: int, itemsize: int, tp: int = 1) -> float:
    """--kv-pool-mb for `slots x positions` cache positions (KVPool:
    (blocks + 1) * bytes_per_block <= budget; per device under tp)."""
    blocks = cfg["slots"] * positions // cfg["kv_block"]
    per_block = (cfg["kv_block"] * cfg["d_model"] * 2 * itemsize
                 * cfg["n_blocks"]) // tp
    return (blocks + 2) * per_block / float(1 << 20)


# ===========================================================================
# children — the only code here that imports JAX
# ===========================================================================

def _device_or_die(rehearse: bool, need: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    want = "cpu" if rehearse else "tpu"
    if dev["platform"] != want:
        sys.exit(f"chip_smoke: no TPU — jax.devices()[0].platform is "
                 f"{dev['platform']!r}, this run needs {want!r}"
                 + ("" if rehearse else
                    " (the CPU rehearsal is --rehearse-cpu)"))
    if dev["count"] < need:
        sys.exit(f"chip_smoke: {need} {want} device(s) needed, "
                 f"{dev['count']} visible")
    return dev


def _lm(cfg: dict, dtype: str):
    from deeplearning4j_tpu.models.zoo import transformer_lm
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    return ComputationGraph(transformer_lm(
        vocab_size=cfg["vocab"], d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], n_blocks=cfg["n_blocks"],
        dtype=dtype)).init()


def _batch(cfg: dict, batch: int, seq: int):
    """One fixed next-token batch of the chain language, one-hot."""
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    ids = np.asarray([chain(11 * r + 2, seq + 1, cfg["vocab"])
                      for r in range(batch)])
    eye = np.eye(cfg["vocab"], dtype=np.float32)
    return DataSet(eye[ids[:, :-1]], eye[ids[:, 1:]])


def _score_listener():
    from deeplearning4j_tpu.optimize.listeners import IterationListener

    class Scores(IterationListener):
        def __init__(self):
            self.rows = []  # (iteration, loss, monotonic seconds)

        def iteration_done(self, model, iteration):
            self.rows.append((int(iteration), float(model.score_),
                              time.monotonic()))
    return Scores()


def child_train(cfg: dict, out: str, rehearse: bool, need: int) -> dict:
    t_start = time.monotonic()
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import numpy as np
    dev = _device_or_die(rehearse, need)
    from deeplearning4j_tpu.datasets.iterators import (
        ListDataSetIterator, MultipleEpochsIterator)
    from deeplearning4j_tpu.models.sampling import generate_transformer
    from deeplearning4j_tpu.ops import pallas_kernels
    from deeplearning4j_tpu.util import model_serializer

    net = _lm(cfg, "bfloat16")
    scores = _score_listener()
    net.set_listeners(scores)
    n_batches = cfg["scans"] * SCAN_BATCHES
    ds = _batch(cfg, cfg["batch"], cfg["seq"])
    net.fit(MultipleEpochsIterator(
        n_batches, ListDataSetIterator(ds, batch=cfg["batch"])))
    losses = [r[1] for r in scores.rows]
    if len(losses) != n_batches:
        raise SmokeFailure(f"fit(iterator) ran {len(losses)} of "
                           f"{n_batches} steps")
    if not all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise SmokeFailure(f"loss did not fall: first {losses[0]} "
                           f"last {losses[-1]}")
    # the first scan dispatch carries the compile; the rest is work
    t_first = scores.rows[SCAN_BATCHES - 1][2]
    result = {
        "device": dev, "compile_cache_dir": cache_dir,
        "steps": n_batches, "loss_first": losses[0],
        "loss_last": losses[-1], "losses": losses,
        "setup_s": round(t_first - t_start, 2),
        "work_s": round(scores.rows[-1][2] - t_first, 2),
    }

    # -- long context: the attention seam probes and compiles the flash
    # kernel (compiled on the TPU, XLA default under the interpreter)
    net.set_listeners()
    pallas_kernels.enable()
    try:
        long_ds = _batch(cfg, 1, cfg["long_seq"])
        t0 = time.monotonic()
        net.fit(long_ds)
        loss_a = float(net.score_)
        t1 = time.monotonic()
        net.fit(long_ds)
        loss_b = float(net.score_)
        t2 = time.monotonic()
        execution = pallas_kernels.kernel_execution()
        decisions = {"/".join(map(str, k[1:])): v for k, v in
                     pallas_kernels.autotune_decisions().items()
                     if k[0] == "attention"}
        refused = {"/".join(map(str, k[1:])): v for k, v in
                   pallas_kernels.autotune_refusals().items()
                   if k[0] == "attention"}
    finally:
        pallas_kernels.disable()
    if not (np.isfinite(loss_a) and np.isfinite(loss_b)):
        raise SmokeFailure(f"T={cfg['long_seq']} loss not finite: "
                           f"{loss_a}, {loss_b}")
    long_key = "/".join(map(str, (
        1, cfg["long_seq"], cfg["n_heads"],
        cfg["d_model"] // cfg["n_heads"], "bfloat16", True)))
    result["long"] = {
        "seq": cfg["long_seq"], "loss": [loss_a, loss_b],
        "kernel_execution": execution,
        "autotune_decision": decisions.get(long_key),
        "autotune_refused": refused.get(long_key, {}),
        "setup_s": round(t1 - t0, 2), "work_s": round(t2 - t1, 2),
    }
    if not rehearse:
        if execution != "compiled":
            raise SmokeFailure("Pallas kernels are not compiled on the TPU")
        if long_key not in decisions:
            raise SmokeFailure(f"no attention autotune decision for "
                               f"{long_key}: {decisions}")
        tried = {"512", "1024", "splash"}
        if tried <= set(refused.get(long_key, {})):
            raise SmokeFailure(f"every flash/splash candidate was refused "
                               f"at T={cfg['long_seq']}: {refused}")
    print(f"attention autotune at T={cfg['long_seq']}: decision="
          f"{decisions.get(long_key)!r} ({execution}); refused="
          f"{refused.get(long_key, {})}", flush=True)

    # -- save: the bfloat16 model, and the same float32 master weights
    # under a float32 compute dtype (the paged-decode kernel's dtype)
    model_serializer.write_model(net, os.path.join(out, "lm_bf16.zip"),
                                 save_updater=False)
    net32 = _lm(cfg, "float32")
    net32.set_params_flat(net.params_flat())
    model_serializer.write_model(net32, os.path.join(out, "lm_f32.zip"),
                                 save_updater=False)

    # -- solo-decode reference tokens (the identity tests assert on CPU:
    # engine output == generate_transformer(use_cache=True))
    solo = {"bf16": [], "f32": []}
    for name, ref_net, lengths in (("bf16", net, cfg["prompts"]),
                                   ("f32", net32, cfg["kernel_prompts"])):
        for i, n in enumerate(lengths[:cfg["solo"]]):
            solo[name].append([int(t) for t in generate_transformer(
                ref_net, prompt_for(i, n, cfg["vocab"]),
                cfg["new_tokens"], cfg["vocab"], use_cache=True)])
    result["solo_tokens"] = solo
    return result


def child_train_dp(cfg: dict, out: str, rehearse: bool, need: int) -> dict:
    t_start = time.monotonic()
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    import numpy as np
    dev = _device_or_die(rehearse, need)
    from deeplearning4j_tpu.datasets.iterators import (
        ListDataSetIterator, MultipleEpochsIterator)
    from deeplearning4j_tpu.parallel.mesh import default_mesh
    from deeplearning4j_tpu.parallel.trainer import (
        IciDataParallelTrainingMaster)

    net = _lm(cfg, "bfloat16")
    scores = _score_listener()
    net.set_listeners(scores)
    mesh = default_mesh(need)
    ds = _batch(cfg, cfg["batch"], cfg["seq"])
    IciDataParallelTrainingMaster(mesh=mesh).execute_training(
        net, MultipleEpochsIterator(
            cfg["dp_steps"], ListDataSetIterator(ds, batch=cfg["batch"])))
    losses = [r[1] for r in scores.rows]
    if len(losses) != cfg["dp_steps"] or not all(np.isfinite(losses)) \
            or not losses[-1] < losses[0]:
        raise SmokeFailure(f"data-parallel loss did not fall: {losses}")
    # the work really spans `need` devices: the replicated parameters
    # and a batch put the trainer's way each have one shard per device,
    # and every device holds live bytes
    leaf = jax.tree_util.tree_leaves(net.params)[0]
    param_devs = sorted(s.device.id for s in leaf.addressable_shards)
    from jax.sharding import NamedSharding, PartitionSpec
    xb = jax.device_put(np.asarray(ds.features),
                        NamedSharding(mesh, PartitionSpec("data")))
    batch_devs = sorted(s.device.id for s in xb.addressable_shards)
    shard_rows = sorted({s.data.shape[0] for s in xb.addressable_shards})
    if len(set(param_devs)) != need or len(set(batch_devs)) != need \
            or shard_rows != [cfg["batch"] // need]:
        raise SmokeFailure(f"work is not on {need} devices: params on "
                           f"{param_devs}, batch on {batch_devs} in rows "
                           f"of {shard_rows}")
    in_use = {}
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        in_use[str(d.id)] = int(stats.get("bytes_in_use", 0))
    if not rehearse and not all(v > 0 for v in in_use.values()):
        raise SmokeFailure(f"a device holds no bytes: {in_use}")
    t_first = scores.rows[0][2]
    return {
        "device": dev, "mesh_devices": need,
        "steps": cfg["dp_steps"], "loss_first": losses[0],
        "loss_last": losses[-1], "losses": losses,
        "param_shard_devices": param_devs,
        "batch_shard_devices": batch_devs,
        "batch_rows_per_device": shard_rows[0],
        "bytes_in_use": in_use,
        "setup_s": round(t_first - t_start, 2),
        "work_s": round(scores.rows[-1][2] - t_first, 2),
    }


def run_child(args) -> int:
    cfg = TINY if args.rehearse_cpu else FULL
    fn = {"train": child_train, "train_dp": child_train_dp}[args.child]
    result = fn(cfg, args.out, args.rehearse_cpu, args.devices)
    result["rehearsal"] = bool(args.rehearse_cpu)
    with open(os.path.join(args.out, f"{args.child}.json"), "w") as fh:
        json.dump(result, fh)
    return 0


# ===========================================================================
# parent — standard library only
# ===========================================================================

def http(url: str, body=None, timeout: float = 300.0):
    """(status, parsed JSON body) — error statuses parsed, not raised."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read().decode())
    except urllib.error.HTTPError as e:
        with e:
            raw = e.read().decode(errors="replace")
        try:
            return e.code, json.loads(raw)
        except ValueError:
            return e.code, {"raw": raw}


def tail(path: str, n: int = 4000) -> str:
    try:
        with open(path, "rb") as fh:
            fh.seek(0, os.SEEK_END)
            fh.seek(max(0, fh.tell() - n))
            return fh.read().decode(errors="replace")
    except OSError as e:
        return f"<no log: {e}>"


class Run:
    """One smoke run: its directory, its children's environment, the
    processes it started (all stopped on the way out) and its results."""

    def __init__(self, args):
        self.rehearse = bool(args.rehearse_cpu)
        self.devices = int(args.devices)
        self.cfg = TINY if self.rehearse else FULL
        self.out = os.path.abspath(args.out)
        shutil.rmtree(self.out, ignore_errors=True)
        os.makedirs(self.out)
        self.env = dict(os.environ)
        self.env["PYTHONUNBUFFERED"] = "1"
        self.env["PYTHONPATH"] = HERE + os.pathsep + self.env.get(
            "PYTHONPATH", "")
        if self.rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
            flags = [f for f in self.env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f]
            flags.append("--xla_force_host_platform_device_count="
                         f"{self.devices}")
            self.env["XLA_FLAGS"] = " ".join(flags)
        self.live: list = []
        self.phases: dict = {}
        self.device = None

    def log(self, msg: str) -> None:
        print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)

    def record(self, name: str, result: dict) -> None:
        dev = result.get("device")
        want = "cpu" if self.rehearse else "tpu"
        if not dev or dev.get("platform") != want or not dev.get("kind") \
                or int(dev.get("count", 0)) < 1:
            raise SmokeFailure(f"phase {name} did not say it ran on "
                               f"{want}: device={dev}")
        self.device = self.device or dev
        self.phases[name] = result
        print(json.dumps({"phase": name, **{
            k: v for k, v in result.items()
            if k not in ("tokens", "losses", "solo_tokens")}}), flush=True)

    def spawn(self, name: str, argv: list) -> "Proc":
        proc = Proc(self, name, argv)
        self.live.append(proc)
        return proc

    def child(self, name: str, timeout: float) -> dict:
        """Run one JAX child of this file to completion."""
        argv = [sys.executable, os.path.abspath(__file__), "--child", name,
                "--out", self.out, "--devices", str(self.devices)]
        if self.rehearse:
            argv.append("--rehearse-cpu")
        proc = self.spawn(name, argv)
        rc = proc.wait(timeout)
        if rc != 0:
            raise SmokeFailure(f"child {name} exited {rc}\n--- log tail "
                               f"---\n{tail(proc.log_path)}")
        with open(os.path.join(self.out, f"{name}.json")) as fh:
            result = json.load(fh)
        self.record(name, result)
        return result

    def stop_all(self) -> None:
        for proc in self.live:
            proc.kill()


class Proc:
    def __init__(self, run: Run, name: str, argv: list):
        self.run, self.name = run, name
        self.log_path = os.path.join(run.out, f"{name}.log")
        self.t0 = time.monotonic()
        with open(self.log_path, "wb") as log:
            # its own session: a fleet's replicas die with their router
            self.popen = subprocess.Popen(
                argv, stdout=log, stderr=subprocess.STDOUT, env=run.env,
                cwd=HERE, start_new_session=True)

    def wait(self, timeout: float) -> int:
        try:
            return self.popen.wait(timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name} still running after {timeout:.0f}s\n--- log "
                f"tail ---\n{tail(self.log_path)}") from None

    def await_line(self, pattern: str, timeout: float):
        """Block until the log shows `pattern`; fail if the process ends
        or the time runs out first."""
        deadline = time.monotonic() + timeout
        rx = re.compile(pattern)
        while True:
            with open(self.log_path, "r", errors="replace") as fh:
                m = rx.search(fh.read())
            if m:
                return m
            rc = self.popen.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"{self.name} exited {rc} before its banner\n--- log "
                    f"tail ---\n{tail(self.log_path)}")
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    f"{self.name}: no banner after {timeout:.0f}s\n--- log "
                    f"tail ---\n{tail(self.log_path)}")
            time.sleep(0.2)

    def interrupt(self, sig=signal.SIGINT, timeout: float = 120.0) -> None:
        self.popen.send_signal(sig)
        rc = self.wait(timeout)
        if rc != 0:
            raise SmokeFailure(
                f"{self.name} exited {rc} on {sig.name}, expected 0\n--- "
                f"log tail ---\n{tail(self.log_path)}")

    def kill(self) -> None:
        if self.popen.poll() is None:
            try:  # the whole session: a router's replicas go with it
                os.killpg(self.popen.pid, signal.SIGKILL)
            except OSError:
                self.popen.kill()
            self.popen.wait(30)


def counter(metrics: dict, name: str) -> float:
    """A counter out of the JSON /metrics snapshot, 0 when never bumped."""
    for section in metrics.values():
        if isinstance(section, dict) and name in section:
            v = section[name]
            return float(v["value"] if isinstance(v, dict) else v)
    return 0.0


def generate_all(base: str, prompts: list, new_tokens: int) -> list:
    """POST every prompt at once; the first one twice more afterwards."""
    out = [None] * len(prompts)

    def one(i):
        try:
            out[i] = http(base + "/generate", {
                "prompt": prompts[i], "max_new_tokens": new_tokens,
                "temperature": 0.0})
        except OSError as e:  # a thread's exception would be lost
            out[i] = (0, {"error": repr(e)})

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    repeats = [http(base + "/generate", {
        "prompt": prompts[0], "max_new_tokens": new_tokens,
        "temperature": 0.0}) for _ in range(2)]
    return out + repeats


def serve_phase(run: Run, name: str, model: str, *, positions: int,
                itemsize: int, prompt_lengths, kernel: str = None,
                tp: int = 0, solo=None) -> dict:
    """One `cli.main serve --generate` launch, driven over HTTP and
    judged by the server's own instruments."""
    cfg = run.cfg
    mb = pool_mb(cfg, positions, itemsize, max(tp, 1))
    argv = [sys.executable, "-m", "deeplearning4j_tpu.cli.main", "serve",
            "--model", os.path.join(run.out, model), "--generate",
            "--port", "0", "--kv-pool-mb", f"{mb:.4f}",
            "--kv-block", str(cfg["kv_block"]),
            "--prefill-chunk", str(cfg["prefill_chunk"]),
            "--decode-slots", str(cfg["slots"])]
    if kernel:
        argv += ["--paged-kernel", kernel]
    if tp:
        argv += ["--tp", str(tp)]
    run.log(f"{name}: " + " ".join(argv[2:]))
    proc = run.spawn(name, argv)
    m = proc.await_line(r"Serving .* at http://127\.0\.0\.1:(\d+) ",
                        timeout=900)
    setup_s = time.monotonic() - proc.t0
    banner = m.group(0)
    base = f"http://127.0.0.1:{m.group(1)}"
    # the engine turns a request it cannot honour (paged pool, tensor
    # parallelism, int8 KV, ...) into a RuntimeWarning and carries on
    with open(proc.log_path, errors="replace") as fh:
        warned = re.findall(
            r"RuntimeWarning: (.*(?:DISABLED|disabled|ignored|did not "
            r"engage).*)", fh.read())
    if warned:
        raise SmokeFailure(f"{name}: the engine downgraded a request: "
                           f"{warned}")

    def check(cond, what):
        if not cond:
            raise SmokeFailure(f"{name}: {what}\n--- log tail ---\n"
                               f"{tail(proc.log_path, 1500)}")

    code, body = http(base + "/healthz")
    check(code == 200, f"/healthz {code} {body}")
    code, body = http(base + "/readyz")
    check(code == 200 and body.get("ready"), f"/readyz {code} {body}")
    code, info = http(base + "/info")
    check(code == 200, f"/info {code}")
    want = "cpu" if run.rehearse else "tpu"
    check(info.get("platform") == want and info.get("device_kind"),
          f"/info says platform={info.get('platform')!r} "
          f"device_kind={info.get('device_kind')!r}, wanted {want}")
    check(info["mesh"]["tp"] == max(tp, 1),
          f"/info tp={info['mesh']['tp']}, asked {max(tp, 1)}")
    if tp:
        check("tensor-parallel over" in banner, f"banner lacks tp: {banner}")
    code, before = http(base + "/debug/engine")
    check(code == 200, f"/debug/engine {code}")
    blocks = cfg["slots"] * positions // cfg["kv_block"]
    check(before["paged"] and before["n_slots"] == cfg["slots"]
          and max(before["prefill_buckets"]) == cfg["prefill_chunk"]
          and before["pool"]["capacity_blocks"] >= blocks
          and before["pool"]["block_positions"] == cfg["kv_block"],
          f"engine state is not what was asked: paged={before['paged']} "
          f"slots={before['n_slots']} chunks={before['prefill_buckets']} "
          f"pool={before.get('pool')}")
    pk = before["paged_kernel"]
    if kernel == "on":
        check(pk["engaged"] and all(pk["buckets"].values()),
              f"--paged-kernel on did not engage every bucket: {pk}")
        check(pk["execution"] == ("interpreted" if run.rehearse
                                  else "compiled"),
              f"kernel execution is {pk['execution']!r}")
    elif kernel == "off":
        check(not pk["engaged"], f"--paged-kernel off engaged: {pk}")

    prompts = [prompt_for(i, n, cfg["vocab"])
               for i, n in enumerate(prompt_lengths)]
    t0 = time.monotonic()
    answers = generate_all(base, prompts, cfg["new_tokens"])
    work_s = time.monotonic() - t0
    tokens = []
    for code, ans in answers:
        check(code == 200, f"/generate {code} {ans}")
        check("retries" not in ans, f"a request was retried: {ans}")
        toks = ans["tokens"]
        check(len(toks) == cfg["new_tokens"]
              and all(isinstance(t, int) and 0 <= t < cfg["vocab"]
                      for t in toks), f"bad tokens {toks}")
        tokens.append(toks)
    check(tokens[0] == tokens[-1] == tokens[-2],
          f"the repeated prompt changed its answer: {tokens[0]} / "
          f"{tokens[-2]} / {tokens[-1]}")
    if solo is not None:
        for i, ref in enumerate(solo):
            check(tokens[i] == ref,
                  f"prompt {i}: server tokens differ from solo decode\n"
                  f"server {tokens[i]}\nsolo   {ref}")

    code, metrics = http(base + "/metrics")
    check(code == 200, f"/metrics {code}")
    check(counter(metrics, "engine_restarts_total") == 0,
          "engine_restarts_total != 0")
    check(counter(metrics, "decode_sequences_total") == len(answers),
          f"decode_sequences_total != {len(answers)}")
    code, after = http(base + "/debug/engine")
    check(code == 200, f"/debug/engine {code}")
    check(after.get("costs", {}).get("per_invocation"),
          "the profiler attributed no program costs (/debug/engine costs)")
    check(after["compile_cache"] == before["compile_cache"],
          f"compiled after the banner: {before['compile_cache']} -> "
          f"{after['compile_cache']}")
    proc.interrupt(signal.SIGINT)
    result = {
        "device": {"platform": info["platform"],
                   "kind": info["device_kind"],
                   "count": info["mesh"]["devices"]},
        "tp": info["mesh"]["tp"], "banner": banner[:600],
        "pool_blocks": before["pool"]["capacity_blocks"],
        "programs": sum(after["compile_cache"].values()),
        "paged_kernel": {k: pk[k] for k in
                         ("mode", "engaged", "execution", "declined",
                          "refused")},
        "peak_flops_per_device": after["costs"]["peak_flops_per_device"],
        "requests": len(answers), "tokens": tokens[:len(prompts)],
        "setup_s": round(setup_s, 2), "work_s": round(work_s, 2),
    }
    run.record(name, result)
    return result


def fleet_phase(run: Run, n: int) -> dict:
    """`router --spawn n`: every replica on a chip of its own, the router
    itself never near one; then one replica too many, refused at launch."""
    cfg = run.cfg
    mb = pool_mb(cfg, cfg["kernel_positions"], 4)
    rargs = ["--slots", str(cfg["slots"]), "--prefill-chunk",
             str(cfg["prefill_chunk"]), "--kv-block", str(cfg["kv_block"]),
             "--kv-pool-mb", f"{mb:.4f}", "--paged-kernel", "on"]
    base_argv = [sys.executable, "-m", "deeplearning4j_tpu.cli.main",
                 "router", "--model", os.path.join(run.out, "lm_f32.zip"),
                 "--port", "0", "--kv-block", str(cfg["kv_block"])]
    base_argv += [f"--replica-arg={a}" for a in rargs]
    proc = run.spawn("fleet", base_argv + ["--spawn", str(n),
                                           "--quorum", str(n)])
    m = proc.await_line(r"fleet router pid=(\d+) on http://127\.0\.0\.1:"
                        r"(\d+) fronting", timeout=300)
    base = f"http://127.0.0.1:{m.group(2)}"
    deadline = time.monotonic() + 900
    while True:
        code, ready = http(base + "/readyz")
        if code == 200 and ready.get("replicas_ready") == n:
            break
        if time.monotonic() > deadline or proc.popen.poll() is not None:
            logs = "".join(
                f"\n--- {r.get('name')} ---\n" + str(r)
                for r in (ready.get("replicas") or {}).values())
            raise SmokeFailure(f"fleet never reached {n} ready replicas: "
                               f"{code}{logs}\n--- router log ---\n"
                               f"{tail(proc.log_path)}")
        time.sleep(1.0)
    setup_s = time.monotonic() - proc.t0
    # the router parent never initialised a backend: libtpu is not even
    # mapped into it (children are other processes)
    with open(f"/proc/{proc.popen.pid}/maps") as fh:
        maps = fh.read()
    if "libtpu" in maps:
        raise SmokeFailure("the router process has libtpu mapped: it "
                           "initialised the TPU backend")
    want = "cpu" if run.rehearse else "tpu"
    replicas = {}
    for name, st in sorted(ready["replicas"].items()):
        code, info = http(st["url"] + "/info")
        if code != 200 or info.get("platform") != want:
            raise SmokeFailure(f"replica {name} /info: {code} {info}")
        code, dbg = http(st["url"] + "/debug/engine")
        code, rmetrics = http(st["url"] + "/metrics")
        replicas[name] = {
            "device_kind": info["device_kind"],
            "devices": info["mesh"]["devices"],
            "visible_chips": info["mesh"]["visible_chips"],
            "kernel_engaged": dbg["paged_kernel"]["engaged"],
            "restarts": counter(rmetrics, "engine_restarts_total"),
            "generation": st.get("generation"),
        }
    if not run.rehearse:
        chips = [v["visible_chips"] for v in replicas.values()]
        if any(v["devices"] != 1 for v in replicas.values()) \
                or None in chips or len(set(chips)) != n:
            raise SmokeFailure("replicas are not on one chip each: "
                               f"{replicas}")
    if any(v["restarts"] or v["generation"] != 1 for v in replicas.values()):
        raise SmokeFailure(f"a replica was restarted: {replicas}")
    prompts = [prompt_for(i, length, cfg["vocab"])
               for i, length in enumerate(cfg["kernel_prompts"])]
    t0 = time.monotonic()
    answers = generate_all(base, prompts, cfg["new_tokens"])
    work_s = time.monotonic() - t0
    tokens, used = [], set()
    for code, ans in answers:
        if code != 200 or "retries" in ans:
            raise SmokeFailure(f"fleet /generate: {code} {ans}")
        tokens.append(ans["tokens"])
        used.add((ans.get("router") or {}).get("replica"))
    if not tokens[0] == tokens[-1] == tokens[-2]:
        raise SmokeFailure("fleet: the repeated prompt changed its answer")
    proc.interrupt(signal.SIGTERM)

    # one replica more than the host has chips: refused at launch
    refusal = None
    if not run.rehearse:
        over = run.spawn("fleet_over", base_argv + ["--spawn", str(n + 1)])
        rc = over.wait(120)
        refusal = tail(over.log_path, 600).strip().splitlines()[-1]
        if rc == 0 or "chips" not in refusal:
            raise SmokeFailure(f"--spawn {n + 1} on {n} chips: rc={rc} "
                               f"{refusal}")
    result = {
        "device": {"platform": want,
                   "kind": next(iter(replicas.values()))["device_kind"],
                   "count": len(replicas)},
        "replicas": replicas, "replicas_used": sorted(map(str, used)),
        "router_holds_libtpu": False, "over_subscription": refusal,
        "tokens": tokens[:len(prompts)],
        "setup_s": round(setup_s, 2), "work_s": round(work_s, 2),
    }
    run.record("fleet", result)
    return result


def smoke(run: Run) -> None:
    cfg = run.cfg
    train = run.child("train", timeout=900)
    if run.devices == 1:
        serve_phase(run, "serve_bf16", "lm_bf16.zip",
                    positions=cfg["positions"], itemsize=2,
                    prompt_lengths=cfg["prompts"],
                    solo=train["solo_tokens"]["bf16"])
    on = serve_phase(run, "serve_f32_kernel_on", "lm_f32.zip",
                     positions=cfg["kernel_positions"], itemsize=4,
                     prompt_lengths=cfg["kernel_prompts"], kernel="on",
                     solo=train["solo_tokens"]["f32"])
    if run.devices == 1:
        off = serve_phase(run, "serve_f32_kernel_off", "lm_f32.zip",
                          positions=cfg["kernel_positions"], itemsize=4,
                          prompt_lengths=cfg["kernel_prompts"], kernel="off")
        if on["tokens"] != off["tokens"]:
            raise SmokeFailure(
                "--paged-kernel on and off disagree:\non  "
                f"{on['tokens']}\noff {off['tokens']}")
        return
    n = run.devices
    dp = run.child("train_dp", timeout=900)
    for k, (got, ref) in enumerate(zip(dp["losses"], train["losses"])):
        if abs(got - ref) > DP_LOSS_RTOL * abs(ref) + DP_LOSS_ATOL:
            raise SmokeFailure(
                f"step {k + 1}: {n}-device loss {got}, one device gave "
                f"{ref} at the same global batch (rtol {DP_LOSS_RTOL}, "
                f"atol {DP_LOSS_ATOL})")
    tp = serve_phase(run, f"serve_f32_tp{n}", "lm_f32.zip",
                     positions=cfg["kernel_positions"], itemsize=4,
                     prompt_lengths=cfg["kernel_prompts"], kernel="on",
                     tp=n)
    if tp["tokens"] != on["tokens"]:
        raise SmokeFailure(f"--tp {n} and one device disagree:\ntp  "
                           f"{tp['tokens']}\none {on['tokens']}")
    fleet = fleet_phase(run, n)
    if fleet["tokens"] != on["tokens"]:
        raise SmokeFailure("the fleet and one server disagree:\nfleet "
                           f"{fleet['tokens']}\none   {on['tokens']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip path; 4: the four-chip host "
                         "checks (fails with fewer than four devices)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU, Pallas interpreted; "
                         "labels itself a rehearsal")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "chip_smoke"),
        help="the run's directory (emptied first): models, logs, results")
    ap.add_argument("--child", choices=("train", "train_dp"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return run_child(args)
    run = Run(args)
    t0 = time.monotonic()
    ok = False
    try:
        smoke(run)
        ok = True
    except SmokeFailure as e:
        run.log(f"FAILED: {e}")
    finally:
        run.stop_all()
        for name in os.listdir(run.out):  # logs and results stay
            if name.endswith(".zip"):
                os.unlink(os.path.join(run.out, name))
    if ok:
        print(json.dumps({
            "summary": "chip_smoke", "rehearsal": run.rehearse,
            "devices_asked": run.devices,
            "phases": {k: {"setup_s": v["setup_s"], "work_s": v["work_s"]}
                       for k, v in run.phases.items()},
            "seconds": round(time.monotonic() - t0, 1), "claim": None}))
    if run.device:  # no device seen, no result line of any kind
        print(json.dumps({"ok": ok, "device": {
            "platform": str(run.device["platform"]),
            "kind": str(run.device["kind"]),
            "count": int(run.device["count"])}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
