"""One run of one cell, as steps that `run.py` strings together and the
tools (`tools/sweep.py`, `tools/seeds.py`) reuse in one process."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

from . import family


def prepare(root: Path, rehearse: bool) -> None:
    """The process's environment, before JAX is first imported: the CPU only
    for a rehearsal, the compile cache at a fixed path inside the checkout
    unless the caller placed it, no TPU logs under /tmp."""
    import os

    if rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(root / ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    # the families are many programs of a second or less each
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: Path, name: str) -> dict:
    """Everything the cell's name leads to, found by name under benchmark/:
    its files, and the family of its configuration's `model_type`."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    b = root / "benchmark"
    cfg = load_json(root / entry["file"])
    return {"root": root, "bench": bench, "cell": cell, "cfg": cfg,
            "family": family.load(root, cfg),
            "mix": load_json(b / "traffic" / f"{cell['traffic']}.json"),
            "wl": load_json(b / "workloads" / f"{cell['name']}.json")}


def reader(root: Path, name: str):
    path = root / "benchmark" / "metrics" / f"{name}.py"
    return family.module_at(f"_metric_{name}", path).read


def metric_names(bench: dict, cell: str, trace: bool) -> list:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [(m["name"], m["unit"]) for m in group
            if "workloads" not in m or cell in m["workloads"]]


def find_device(chips: int, rehearse: bool) -> Optional[dict]:
    """The device as JAX reports it, or None when the cell's chips are not
    there. Only a rehearsal may go on without a TPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return None
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"the cell needs {chips} TPU chip(s); found {len(devs)} x "
              f"{devs[0].platform}", file=sys.stderr)
        return None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": 1 if rehearse else chips}


def setup(ctx: dict, seed: int) -> dict:
    """Weights from the seed, the engine, and the warm-up of every shape the
    mix's lengths can reach. All of it is `setup_s`."""
    import jax
    import jax.numpy as jnp
    from . import engine_driver, loadgen

    cfg, wl, fam = ctx["cfg"], ctx["wl"], ctx["family"]
    dtype = wl.get("dtype", "bfloat16")
    t = [time.time()]

    def lap():
        t.append(time.time())
        return t[-1] - t[-2]

    params = fam.weights.make_params(cfg, seed, jnp.dtype(dtype))
    jax.block_until_ready(params)
    took = {"weights_s": lap()}
    net = engine_driver.build_net(fam, cfg, params, dtype)
    eng = engine_driver.build_engine(net, cfg["vocab_size"], wl["engine"])
    took["engine_s"] = lap()
    limits = loadgen.length_limits(ctx["mix"])
    warmed = engine_driver.warm(eng, limits, cfg["vocab_size"], seed)
    took["warm_s"] = lap()
    print(f"set-up: {took} {warmed}", file=sys.stderr, flush=True)
    return {"params": params, "net": net, "eng": eng, "limits": limits,
            "warmed": {**warmed, **took}}


def reseed(ctx: dict, st: dict, seed: int) -> None:
    """New weights into the same engine (it reads `net.params` at every
    dispatch): what `tools/seeds.py` does between seeds, so that one set-up
    serves a dozen. The old weights are freed as the last reference goes."""
    import jax
    import jax.numpy as jnp
    fam = ctx["family"]
    dtype = ctx["wl"].get("dtype", "bfloat16")
    st["params"] = None
    st["net"].params = {}
    st["params"] = fam.weights.make_params(ctx["cfg"], seed, jnp.dtype(dtype))
    jax.block_until_ready(st["params"])
    st["net"].params = fam.graph.graph_tree(st["params"])


def trace_hooks(ctx: dict, seconds: float, trace: dict) -> dict:
    """Start and stop of a few seconds of profiler trace, the window's last
    (steady state, for requests that live a third of the window). Stopping
    a trace takes ~17 s in which the scheduler's thread is starved; in
    mid-window that built a queue of 2-3 s (`queue_p95_ms` 2,190-2,710 against
    an untraced `ttft_p95_ms` of 221; my chip runs, PR 25). At the window's
    close the cost falls into the drain, which no metric reads."""
    import jax

    span = ctx["wl"].get("trace_seconds", 3.0)
    on = max(0.0, seconds - span)
    shutil.rmtree(trace["dir"], ignore_errors=True)

    def start():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace["dir"], profiler_options=opts)
        trace["t_on"] = time.monotonic()

    def stop():
        trace["t_off"] = time.monotonic()
        jax.profiler.stop_trace()

    return {on: start, seconds: stop}


def measure(ctx: dict, st: dict, seed: int, seconds: float, trace_on: bool,
            mix: Optional[dict] = None) -> dict:
    """The window: open loop from the seeded schedule, then the drain."""
    import jax
    from . import engine_driver, loadgen

    mix = mix or ctx["mix"]
    requests = loadgen.schedule(mix, seed, seconds, ctx["cfg"]["vocab_size"])
    trace = {"dir": str(ctx["root"] / "benchmark" / ".trace"),
             "summary": None}
    hooks = trace_hooks(ctx, seconds, trace) if trace_on else {}
    window = engine_driver.run_window(
        st["eng"], requests, seconds,
        drain_s=ctx["wl"].get("drain_seconds", 60.0), at=hooks)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.local_devices())
    return {"rows": engine_driver.request_rows(requests), "window": window,
            "prompts": {r.index: r.prompt for r in requests},
            "trace": trace, "memory_peak_bytes": int(mem)}


def free_engine(st: dict) -> None:
    st["eng"].stop()
    st.pop("eng")
    st.pop("net")


def compare(ctx: dict, st: dict, m: dict, seed: int,
            controls: tuple = ()) -> dict:
    """The reference over a seeded sample of what the window finished;
    `controls` (the tools' and the tests', never a run's) also reads the
    reference in those lower precisions."""
    from . import check

    wl, lim = ctx["wl"], st["limits"]
    sample = check.pick(m["rows"], seed, wl["check"]["sample_requests"])
    t = time.time()
    got = {}
    if sample:
        got = check.gaps(ctx["family"].reference.logits_at, st["params"],
                         ctx["cfg"], sample, m["prompts"],
                         lim["total_max"], lim["out_max"],
                         quants=(None,) + tuple(controls),
                         batch=wl["check"].get("batch", 4))
    gap = got.pop(None, None)
    v = check.verdict(m["rows"], m["window"], gap, wl["check"])
    return {**v, "gap": gap, "control": got or None,
            "reference_s": time.time() - t}


def facts(ctx: dict, m: dict, device: dict, setup_s: float) -> dict:
    """What a metric's reader is handed."""
    from . import peaks

    pk = peaks.peaks_for(device["kind"]) if device["platform"] == "tpu" \
        else None
    return {"rows": m["rows"], "window": m["window"], "cfg": ctx["cfg"],
            "family": ctx["family"], "geometry": ctx["wl"]["engine"],
            "traffic": ctx["mix"], "peaks": pk, "device": device,
            "setup_s": setup_s, "trace": m["trace"]}


def read_metrics(ctx: dict, run: dict, trace_on: bool) -> dict:
    """Each metric BENCHMARK.json lists for the cell, by its own reader; one
    that finds nothing to read is left out."""
    metrics = {}
    for name, unit in metric_names(ctx["bench"], ctx["cell"]["name"],
                                   trace_on):
        value = reader(ctx["root"], name)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    return metrics


def result(ctx: dict, st: dict, m: dict, v: dict, device: dict,
           setup_s: float, trace_on: bool) -> dict:
    """The contract's one line, as a dict."""
    from . import reducer

    trace, window = m["trace"], m["window"]
    if trace_on and "t_off" in trace:
        path = reducer.find_xplane(trace["dir"])
        trace["summary"] = reducer.summarize(path) if path else None
        shutil.rmtree(trace["dir"], ignore_errors=True)
    metrics = read_metrics(ctx, facts(ctx, m, device, setup_s), trace_on)
    out = {"correct": v["correct"], "attempted": len(m["rows"]),
           "failed": v["failed"], "metrics": metrics,
           "device": {**device, "memory_peak_bytes": m["memory_peak_bytes"]}}
    if trace_on and trace["summary"]:
        s = trace["summary"]
        out["device"]["busy_s"] = s["busy_s"]
        out["device"]["window_s"] = s["window_s"]
        out["breakdown"] = {"device_ops": s["device_ops"],
                            "idle_gaps": s["idle_gaps"]}
    it = max(1, window["iterations"])
    out["harness"] = {
        "warmed": st["warmed"], "reference_s": v["reference_s"],
        "drained_s": window["drained_s"], "preempted": window["preempted"],
        "pool_blocks_live_max": window["pool_live_max"],
        "capacity_blocks": window["capacity_blocks"],
        "dispatches": window["dispatches"],
        "phase_ms_per_iter": {k: s_ / it * 1e3
                              for k, s_ in window["phase_seconds"].items()},
        "gap": v["gap"], "control": v["control"],
    }
    out["checks"] = v["checks"]       # last: each number beside its limit
    return out


def print_result(out: dict) -> None:
    for name, (val, lim) in out["checks"].items():
        print(f"check {name}: {val} (limit {lim})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
