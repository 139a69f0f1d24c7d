"""chip_smoke.py and the bring-up seams around it, on the CPU.

The smoke itself proves the train -> save -> serve path on a TPU; these
tests keep it from rotting between chip runs: its CPU rehearsal end to
end, its refusal to run without a chip, the parent staying off JAX, and
the small contracts the bring-up PR added (compile-cache placement, the
router parent initialising no backend, no default peak for an unknown
device, autotune candidates that raise keeping their reason).
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = {k: v for k, v in os.environ.items()
           # the suite holds the persistent cache off for itself; the
           # smoke's children are where it is supposed to work
           if k not in ("JAX_ENABLE_COMPILATION_CACHE",
                        "JAX_COMPILATION_CACHE_DIR")}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def _py(code, **extra):
    return subprocess.run([sys.executable, "-c", code], env=_env(**extra),
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO)


@pytest.mark.slow  # ~45 s of child processes: tier-1 is cut off by its
# time cap, so this runs with the slow set (and before every chip run)
def test_rehearsal_runs_end_to_end_and_labels_itself(tmp_path):
    proc = subprocess.run(
        [sys.executable, SMOKE, "--rehearse-cpu", "--out",
         str(tmp_path / "run")],
        env=_env(JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    # the last line is the contract's result object and nothing more
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    summary, phases = lines[-2], lines[:-2]
    assert summary["rehearsal"] is True and summary["claim"] is None
    assert set(summary["phases"]) == {
        "train", "serve_bf16", "serve_f32_kernel_on",
        "serve_f32_kernel_off"}
    # every phase said what it ran on, and the kernel launch said the
    # kernel was interpreted (on the chip the smoke demands "compiled")
    assert all(p["device"]["platform"] == "cpu" for p in phases)
    on = next(p for p in phases if p["phase"] == "serve_f32_kernel_on")
    assert on["paged_kernel"]["engaged"]
    assert on["paged_kernel"]["execution"] == "interpreted"
    # the cache was written where the environment said, nowhere else
    assert os.listdir(tmp_path / "cache")
    # the run cleans up its models and leaves logs and results
    left = os.listdir(tmp_path / "run")
    assert "train.json" in left and not [n for n in left
                                         if n.endswith(".zip")]


def test_without_a_chip_it_fails_and_names_the_missing_chip(tmp_path):
    proc = subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "run")],
        env=_env(JAX_PLATFORMS="cpu"), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""  # no result of any kind
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_parent_and_fleet_parents_stay_off_jax_backends():
    """One subprocess, three facts: importing chip_smoke imports no jax
    at all; importing serving.router, building its parser and dividing
    the host's chips (jax is imported by the package) initialises no
    backend; and the compile-cache helper answers with the in-checkout
    path."""
    out = _py(
        "import sys, json, argparse\n"
        "import chip_smoke\n"
        "assert 'jax' not in sys.modules, 'chip_smoke imported jax'\n"
        "from deeplearning4j_tpu.serving import router\n"
        "router.build_parser().parse_args(['--spawn', '2'])\n"
        "from deeplearning4j_tpu.serving.replica import one_chip_envs\n"
        "one_chip_envs(2)\n"
        "from deeplearning4j_tpu.util.compile_cache import "
        "enable_compile_cache\n"
        "path = enable_compile_cache()\n"
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "print(json.dumps({'path': path, 'cfg': "
        "jax.config.jax_compilation_cache_dir}))\n")
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["path"] == got["cfg"] == os.path.join(REPO, ".jax_cache")


def test_compile_cache_placement(monkeypatch):
    """Same in-checkout path from this process as from the other one
    above; and with JAX_COMPILATION_CACHE_DIR set, no directory is set
    in code at all (JAX reads the variable itself)."""
    import jax

    from deeplearning4j_tpu.util import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        assert compile_cache.enable_compile_cache() == os.path.join(
            REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == "sentinel"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    src = open(compile_cache.__file__).read()
    assert not any(w in src for w in ("import tempfile", "getpid", "time."))


def test_unknown_device_kind_has_no_peak():
    from deeplearning4j_tpu.inference import profiler

    assert profiler.device_peak_flops("TPU v5 lite") == 197e12
    assert profiler.device_peak_flops("TPU v99 imaginary") is None
    assert profiler.device_peak_flops("cpu") is None
    prof = profiler.StepPhaseProfiler(enabled=True)  # this host: a CPU
    assert prof.peak_flops is None
    assert prof.rates()["mfu_estimate"] is None
    assert "no published peak" in prof.cost_snapshot()["peak_note"]


def test_autotune_candidate_that_raises_keeps_its_reason(monkeypatch):
    """A refused candidate loses like a slow one — verdict XLA — but the
    verdict no longer reads the same as an honest loss: the reason is in
    autotune_refusals(), and mode="on" still raises."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    def refuse(*a, **k):
        raise ValueError("Mosaic says no\nsecond line")

    monkeypatch.setattr(pk, "_INTERPRET", False)
    monkeypatch.setattr(pk, "_paged_decode_call", refuse)
    pk.clear_autotune_cache()
    try:
        B, nb, block, Hkv, H, Dh = 2, 2, 8, 2, 2, 16
        verdict = pk._autotune_paged_decode(B, nb, block, Hkv, H, Dh,
                                            jnp.float32, False)
        assert verdict is False
        key = ("paged_decode", B, nb, block, Hkv, H, Dh, "float32", False)
        assert pk.autotune_refusals()[key] == {
            "bh": "ValueError: Mosaic says no",
            "hb": "ValueError: Mosaic says no"}
        q = jnp.zeros((B, 1, H, Dh), jnp.float32)
        pages = jnp.zeros((B * nb + 1, block, Hkv, Dh), jnp.float32)
        with pytest.raises(ValueError, match="Mosaic says no"):
            pk.paged_decode_attention_pallas(
                q, pages, pages, jnp.zeros((B, nb), jnp.int32),
                jnp.zeros((B,), jnp.int32), mode="on")
    finally:
        pk.clear_autotune_cache()
    assert pk.autotune_refusals() == {}
