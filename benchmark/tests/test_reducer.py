"""The trace reducer on a small trace recorded on a TPU v5e (my chip run,
PR 25: the toy cell for 3 s through `tools/probe_trace.py`, 0.5 s traced),
read with `jax.profiler.ProfileData` alone."""
import sys
from pathlib import Path

import pytest

from benchmark.harness import reducer

TRACE = Path(__file__).resolve().parent / "data" / "tiny_tpu_v5e.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return reducer.summarize(str(TRACE))


def test_reads_without_tensorflow(summary):
    assert summary is not None and summary["devices"] == 1
    assert "tensorflow" not in sys.modules and "tsl" not in sys.modules


def test_busy_union_window_and_idle_share(summary):
    # 2,884 op events on the device's "XLA Ops" line; their union is what
    # ran, the span they cover is the window (hand-checked once against a
    # dump of the events' start and duration)
    assert summary["busy_s"] == pytest.approx(421.574e-6, rel=1e-6)
    assert summary["window_s"] == pytest.approx(0.230830665, rel=1e-6)
    assert 0 < summary["busy_s"] < summary["window_s"]
    idle = 1 - summary["busy_s"] / summary["window_s"]
    assert idle == pytest.approx(0.99817, abs=1e-4)   # a toy: nearly all idle


def test_time_per_module_by_name_pattern(summary):
    n, s = reducer.module_seconds(summary, r"^jit__step_paged_fn$")
    assert n == 13 and s == pytest.approx(343.17e-6, rel=1e-4)
    n, s = reducer.module_seconds(summary, r"^jit__prefill_paged_fn$")
    assert n == 2 and s == pytest.approx(83.262e-6, rel=1e-4)
    assert reducer.module_seconds(summary, r"^jit_no_such_program$") == (0, 0)
    # programs' executions cover the operations' busy time, and little more
    total = sum(m["seconds"] for m in summary["modules"])
    assert summary["busy_s"] <= total <= 1.1 * summary["busy_s"]


def test_breakdown_lists_are_short_and_named(summary):
    assert len(summary["device_ops"]) <= 10 and len(summary["idle_gaps"]) <= 10
    assert all(len(name) <= 96 and sec > 0
               for name, sec in summary["device_ops"])
    # the copies of the page arrays (neither program donates `states`) lead
    assert summary["device_ops"][0][0].startswith("%copy")
    gaps = dict(summary["idle_gaps"])
    assert set(gaps) == {"before jit__zero_fn", "before jit__step_paged_fn",
                         "before jit__prefill_paged_fn"}
    assert sum(gaps.values()) == pytest.approx(
        summary["window_s"] - sum(m["seconds"] for m in summary["modules"]),
        rel=1e-3)


def test_module_name_drops_the_fingerprint():
    assert reducer.module_name("jit__step_paged_fn(4051536863261839432)") \
        == "jit__step_paged_fn"


def test_a_trace_without_a_device_reads_as_nothing(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = reducer.find_xplane(str(tmp_path))
    assert path is not None and reducer.summarize(path) is None
