"""The command end to end on the CPU, at a toy size, in a scratch root that
adds a third configuration, a third traffic mix, a third cell and a tenth
per-layer metric as new files plus one entry each in BENCHMARK.json: no
file under benchmark/ is edited."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.tests.util import REPO, make_root

COUNT_REQUESTS = '''"""A tenth per-layer metric, as a later PR would add it."""


def read(run):
    return float(len(run["rows"]))
'''
NOTHING_TO_READ = '''def read(run):
    return None
'''


def _run(root, *extra, env=None):
    e = {**os.environ, "PYTHONPATH": str(REPO), **(env or {})}
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--bench-root",
         str(root), "--workload", "tiny.cell", "--seconds", "3", *extra],
        capture_output=True, text=True, env=e, cwd=str(root), timeout=600)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"), extra_metrics=[
        ("requests_seen", COUNT_REQUESTS), ("never_there", NOTHING_TO_READ)])


def test_refuses_to_run_without_a_tpu(root):
    p = _run(root, "--seed", "1", "--trace", "0",
             env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_refuses_to_run_without_the_program(root):
    # a directory that holds only BENCHMARK.json and the benchmark's files
    p = _run(root, "--seed", "1", "--trace", "0", "--rehearse-cpu",
             env={"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "deeplearning4j_tpu" in p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(root, trace):
    p = _run(root, "--seed", "3000000001", "--trace", str(trace),
             "--rehearse-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    assert line["device"]["platform"] == "cpu"       # never a device metric
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for name, (value, limit) in line["checks"].items():
        assert value <= limit, name
    assert line["checks"]["compiles_in_window"] == [0, 0]
    assert "check max_gap" in p.stderr and "correct: True" in p.stderr
    got = set(line["metrics"])
    if trace:
        assert {"gen_late_p95_ms", "queue_p95_ms", "sched_iter_ms",
                "decode_batch_mean", "kv_pool_peak_pct",
                "requests_seen"} <= got
        # a reader that finds nothing to read is left out, never 0
        assert not got & {"never_there", "decode_step_roofline",
                          "prefill_chunk_roofline", "serve_mfu_pct",
                          "device_idle_pct"}
        assert line["metrics"]["requests_seen"]["value"] == 12.0
    else:
        assert got == {"ttft_p95_ms", "itl_p95_ms", "out_tok_s", "setup_s"}
        assert all(m["value"] > 0 for m in line["metrics"].values())
    assert not (root / "benchmark" / ".trace").exists()


def test_a_cell_like_the_chat_cell_reports_its_ttft_per_layer(
        tmp_path_factory):
    """A cell that joins the lists `sc2-3b.chat` is on has no end-to-end
    `ttft_p95_ms`: the same arithmetic is read per layer, under `chat.`."""
    root = make_root(tmp_path_factory.mktemp("chat"), like="sc2-3b.chat")
    got = {}
    for trace in (0, 1):
        p = _run(root, "--seed", "7", "--trace", str(trace), "--rehearse-cpu")
        assert p.returncode == 0, p.stderr[-2000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])
        assert line["correct"] is True
        got[trace] = line["metrics"]
    assert set(got[0]) == {"itl_p95_ms", "out_tok_s", "setup_s"}
    assert {"chat.ttft_p95_ms", "chat.queue_p95_ms",
            "chat.gen_late_p95_ms", "sched_iter_ms"} <= set(got[1])
    assert not set(got[1]) & {"ttft_p95_ms", "queue_p95_ms",
                              "gen_late_p95_ms", "prefill_chunk_roofline",
                              "chat.prefill_chunk_roofline"}
    assert got[1]["chat.ttft_p95_ms"]["value"] > 0
