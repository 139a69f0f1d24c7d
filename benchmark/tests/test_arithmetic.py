"""Percentile and rate arithmetic on a hand-made run with a stall in it,
through the metric readers the benchmark itself uses."""
import json
from pathlib import Path

import pytest

from benchmark.harness import family, peaks, runner, stats

ROOT = Path(__file__).resolve().parents[2]


def _row(i, due, first, gaps, prompt_len=10):
    stamps = [first]
    for g in gaps:
        stamps.append(stamps[-1] + g)
    return {"index": i, "id": f"r{i}", "due": due, "sent": due + 0.001,
            "admitted": due + 0.002, "first": first, "done": stamps[-1],
            "prompt_len": prompt_len, "out_len": len(stamps),
            "tokens": [1] * len(stamps), "stamps": stamps, "error": None}


def _run():
    # 20 requests, one a second; each first token 0.1 s after due, then 4
    # tokens 0.01 s apart. Request 7 stalls: first token 2.0 s late and one
    # gap of 0.5 s.
    rows = [_row(i, 100.0 + i, 100.1 + i, [0.01] * 4) for i in range(20)]
    rows[7] = _row(7, 107.0, 109.0, [0.01, 0.5, 0.01, 0.01])
    return {"rows": rows, "setup_s": 12.5,
            "window": {"t0": 100.0, "seconds": 20.0, "drained_s": 1.0}}


def test_percentile_by_hand():
    assert stats.percentile([], 95) is None
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == pytest.approx(95.0)
    assert stats.percentile([0, 10], 95) == pytest.approx(9.5)
    assert stats.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4


def test_tail_counts_the_stall_from_the_due_time():
    run = _run()
    ttft = runner.reader(ROOT, "ttft_p95_ms")(run)
    # sorted: nineteen at 100 ms, one at 2000 ms; rank 0.95*19 = 18.05
    assert ttft == pytest.approx(100 + 0.05 * 1900, rel=1e-6)
    itl = runner.reader(ROOT, "itl_p95_ms")(run)
    # 80 gaps: 79 of 10 ms and one of 500 ms; rank 0.95*79 = 75.05 -> 10 ms
    assert itl == pytest.approx(10.0, rel=1e-6)
    assert runner.reader(ROOT, "setup_s")(run) == 12.5


def test_rate_is_all_tokens_over_all_the_window():
    run = _run()
    # the last request's tokens at 119.1 .. 119.14 are inside [100, 120]
    assert runner.reader(ROOT, "out_tok_s")(run) == pytest.approx(100 / 20.0)
    run["window"]["seconds"] = 10.0   # tokens stamped after 110.0 fall out
    inside = sum(1 for r in run["rows"] for t in r["stamps"] if t <= 110.0)
    assert runner.reader(ROOT, "out_tok_s")(run) == pytest.approx(inside / 10)


def test_late_generator_and_queue_read_from_due():
    run = _run()
    assert runner.reader(ROOT, "gen_late_p95_ms")(run) == pytest.approx(1.0)
    assert runner.reader(ROOT, "queue_p95_ms")(run) == pytest.approx(2.0)


def test_pool_peak_counts_resident_blocks():
    run = _run()
    run["geometry"] = {"kv_block": 8}
    run["window"]["capacity_blocks"] = 10
    # a prompt of 10 tokens and 5 outputs holds 2 blocks of 8; only the
    # stalled request (107.0 .. 109.5) overlaps another: peak 4 blocks of 10
    assert runner.reader(ROOT, "kv_pool_peak_pct")(run) == pytest.approx(40.0)


def _cfg(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json")
                      .read_text())


def _work(cfg):
    """The family's count of work, found as a run finds it."""
    return family.load(ROOT, cfg).work


@pytest.mark.parametrize("name,per_layer,kv", [
    # hand count: d*d*2 (q, o) + d*kv*2 (k, v) + 2*d*ff
    ("starcoder2-3b", 3072 * 3072 * 2 + 3072 * 256 * 2 + 2 * 3072 * 12288,
     30 * 2 * 2 * 128 * 2),
    ("starcoder2-7b-d16", 4608 * 4608 * 2 + 4608 * 512 * 2 + 2 * 4608 * 18432,
     16 * 2 * 4 * 128 * 2),
])
def test_work_against_a_hand_count(name, per_layer, kv):
    cfg = _cfg(name)
    work = _work(cfg)
    L, d, v = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    assert work.layer_matmul_params(cfg) == per_layer
    assert work.kv_bytes_per_position(cfg) == kv
    # one slot decoding over 100 keys
    f, b = work.decode_step(cfg, [100])
    assert f == 2 * (L * per_layer + d * v) + L * 4 * h * 128 * 100
    vec = L * (4 * d + d + cfg["intermediate_size"] + d)
    head = d * v + v + 2 * d
    assert b == 2 * (L * per_layer + vec + head) + 2 * d + 100 * kv + kv
    # a first chunk of 256 tokens, not the prompt's last
    f, b = work.prefill_chunk(cfg, 256, 0, final=False)
    assert f == 2 * L * per_layer * 256 + L * 4 * h * 128 * (256 * 257 // 2)
    assert b == 2 * (L * per_layer + vec) + 256 * d * 2 + 256 * kv + 256 * kv
    # bandwidth bounds a decode step, compute a chunk (the issue's reckoning)
    pk = peaks.peaks_for("TPU v5 lite")
    fd, bd = work.decode_step(cfg, [600] * 16)
    assert bd / pk["hbm_bytes_per_s"] > fd / pk["bf16_flops_per_s"]
    fc, bc = work.prefill_chunk(cfg, 256, 1024, final=True)
    assert fc / pk["bf16_flops_per_s"] > bc / pk["hbm_bytes_per_s"]


def test_parameter_count_is_the_issue_s():
    for name, gb in (("starcoder2-3b", 6.36e9), ("starcoder2-7b-d16", 7.85e9)):
        cfg = _cfg(name)
        assert _work(cfg).param_count(cfg) * 2 == pytest.approx(gb, rel=2e-3)


# Computed on the parent of PR 28 (commit 6298da9, `harness/work.py` before
# it moved to `families/starcoder2/work.py`): the move reproduces every
# number exactly, and so do both rooflines and `serve_mfu_pct`.
PINNED_WORK = {
    "starcoder2-3b": {
        "decode_1x100": (6095536128.0, 6063734784.0),
        "decode_16x600": (100477698048.0, 6356127744.0),
        "decode_empty": (0.0, 6060625920.0),
        "chunk_256_0": (1485837434880.0, 5775826944.0),
        "chunk_256_1024_final": (1582776188928.0, 6109384704.0),
        "chunk_37_3000_final": (254477426688.0, 6155286528.0),
        "param_count": 3181310976,
        "kv_bytes_per_position": 30720,
    },
    "starcoder2-7b-d16": {
        "decode_1x100": (7428243456.0, 7403662336.0),
        "decode_16x600": (121211191296.0, 7715588096.0),
        "decode_empty": (0.0, 7400343552.0),
        "chunk_256_0": (1787817885696.0, 6966378496.0),
        "chunk_256_1024_final": (1865580281856.0, 7453034496.0),
        "chunk_37_3000_final": (290388934656.0, 7501413376.0),
        "param_count": 3926668800,
        "kv_bytes_per_position": 32768,
    },
}


@pytest.mark.parametrize("name", sorted(PINNED_WORK))
def test_work_is_what_it_was_before_the_move(name):
    cfg, pin = _cfg(name), PINNED_WORK[name]
    work = _work(cfg)
    got = {
        "decode_1x100": work.decode_step(cfg, [100]),
        "decode_16x600": work.decode_step(cfg, [600] * 16),
        "decode_empty": work.decode_step(cfg, []),
        "chunk_256_0": work.prefill_chunk(cfg, 256, 0, False),
        "chunk_256_1024_final": work.prefill_chunk(cfg, 256, 1024, True),
        "chunk_37_3000_final": work.prefill_chunk(cfg, 37, 3000, True),
        "param_count": work.param_count(cfg),
        "kv_bytes_per_position": work.kv_bytes_per_position(cfg)}
    assert got == pin
    # the run, which this family is handed and ignores
    run = {"window": {"spans": [], "counters": {"x": 1}}}
    assert work.decode_step(cfg, [100], run=run, t_lo=0.0, t_hi=1.0) == \
        pin["decode_1x100"]
    assert work.prefill_chunk(cfg, 256, 0, False, run=run, span={}) == \
        pin["chunk_256_0"]


def test_least_seconds_is_the_larger_bound():
    pk = peaks.peaks_for("TPU v5 lite")
    assert peaks.least_seconds(197e12, 0.0, pk) == 1.0
    assert peaks.least_seconds(197e12, 2 * 819e9, pk) == 2.0


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
