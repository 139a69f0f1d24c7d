"""The benchmark's family loader, in tier-1 (ISSUE 28 asked for it, ISSUE 30
brings it with the second real family, ISSUE 34 the third, ISSUE 36 the
fourth): every configuration of
`BENCHMARK.json` resolves to its family's four files, an unknown `model_type`
names the directory to add, a family lacking a file or a function fails at
load and not in mid-run, and `evabyte-d16.json` is the published
configuration key for key but for what it lists as reduced."""
import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.harness import family  # noqa: E402

BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
EVABYTE = REPO / "benchmark" / "families" / "evabyte"


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_resolves_to_its_family(entry):
    cfg = json.loads((REPO / entry["file"]).read_text())
    fam = family.load(REPO, cfg)
    assert fam.name == cfg["model_type"]
    assert fam.path == REPO / "benchmark" / "families" / cfg["model_type"]
    for part, functions in family.PARTS.items():
        for f in functions:
            assert callable(getattr(getattr(fam, part), f)), (part, f)


WORKLOADS = sorted((REPO / "benchmark" / "workloads").glob("*.json")) + [
    REPO / "benchmark" / "tests" / "data" / "tiny" / "workload.json"]


@pytest.mark.parametrize("path", WORKLOADS,
                         ids=lambda p: p.parent.name + "/" + p.stem)
def test_the_scheduler_takes_every_workloads_engine_block_whole(path):
    """A workload file's `engine` block goes to `DecodeScheduler` as
    keywords, whole: an option leaves the constructor only after a
    `benchmark` PR has taken it out of these files, or every cell fails
    at set-up (ROADMAP Design 5: `paged_kernel`, `mask_rows`). And a
    cell is served from the paged pool."""
    import inspect

    from deeplearning4j_tpu.inference import DecodeScheduler
    engine = json.loads(path.read_text())["engine"]
    inspect.signature(DecodeScheduler.__init__).bind(
        None, "net", 320, **engine)
    assert engine["kv_pool_mb"] > 0


def test_every_family_directory_is_some_configurations_model_type():
    """Four families since ISSUE 36, and no directory that no configuration
    names (nor a configuration without its directory)."""
    types = {json.loads((REPO / c["file"]).read_text())["model_type"]
             for c in BENCH["configs"]}
    assert types == {"starcoder2", "evabyte", "axk1", "nemotron_h"}
    assert {p.name for p in (REPO / "benchmark" / "families").iterdir()
            if p.is_dir() and p.name != "__pycache__"} == types


def test_an_unknown_model_type_names_the_directory_to_add():
    with pytest.raises(KeyError, match="benchmark/families/afmoe/"):
        family.load(REPO, {"model_type": "afmoe"})
    with pytest.raises(KeyError, match="model_type"):
        family.load(REPO, {"hidden_size": 64})


@pytest.mark.parametrize("part", sorted(family.PARTS))
def test_a_family_lacking_a_file_or_a_function_fails_at_load(tmp_path, part):
    here = tmp_path / "benchmark" / "families" / "half"
    here.mkdir(parents=True)
    for p in family.PARTS:
        if p != part:
            (here / f"{p}.py").write_text((EVABYTE / f"{p}.py").read_text())
    with pytest.raises(KeyError, match=f"families/half/{part}.py"):
        family.load(tmp_path, {"model_type": "half"})
    gone = family.PARTS[part][-1]
    (here / f"{part}.py").write_text(
        (EVABYTE / f"{part}.py").read_text().replace(
            f"def {gone}(", f"def _{gone}("))
    with pytest.raises(KeyError, match=gone):
        family.load(tmp_path, {"model_type": "half"})


def test_evabyte_d16_is_the_published_configuration_but_for_reduced():
    entry = next(c for c in BENCH["configs"] if c["name"] == "evabyte-d16")
    path = REPO / entry["file"]
    cfg = json.loads(path.read_text())
    source = json.loads(path.with_suffix(".published.json").read_text())
    assert source.pop("source") == entry["source"] == cfg["source"]
    assert cfg["reduced"] == entry["reduced"] == \
        ["num_hidden_layers", "num_pred_heads"]
    assert cfg["published"] == {"num_hidden_layers": 32, "num_pred_heads": 8}
    assert (cfg["num_hidden_layers"], cfg["num_pred_heads"]) == (16, 1)
    for key, value in source.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    # no width among them, and the widths themselves
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_attention_heads"], cfg["window_size"],
            cfg["chunk_size"]) == (4096, 11008, 320, 32, 2048, 16)
    assert cfg["deployment"].startswith("the first stage of a two-chip")
    assert any("mu_h" in a and "phi_h" in a for a in cfg["assumed"])
    assert any("fp32_skip_add" in d for d in cfg["departures"])


def test_evabyte_s_work_counts_what_a_query_attends_over():
    """48 pages where a full cache holds 256 is the point of the cell: the
    work counted per decode byte is the compressed read, not the depth."""
    cfg = json.loads((REPO / "benchmark/configs/evabyte-d16.json").read_text())
    work = family.load(REPO, cfg).work
    assert work.rows_attended(cfg, 0) == (1, 0)
    assert work.rows_attended(cfg, 2047) == (2048, 0)
    assert work.rows_attended(cfg, 2048) == (1, 128)
    assert work.rows_attended(cfg, 16383) == (2048, 7 * 128)
    assert work.kv_bytes_per_position(cfg) == 16 * 2 * 4096 * 2
    assert work.param_count(cfg) == pytest.approx(3.24e9, rel=0.01)
    kvb = work.kv_bytes_per_position(cfg)
    _, b0 = work.decode_step(cfg, [])
    f1, b1 = work.decode_step(cfg, [16384])
    assert b1 - b0 == (2048 + 896 + 16 + 2) * kvb + 4096 * 2
    # a chunk's queries, each at its own depth; the cache read once
    fc, bc = work.prefill_chunk(cfg, 256, 2048, False)
    per_tok = 2 * 16 * work.layer_matmul_params(cfg)
    attn = sum(4 * 16 * 4096 * (i + 1 + 128) for i in range(256))
    assert fc == 256 * per_tok + attn + 16 * 8 * 4096 * 256


def test_a_toy_evabyte_cell_runs_to_a_correct_line(tmp_path):
    """The command itself, on the CPU at the tests' small size, in a scratch
    root (`benchmark/tests/util.py`, the recipe a new cell follows): the
    second real family through `run.py` to a `correct` line, with the three
    readers this PR adds finding what they read and `kv_pool_peak_pct`, which
    would count a full cache's blocks, not reported."""
    import os
    import subprocess
    from benchmark.tests.util import make_root
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from eva_util import CFG
    root = make_root(tmp_path, config="tiny-evabyte", cell="toy.eva",
                     config_keys=CFG, like="evabyte-d16.longdoc-complete")
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--bench-root",
         str(root), "--workload", "toy.eva", "--seconds", "3", "--seed",
         "3000000007", "--rehearse-cpu", "--trace", "1"],
        capture_output=True, text=True, cwd=str(root), timeout=900,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name, (value, limit) in line["checks"].items():
        assert value <= limit, name
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["eva_pool_peak_pct"] <= 100
    assert m["eva_roll_ms"] > 0
    assert 0 < m["eva_summary_row_share"] < 100
    assert "kv_pool_peak_pct" not in m and "sched_iter_ms" in m
    assert line["harness"]["phase_ms_per_iter"]["roll"] > 0


# -- the third family: A.X-K1's share of a 16-chip deployment (ISSUE 34) ----

def _axk1():
    entry = next(c for c in BENCH["configs"] if c["name"] == "axk1-ep16-d6")
    path = REPO / entry["file"]
    return entry, path, json.loads(path.read_text())


def test_axk1_ep16_d6_is_the_published_configuration_but_for_reduced():
    entry, path, cfg = _axk1()
    source = json.loads(path.with_suffix(".published.json").read_text())
    assert source.pop("source") == entry["source"] == cfg["source"]
    assert cfg["reduced"] == entry["reduced"] == \
        ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 192,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 12, 20480)
    for key, value in source.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    # the floors of a cut: the dense layer once and >= 4 routed layers,
    # >= 8 experts, >= an eighth of the vocabulary; every width as published
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["router_outputs"] == 192 and cfg["num_experts_per_tok"] == 8
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"]) == \
        (7168, 18432, 2048, 1536, 512, 128, 64, 128)
    assert cfg["rope_scaling"]["factor"] == 32
    assert cfg["deployment"].startswith("one chip's share of a 16-chip")
    assert any("topk_method" in a and "_route" in a for a in cfg["assumed"])
    assert any("rotate-half" in d for d in cfg["departures"])


def _counters(**kw):
    return {"window": {"counters": kw}}


def test_axk1_s_work_follows_the_routing():
    """The issue's arithmetic, and a decode step with all twelve held
    experts hit, and with none."""
    _, _, cfg = _axk1()
    work = family.load(REPO, cfg).work
    assert work.attn_params(cfg) == 101_122_048
    assert work.expert_params(cfg) == 44_040_192
    matrices = 4_166_189_056
    vectors = work.vector_params(cfg) + 7168 + 20480 + 7168
    assert vectors == 6 * (2 * 7168 + 1536 + 512) + (2 * 18432 + 7168) \
        + 5 * (2 * 2048 + 7168) + 2 * 7168 + 20480
    assert work.param_count(cfg) == matrices + vectors
    assert work.kv_bytes_per_position(cfg) == 6912
    # no run: even routing (12 of 192 of the pairs), every expert hit
    assert work.routing(cfg, None) == (12 / 192, 1.0)
    experts = 5 * 12 * 44_040_192 * 2
    run_all = _counters(moe_pairs_routed_total=1600, moe_pairs_held_total=100,
                        moe_expert_slots_total=600, moe_experts_hit_total=600)
    run_none = _counters(moe_pairs_routed_total=1600, moe_pairs_held_total=0,
                         moe_expert_slots_total=600, moe_experts_hit_total=0)
    _, b_all = work.decode_step(cfg, [], run=run_all)
    _, b_none = work.decode_step(cfg, [], run=run_none)
    assert b_all - b_none == experts
    assert b_all == (matrices - 20480 * 7168 + vectors - 7168) * 2
    f_all, b1 = work.decode_step(cfg, [5000], run=run_all)
    f_none, _ = work.decode_step(cfg, [5000], run=run_none)
    # 8 experts a token x 5 layers x a sixteenth of the pairs, 88.08 MFLOP each
    assert f_all - f_none == pytest.approx(8 * 5 / 16 * 2 * 44_040_192)
    assert b1 - b_all == 5000 * 6912 + 6912 + 7168 * 2
    outside = work.matmul_params_outside_experts(cfg)
    assert f_none == 2 * (outside + 7168 * 20480) \
        + 5000 * 6 * 2 * 64 * (576 + 512)
    # a chunk: expanded attention, 320 operations a head a query-key pair;
    # 4,096 pairs a layer reach every held expert
    fc, bc = work.prefill_chunk(cfg, 512, 1024, False, run=run_none)
    assert fc == 512 * 2 * outside \
        + (512 * 1024 + 512 * 513 // 2) * 6 * 2 * 64 * 320
    _, bc_all = work.prefill_chunk(cfg, 512, 1024, False)
    assert bc_all - bc == pytest.approx(experts, rel=1e-6)


def test_a_toy_axk1_cell_runs_to_a_correct_line(tmp_path):
    """The command itself, on the CPU at the tests' small size, in a scratch
    root: the third family through `run.py` to a `correct` line, with the
    readers this PR adds finding what they read."""
    import os
    import subprocess
    from benchmark.tests.util import make_root
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from axk1_util import cfg
    root = make_root(tmp_path, config="tiny-axk1", cell="toy.axk1",
                     config_keys=cfg(4, 8), like="axk1-ep16-d6.longctx-chat")
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--bench-root",
         str(root), "--workload", "toy.axk1", "--seconds", "3", "--seed",
         "3000000011", "--rehearse-cpu", "--trace", "1"],
        capture_output=True, text=True, cwd=str(root), timeout=900,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name, (value, limit) in line["checks"].items():
        assert value <= limit, name
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["moe_held_pair_share"] < 100
    assert 0 < m["moe_experts_hit_share"] <= 100
    assert 0 < m["mla_pool_peak_pct"] <= 100
    assert m["mla_rows_per_decode_token"] > 16
    # the CPU under "auto" keeps the gather body: every page named is read
    assert m["mla_pages_read_share"] == 100.0
    assert "kv_pages_read_share" not in m
    assert "kv_pool_peak_pct" not in m and "eva_roll_ms" not in m
    assert "sched_iter_ms" in m and "longctx.ttft_p95_ms" in m


# -- the fourth family: Nemotron-H's share of an 8-chip deployment (ISSUE 36)

def _nemotron():
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "nemotron3-nano-ep8")
    path = REPO / entry["file"]
    return entry, path, json.loads(path.read_text())


def test_nemotron3_nano_ep8_is_the_published_configuration_but_for_reduced():
    entry, path, cfg = _nemotron()
    source = json.loads(path.with_suffix(".published.json").read_text())
    assert source.pop("source") == entry["source"] == cfg["source"]
    assert cfg["reduced"] == entry["reduced"] == \
        ["n_routed_experts", "vocab_size"]
    assert cfg["published"] == {"n_routed_experts": 128,
                                "vocab_size": 131072}
    for key, value in source.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    # no cut in depth: all 52 blocks of the pattern, 23 M / 23 E / 6 *
    pattern = cfg["hybrid_override_pattern"]
    assert cfg["num_hidden_layers"] == len(pattern) == 52
    assert [pattern.count(c) for c in "ME*"] == [23, 23, 6]
    assert [i for i, c in enumerate(pattern) if c == "*"] == \
        [5, 12, 19, 26, 33, 42]
    # the floors of a cut: >= 8 experts, >= an eighth of the vocabulary;
    # every width as published
    assert (cfg["n_routed_experts"], cfg["router_outputs"],
            cfg["num_experts_per_tok"]) == (16, 128, 6)
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert (cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
            cfg["chunk_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"]) == \
        (2688, 64, 64, 128, 8, 4, 128, 128, 32, 2, 1856, 3712)
    assert cfg["deployment"].startswith("one chip's share of an 8-chip")
    assert "5,258,420,544" in cfg["deployment"] \
        and "49,082,368" in cfg["deployment"]
    for needle in ("no positional embedding", "not expand x hidden_size",
                   "selection bias", "head width", "A_log = ln U(1, 16)",
                   "keys not read"):
        assert any(needle in a for a in cfg["assumed"]), needle
    assert any("one-hot" in d for d in cfg["departures"])


def test_nemotron_s_work_charges_state_by_live_slots_and_experts_by_routing():
    """The issue's arithmetic; then a decode step's bytes: the state of the
    live slots alone, read and written once each, and the held experts by
    what the dispatches hit."""
    _, _, cfg = _nemotron()
    work = family.load(REPO, cfg).work
    assert work.mamba_matmul_params(cfg) + work.mamba_vector_params(cfg) \
        == 38_742_208
    assert work.attn_params(cfg) == 23_396_352
    assert work.expert_params(cfg) == 9_977_856
    assert work.published_params(cfg) == 5_258_420_544
    # the graph's own biases on top: embedding, six attention outputs, the
    # shared experts' two, the head
    assert work.param_count(cfg) == 5_258_420_544 + 2688 + 6 * 2688 \
        + 23 * (3712 + 2688) + 16384
    assert work.state_bytes(cfg) == 49_082_368
    assert work.kv_bytes_per_position(cfg) == 6144
    assert work.routing(cfg, None) == (16 / 128, 1.0)
    _, b0 = work.decode_step(cfg, [])
    _, b1 = work.decode_step(cfg, [300])
    _, b9 = work.decode_step(cfg, [300] * 9)
    per_slot = 2 * 49_082_368 + 300 * 6144 + 6144 + 2688 * 2
    assert b1 - b0 == per_slot and b9 - b0 == 9 * per_slot
    experts = 23 * 16 * 9_977_856 * 2
    hit_all = _counters(moe_pairs_routed_total=1380, moe_pairs_held_total=172,
                        moe_expert_slots_total=368, moe_experts_hit_total=368)
    hit_none = _counters(moe_pairs_routed_total=1380, moe_pairs_held_total=0,
                         moe_expert_slots_total=368, moe_experts_hit_total=0)
    f_all, b_all = work.decode_step(cfg, [300], run=hit_all)
    f_none, b_none = work.decode_step(cfg, [300], run=hit_none)
    assert b_all - b_none == experts and b_all == b1
    # 6 experts a token x 23 blocks x the held share of the pairs
    assert f_all - f_none == pytest.approx(
        6 * 23 * 172 / 1380 * 2 * 9_977_856)
    outside = work.matmul_params_outside_experts(cfg)
    assert f_none == 2 * (outside + 2688 * 16384) \
        + work.scan_flops_per_token(cfg) + 300 * 6 * 4 * 32 * 128
    assert work.scan_flops_per_token(cfg) == 23 * (5 * 64 * 64 * 128
                                                   + 2 * 4 * 6144)
    # a chunk: one slot's state once, the scan by its real tokens
    fc, bc = work.prefill_chunk(cfg, 200, 256, False, run=hit_none)
    assert fc == 200 * 2 * outside + 200 * work.scan_flops_per_token(cfg) \
        + (200 * 256 + 200 * 201 // 2) * 6 * 4 * 32 * 128
    # 1,200 pairs a block reach all but 1 in 12,000 of the held experts
    _, bc_all = work.prefill_chunk(cfg, 200, 256, False)
    assert bc_all - bc == pytest.approx(experts, rel=1e-3)
    _, bc0 = work.prefill_chunk(cfg, 200, 0, False, run=hit_none)
    assert bc - bc0 == 256 * 6144


def test_a_toy_nemotron_cell_runs_to_a_correct_line(tmp_path):
    """The command itself, on the CPU at the tests' small size, in a scratch
    root: the fourth family through `run.py` to a `correct` line, with the
    readers this PR adds finding what they read."""
    import os
    import subprocess
    from benchmark.tests.util import make_root
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from nemotron_util import cfg
    root = make_root(tmp_path, config="tiny-nemotron", cell="toy.nemo",
                     config_keys=cfg(4, 8),
                     like="nemotron3-nano-ep8.chat-burst")
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--bench-root",
         str(root), "--workload", "toy.nemo", "--seconds", "3", "--seed",
         "3000000013", "--rehearse-cpu", "--trace", "1"],
        capture_output=True, text=True, cwd=str(root), timeout=900,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name, (value, limit) in line["checks"].items():
        assert value <= limit, name
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # every slot's row is stepped whatever is live, today
    assert m["ssm_rows_stepped_share"] == 100.0
    assert 0 < m["ssm_state_rows_peak_pct"] <= 100
    assert 0 < m["nemo.moe_held_pair_share"] < 100
    assert 0 < m["nemo.moe_experts_hit_share"] <= 100
    # the CPU under "auto" keeps the gather body: every page named is read
    assert m["nemo.kv_pages_read_share"] == 100.0
    assert "kv_pages_read_share" not in m and "moe_held_pair_share" not in m
    assert "kv_pool_peak_pct" not in m and "mla_pool_peak_pct" not in m
    # the pool's blocks beside the state rows: which of the two fills
    assert 0 < m["nemo.kv_pool_peak_pct"] <= 100
    assert "sched_iter_ms" in m and "nemo.ttft_p95_ms" in m \
        and "nemo.queue_p95_ms" in m and "nemo.gen_late_p95_ms" in m


def test_a_program_without_the_layer_fails_the_family_at_load(tmp_path):
    """What the parent commit does with the new cell: the family's graph
    names what the program lacks when it is loaded, a KeyError that `run.py`
    turns into exit 2 before any device is asked for."""
    src = (REPO / "benchmark/families/nemotron_h/graph.py").read_text()
    here = tmp_path / "benchmark" / "families" / "nemotron_h"
    here.mkdir(parents=True)
    for part in family.PARTS:
        text = (REPO / f"benchmark/families/nemotron_h/{part}.py").read_text()
        (here / f"{part}.py").write_text(text)
    (here / "graph.py").write_text(src.replace(
        "import Mamba2Layer  # noqa", "import Mamba3Layer  # noqa"))
    with pytest.raises(KeyError, match="nemotron_h.*Mamba2Layer"):
        family.load(tmp_path, {"model_type": "nemotron_h"})
