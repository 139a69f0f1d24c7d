"""BENCHMARK.json against the files it names, and the warm-up plan against
the engine's bucket rules."""
import json
import re
from pathlib import Path

import pytest

from benchmark.harness import engine_driver, loadgen

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_name_leads_to_its_file():
    b = ROOT / "benchmark"
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert "assumed" in cfg and "departures" in cfg
    for w in BENCH["workloads"]:
        assert (b / "traffic" / f"{w['traffic']}.json").exists()
        wl = json.loads((b / "workloads" / f"{w['name']}.json").read_text())
        assert {"engine", "check"} <= set(wl)
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert (b / "metrics" / f"{m['name']}.py").exists()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    assert BENCH["command"] == ["python3", "benchmark/run.py"]


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_what_its_layers_move(cell):
    """A metric that lists its cells names cells that are there; a cell
    reports `setup_s`, another end-to-end metric and a per-layer metric; and
    every per-layer metric it reports moves an end-to-end metric it reports
    (why `sc2-3b.chat`, which has no end-to-end `ttft_p95_ms`, has readers
    of its own under `chat.`)."""
    from benchmark.harness.runner import metric_names
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    e2e = {n for n, _ in metric_names(BENCH, cell, False)}
    layer = {n for n, _ in metric_names(BENCH, cell, True)}
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    assert {moves[n] for n in layer} <= e2e - {"setup_s"}


# what `reduced` may never name (the contract): a width of any kind
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj\w*)_size$|_dim$|"
                   r"_rank$|head_size|expand|experts_per_tok")


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_published_widths_are_kept(entry):
    """Each configuration brings the source's numbers in a file of its own
    beside it, `<file>.published.json`: the configuration equals them key
    for key, but for what `reduced` lists, which is smaller, no width, and
    stands with the source's value in the file's own `published` block."""
    path = ROOT / entry["file"]
    cfg = json.loads(path.read_text())
    source = json.loads(path.with_suffix(".published.json").read_text())
    assert source.pop("source") == entry["source"] == cfg["source"]
    assert {"hidden_size", "num_hidden_layers", "vocab_size"} <= set(source)
    published = cfg.get("published", {})
    assert sorted(published) == sorted(cfg["reduced"])
    for key, value in source.items():
        if key in cfg["reduced"]:
            assert not WIDTH.search(key), key
            assert published[key] == value and cfg[key] < value, key
        else:
            assert cfg[key] == value, key
    assert isinstance(cfg["model_type"], str) and cfg["model_type"]
    assert cfg["hidden_size"] % cfg["num_attention_heads"] == 0
    assert cfg["num_attention_heads"] % cfg["num_key_value_heads"] == 0
    assert any("head width" in a and
               str(cfg["hidden_size"] // cfg["num_attention_heads"]) in a
               for a in cfg["assumed"])


def _facts(capacity):
    tb, b = [], 1
    while b < capacity:
        tb.append(b)
        b *= 2
    return {"kv_block": 64, "prefill_chunk": 256,
            "prefill_buckets": [16, 32, 64, 128, 256],
            "table_buckets": tb + [capacity]}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_warm_plan_reaches_every_program_the_mix_can(cell):
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    vocab = json.loads((ROOT / entry["file"]).read_text())["vocab_size"]
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    lim = loadgen.length_limits(mix)
    facts = _facts(1066)
    plan = engine_driver.warm_plan(lim, facts)
    have = set().union(*(engine_driver.programs_of(p, o, facts)
                         for p, o in plan))
    # every request any seed can draw stays inside the warmed family
    for seed in (1, 2, 3_000_000_001):
        for r in loadgen.schedule(mix, seed, 40, vocab):
            need = engine_driver.programs_of(len(r.prompt), r.out_tokens,
                                             facts)
            # a batched decode step runs at the deepest live slot's bucket:
            # any bucket between this request's own and the deepest
            assert need <= have, (len(r.prompt), r.out_tokens, need - have)
    assert len(plan) <= 16 and len(have) <= 24
    assert all(lim["prompt_min"] <= p <= lim["prompt_max"] for p, _ in plan)
