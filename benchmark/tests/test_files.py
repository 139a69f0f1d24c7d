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
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert (b / "metrics" / f"{m['name']}.py").exists()
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(m["bound"] <= 0.1 for m in BENCH["end_to_end"])
    assert BENCH["command"] == ["python3", "benchmark/run.py"]


def test_published_widths_are_kept():
    pub = {"starcoder2-3b": (3072, 24, 2, 12288, 30),
           "starcoder2-7b-d16": (4608, 36, 4, 18432, 16)}
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["intermediate_size"],
                cfg["num_hidden_layers"]) == pub[c["name"]]
        assert cfg["vocab_size"] == 49152
        assert cfg["hidden_size"] // cfg["num_attention_heads"] == 128
    assert json.loads((ROOT / "benchmark/configs/starcoder2-7b-d16.json")
                      .read_text())["published"]["num_hidden_layers"] == 32


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
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / f"{w['traffic']}.json").read_text())
    lim = loadgen.length_limits(mix)
    facts = _facts(1066)
    plan = engine_driver.warm_plan(lim, facts)
    have = set().union(*(engine_driver.programs_of(p, o, facts)
                         for p, o in plan))
    # every request any seed can draw stays inside the warmed family
    for seed in (1, 2, 3_000_000_001):
        for r in loadgen.schedule(mix, seed, 40, 49152):
            need = engine_driver.programs_of(len(r.prompt), r.out_tokens,
                                             facts)
            # a batched decode step runs at the deepest live slot's bucket:
            # any bucket between this request's own and the deepest
            assert need <= have, (len(r.prompt), r.out_tokens, need - have)
    assert len(plan) <= 16 and len(have) <= 24
    assert all(lim["prompt_min"] <= p <= lim["prompt_max"] for p, _ in plan)
