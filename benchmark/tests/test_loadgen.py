import json
from pathlib import Path

import pytest

from benchmark.harness import loadgen

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_inputs(name):
    a = loadgen.schedule(_mix(name), 3_000_000_001, 10, 49152)
    b = loadgen.schedule(_mix(name), 3_000_000_001, 10, 49152)
    assert [(r.due_s, r.prompt, r.out_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.out_tokens) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_change_the_tokens_and_not_the_work(name):
    mix = _mix(name)
    a = loadgen.schedule(mix, 1, 10, 49152)
    b = loadgen.schedule(mix, 3_000_000_002, 10, 49152)
    assert all(x.prompt != y.prompt for x, y in zip(a, b))
    assert [(x.due_s, len(x.prompt), x.out_tokens) for x in a] == \
        [(y.due_s, len(y.prompt), y.out_tokens) for y in b]
    # another order of the same set comes from the mix's own order_seed
    c = loadgen.schedule({**mix, "order_seed": 1}, 1, 10, 49152)
    assert [len(x.prompt) for x in c] != [len(x.prompt) for x in a]
    assert sorted(len(x.prompt) for x in c) == sorted(len(x.prompt) for x in a)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_and_arrivals_keep_to_the_file(name):
    mix = _mix(name)
    lim = loadgen.length_limits(mix)
    reqs = loadgen.schedule(mix, 7, 20, 49152)
    assert len(reqs) == round(mix["arrival"]["rate_per_s"] * 20)
    assert all(0 < r.due_s < 20 for r in reqs)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    for r in reqs:
        assert lim["prompt_min"] <= len(r.prompt) <= lim["prompt_max"]
        assert 1 <= r.out_tokens <= lim["out_max"]
        assert len(r.prompt) + r.out_tokens <= lim["total_max"]
        assert all(0 <= t < 49152 for t in r.prompt[:8])


def test_bursts_and_shared_prefixes_are_data():
    mix = _mix(MIXES[0])
    mix["arrival"] = {"process": "gamma", "cv": 3.0, "rate_per_s": 5.0}
    mix["shared_prefix"] = {"groups": 2, "share": 1.0,
                            "tokens": {"dist": "constant", "value": 32}}
    reqs = loadgen.schedule(mix, 5, 20, 1000)
    heads = {tuple(r.prompt[:32]) for r in reqs}
    assert len(heads) == 2
    gaps = [b.due_s - a.due_s for a, b in zip(reqs, reqs[1:])]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert cv > 1.8
