"""An architecture arrives as files. In a scratch root a second family (a
post-LN block with a ReLU feed-forward part and no FFN biases: another
graph, other weight names, another reference, another count of work) is
added as new files only, with its configuration, its cell and two per-layer
metrics, one over `window["counters"]`; the command runs it to a `correct`
line. No copied file of `benchmark/` is edited. Beside it: what the loader
refuses, and the checksums that pin StarCoder2's move into its family."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from benchmark.harness import family, loadgen, runner
from benchmark.tests.util import BENCH, REPO, TINY, make_root

POSTLN = {p: (TINY.parent / "postln" / f"{p}.py").read_text()
          for p in family.PARTS}
STARCODER2 = {p: (BENCH / "families" / "starcoder2" / f"{p}.py").read_text()
              for p in family.PARTS}

# a per-layer metric of a later PR's: a counter of the program that the
# harness has never heard of, read from the window's differences
PREFILLED = '''"""Prompt tokens the scheduler prefilled inside the window."""


def read(run):
    return float(run["window"]["counters"]["prefill_tokens_total"])
'''
# and one that shows the family's work being handed the run and the interval
STEPS_COUNTED = '''"""Decode steps the family's `work.decode_step` found among the spans of
the window it was handed: its bytes over one step's."""
from benchmark.harness import facts


def read(run):
    w = run["window"]
    t0, t1 = w["t0"], w["t0"] + w["seconds"]
    depths = facts.decode_depths(run["rows"], t0, t1)
    work, cfg = run["family"].work, run["cfg"]
    _, many = work.decode_step(cfg, depths, run=run, t_lo=t0, t_hi=t1)
    _, one = work.decode_step(cfg, depths)
    _, step = work.decode_step(cfg, [])
    return 1.0 + (many - one) / step
'''


def _root(tmp, model_type, families):
    return make_root(
        tmp, config=f"tiny-{model_type}", cell="toy.cell",
        config_keys={"model_type": model_type}, families=families,
        extra_metrics=[("prefilled_in_window", PREFILLED),
                       ("decode_steps_counted", STEPS_COUNTED)])


def _run(root, *extra):
    return subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--bench-root",
         str(root), "--workload", "toy.cell", "--seconds", "3", "--seed",
         "3000000005", "--rehearse-cpu", *extra],
        capture_output=True, text=True, cwd=str(root), timeout=600,
        env={**os.environ, "PYTHONPATH": str(REPO)})


@pytest.fixture(scope="module")
def postln_root(tmp_path_factory):
    return _root(tmp_path_factory.mktemp("postln"), "postln",
                 {"postln": POSTLN})


def test_no_copied_file_is_edited(postln_root):
    """What the scratch root adds is new files; every file it shares with
    the repository's `benchmark/` is byte for byte the repository's."""
    added = []
    for path in sorted((postln_root / "benchmark").rglob("*")):
        if not path.is_file() or "__pycache__" in path.parts:
            continue
        rel = path.relative_to(postln_root / "benchmark")
        if (BENCH / rel).exists():
            assert path.read_bytes() == (BENCH / rel).read_bytes(), rel
        else:
            added.append(str(rel))
    assert added == [
        "configs/tiny-postln.json", "families/postln/graph.py",
        "families/postln/reference.py", "families/postln/weights.py",
        "families/postln/work.py", "metrics/decode_steps_counted.py",
        "metrics/prefilled_in_window.py", "traffic/tiny-mix.json",
        "workloads/toy.cell.json"]


def test_a_second_family_runs_to_a_correct_line(postln_root):
    p = _run(postln_root, "--trace", "1")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 12
    for name, (value, limit) in line["checks"].items():
        assert value <= limit, name
    assert line["harness"]["gap"]["requests"] == 8
    m = line["metrics"]
    # the counter's difference over the window: the 12 prompts and not the
    # warm-up's 114 tokens before them (the last prompt may still be open)
    mix = json.loads((postln_root / "benchmark" / "traffic"
                      / "tiny-mix.json").read_text())
    prompts = [len(r.prompt) for r in loadgen.schedule(
        mix, 3000000005, 3, 4096)]
    assert sum(prompts) - max(prompts) <= \
        m["prefilled_in_window"]["value"] <= sum(prompts)
    # the family's work read the window's `decode_step` spans
    assert m["decode_steps_counted"]["value"] == \
        line["harness"]["dispatches"]["decode"] > 1
    assert {"sched_iter_ms", "decode_batch_mean", "kv_pool_peak_pct"} <= set(m)


def test_the_toy_family_is_not_starcoder2(postln_root):
    """Another tree, another function: StarCoder2's reference cannot even
    take the toy's weights, and the toy's does not agree with the engine
    running StarCoder2's graph."""
    ctx = runner.load_cell(postln_root, "toy.cell")
    sc2 = family.load(REPO, {"model_type": "starcoder2"})
    import jax.numpy as jnp
    cfg = ctx["cfg"]
    p = ctx["family"].weights.make_params(cfg, 3, jnp.float32)
    ids = jnp.asarray(np.arange(24, dtype=np.int32).reshape(2, 12))
    pos = jnp.asarray(np.array([[3, 11], [5, 7]], np.int32))
    with pytest.raises(KeyError):
        sc2.reference.logits_at(p, cfg, ids, pos)
    theirs = sc2.reference.logits_at(
        sc2.weights.make_params(cfg, 3, jnp.float32), cfg, ids, pos)
    ours = ctx["family"].reference.logits_at(p, cfg, ids, pos)
    assert ours.shape == theirs.shape == (2, 2, cfg["vocab_size"])
    assert float(jnp.abs(ours - theirs).max()) > 0.1
    assert ctx["family"].work.param_count(cfg) != sc2.work.param_count(cfg)


def test_weights_that_do_not_match_the_graph_stop_at_build_net(
        tmp_path_factory):
    """A family whose weights and graph disagree (here StarCoder2's tree,
    under StarCoder2's layer names, against the toy's graph) is stopped at
    set-up, by the name of the first layer that finds no weights of its
    shape."""
    cut = "def graph_tree"
    graph = POSTLN["graph"].split(cut)[0] + cut + \
        STARCODER2["graph"].split(cut)[1]
    root = _root(tmp_path_factory.mktemp("mixed"), "mixed",
                 {"mixed": {**STARCODER2, "graph": graph}})
    p = _run(root, "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "do not match the graph" in p.stderr


def test_a_model_type_with_no_directory_exits_2_and_names_it(
        tmp_path_factory):
    root = _root(tmp_path_factory.mktemp("none"), "afmoe", None)
    p = _run(root, "--trace", "0")
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "cannot load the cell" in p.stderr
    assert "benchmark/families/afmoe/" in p.stderr


def test_an_engine_key_the_scheduler_does_not_know_is_its_typeerror(
        postln_root, tmp_path):
    """The workload's `engine` block goes to `DecodeScheduler` whole."""
    ctx = runner.load_cell(postln_root, "toy.cell")
    ctx["wl"] = {**ctx["wl"], "engine": {**ctx["wl"]["engine"],
                                         "window_pool_mb": 1}}
    with pytest.raises(TypeError, match="window_pool_mb"):
        runner.setup(ctx, 1)


# -- the loader -------------------------------------------------------------

@pytest.mark.parametrize("name", ["starcoder2-3b", "starcoder2-7b-d16"])
def test_loader_resolves_the_real_configurations(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    fam = family.load(REPO, cfg)
    assert fam.name == cfg["model_type"]
    assert fam.path == BENCH / "families" / cfg["model_type"]
    for part, functions in family.PARTS.items():
        for f in functions:
            assert callable(getattr(getattr(fam, part), f))


def test_unknown_model_type_names_the_directory_to_add():
    with pytest.raises(KeyError, match="benchmark/families/afmoe/"):
        family.load(REPO, {"model_type": "afmoe"})
    with pytest.raises(KeyError, match="model_type"):
        family.load(REPO, {"hidden_size": 64})


@pytest.mark.parametrize("part", sorted(family.PARTS))
def test_a_family_lacking_a_file_or_a_function_fails_at_load(tmp_path, part):
    here = tmp_path / "benchmark" / "families" / "half"
    here.mkdir(parents=True)
    for p, src in POSTLN.items():
        if p != part:
            (here / f"{p}.py").write_text(src)
    with pytest.raises(KeyError, match=f"families/half/{part}.py"):
        family.load(tmp_path, {"model_type": "half"})
    gone = family.PARTS[part][-1]
    (here / f"{part}.py").write_text(
        POSTLN[part].replace(f"def {gone}(", f"def _{gone}("))
    with pytest.raises(KeyError, match=gone):
        family.load(tmp_path, {"model_type": "half"})


def test_no_shared_module_knows_a_family():
    """The acceptance grep, kept as a test: outside `families/` nothing
    names StarCoder2 or a leaf of its block, comments apart."""
    import io
    import re
    import tokenize
    words = re.compile(r"starcoder2|\bwq\b|w_up|ln1_g|intermediate_size")
    files = [BENCH / "run.py"]
    for d in ("harness", "metrics", "tools"):
        files += sorted((BENCH / d).glob("*.py"))
    for path in files:
        for tok in tokenize.generate_tokens(
                io.StringIO(path.read_text()).readline):
            if tok.type in (tokenize.NAME, tokenize.STRING) \
                    and not tok.string.startswith(('"""', "'''")):
                assert not words.search(tok.string), (path.name, tok.start)


# -- the move, pinned on the parent of PR 28 (commit 6298da9) ---------------

def _digest(tree):
    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


PINNED_WEIGHTS = {
    (7, "float32"):
        "820f6f8b2b089b54ecf25a8c346e727fb773b3dcf069d37a2a9ff756ef6b1fca",
    (7, "bfloat16"):
        "944c408775f876edf207e0042d6c35ea56f2be6ebcb6a141e81c4e9a260c9474",
    (3_000_000_011, "float32"):
        "ae173251a592fb21b39ad5958c7c9441700e998afa5cab26bb562c4466106776",
    (3_000_000_011, "bfloat16"):
        "31d3114a75d0b39f9ff28c66c23be7e63f3e4bc13cdbdaf6192b82f827df0670",
}
PINNED_LOGITS = {
    None: "e4ca06ed715bbf8b5bb7a1634e90fe928e4800bf7954200de4919197dee7db57",
    "fp8": "8a9c004f7b4786ed9db9b66b30f3e0b29556b75c6c308519235f244efb1d544a",
    "int8": "70bcd8a2f277dbbbacb0ae7dd92b3e8faf51e8b1cf1e6ba53452c6e6ec41bea4",
}


@pytest.fixture(scope="module")
def tiny():
    cfg = json.loads((TINY / "config.json").read_text())
    return cfg, family.load(REPO, cfg)


@pytest.mark.parametrize("seed,dtype", sorted(PINNED_WEIGHTS))
def test_the_move_draws_the_same_weights(tiny, seed, dtype):
    import jax.numpy as jnp
    cfg, fam = tiny
    tree = fam.weights.make_params(cfg, seed, jnp.dtype(dtype))
    assert _digest(tree) == PINNED_WEIGHTS[seed, dtype]


@pytest.mark.parametrize("quant", [None, "fp8", "int8"])
def test_the_move_keeps_the_reference_s_logits(tiny, quant):
    """One padded batch (rows of 48, 30 and 17 real ids), and both control
    precisions, which now come from `harness/precision.py`."""
    import jax.numpy as jnp
    cfg, fam = tiny
    p = fam.weights.make_params(cfg, 3_000_000_011, jnp.float32)
    ids = np.random.default_rng(5).integers(
        0, cfg["vocab_size"], (3, 48)).astype(np.int32)
    ids[1, 30:] = 0
    ids[2, 17:] = 0
    pos = np.array([[40, 41, 47, 0], [20, 29, 0, 0], [16, 0, 0, 0]], np.int32)
    lg = np.asarray(fam.reference.logits_at(
        p, cfg, jnp.asarray(ids), jnp.asarray(pos), quant=quant))
    assert lg.dtype == np.float32 and lg.shape == (3, 4, cfg["vocab_size"])
    assert hashlib.sha256(lg.tobytes()).hexdigest() == PINNED_LOGITS[quant]
