"""CI gate: the repo must stay graftlint-clean (ISSUE 3 satellite;
race pass + runtime happens-before checker: ISSUE 8).

Five layers of enforcement:
  1. the static analyzer over ``deeplearning4j_tpu/`` must report no
     finding beyond the committed baseline — new violations fail CI with
     the exact file:line and remedy in the message;
  2. the static lock-acquisition graph across the threaded modules must
     stay acyclic;
  3. a live serving workload (decode scheduler + micro-batcher + metrics
     scrape) run with instrumented locks must observe only acquisition
     orders consistent with the static graph (the runtime half of the
     deadlock argument);
  4. the CC005/CC006 lockset race pass must run CLEAN with NO baseline
     at all — the repo carries zero accepted race debt, only reviewed
     inline suppressions (each with its GIL-atomicity / single-writer
     rationale in a comment);
  5. the same serving workload re-run under the vector-clock
     happens-before checker (`races.race_audit`) with engine state,
     supervisor-free metrics internals watched must report zero
     violations — the dynamic cross-check that keeps the static lockset
     model honest, exactly as layer 3 cross-checks CC001. (The chaos
     variant — crash/restart under the checker — lives in
     tests/test_chaos.py.)
  Layer 6 (ISSUE 18): the LC resource-lifecycle pass must ALSO run
  clean with no baseline at all, the CLI gate runs with
  --strict-baseline so unreviewed TODO ledger entries fail, and
  tools/lint_gate.sh — the single CI entrypoint over every pack —
  must exit 0 on the tree as committed.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from deeplearning4j_tpu.analysis import (CompileCounter,
                                         concurrency_rule_pack,
                                         crosscheck_lock_order,
                                         jax_rule_pack, lifecycle_rule_pack,
                                         lock_audit, race_audit,
                                         race_rule_pack)
from deeplearning4j_tpu.analysis.concurrency_rules import (build_lock_graph,
                                                           find_cycle)
from deeplearning4j_tpu.analysis.core import Baseline, load_modules
from deeplearning4j_tpu.analysis.lint import (_DEFAULT_BASELINE,
                                              _DEFAULT_TARGET, run_lint)

_THREADED_SCOPE = ["inference", "serving", "datasets", "ui", "util"]


def test_rule_packs_meet_the_contract_floor():
    assert len(jax_rule_pack()) >= 5
    assert len(concurrency_rule_pack()) >= 3
    assert len(race_rule_pack()) >= 2
    assert len(lifecycle_rule_pack()) == 4
    ids = [r.id for r in jax_rule_pack() + concurrency_rule_pack()
           + race_rule_pack() + lifecycle_rule_pack()]
    assert len(ids) == len(set(ids))
    assert {"CC005", "CC006"} <= {r.id for r in race_rule_pack()}
    assert {"LC001", "LC002", "LC003", "LC004"} == \
        {r.id for r in lifecycle_rule_pack()}


def test_graftlint_clean_against_committed_baseline():
    """The CI gate proper: any NEW finding (not in baseline.json) fails.
    To accept debt deliberately, run
    `python -m deeplearning4j_tpu.analysis.lint --update-baseline`
    and commit the reviewed baseline diff; to silence a single line,
    annotate it `# graftlint: disable=<RULE>` with a rationale."""
    findings, errors = run_lint()
    assert not errors, errors
    baseline = Baseline.load(_DEFAULT_BASELINE)
    assert baseline.entries, "committed baseline missing or empty"
    new, _fixed = baseline.diff(findings)
    assert not new, "new graftlint violations:\n" + "\n".join(
        f.format() for f in new)


def test_race_pass_runs_clean_with_no_baseline_at_all():
    """ISSUE 8 acceptance: 0 unsuppressed CC005/CC006 findings across
    the package, with NO baseline entries — every accepted residual
    race is an inline `# graftlint: disable=CC005` whose surrounding
    comment states the GIL-atomicity or single-writer-protocol
    justification. New unsynchronized cross-thread state fails CI here
    with the writer/reader pair and lockset in the message."""
    findings, errors = run_lint(rules=["CC005", "CC006"])
    assert not errors, errors
    assert findings == [], "unsuppressed race findings:\n" + "\n".join(
        f.format() for f in findings)
    # and the committed ledger holds NO race-rule debt either (the gate
    # above is not being saved by baselined entries)
    baseline = Baseline.load(_DEFAULT_BASELINE)
    assert not any(e["rule"] in ("CC005", "CC006")
                   for e in baseline.entries.values())


def test_lifecycle_pass_runs_clean_with_no_baseline_at_all():
    """ISSUE 18 acceptance: 0 unsuppressed LC001-LC004 findings across
    the package with NO baseline entries — resource-lifecycle findings
    in new code gate absolutely, they are never accepted as debt. (The
    pass earned this bar by finding and fixing two real leaks — an
    unclosed trace-fetch response body and an unclosed drain probe —
    before it was turned on.)"""
    findings, errors = run_lint(rules=["LC001", "LC002", "LC003", "LC004"])
    assert not errors, errors
    assert findings == [], "unsuppressed lifecycle findings:\n" + "\n".join(
        f.format() for f in findings)
    baseline = Baseline.load(_DEFAULT_BASELINE)
    assert not any(e["rule"].startswith("LC")
                   for e in baseline.entries.values())


def test_cli_gate_passes_with_strict_baseline():
    """The CI invocation is `--strict-baseline`: beyond new-finding
    detection, any committed ledger entry still carrying the
    auto-generated TODO justification fails the run."""
    from deeplearning4j_tpu.analysis.lint import main as lint_main
    assert lint_main(["--strict-baseline"]) == 0


def test_lint_gate_script_exits_zero_on_the_committed_tree():
    """tools/lint_gate.sh is the single CI entrypoint: full packs
    against the strict baseline plus the LC pack with no baseline.
    It must pass on the tree as committed."""
    gate = Path(_DEFAULT_TARGET).parent / "tools" / "lint_gate.sh"
    assert gate.exists()
    proc = subprocess.run(
        ["sh", str(gate)], capture_output=True, text=True,
        env={**os.environ, "PYTHON": sys.executable})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lint_gate: clean" in proc.stdout


def test_every_baseline_entry_carries_a_reviewed_justification():
    """The debt ledger is only acceptable debt if someone wrote down
    WHY: every entry must carry a non-empty, non-TODO justification."""
    baseline = Baseline.load(_DEFAULT_BASELINE)
    assert baseline.entries
    for fp, e in baseline.entries.items():
        just = e.get("justification", "")
        assert just and not just.startswith("TODO"), \
            f"baseline entry {fp} lacks a reviewed justification"


def test_static_lock_graph_models_the_threaded_modules_and_is_acyclic():
    mods, errors = load_modules(
        [Path(_DEFAULT_TARGET) / d for d in _THREADED_SCOPE])
    assert not errors, errors
    graph = build_lock_graph(mods)
    # the serving stack's locks really are modeled (engine + batcher
    # condvars, metrics instrument locks, server maps, ui storage)
    assert len(graph.locks) >= 8
    assert any(lid.endswith("DecodeScheduler._cond") for lid in graph.locks)
    assert any(lid.endswith("Histogram._lock") for lid in graph.locks)
    assert graph.edges, "no acquisition-order edges modeled"
    assert find_cycle(graph.edge_set) is None, \
        f"static lock-order cycle: {find_cycle(graph.edge_set)}"


def test_runtime_lock_orders_match_static_graph_on_live_serving():
    """Instrumented-lock mode over a real mixed workload: every observed
    held->acquired edge between statically-known locks must be consistent
    (combined static+observed graph acyclic). The workload deliberately
    crosses the known lock layers: scheduler condvar -> metrics
    instruments, batcher condvar -> metrics instruments. The scheduler
    runs with the prefix KV pool enabled, and the run must also respect
    the jit-program budgets (decode/prefill/admit AND the kvpool
    restore/publish families registered in CompileCounter.for_scheduler)."""
    mods, errors = load_modules(
        [Path(_DEFAULT_TARGET) / d for d in _THREADED_SCOPE])
    assert not errors
    graph = build_lock_graph(mods)

    with lock_audit() as auditor:
        from deeplearning4j_tpu.inference import (DecodeScheduler,
                                                  MetricsRegistry,
                                                  MicroBatcher)
        from deeplearning4j_tpu.models.zoo import transformer_lm
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        V = 13
        conf = transformer_lm(vocab_size=V, d_model=16, n_heads=2,
                              n_blocks=2, rope=True)
        for vert in conf.vertices.values():
            layer = getattr(vert, "layer", None)
            if layer is not None and hasattr(layer, "max_cache_len"):
                layer.max_cache_len = 96
        net = ComputationGraph(conf).init()
        m = MetricsRegistry()
        eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                              kv_pool_mb=1.0, kv_block=8,
                              metrics=m).start()
        audit = CompileCounter.for_scheduler(eng)
        try:
            rng = np.random.default_rng(0)
            repeat = list(rng.integers(0, V, 17))
            handles = [eng.submit(p, 3)
                       for p in ([list(rng.integers(0, V, 9)), repeat,
                                  list(rng.integers(0, V, 4))])]
            for h in handles:
                h.result(120)
            eng.submit(repeat, 3).result(120)  # prefix hit -> table remap
        finally:
            eng.stop()
        audit.assert_within_budget()
        assert audit.count("restore_setpos") == 1
        assert m.counter("prefix_cache_hits_total").value >= 1
        mb = MicroBatcher(lambda a: a * 2, max_batch=8, metrics=m).start()
        try:
            assert (np.asarray(mb.predict(np.ones((2, 3)))) == 2.0).all()
        finally:
            mb.stop()
        m.snapshot()  # the /metrics scrape path, racing nothing by now

    observed = auditor.observed_edges()
    known = graph.by_site()
    mapped = {(known[a], known[b]) for a, b in observed
              if a in known and b in known and known[a] != known[b]}
    # non-vacuous: the cross-layer orders were really exercised
    assert any("DecodeScheduler._cond" in a for a, _ in mapped), mapped
    violations, unmodeled = crosscheck_lock_order(observed, graph)
    assert not violations, violations
    # every observed cross-lock order was predicted by the static pass
    assert not unmodeled, \
        f"runtime lock orders the static graph missed: {unmodeled}"


def test_runtime_happens_before_checker_clean_on_live_serving():
    """Layer 5: the decode scheduler + micro-batcher workload re-run
    under the vector-clock checker. Watched state is the code whose
    discipline CLAIMS ordering — scheduler-thread-only engine state
    (`_states`, `_prefill_next`, `_emitted_this_iter`) and the
    lock-guarded histogram internals the CC004 fix consolidated — so a
    future edit that lets a second thread touch any of it without a
    sanctioned channel fails HERE with the exact access pair, not in a
    once-a-month flaky test. Deliberately lock-free state (heartbeat,
    readiness flags — the reviewed CC005 suppressions) is NOT watched:
    the runtime checker asserts the invariants the static pass accepts,
    not the ones it waived."""
    with race_audit() as det:
        from deeplearning4j_tpu.inference import (DecodeScheduler,
                                                  MetricsRegistry,
                                                  MicroBatcher)
        from deeplearning4j_tpu.models.zoo import transformer_lm
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        V = 13
        conf = transformer_lm(vocab_size=V, d_model=16, n_heads=2,
                              n_blocks=2, rope=True)
        for vert in conf.vertices.values():
            layer = getattr(vert, "layer", None)
            if layer is not None and hasattr(layer, "max_cache_len"):
                layer.max_cache_len = 96
        net = ComputationGraph(conf).init()
        m = MetricsRegistry()
        eng = DecodeScheduler(net, V, n_slots=2, prefill_chunk=16,
                              kv_pool_mb=1.0, kv_block=8,
                              metrics=m).start()
        det.watch(eng, ["_states", "_prefill_next", "_emitted_this_iter"],
                  label="engine")
        hist = m.histogram("decode_step_time_sec")
        det.watch(hist, ["_count", "_sum", "_min", "_max", "_counts"],
                  label="decode_step_time_sec")
        rng = np.random.default_rng(0)
        repeat = list(rng.integers(0, V, 17))
        try:
            handles = [eng.submit(p, 3)
                       for p in ([list(rng.integers(0, V, 9)), repeat,
                                  list(rng.integers(0, V, 4))])]
            for h in handles:
                h.result(120)
            eng.submit(repeat, 3).result(120)  # prefix hit -> table remap
        finally:
            eng.stop()  # joins the scheduler thread: orders the reads below
        assert hist.count > 0 and hist.snapshot()["count"] > 0
        mb = MicroBatcher(lambda a: a * 2, max_batch=8, metrics=m).start()
        try:
            assert (np.asarray(mb.predict(np.ones((2, 3)))) == 2.0).all()
        finally:
            mb.stop()
        m.snapshot()
    assert det.violations == [], det.format_violations()
    assert det.tracking  # the workload really ran armed, not fast-pathed
