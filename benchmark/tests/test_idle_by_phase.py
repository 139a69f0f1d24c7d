"""The overlap arithmetic of `tools/idle_by_phase.py`, by hand, and the tool
on the recorded TPU trace (my chip run, PR 25), which dates from before the
program wrote `sched/*` annotations: everything there is `unannotated`."""
from pathlib import Path

import pytest

from benchmark.harness import reducer
from benchmark.tools import idle_by_phase as ibp

TRACE = Path(__file__).resolve().parent / "data" / "tiny_tpu_v5e.xplane.pb"


def test_gaps_are_what_lies_between_program_executions():
    mods = [(0, 10, "a"), (12, 20, "b"), (18, 25, "c"), (25, 30, "d"),
            (40, 41, "e")]
    # 18-25 overlaps 12-20, and 25-30 touches it: neither opens a gap
    assert ibp.idle_gaps(mods) == [(10, 12), (30, 40)]
    assert ibp.idle_gaps([]) == []


def test_a_gap_is_split_over_the_spans_that_overlap_it():
    spans = [(0, 11, "sched/decode_wait"), (11, 14, "sched/decode_read"),
             (14, 15, "sched/accept"), (31, 36, "sched/idle"),
             (36, 37, "sched/admit"), (50, 60, "sched/idle")]
    got = ibp.split_gaps([(10, 12), (30, 40)], spans)
    assert got == {"sched/decode_wait": 1, "sched/decode_read": 1,
                   "sched/idle": 5, "sched/admit": 1, "unannotated": 4}
    assert sum(got.values()) == (12 - 10) + (40 - 30)
    # no span at all, and a span that covers the whole gap
    assert ibp.split_gaps([(3, 7)], []) == {"unannotated": 4}
    assert ibp.split_gaps([(3, 7)], [(0, 100, "sched/idle")]) == \
        {"sched/idle": 4}
    assert ibp.split_gaps([], spans) == {}


def test_the_host_shift_is_what_causality_allows():
    # the device's clock reads 1.0 behind the host's: a program seen at
    # 9.5-10.5 was issued at 10.2 on the host's clock (9.2 on the device's)
    # and reported done at 11.8 (10.8)
    programs = [(9.5, 10.5), (19.3, 20.3), (29.6, 30.6)]
    before = [10.2, 19.9, 30.3]
    after = [11.8, 21.7, 32.0]
    low, high = ibp.causal_bounds(before, after, programs)
    assert low == pytest.approx(-1.3) and high == pytest.approx(-0.7)
    assert low <= -1.0 <= high
    # the trace began after the first program was issued, and ended before
    # the last was reported done: the instants still find their programs
    low, high = ibp.causal_bounds(before[1:], after[:-1], programs)
    assert low == pytest.approx(-1.3) and high == pytest.approx(-0.7)
    assert ibp.causal_bounds([], [], programs) == (-float("inf"),
                                                   float("inf"))
    assert ibp.causal_bounds(before, after, []) == (-float("inf"),
                                                    float("inf"))


def test_the_recorded_trace_has_the_reducers_gaps_and_no_phase():
    r = ibp.report(str(TRACE))
    s = reducer.summarize(str(TRACE))
    assert r["idle_s"] == pytest.approx(sum(v for _, v in s["idle_gaps"]),
                                        rel=1e-6)
    assert r["gaps"] == 16 and r["iterations"] == 0
    assert [name for name, _, _ in r["by_phase"]] == ["unannotated"]
    assert r["named_share"] == 0 and len(r["longest"]) == 5
    # every program there is reported done at least 1.78 ms after it ends,
    # and starts 1.4-1.7 ms BEFORE the runtime issues it: the planes are
    # 1.6 ms apart. A toy's programs follow one another every 2.8 ms, less
    # than twice that, so each issue is matched to the next program and the
    # upper bound is loose (0.75 for -1.49): wider, not wrong
    low, high = r["host_shift_allowed_ms"]
    assert low == pytest.approx(-1.783, abs=1e-3)
    assert high == pytest.approx(0.755, abs=1e-3)
    assert r["host_shift_ms"] == pytest.approx((low + high) / 2)
    assert r["bounds_ms"]["step_launch_wait"] == [-float("inf"),
                                                  float("inf")]
