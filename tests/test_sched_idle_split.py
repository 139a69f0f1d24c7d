"""The benchmark's readers of the scheduler's `sched_iter` records (ISSUE 38):
the ring's iterations laid against a device trace's program executions on a
clock whose origin they do not share. Synthetic records and modules under a
known offset give back a known split; bounds that cross, a run with no trace
and a program that keeps no such record read nothing."""
import random
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.harness import runner  # noqa: E402

T_ON = 5000.0          # ring s at which the trace's clock reads 0, guessed
TRUE = 0.0375          # ... off by 37.5 ms, three iterations and more
MS = 1e-3
# an iteration's phases (offsets ms): a decode dispatch at 2.0; the program
# starts 0.75 ms after it and ends as its wait does, or (every third) 0.1 ms
# before: the tight end of what causality allows is the true offset
PHASES = [("admit", 0.0), ("decode_launch", 1.0), ("decode_wait", 3.0),
          ("decode_read", 9.0), ("accept", 10.0), ("flush", 11.0)]
END, DISPATCH, START, STOP = 12.0, 2.0, 2.75, 9.0


def _late(i):
    """How long before its wait returns iteration i's program ends, ms."""
    return 0.1 if i % 3 == 0 else 0.0


def _read(name, run):
    return runner.reader(REPO, name)(run)


def _cell(n=40, first=5, last=34, seed=0):
    """n iterations on the ring, unbooked gaps of 0-6 ms between them; the
    trace holds the programs of iterations first..last."""
    rng = random.Random(seed)
    gaps = [rng.uniform(0.0, 6.0) for _ in range(n)]
    begins, t = [], T_ON - 0.2
    for g in gaps:
        begins.append(t)
        t += (END + g) * MS
    # the record is written at the iteration's close
    spans = [{"name": "sched_iter", "track": "scheduler", "t": b + END * MS,
              "phases": [(p, o * MS) for p, o in PHASES], "end": END * MS,
              "dispatches": [("decode", 64, DISPATCH * MS)],
              "cpu_s": 0.004} for b in begins]
    modules = [{"name": "jit__step_paged_fn",
                "start_ns": round((b + START * MS - T_ON + TRUE) * 1e9),
                "seconds": (STOP - _late(i) - START) * MS}
               for i, b in enumerate(begins) if first <= i <= last]
    run = {"trace": {"summary": {"modules": modules}, "t_on": T_ON,
                     "t_off": T_ON + 3.0},
           "window": {"spans": spans, "t0": begins[0] - 0.001,
                      "seconds": 45.0, "counters": {},
                      "phase_seconds": {}}}
    # by hand: the first gap counted opens at iteration first+1's begin,
    # the last closes at iteration last-1's end; between them each gap is
    # what is left of the wait, read 1, accept 1, flush 1, the unbooked
    # gap, admit 1, the launch up to the program's start 1.75
    full = range(first + 1, last - 1)
    want = {"launch": 1.75 * (len(full) + 1),
            "read": 2.0 * (len(full) + 1),
            "other": sum(_late(i) + 2.0 for i in full) + 1.0
            + _late(last - 1) + 1.0,
            "unbooked": sum(gaps[i] for i in full)}
    return run, want, begins


def _shares(run):
    return {k: _read(f"idle_{k}_pct", run)
            for k in ("in_launch", "in_read", "unbooked")}


def test_a_known_split_comes_back_under_an_unknown_offset():
    run, want, _ = _cell()
    split = runner.reader(REPO, "idle_in_launch_pct").__globals__["split"]
    got = split(run)
    total = sum(want.values())
    for k, v in want.items():
        assert got[k] == pytest.approx(v * MS, abs=1e-6), k
    assert got["total"] == pytest.approx(total * MS, abs=1e-6)
    # the correction is 37.5 ms, the low end of an interval 0.75 ms wide
    assert got["correction_ms"] == pytest.approx(TRUE * 1e3, abs=1e-3)
    assert got["slack_us"] == pytest.approx(750.0, abs=0.01)
    shares = _shares(run)
    assert shares["in_launch"] == pytest.approx(100 * want["launch"] / total)
    assert shares["in_read"] == pytest.approx(100 * want["read"] / total)
    assert shares["unbooked"] == pytest.approx(
        100 * want["unbooked"] / total)
    assert sum(shares.values()) <= 100.0


def test_a_program_no_dispatch_stamps_is_split_but_does_not_bound():
    run, want, begins = _cell()
    # 0.2 ms programs inside iteration 10's flush and iteration 20's admit:
    # the gaps shrink, the alignment does not move
    for b in (begins[10] + 11.2 * MS, begins[20] + 0.1 * MS):
        run["trace"]["summary"]["modules"].append(
            {"name": "jit__zero_fn",
             "start_ns": round((b - T_ON + TRUE) * 1e9),
             "seconds": 0.2 * MS})
    split = runner.reader(REPO, "idle_in_launch_pct").__globals__["split"]
    got = split(run)
    assert got["other"] == pytest.approx((want["other"] - 0.4) * MS,
                                         abs=1e-6)
    assert got["slack_us"] == pytest.approx(750.0, abs=0.01)


def test_crossing_bounds_and_no_trace_read_nothing():
    run, _, _ = _cell()
    mods = run["trace"]["summary"]["modules"]
    # one program ends 1 ms after the wait for it returned: no shift fits
    mods[12]["seconds"] += 1.25 * MS
    assert _shares(run) == dict.fromkeys(_shares(run), None)
    run, _, _ = _cell()
    run["trace"] = {"dir": "x", "summary": None}
    assert set(_shares(run).values()) == {None}


def test_the_stall_readers():
    run, _, begins = _cell()
    w = run["window"]
    w["spans"][7]["end"] = 0.0504                  # the longest
    # longer, but begun before the window or after it
    w["spans"].append({**w["spans"][3], "t": w["t0"] + 5.0, "end": 9.0})
    w["spans"].append({**w["spans"][3], "t": w["t0"] + 59.0, "end": 9.0})
    assert _read("sched_iter_max_ms", run) == pytest.approx(50.4)
    w["counters"] = {"sched_host_cpu_seconds_total": 0.9}
    w["phase_seconds"] = {"admit": 0.2, "decode_wait": 5.0, "accept": 0.8}
    assert _read("sched_host_cpu_pct", run) == pytest.approx(90.0)


def test_a_program_without_the_record_reads_nothing():
    """The parent's program: no `sched_iter` record, no counter."""
    run, _, _ = _cell()
    run["window"]["spans"] = [{"name": "decode_step", "track": "scheduler",
                               "t": T_ON, "live_slots": 3}]
    run["window"]["phase_seconds"] = {"admit": 0.2, "decode_wait": 5.0}
    for name in ("idle_in_launch_pct", "idle_in_read_pct",
                 "idle_unbooked_pct", "sched_host_cpu_pct",
                 "sched_iter_max_ms"):
        assert _read(name, run) is None, name
