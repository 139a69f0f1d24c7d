"""What decides `correct`, at a size a test can hold: the engine's timed
path (chunked prefill, then decode through the paged cache, slots batched)
agrees with the plain reference; the control (the reference in int8) does
not (nor does the int8 one, at this size); and a run whose tokens are altered where they are produced comes out
not correct."""
import pytest

from benchmark.harness import runner
from benchmark.tests.util import make_root


@pytest.fixture(scope="module")
def ctx(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("bench"))
    return runner.load_cell(root, "tiny.cell")


def _drive(ctx, seed, controls=(), spoil=None):
    """The rest of a run, past the harness's look for a chip."""
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    st = runner.setup(ctx, seed)
    if spoil:
        spoil(st["eng"])
    m = runner.measure(ctx, st, seed, 3.0, False)
    runner.free_engine(st)
    v = runner.compare(ctx, st, m, seed, controls=controls)
    return runner.result(ctx, st, m, v, device, 1.0, False)


@pytest.mark.parametrize("seed", [11, 3_000_000_007, 5])
def test_engine_agrees_with_the_reference_and_the_control_does_not(ctx, seed):
    out = _drive(ctx, seed, controls=("fp8", "int8"))
    limit = ctx["wl"]["check"]["max_gap"]
    h = out["harness"]
    assert out["correct"] is True
    assert h["gap"]["max_gap"] <= limit
    assert h["gap"]["tokens"] >= 64 and h["gap"]["requests"] == 8
    # chunked prefill really crossed chunk boundaries, and slots shared steps
    assert h["dispatches"]["prefill"] > out["attempted"]
    assert h["dispatches"]["decode"] > 0
    # the control fails the same comparison, by a wide margin
    for q in ("fp8", "int8"):
        assert h["control"][q]["max_gap"] > 3 * max(limit, h["gap"]["max_gap"])
    assert h["control"]["fp8"]["mean_gap"] > h["control"]["int8"]["mean_gap"]


def test_a_token_altered_where_it_is_produced_is_not_correct(ctx):
    vocab = ctx["cfg"]["vocab_size"]

    def spoil(eng):
        emit, n = eng._emit, [0]

        def altered(slot, seq, tok):
            n[0] += 1
            return emit(slot, seq, (tok + 1) % vocab if n[0] % 5 == 0
                        else tok)
        eng._emit = altered

    out = _drive(ctx, 13, spoil=spoil)
    assert out["correct"] is False
    assert out["checks"]["max_gap"][0] > out["checks"]["max_gap"][1]
    assert out["checks"]["unfinished"] == [0, 0]


def test_an_unfinished_request_is_failed_and_not_correct(ctx):
    def spoil(eng):
        submit = eng.submit

        def refusing(prompt, n, **kw):
            if kw.get("seed") == 2:
                raise RuntimeError("refused")
            return submit(prompt, n, **kw)
        eng.submit = refusing

    out = _drive(ctx, 17, spoil=spoil)
    assert out["correct"] is False and out["failed"] == 1
