"""`RoutedExpertsLayer` (ISSUE 34) at the tests' small size (hidden 64, 16
experts of width 32, 2 a token, sigmoid scores normalised over the chosen,
scale 2.5), seeded weights, against the `axk1` family's plain reference: the
uncut layer, the shares that add up to it, no token dropped however uneven
the routing, and the grouped path against the dense masked loop."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from axk1_util import CFG, load

from deeplearning4j_tpu.nn.conf.layers import RoutedExpertsLayer
from deeplearning4j_tpu.ops import grouped_matmul
from deeplearning4j_tpu.nn.layers.base import impl_for

D, E, K = CFG["hidden_size"], 16, 2


@pytest.fixture(scope="module")
def small():
    fam, params, net = load()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 37, D))
    return fam, params["blocks"][1], net._impls["moe1"], net.params["moe1"], x


def _share(first, count):
    return impl_for(RoutedExpertsLayer(
        n_in=D, n_out=D, n_experts=E, held=(first, count), top_k=K,
        scoring="sigmoid", norm_topk=True, scale=2.5, width=32,
        activation="identity"))


def _cut(lp, first, count):
    return {"Wr": lp["Wr"], **{k: lp[k][first:first + count]
                               for k in ("Wg", "Wu", "Wd")}}


def _ref(fam, p, x, first=0, count=E, shared=True):
    m = {"k": K, "held": count, "first": first, "scoring": "sigmoid",
         "norm_topk": True, "scale": 2.5}
    p = {**p, **{k: p[k][first:first + count]
                 for k in ("we_gate", "we_up", "we_down")}}
    with jax.default_matmul_precision("highest"):
        y, _ = fam.reference._routed(x, p, m, None)
        return y if shared else y - fam.reference._gated(x, p, "ws", "bs",
                                                         None)


def test_the_uncut_layer_is_the_reference(small):
    fam, p, impl, lp, x = small
    with jax.default_matmul_precision("highest"):
        got, var = impl.forward(lp, x)
    want = _ref(fam, p, x, shared=False)
    # float32 both sides: measured 5e-7
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-6
    assert float(jnp.abs(want).max()) > 0.1
    counts = np.asarray(var["routing_counts"])
    assert counts.shape == (E,) and counts.sum() == 2 * 37 * K


@pytest.mark.parametrize("shares", [[(0, 4), (4, 4), (8, 4), (12, 4)],
                                    [(0, 12), (12, 4)], [(0, 1), (1, 15)]],
                         ids=["4x4", "12+4", "1+15"])
def test_the_shares_add_up_to_the_uncut_layer(small, shares):
    """What every share gives, with the shared expert (which every device
    computes alike) counted once, is the uncut reference's whole layer; and
    the shares' routing counts are the uncut layer's, side by side."""
    fam, p, impl, lp, x = small
    total, counts = 0.0, []
    with jax.default_matmul_precision("highest"):
        for first, count in shares:
            y, var = _share(first, count).forward(_cut(lp, first, count), x)
            part = _ref(fam, p, x, first, count, shared=False)
            assert np.abs(np.asarray(y) - np.asarray(part)).max() < 5e-6
            total = total + y
            counts += np.asarray(var["routing_counts"]).tolist()
        total = total + fam.reference._gated(x, p, "ws", "bs", None)
        _, whole = impl.forward(lp, x)
    assert np.abs(np.asarray(total)
                  - np.asarray(_ref(fam, p, x))).max() < 1e-5
    assert counts == np.asarray(whole["routing_counts"]).tolist()


@pytest.mark.parametrize("tokens", [5, 48, 300])
def test_every_token_choosing_one_expert_drops_none(small, tokens):
    """The router made to send every token to experts 6 and 9: all the
    pairs of a share fall on one expert (300 rows: three tiles of it), none
    is dropped, and a share that holds neither returns zeros."""
    _, _, _, lp, _ = small
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, D))
    wr = jnp.zeros((D, E)).at[:, 6].set(1e3 * jnp.sign(x[0, 0])) \
        .at[:, 9].set(1e3 * jnp.sign(x[0, 0]))
    x = jnp.abs(x) * jnp.sign(x[0, 0])        # every row scores both high
    lp = {**lp, "Wr": wr}
    with jax.default_matmul_precision("highest"):
        for first, count, want in ((4, 4, [0, 0, tokens, 0]),
                                   (8, 4, [0, tokens, 0, 0]),
                                   (10, 6, [0] * 6)):
            share, cut = _share(first, count), _cut(lp, first, count)
            y, var = share.forward(cut, x)
            assert np.asarray(var["routing_counts"]).tolist() == want
            dense, _ = share.forward(cut, x, train=True)
            assert np.abs(np.asarray(y) - np.asarray(dense)).max() < 5e-6
            if any(want):
                e = want.index(tokens)
                one = 1.25 * share._expert(cut, e, x[0])
                assert np.abs(np.asarray(y[0]) - np.asarray(one)).max() < 5e-6
                assert float(jnp.abs(y).min(axis=-1).max()) > 0
            else:
                assert not np.asarray(y).any()


@pytest.mark.parametrize("tile", [128, 8])
@pytest.mark.parametrize("masked", [False, True], ids=["all", "masked"])
@pytest.mark.parametrize("shape", [(1, 5), (3, 16), (2, 150)])
def test_the_grouped_path_is_the_dense_masked_loop(small, shape, masked,
                                                   tile, monkeypatch):
    """Sorted tiles of one expert each against every expert over every
    token under a mask; lanes the feature mask holds off route nowhere."""
    _, _, _, lp, _ = small
    monkeypatch.setattr(grouped_matmul, "_ROWS", tile)
    x = jax.random.normal(jax.random.PRNGKey(11), shape + (D,))
    mask = None
    if masked:
        mask = (jnp.arange(shape[0] * shape[1]) % 3 > 0).reshape(shape) \
            .astype(jnp.float32)
    share, cut = _share(2, 9), _cut(lp, 2, 9)
    with jax.default_matmul_precision("highest"):
        got, var = jax.jit(lambda p, a: share.forward(p, a, mask=mask))(
            cut, x)
        want, none = share.forward(cut, x, train=True, mask=mask)
    assert "routing_counts" not in none
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-6
    idx, _ = share.route(cut, x.reshape(-1, D))
    ours = (idx >= 2) & (idx < 11)
    if masked:
        ours &= (mask.reshape(-1) > 0)[:, None]
        off = np.asarray(got).reshape(-1, D)[np.asarray(mask).reshape(-1) == 0]
        assert not off.any()
    want_counts = np.bincount(np.asarray(idx)[np.asarray(ours)] - 2,
                              minlength=9)
    assert np.asarray(var["routing_counts"]).tolist() == want_counts.tolist()


def _layer(gated, d=D, width=32, held=(0, 4), key=3):
    """A share of 16 experts, 2 a token: SwiGLU, or plain relu2 with the
    selection bias; its own seeded float32 weights."""
    kw = {} if gated else dict(gated=False, expert_activation="relu2",
                               selection_bias=True)
    share = impl_for(RoutedExpertsLayer(
        n_in=d, n_out=d, n_experts=E, held=held, top_k=K, scale=2.5,
        width=width, activation="identity", **kw))
    return share, share.init_params(jax.random.PRNGKey(key))


def _routed_to(lp, x, experts):
    """The router made to send every token to ``experts``: every row
    scores them highest, as above."""
    x = jnp.abs(x) * jnp.sign(x[0, 0])
    wr = jnp.zeros_like(lp["Wr"])
    for e in experts:
        wr = wr.at[:, e].set(1e3 * jnp.sign(x[0, 0]))
    return {**lp, "Wr": wr}, x


@pytest.mark.parametrize("gated", [True, False], ids=["swiglu", "relu2"])
@pytest.mark.parametrize("case", ["none_held", "one_expert_tiles",
                                  "unaligned_width", "masked_lanes"])
def test_the_kernel_path_is_the_dense_loop(case, gated):
    """The grouped-matmul path against `_dense` (every held expert over
    every token under a mask), in float32: no pair held (exactly zero, no
    NaN from the rows the kernel leaves unset); every pair of the share on
    one expert, 300 rows over three row tiles; a stack whose width is not
    a multiple of 128 at a depth that is (hidden 128, width 72: read
    transposed, as the TPU lays it out); a feature mask holding a third of
    the lanes off. The routing counts are the router's own, pair for
    pair."""
    d, width, mask = D, 32, None
    if case == "unaligned_width":
        d, width = 128, 72
    share, lp = _layer(gated, d=d, width=width)
    tokens = 300 if case == "one_expert_tiles" else 37
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, d))
    if case == "none_held":
        lp, x = _routed_to(lp, x, (9, 12))
    elif case == "one_expert_tiles":
        lp, x = _routed_to(lp, x, (1, 9))
    elif case == "masked_lanes":
        mask = (jnp.arange(tokens) % 3 > 0).reshape(1, tokens) \
            .astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, var = jax.jit(lambda p, a: share.forward(p, a, mask=mask))(lp, x)
        want, _ = share.forward(lp, x, train=True, mask=mask)
    got = np.asarray(got)
    assert not np.isnan(got).any()
    assert np.abs(got - np.asarray(want)).max() < 5e-6
    idx, _ = share.route(lp, x.reshape(-1, d))
    ours = np.asarray(idx) < 4
    if mask is not None:
        ours = ours & (np.asarray(mask).reshape(-1, 1) > 0)
        assert not got.reshape(-1, d)[np.asarray(mask).reshape(-1) == 0].any()
    counts = np.asarray(var["routing_counts"]).tolist()
    assert counts == np.bincount(np.asarray(idx)[ours], minlength=4).tolist()
    if case == "none_held":
        assert counts == [0, 0, 0, 0] and not got.any()
    elif case == "one_expert_tiles":
        assert counts == [0, tokens, 0, 0] and np.abs(got).min(-1).max() > 0
        rows = grouped_matmul.row_tile(tokens * K)
        assert grouped_matmul.tile_visits(np.asarray(counts), rows).sum() == 3
    else:
        assert np.abs(got).max() > 1e-3


def test_the_conf_round_trips_and_refuses_a_wrong_share():
    from deeplearning4j_tpu.nn.conf import serde
    conf = RoutedExpertsLayer(n_in=8, n_out=8, n_experts=192, held=(0, 12),
                              top_k=8, scale=2.5, width=4)
    back = serde.from_json(serde.to_json(conf))
    assert back == conf and back.held == (0, 12)
    with pytest.raises(ValueError, match="held"):
        impl_for(RoutedExpertsLayer(n_in=8, n_out=8, n_experts=16, top_k=2,
                                    width=4, held=(12, 8))).init_params(
            jax.random.PRNGKey(0))
    every = impl_for(RoutedExpertsLayer(n_in=8, n_out=8, n_experts=4,
                                        top_k=1, width=4))
    shapes = {k: v.shape for k, v in
              every.init_params(jax.random.PRNGKey(0)).items()}
    assert shapes == {"Wr": (8, 4), "Wg": (4, 8, 4), "Wu": (4, 8, 4),
                      "Wd": (4, 4, 8)}


@pytest.mark.parametrize("left_out", ["n_experts", "top_k", "width"])
def test_the_model_s_numbers_have_no_default(left_out):
    given = {k: v for k, v in dict(n_experts=4, top_k=1, width=4).items()
             if k != left_out}
    with pytest.raises(ValueError,
                       match=f"RoutedExpertsLayer needs {left_out}"):
        RoutedExpertsLayer(n_in=8, n_out=8, **given)


def test_a_scoring_that_is_not_built_is_refused(small):
    _, _, _, lp, x = small
    other = impl_for(RoutedExpertsLayer(
        n_in=D, n_out=D, n_experts=E, top_k=K, width=32, scoring="softmax"))
    with pytest.raises(ValueError, match="scoring 'softmax' is not built"):
        other.route(lp, x.reshape(-1, D))


# -- the plain two-matrix relu2 expert and the selection bias (ISSUE 36) ----
# at the `nemotron_h` tests' small size: hidden 48, 16 experts of width 24 of
# which 3 a token, one shared expert of 40, against that family's reference

@pytest.fixture(scope="module")
def nemo():
    import nemotron_util as nu
    fam, params, net = nu.load()
    name = next(n for n in net._impls if n.startswith("moe"))
    i = int(name[3:])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 29, nu.CFG["hidden_size"]))
    return nu, fam, params["blocks"][i], net._impls[name], net.params[name], x


def _nemo_ref(nu, fam, p, x, first=0, count=16, chosen=None):
    """(routed part without the shared expert, the shared expert, chosen)."""
    m = {**dict(fam.reference.dims(nu.cfg(first, count)))}
    p = {**p, **{k: p[k][first:first + count] for k in ("we_up", "we_down")}}
    with jax.default_matmul_precision("highest"):
        y, pick = fam.reference._routed(x, p, m, None, chosen)
        zero = {**p, "we_up": p["we_up"] * 0, "we_down": p["we_down"] * 0}
        shared, _ = fam.reference._routed(x, zero, m, None, chosen)
    return y - shared, shared, pick


def test_relu2_is_the_square_of_relu():
    from deeplearning4j_tpu.ops import activations
    x = jnp.asarray([-2.0, -0.0, 0.5, 3.0])
    np.testing.assert_array_equal(activations.get("relu2")(x),
                                  jnp.asarray([0.0, 0.0, 0.25, 9.0]))


def test_the_plain_relu2_expert_is_the_reference(nemo):
    nu, fam, p, impl, lp, x = nemo
    assert set(lp) == {"Wr", "b_sel", "Wu", "Wd"} and not impl.conf.gated
    with jax.default_matmul_precision("highest"):
        got, var = impl.forward(lp, x)
    want, shared, _ = _nemo_ref(nu, fam, p, x)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-6
    assert float(jnp.abs(want).max()) > 0.05
    assert int(np.asarray(var["routing_counts"]).sum()) == 2 * 29 * 3
    # a gated layer over the same stacks is another function
    gated = impl_for(RoutedExpertsLayer(
        n_in=48, n_out=48, n_experts=16, top_k=3, scale=2.5, width=24,
        selection_bias=True, activation="identity"))
    other, _ = gated.forward({**lp, "Wg": lp["Wu"]}, x)
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-3


def test_the_selection_bias_chooses_and_does_not_weigh(nemo):
    """A bias that lifts expert 5 over every score puts it into every
    token's set; the gates of the chosen stay their own scores over their
    sum: the layer equals the reference made to take that choice, and the
    weights of a token's unchanged choices move only through the sum."""
    nu, fam, p, impl, lp, x = nemo
    xf = x.reshape(-1, x.shape[-1])
    idx0, g0 = impl.route(lp, xf)
    lifted = {**lp, "b_sel": lp["b_sel"].at[5].set(10.0)}
    idx1, g1 = impl.route(lifted, xf)
    assert bool(jnp.all(jnp.any(idx1 == 5, axis=-1)))
    assert not bool(jnp.all(jnp.any(idx0 == 5, axis=-1)))
    sigma = jax.nn.sigmoid(xf @ lp["Wr"])
    top = jnp.take_along_axis(sigma, idx1, -1)
    np.testing.assert_allclose(g1, 2.5 * top / top.sum(-1, keepdims=True),
                               rtol=1e-5)
    # no bias at all: the plain top-k of the scores
    none = {**lp, "b_sel": jnp.zeros_like(lp["b_sel"])}
    idx2, _ = impl.route(none, xf)
    np.testing.assert_array_equal(
        np.sort(idx2, -1), np.sort(jax.lax.top_k(sigma, 3)[1], -1))
    with jax.default_matmul_precision("highest"):
        got, _ = impl.forward(lifted, x)
    want, _, pick = _nemo_ref(nu, fam, {**p, "b_sel": lifted["b_sel"]}, x)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 5e-6
    np.testing.assert_array_equal(np.sort(np.asarray(pick).reshape(-1, 3), -1),
                                  np.sort(np.asarray(idx1), -1))


def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer(nemo):
    """ISSUE 36's deployment in small: 16 experts 8 ways, 2 a share; what
    every chip computes alike (the shared expert) counted once."""
    nu, fam, p, impl, lp, x = nemo
    whole, shared, _ = _nemo_ref(nu, fam, p, x)
    parts = []
    for first in range(0, 16, 2):
        share = impl_for(RoutedExpertsLayer(
            n_in=48, n_out=48, n_experts=16, held=(first, 2), top_k=3,
            scale=2.5, width=24, gated=False, expert_activation="relu2",
            selection_bias=True, activation="identity"))
        cut = {"Wr": lp["Wr"], "b_sel": lp["b_sel"],
               "Wu": lp["Wu"][first:first + 2], "Wd": lp["Wd"][first:first + 2]}
        with jax.default_matmul_precision("highest"):
            y, var = share.forward(cut, x)
        ref, _, _ = _nemo_ref(nu, fam, p, x, first, 2)
        assert np.abs(np.asarray(y) - np.asarray(ref)).max() < 5e-6
        parts.append((np.asarray(y), int(var["routing_counts"].sum())))
    total = sum(y for y, _ in parts) + np.asarray(shared)
    assert np.abs(total - np.asarray(whole + shared)).max() < 1e-5
    assert sum(n for _, n in parts) == 2 * 29 * 3


@pytest.mark.parametrize("rows", [128, 2], ids=["one_tile", "tiles_of_2"])
@pytest.mark.parametrize("which", ["axk1", "nemotron"])
def test_the_weight_passes_are_the_kernel_s_visits(which, rows, monkeypatch):
    """Through the engine (three slots, the small nets' shares of 8 of 16
    experts): ``moe_weight_passes_total`` is the (expert, row tile) visits
    of every decode dispatch's counts, counted here from the rows each
    expert's sorted pairs cover; at the default tile every dispatch's held
    pairs fit one tile, so it equals ``moe_experts_hit_total``, and in
    tiles of two rows it is more."""
    import axk1_util
    import nemotron_util
    from deeplearning4j_tpu.inference import DecodeScheduler, MetricsRegistry
    util = axk1_util if which == "axk1" else nemotron_util
    monkeypatch.setattr(grouped_matmul, "_ROWS", rows)
    _, _, net = util.load(conf=util.cfg(4, 8))
    V = util.CFG["vocab_size"]
    eng = DecodeScheduler(net, V, n_slots=3, prefill_chunk=16,
                          kv_block=util.BLOCK, kv_pool_mb=util.pool_mb(40),
                          metrics=MetricsRegistry())
    seen, note = [], eng._note_routing

    def keep(counts, tokens, decode):
        if decode:
            seen.append(np.array(counts))
        return note(counts, tokens, decode)

    eng._note_routing = keep
    eng.start()
    try:
        rng = np.random.default_rng(2)
        hs = [eng.submit(rng.integers(0, V, n).tolist(), 10)
              for n in (19, 7, 30)]
        for h in hs:
            assert len(h.result(600)) == 10
        c = eng.metrics.snapshot()["counters"]
    finally:
        eng.stop()
    tm = min(rows, 16)      # 3 slots x top_k pairs round up to one tile

    def visits(counts):
        total, start = 0, 0
        for n in counts:
            total += len({r // tm for r in range(start, start + n)})
            start += n
        return total

    want = sum(visits(layer) for step in seen for layer in step)
    assert c["moe_weight_passes_total"] == want
    assert c["moe_weight_passes_total"] >= c["moe_experts_hit_total"] > 0
    if rows == 128:
        assert want == c["moe_experts_hit_total"]
    else:
        assert want > c["moe_experts_hit_total"]


@pytest.mark.parametrize("sizes,k,n", [
    ([3, 0, 10, 7, 0], 64, 32),          # empty groups between and after
    ([0, 150, 2, 100], 48, 24),          # groups over several row tiles
    ([100, 0, 150], 256, 200),           # read transposed: 200 % 128, 256
    ([0, 0, 0], 32, 16)],                # no row in any group
    ids=["gaps", "tiles", "transposed", "empty"])
def test_the_kernel_multiplies_each_group_by_its_own_matrix(sizes, k, n):
    """`grouped_matmul` in row tiles of 16: row r of group g is x[r] @
    w[g] (the rows past the groups are not compared: the kernel leaves
    them); `grouped_matmul_sum` adds each of them, scaled, into its target
    row, and nothing for the rows of no group."""
    rng = np.random.default_rng(len(sizes) + k)
    M = sum(sizes) + 11
    x = rng.standard_normal((M, k)).astype(np.float32)
    w = rng.standard_normal((len(sizes), k, n)).astype(np.float32)
    to = rng.integers(0, 7, M).astype(np.int32)
    scale = rng.standard_normal(M).astype(np.float32)
    group = np.repeat(np.arange(len(sizes)), sizes)
    want = np.stack([x[r] @ w[g] for r, g in enumerate(group)]
                    or [np.zeros(n, np.float32)])[:len(group)]
    want_sum = np.zeros((7, n), np.float32)
    for r in range(len(group)):
        want_sum[to[r]] += scale[r] * want[r]
    sz = np.asarray(sizes, np.int32)
    assert grouped_matmul.transposed(k, n) == (k == 256)
    with jax.default_matmul_precision("highest"):
        got = grouped_matmul.grouped_matmul(x, w, sz, tm=16, interpret=True)
        got_sum = grouped_matmul.grouped_matmul_sum(
            x, w, sz, to, scale, n=7, tm=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:len(group)], want,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_sum), want_sum, rtol=1e-5,
                               atol=1e-4)


# ---- both cells' layers through the chip's compiler, no chip attached: what
# the interpreter cannot show (the kernel's custom call, the layout the
# device keeps the stacks in, loops and temporaries) ----
_CELLS = {
    "axk1": dict(n_in=7168, n_out=7168, n_experts=192, held=(0, 12), top_k=8,
                 scale=2.5, width=2048),
    "nemotron": dict(n_in=2688, n_out=2688, n_experts=128, held=(0, 16),
                     top_k=6, scale=2.5, width=1856, gated=False,
                     expert_activation="relu2", selection_bias=True)}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell,tokens", [("nemotron", 64), ("nemotron", 256),
                                         ("axk1", 48), ("axk1", 512)],
                         ids=["nemotron-decode", "nemotron-chunk",
                              "axk1-decode", "axk1-chunk"])
def test_the_layer_compiles_to_the_kernel_for_a_v5e(one_chip, monkeypatch,
                                                    cell, tokens):
    """The layer as a step program traces it on the chip, at the cell's
    widths and held stacks (bfloat16), a decode step's and a chunk's
    tokens: one kernel call a matrix; no `while` (the walk carried the
    float32 [tokens, hidden] sum); no copy or transpose of any [G, ...]
    stack, Nemotron's up matrix of width 1,856 included (its entry layout
    is {1,2,0}); temporaries under one copy of the sorted rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    conf = _CELLS[cell]
    impl = impl_for(RoutedExpertsLayer(activation="identity", **conf))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: impl.init_params(jax.random.PRNGKey(0), jnp.bfloat16)))
    d, G = conf["n_in"], conf["held"][1]
    compiled = jax.jit(lambda p, x, m: impl.forward(p, x, mask=m)).lower(
        params, sds((tokens, 1, d), jnp.bfloat16),
        sds((tokens, 1), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == (3 if conf.get("gated", True)
                                             else 2)
    assert " while(" not in text
    assert not re.findall(rf"= bf16\[{G},\d+,\d+\]\S* (?:copy|transpose)\(",
                          text)
    sorted_rows = tokens * conf["top_k"] * d * 2
    assert compiled.memory_analysis().temp_size_in_bytes < sorted_rows
