"""`RoutedExpertsLayer` (ISSUE 34) at the tests' small size (hidden 64, 16
experts of width 32, 2 a token, sigmoid scores normalised over the chosen,
scale 2.5), seeded weights, against the `axk1` family's plain reference: the
uncut layer, the shares that add up to it, no token dropped however uneven
the routing, and the grouped path against the dense masked loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from axk1_util import CFG, load

from deeplearning4j_tpu.nn.conf.layers import RoutedExpertsLayer
from deeplearning4j_tpu.nn.layers import experts
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
    monkeypatch.setattr(experts, "_TILE", tile)
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
