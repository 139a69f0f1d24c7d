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
