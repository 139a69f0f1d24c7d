"""`Mamba2Layer` (ISSUE 36): the chunked (SSD) form, the step-by-step
recurrence and the family's plain reference are one function; a sequence
fed as chunks of unequal length, one padded to its bucket, ends in the state
and outputs of one pass; lanes outside the write mask keep `ssm` and `conv`
bit for bit; and the comparison is tight enough that leaving out the carried
state, `D`, the conv's bias or the gate fails it under the family's draws.
Small CPU size (tests/nemotron_util.py), float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nemotron_util import CFG, REPO

from benchmark.harness import family
from deeplearning4j_tpu.nn.conf.layers import Mamba2Layer
from deeplearning4j_tpu.nn.layers.base import impl_for

D = CFG["hidden_size"]
TOL = 2e-5


@pytest.fixture(scope="module")
def fam():
    return family.load(REPO, CFG)


@pytest.fixture(scope="module")
def layer(fam):
    """(impl, the program's params, the reference's block) under the
    family's draws for A_log, D, dt_bias, the conv."""
    conf = Mamba2Layer(
        n_in=D, n_out=D, n_heads=CFG["mamba_num_heads"],
        head_dim=CFG["mamba_head_dim"], state_size=CFG["ssm_state_size"],
        n_groups=CFG["n_groups"], conv_kernel=CFG["conv_kernel"],
        chunk_size=CFG["chunk_size"], activation="identity")
    block = fam.weights.make_params(CFG, 11, jnp.float32)["blocks"][0]
    assert "w_in" in block
    tree = fam.graph.graph_tree({
        "embed_w": 0, "embed_b": 0, "lnf_g": 0, "head_w": 0, "head_b": 0,
        "blocks": [block]})["mamba0"]
    return impl_for(conf), tree, block


def _x(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape),
                       jnp.float32)


def _reference(fam, block, x, drop=()):
    with jax.default_matmul_precision("highest"):
        return fam.reference._mamba(x, block, dict(fam.reference.dims(CFG)),
                                    None, drop=drop)


def _stepwise(impl, params, x, state=None):
    """One token after another through the T = 1 recurrence."""
    st = state or impl.init_state(x.shape[0], x.dtype)
    ys = []
    for t in range(x.shape[1]):
        y, st = impl.forward_with_state(params, x[:, t:t + 1], st)
        ys.append(y)
    return jnp.concatenate(ys, 1), st


def test_the_conf_needs_the_models_own_sizes():
    with pytest.raises(ValueError, match="state_size"):
        Mamba2Layer(n_in=8, n_out=8, n_heads=2, head_dim=4, n_groups=1)
    with pytest.raises(ValueError, match="n_groups"):
        Mamba2Layer(n_in=8, n_out=8, n_heads=4, head_dim=4, state_size=4,
                    n_groups=3)


def test_state_is_float32_whatever_the_compute_dtype(layer):
    impl, _, _ = layer
    st = impl.init_state(3, jnp.bfloat16)
    H, P, N = (CFG["mamba_num_heads"], CFG["mamba_head_dim"],
               CFG["ssm_state_size"])
    assert st["ssm"].shape == (3, H, P, N) and st["ssm"].dtype == jnp.float32
    assert st["conv"].shape == (3, CFG["conv_kernel"] - 1,
                                H * P + 2 * CFG["n_groups"] * N)
    assert st["conv"].dtype == jnp.bfloat16
    assert impl.takes_chunk() and impl.masks_own_lanes() \
        and not impl.keeps_pages()


@pytest.mark.parametrize("T", [1, 5, 8, 21, 32])
def test_chunked_stepwise_and_reference_agree(layer, fam, T):
    """T = 21 is not a whole number of chunks of 8; T = 5 is under one."""
    impl, params, block = layer
    x = _x((2, T, D), seed=T)
    full, st_full = impl.forward_with_state(params, x, None)
    step, st_step = _stepwise(impl, params, x)
    ref = _reference(fam, block, x)
    np.testing.assert_allclose(full, ref, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(step, ref, atol=TOL, rtol=TOL)
    for leaf in ("ssm", "conv"):
        np.testing.assert_allclose(st_full[leaf], st_step[leaf], atol=TOL,
                                   rtol=TOL)
    # `forward` (training, `output`) is the same pass
    np.testing.assert_allclose(impl.forward(params, x)[0], full, atol=0)


def test_unequal_chunks_one_of_them_padded_equal_one_pass(layer):
    """13 + 16 (of which 9 real, under `wmask` as the engine's chunk
    program hands it) + 1 + 7 tokens, against the 30 in one pass."""
    impl, params, _ = layer
    x = _x((1, 30, D), seed=3)
    want, st_want = impl.forward_with_state(params, x, None)
    st = impl.init_state(1, x.dtype)
    got, at = [], 0
    for n_real, bucket in ((13, 13), (9, 16), (1, 1), (7, 7)):
        chunk = jnp.zeros((1, bucket, D), x.dtype) \
            .at[:, :n_real].set(x[:, at:at + n_real])
        # the pad is not zeros to the layer: it must be the mask that
        # keeps it out, not its value
        chunk = chunk.at[:, n_real:].set(7.0)
        y, st = impl.forward_with_state(
            params, chunk, {**st, "wmask": (jnp.arange(bucket) < n_real)[None]})
        assert set(st) == {"ssm", "conv"}
        got.append(y[:, :n_real])
        at += n_real
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=TOL,
                               rtol=TOL)
    for leaf in ("ssm", "conv"):
        np.testing.assert_allclose(st[leaf], st_want[leaf], atol=TOL,
                                   rtol=TOL)


@pytest.mark.parametrize("T", [1, 16])
def test_masked_lanes_keep_their_state_bit_for_bit(layer, T):
    """Lanes 1 and 3 hold no token (a decode step's idle slots, a chunk of
    no real token): `ssm` and `conv` come back as they went in, -0.0 and
    all, whatever the lane's input, NaN included; lanes 0 and 2 step as
    they would alone."""
    impl, params, _ = layer
    x = _x((4, T, D), seed=5)
    x = x.at[3].set(jnp.nan)
    _, st0 = impl.forward_with_state(params, _x((4, 9, D), seed=6), None)
    st0 = {"ssm": st0["ssm"].at[1, 0, 0, 0].set(-0.0),
           "conv": st0["conv"].at[1, 0, 0].set(-0.0)}
    live = jnp.asarray([True, False, True, False])
    wmask = jnp.broadcast_to(live[:, None], (4, T))
    y, st = impl.forward_with_state(params, x, {**st0, "wmask": wmask})
    for leaf in ("ssm", "conv"):
        a, b = np.asarray(st[leaf]), np.asarray(st0[leaf])
        assert a[[1, 3]].tobytes() == b[[1, 3]].tobytes(), leaf
        assert not np.array_equal(a[[0, 2]], b[[0, 2]]), leaf
    solo = {k: v[jnp.asarray([0, 2])] for k, v in st0.items()}
    y2, st2 = impl.forward_with_state(params, x[jnp.asarray([0, 2])], solo)
    np.testing.assert_allclose(y[jnp.asarray([0, 2])], y2, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(st["ssm"][jnp.asarray([0, 2])], st2["ssm"],
                               atol=TOL, rtol=TOL)
    assert bool(jnp.all(jnp.isfinite(y[jnp.asarray([0, 1, 2])])))


def test_a_feature_mask_is_read_as_the_write_mask(layer):
    """Training's variable-length batches: padding at the end of a row
    leaves the state at the row's last real token."""
    impl, params, _ = layer
    x = _x((2, 12, D), seed=8)
    mask = (jnp.arange(12)[None] < jnp.asarray([[12], [7]])).astype(x.dtype)
    _, st = impl.forward_with_state(params, x, None, mask=mask)
    _, st_short = impl.forward_with_state(params, x[1:, :7], None)
    np.testing.assert_allclose(st["ssm"][1], st_short["ssm"][0], atol=TOL,
                               rtol=TOL)
    np.testing.assert_allclose(st["conv"][1], st_short["conv"][0], atol=0)


@pytest.mark.parametrize("part", ["state", "D", "conv_bias", "gate"])
def test_leaving_a_part_out_fails_the_comparison(layer, fam, part):
    """Under the family's draws each part moves the output by far more than
    the tolerance the agreement above is held to: a program that dropped it
    would not pass as rounding."""
    impl, params, block = layer
    x = _x((2, 24, D), seed=9)
    got, _ = impl.forward_with_state(params, x, None)
    ref = _reference(fam, block, x)
    cut = _reference(fam, block, x, drop=(part,))
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(got - ref))) < TOL * max(1.0, scale)
    assert float(jnp.max(jnp.abs(got - cut))) > 50 * TOL * max(1.0, scale), \
        part


def test_zeroing_the_carried_state_between_chunks_shows(layer):
    """The program's own way to lose the state: a second chunk that starts
    from zeros is not the sequence's second half."""
    impl, params, _ = layer
    x = _x((1, 32, D), seed=10)
    want, _ = impl.forward_with_state(params, x, None)
    _, st = impl.forward_with_state(params, x[:, :16], None)
    kept, _ = impl.forward_with_state(params, x[:, 16:], st)
    lost, _ = impl.forward_with_state(
        params, x[:, 16:], {"ssm": jnp.zeros_like(st["ssm"]),
                            "conv": st["conv"]})
    np.testing.assert_allclose(kept, want[:, 16:], atol=TOL, rtol=TOL)
    assert float(jnp.max(jnp.abs(lost - want[:, 16:]))) > 50 * TOL


def test_rnn_time_step_streams_a_mamba_net():
    """The facade's own streaming API (reference rnnTimeStep) over a graph
    with the layer: token by token equals the whole sequence."""
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import RnnOutputLayer
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.updater.updaters import Sgd
    gb = (NeuralNetConfiguration.builder().seed(1).learning_rate(0.0)
          .updater(Sgd()).graph_builder().add_inputs("in")
          .add_layer("m", Mamba2Layer(n_in=12, n_out=12, n_heads=2,
                                      head_dim=4, state_size=4, n_groups=1,
                                      chunk_size=4, activation="identity"),
                     "in")
          .add_layer("out", RnnOutputLayer(n_in=12, n_out=5,
                                           activation="softmax",
                                           loss="mcxent"), "m")
          .set_outputs("out"))
    net = ComputationGraph(gb.build()).init()
    x = _x((2, 9, 12), seed=12)
    whole = net.output(x)[0]
    net.rnn_clear_previous_state()
    steps = [net.rnn_time_step(x[:, t:t + 1])[0] for t in range(9)]
    np.testing.assert_allclose(jnp.concatenate(steps, 1), whole, atol=TOL,
                               rtol=TOL)
