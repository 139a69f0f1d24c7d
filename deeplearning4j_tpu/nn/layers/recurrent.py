"""Recurrent layer impls: LSTM, GravesLSTM (peepholes), bidirectional, GRU.

Parity: reference nn/layers/recurrent/GravesLSTM.java + LSTMHelpers.java
(shared fwd `activateHelper:55` with hot per-timestep loop `:132-145`, bwd
`:273`), GravesBidirectionalLSTM.java, GRU.java, BaseRecurrentLayer.java
(rnnTimeStep stateful inference + TBPTT state carry).

TPU-first redesign of the :132 timestep loop:
  - the input projection x·W for ALL timesteps is hoisted out of the loop
    into one large [B*T, n_in]x[n_in, 4H] matmul (MXU-friendly), so the
    `lax.scan` body only carries the [B,H]x[H,4H] recurrent matmul;
  - the backward pass is jax.grad through the scan (no handwritten BPTT);
  - masking for variable-length sequences gates both output and state carry
    (reference per-timestep masking, GradientCheckTestsMasking).
Layout: [batch, time, features] (reference uses [b, f, t]).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .base import LayerImpl, register_impl
from .. import weights as winit
from ...ops import helpers as ophelpers

Array = jax.Array
State = Dict[str, Array]


class BaseRecurrentImpl(LayerImpl):
    WEIGHT_KEYS = ("W", "RW")
    # whether TBPTT carries this impl's state across windows (true RNN
    # state; the attention KV cache opts out — it is inference-only)
    TBPTT_STATE = True

    def init_state(self, batch: int, dtype=jnp.float32) -> State:
        raise NotImplementedError

    def step(self, params: Dict[str, Array], x_t: Array, state: State) -> Tuple[Array, State]:
        """One timestep for stateful inference (reference rnnTimeStep)."""
        raise NotImplementedError

    def forward(self, params, x, *, train=False, rng=None, variables=None, mask=None):
        y, _ = self.forward_with_state(params, x, None, train=train, rng=rng, mask=mask)
        return y, variables or {}

    def forward_with_state(self, params, x, state0: Optional[State], *,
                           train=False, rng=None, mask=None) -> Tuple[Array, State]:
        raise NotImplementedError

    # -- what the serving engine asks of a stateful layer ---------------------
    # (`inference/engine.py`: three questions, answered by the layer kind)
    def takes_chunk(self) -> bool:
        """Whether the inference step takes T > 1 tokens at per-slot state
        in one forward (a prefill chunk). An h/c layer steps one token at a
        time: the engine scans it."""
        return False

    def keeps_pages(self) -> bool:
        """Whether the state is a cache addressed by position, which the
        engine may keep in pool pages behind a block table (the layer then
        also answers `paged_leaves`, `blocks_needed`, `page_recycling`)."""
        return False

    def masks_own_lanes(self) -> bool:
        """Whether the layer keeps per-slot leaves of a fixed size that it
        itself leaves exactly as they were where the engine's write mask
        (``wmask`` [B, T] bool, injected into its state) is off: the engine
        then hands it the mask and does not freeze those leaves again."""
        return False

    def _mask_carry(self, new_state: State, old_state: State, m_t: Array) -> State:
        """Masked timesteps keep the previous state (variable-length support)."""
        return {k: m_t * new_state[k] + (1.0 - m_t) * old_state[k] for k in new_state}


def _materialize_rnn_states(impl_items, existing, batch, dtype, *,
                            tbptt=False):
    """Initial states for stateful layers: existing entries are kept, the
    rest are init_state'd. ``tbptt`` restricts to impls whose state TBPTT
    carries across windows (excludes the inference-only attention KV cache).
    Shared by both facades' rnn_time_step and _do_truncated_bptt."""
    states = dict(existing or {})
    for key, impl in impl_items:
        if not isinstance(impl, BaseRecurrentImpl):
            continue
        if tbptt and not impl.TBPTT_STATE:
            # no cache allocated, but the key must exist: the step returns
            # new_states for every stateful impl, and a key appearing only
            # after window 1 would change the carried pytree structure and
            # force a second XLA compile of the TBPTT train step
            states.setdefault(key, None)
            continue
        if states.get(key) is None:
            states[key] = impl.init_state(batch, dtype)
    return states


def _init_gate_weights(key, conf, n_gates: int, dtype, forget_slot: Optional[int] = None):
    conf_dist = conf.dist.spec() if getattr(conf, "dist", None) is not None else None
    k1, k2 = jax.random.split(key)
    H = conf.n_out
    W = winit.init_weights(k1, (conf.n_in, n_gates * H), conf.weight_init or "xavier",
                           conf_dist, dtype)
    RW = winit.init_weights(k2, (H, n_gates * H), conf.weight_init or "xavier",
                            conf_dist, dtype)
    b = jnp.full((n_gates * H,), float(conf.bias_init or 0.0), dtype)
    if forget_slot is not None:
        fb = float(getattr(conf, "forget_gate_bias_init", 1.0))
        b = b.at[forget_slot * H:(forget_slot + 1) * H].set(fb)
    return W, RW, b


class _LSTMCore(BaseRecurrentImpl):
    """Shared LSTM machinery; gate packing order [i, f, o, g]."""

    PEEPHOLE = False

    def init_params(self, key, dtype=jnp.float32):
        W, RW, b = _init_gate_weights(key, self.conf, 4, dtype, forget_slot=1)
        params = {"W": W, "RW": RW, "b": b}
        if self.PEEPHOLE:
            H = self.conf.n_out
            params.update({
                "pI": jnp.zeros((H,), dtype),
                "pF": jnp.zeros((H,), dtype),
                "pO": jnp.zeros((H,), dtype),
            })
        return params

    def init_state(self, batch, dtype=jnp.float32):
        H = self.conf.n_out
        return {"h": jnp.zeros((batch, H), dtype), "c": jnp.zeros((batch, H), dtype)}

    def _gates(self, params, xproj_t, state):
        """xproj_t: [B, 4H] (x·W + b precomputed); state: {h, c}.
        Cell math lives in ops/helpers.lstm_cell (single definition shared
        with the lstm_sequence seam)."""
        z = xproj_t + state["h"] @ params["RW"]
        peep = ((params["pI"], params["pF"], params["pO"]) if self.PEEPHOLE
                else (0.0, 0.0, 0.0))
        h, c = ophelpers.lstm_cell(z, state["c"], peep, self.activation_fn())
        return h, {"h": h, "c": c}

    def step(self, params, x_t, state):
        xproj = x_t @ params["W"] + params["b"]
        return self._gates(params, xproj, state)

    def forward_with_state(self, params, x, state0, *, train=False, rng=None,
                           mask=None, reverse=False):
        x = self._dropout(x, train, rng)
        B, T, _ = x.shape
        if state0 is None:
            state0 = self.init_state(B, x.dtype)
        # one big MXU matmul for all timesteps
        xproj = jnp.einsum("btf,fg->btg", x, params["W"]) + params["b"]
        xproj_t = jnp.swapaxes(xproj, 0, 1)  # [T, B, 4H]
        mask_t = (None if mask is None
                  else jnp.swapaxes(mask.astype(x.dtype), 0, 1)[..., None])  # [T, B, 1]

        if mask_t is None:
            # hot path: the whole sequence through the accelerated-helper
            # seam (ops/helpers.lstm_sequence; Pallas override available)
            H = self.conf.n_out
            peep = (jnp.stack([params["pI"], params["pF"], params["pO"]])
                    if self.PEEPHOLE else jnp.zeros((3, H), x.dtype))
            ys, ht, ct = ophelpers.lstm_sequence(
                xproj_t, params["RW"], peep, state0["h"], state0["c"],
                activation=self.conf.activation or "identity", reverse=reverse)
            return jnp.swapaxes(ys, 0, 1), {"h": ht, "c": ct}

        def body(state, inp):
            xp, m = inp
            h, new_state = self._gates(params, xp, state)
            new_state = self._mask_carry(new_state, state, m)
            h = h * m
            return new_state, h

        final, ys = lax.scan(body, state0, (xproj_t, mask_t), reverse=reverse)
        return jnp.swapaxes(ys, 0, 1), final  # [B, T, H]


@register_impl("LSTM")
class LSTMImpl(_LSTMCore):
    PEEPHOLE = False


@register_impl("GravesLSTM")
class GravesLSTMImpl(_LSTMCore):
    PEEPHOLE = True


@register_impl("GravesBidirectionalLSTM")
class GravesBidirectionalLSTMImpl(BaseRecurrentImpl):
    """Forward + backward GravesLSTM; outputs summed (reference
    GravesBidirectionalLSTM combines directional activations additively)."""

    WEIGHT_KEYS = ("fwd_W", "fwd_RW", "bwd_W", "bwd_RW")

    def __init__(self, conf):
        super().__init__(conf)
        self._cell = GravesLSTMImpl(conf)

    def init_params(self, key, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        fwd = self._cell.init_params(kf, dtype)
        bwd = self._cell.init_params(kb, dtype)
        out = {f"fwd_{k}": v for k, v in fwd.items()}
        out.update({f"bwd_{k}": v for k, v in bwd.items()})
        return out

    def init_state(self, batch, dtype=jnp.float32):
        return self._cell.init_state(batch, dtype)

    def forward_with_state(self, params, x, state0, *, train=False, rng=None, mask=None):
        fwd_p = {k[4:]: v for k, v in params.items() if k.startswith("fwd_")}
        bwd_p = {k[4:]: v for k, v in params.items() if k.startswith("bwd_")}
        yf, sf = self._cell.forward_with_state(fwd_p, x, None, train=train, rng=rng,
                                               mask=mask)
        yb, _ = self._cell.forward_with_state(bwd_p, x, None, train=train, rng=rng,
                                              mask=mask, reverse=True)
        return yf + yb, sf

    def step(self, params, x_t, state):
        # stateful stepping only uses the forward direction (bidirectional
        # inference needs the full sequence; matches reference behavior of
        # disallowing rnnTimeStep on bidirectional layers)
        raise NotImplementedError("rnnTimeStep is not supported for bidirectional LSTM")


@register_impl("GRU")
class GRUImpl(BaseRecurrentImpl):
    """Gated recurrent unit (reference nn/layers/recurrent/GRU.java).
    Gate packing [r, z, h~]; h_t = z*h_{t-1} + (1-z)*h~."""

    def init_params(self, key, dtype=jnp.float32):
        W, RW, b = _init_gate_weights(key, self.conf, 3, dtype)
        return {"W": W, "RW": RW, "b": b}

    def init_state(self, batch, dtype=jnp.float32):
        return {"h": jnp.zeros((batch, self.conf.n_out), dtype)}

    def _gates(self, params, xproj_t, state):
        H = self.conf.n_out
        act = self.activation_fn()
        h_prev = state["h"]
        rz = xproj_t[:, :2 * H] + h_prev @ params["RW"][:, :2 * H]
        r = jax.nn.sigmoid(rz[:, :H])
        z = jax.nn.sigmoid(rz[:, H:])
        hc = act(xproj_t[:, 2 * H:] + (r * h_prev) @ params["RW"][:, 2 * H:])
        h = z * h_prev + (1.0 - z) * hc
        return h, {"h": h}

    def step(self, params, x_t, state):
        xproj = x_t @ params["W"] + params["b"]
        return self._gates(params, xproj, state)

    def forward_with_state(self, params, x, state0, *, train=False, rng=None, mask=None):
        x = self._dropout(x, train, rng)
        B, T, _ = x.shape
        if state0 is None:
            state0 = self.init_state(B, x.dtype)
        xproj = jnp.einsum("btf,fg->btg", x, params["W"]) + params["b"]
        xproj_t = jnp.swapaxes(xproj, 0, 1)
        mask_t = (None if mask is None
                  else jnp.swapaxes(mask.astype(x.dtype), 0, 1)[..., None])

        def body(state, inp):
            xp, m = inp
            h, new_state = self._gates(params, xp, state)
            if m is not None:
                new_state = self._mask_carry(new_state, state, m)
                h = h * m
            return new_state, h

        if mask_t is None:
            final, ys = lax.scan(lambda s, xp: body(s, (xp, None)), state0, xproj_t)
        else:
            final, ys = lax.scan(body, state0, (xproj_t, mask_t))
        return jnp.swapaxes(ys, 0, 1), final
