"""Recurrent layers: LSTM, GravesLSTM (peepholes), GravesBidirectionalLSTM.

Parity surface: ``nn/layers/recurrent/GravesLSTM.java:41`` /
``GravesBidirectionalLSTM.java`` / ``LSTMHelpers.java:58 (fwd), :260 (bwd)``.

TPU-first design: the reference runs a per-timestep Java loop of small gemms
(``LSTMHelpers.java:159-173``). Here the input projection for ALL timesteps is
one large [batch*time, 4H] matmul (MXU-sized), and only the recurrent part runs
inside ``lax.scan`` — the XLA while-loop form that the BASELINE names as the
accelerated-LSTM requirement (BASELINE.md: "XLA-scan LSTM"). Gate packing order
is [i, f, g, o] (documented for checkpoint/Keras-import fidelity).

Data layout: [batch, time, features] (NTC). Masking: mask [batch, time]; masked
steps emit 0 and hold (h, c).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.input_type import FeedForward, Recurrent
from deeplearning4j_tpu.nn.layers.base import BaseLayer, register_layer
from deeplearning4j_tpu.ops import activations as activations_mod


def _lstm_gates(z, c_prev, peep, cell_act, gate_act, n_out):
    """Split packed preactivations and apply the LSTM cell. z: [batch, 4H]."""
    i, f, g, o = (z[:, :n_out], z[:, n_out:2 * n_out],
                  z[:, 2 * n_out:3 * n_out], z[:, 3 * n_out:])
    if peep is not None:
        i = i + c_prev * peep[0]
        f = f + c_prev * peep[1]
    i = gate_act(i)
    f = gate_act(f)
    g = cell_act(g)
    c = f * c_prev + i * g
    if peep is not None:
        o = o + c * peep[2]
    o = gate_act(o)
    h = o * cell_act(c)
    return h, c


@register_layer
@dataclass
class LSTM(BaseLayer):
    """Vanilla LSTM (no peepholes). activation = cell activation (default tanh)."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"

    peephole = False

    def set_input_type(self, input_type):
        if self.n_in is None:
            if isinstance(input_type, Recurrent):
                self.n_in = input_type.size
            elif isinstance(input_type, FeedForward):
                self.n_in = input_type.size
            else:
                raise ValueError(f"{type(self).__name__} got {input_type}")
        # defer to output_type so subclasses that widen the output
        # (bidirectional concat) report the right downstream size
        return self.output_type(input_type)

    def output_type(self, input_type):
        t = input_type.timeseries_length if isinstance(input_type, Recurrent) else None
        return Recurrent(self.n_out, t)

    def param_shapes(self):
        shapes = {"W": (self.n_in, 4 * self.n_out),
                  "RW": (self.n_out, 4 * self.n_out),
                  "b": (4 * self.n_out,)}
        if self.peephole:
            shapes["P"] = (3, self.n_out)
        return shapes

    @property
    def param_order(self):
        return ["W", "RW", "b"] + (["P"] if self.peephole else [])

    def init_params(self, key, dtype=jnp.float32):
        k1, k2, k3 = jax.random.split(key, 3)
        b = jnp.zeros((4 * self.n_out,), dtype)
        # forget-gate bias init (reference GravesLSTM forgetGateBiasInit, default 1)
        b = b.at[self.n_out:2 * self.n_out].set(self.forget_gate_bias_init)
        params = {
            "W": self._init_weight(k1, (self.n_in, 4 * self.n_out),
                                   fan_override=(self.n_in, self.n_out), dtype=dtype),
            "RW": self._init_weight(k2, (self.n_out, 4 * self.n_out),
                                    fan_override=(self.n_out, self.n_out), dtype=dtype),
            "b": b,
        }
        if self.peephole:
            params["P"] = 0.0 * jax.random.normal(k3, (3, self.n_out), dtype)
        return params

    def _scan(self, params, x, h0, c0, mask, reverse=False):
        # explicit kernel selection first (DL4J_TPU_LSTM_KERNEL=pallas, a
        # trace-time knob): the fused Pallas cell — then the accelerated-
        # helper probe (ConvolutionLayer.java:69-76 role; SURVEY §2.8
        # accelerated LSTM): use the registered helper when it claims
        # support, the built-in scan when it declines
        from deeplearning4j_tpu.config import env_str
        if env_str("DL4J_TPU_LSTM_KERNEL") == "pallas":
            from deeplearning4j_tpu.ops import pallas_kernels
            if pallas_kernels.lstm_cell_supported(self.gate_activation,
                                                  self.activation):
                return self._scan_pallas(params, x, h0, c0, mask, reverse)
        from deeplearning4j_tpu.nn import helpers as _helpers
        helper = _helpers.get_helper(self)
        if helper is not None and helper.supports(self, mask=mask,
                                                  seq_len=x.shape[1]):
            return helper.scan(self, params, x, h0, c0, mask, reverse)
        return self._scan_builtin(params, x, h0, c0, mask, reverse)

    def _scan_pallas(self, params, x, h0, c0, mask, reverse=False):
        """The built-in scan with the per-step cell math swapped for the
        fused Pallas kernel (``ops/pallas_kernels.lstm_cell``): the input
        projection stays ONE big MXU matmul across all timesteps; inside
        the time scan each step is a single kernel fusing the recurrent
        matmul epilogue, gate activations, peephole terms and cell update
        (custom-vjp fused backward). Mask hold/zero semantics are applied
        around the kernel, identical to ``_scan_builtin``; the reverse
        pass (GravesBidirectionalLSTM) rides ``lax.scan(reverse=True)``
        unchanged."""
        from deeplearning4j_tpu.ops import pallas_kernels

        n_out = self.n_out
        peep = params.get("P")
        b, t, _ = x.shape
        zx = (x.reshape(b * t, -1) @ params["W"] + params["b"]).reshape(
            b, t, 4 * n_out)
        zx_t = jnp.swapaxes(zx, 0, 1)  # [time, batch, 4H]
        mask_t = None if mask is None else jnp.swapaxes(mask, 0, 1)[..., None]

        def step(carry, inp):
            h_prev, c_prev = carry
            if mask is None:
                z_t = inp
            else:
                z_t, m_t = inp
            h, c = pallas_kernels.lstm_cell(z_t, h_prev, c_prev,
                                            params["RW"], peep)
            if mask is not None:
                h = jnp.where(m_t > 0, h, h_prev)
                c = jnp.where(m_t > 0, c, c_prev)
            return (h, c), (h if mask is None else h * (m_t > 0))

        xs = zx_t if mask is None else (zx_t, mask_t)
        (h_f, c_f), out = jax.lax.scan(step, (h0, c0), xs, reverse=reverse)
        return jnp.swapaxes(out, 0, 1), (h_f, c_f)

    def _scan_builtin(self, params, x, h0, c0, mask, reverse=False):
        n_out = self.n_out
        cell_act = self.activation_fn() if self.activation else activations_mod.get("tanh")
        gate_act = activations_mod.get(self.gate_activation)
        peep = params.get("P")

        b, t, _ = x.shape
        # one big MXU matmul for the input projection of every timestep
        zx = (x.reshape(b * t, -1) @ params["W"] + params["b"]).reshape(b, t, 4 * n_out)
        zx_t = jnp.swapaxes(zx, 0, 1)  # [time, batch, 4H]
        mask_t = None if mask is None else jnp.swapaxes(mask, 0, 1)[..., None]

        def step(carry, inp):
            h_prev, c_prev = carry
            if mask is None:
                z_t = inp
            else:
                z_t, m_t = inp
            z = z_t + h_prev @ params["RW"]
            h, c = _lstm_gates(z, c_prev, peep, cell_act, gate_act, n_out)
            if mask is not None:
                h = jnp.where(m_t > 0, h, h_prev)
                c = jnp.where(m_t > 0, c, c_prev)
            return (h, c), (h if mask is None else h * (m_t > 0))

        xs = zx_t if mask is None else (zx_t, mask_t)
        (h_f, c_f), out = jax.lax.scan(step, (h0, c0), xs, reverse=reverse)
        return jnp.swapaxes(out, 0, 1), (h_f, c_f)

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, train=train, rng=rng)
        b = x.shape[0]
        h0 = jnp.zeros((b, self.n_out), x.dtype)
        c0 = jnp.zeros((b, self.n_out), x.dtype)
        out, _ = self._scan(params, x, h0, c0, mask)
        return out, state

    def step(self, params, x_t, carry):
        """Single-timestep stateful inference (reference rnnTimeStep path)."""
        n_out = self.n_out
        cell_act = self.activation_fn() if self.activation else activations_mod.get("tanh")
        gate_act = activations_mod.get(self.gate_activation)
        h_prev, c_prev = carry
        z = x_t @ params["W"] + params["b"] + h_prev @ params["RW"]
        h, c = _lstm_gates(z, c_prev, params.get("P"), cell_act, gate_act, n_out)
        return h, (h, c)

    def initial_carry(self, batch, dtype=jnp.float32):
        return (jnp.zeros((batch, self.n_out), dtype), jnp.zeros((batch, self.n_out), dtype))


@register_layer
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (Graves 2013 formulation; GravesLSTM.java:41)."""

    peephole = True


@register_layer
@dataclass
class GravesBidirectionalLSTM(LSTM):
    """Bidirectional peephole LSTM (GravesBidirectionalLSTM.java).

    Two independent parameter sets (prefix F/B); outputs combined by ``mode``
    ("add" — the reference's behaviour — or "concat").
    """

    mode: str = "add"
    peephole = True

    def output_type(self, input_type):
        t = input_type.timeseries_length if isinstance(input_type, Recurrent) else None
        n = self.n_out * (2 if self.mode == "concat" else 1)
        return Recurrent(n, t)

    def param_shapes(self):
        one = super().param_shapes()
        shapes = {}
        for d in ("F", "B"):
            for k, v in one.items():
                shapes[f"{d}_{k}"] = v
        return shapes

    @property
    def param_order(self):
        one = super().param_order
        return [f"F_{k}" for k in one] + [f"B_{k}" for k in one]

    def init_params(self, key, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        f = super().init_params(kf, dtype)
        bwd = super().init_params(kb, dtype)
        out = {f"F_{k}": v for k, v in f.items()}
        out.update({f"B_{k}": v for k, v in bwd.items()})
        return out

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, train=train, rng=rng)
        b = x.shape[0]
        h0 = jnp.zeros((b, self.n_out), x.dtype)
        c0 = jnp.zeros((b, self.n_out), x.dtype)
        pf = {k[2:]: v for k, v in params.items() if k.startswith("F_")}
        pb = {k[2:]: v for k, v in params.items() if k.startswith("B_")}
        out_f, _ = self._scan(pf, x, h0, c0, mask)
        out_b, _ = self._scan(pb, x, h0, c0, mask, reverse=True)
        if self.mode == "concat":
            return jnp.concatenate([out_f, out_b], axis=-1), state
        return out_f + out_b, state


@register_layer
@dataclass
class LastTimeStepLayer(BaseLayer):
    """[batch, time, size] → [batch, size] last (unmasked) step — the layer
    form of rnn/LastTimeStepVertex.java, used by Keras import for
    return_sequences=False RNNs."""

    def set_input_type(self, input_type):
        return self.output_type(input_type)

    def output_type(self, input_type):
        return FeedForward(input_type.size)

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        if mask is None:
            return x[:, -1, :], state
        # last NONZERO mask index (handles pre-padded masks, LastTimeStepVertex.java)
        t = x.shape[1]
        rev = jnp.flip(mask > 0, axis=1)
        idx = t - 1 - jnp.argmax(rev, axis=1).astype(jnp.int32)
        return x[jnp.arange(x.shape[0]), idx, :], state

    def feed_forward_mask(self, mask):
        return None
