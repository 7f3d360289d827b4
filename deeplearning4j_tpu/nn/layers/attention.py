"""Self-attention layer for sequence models.

Beyond-reference capability (the reference predates attention; its RNN stack
is the only sequence machinery — SURVEY §5.7): a multi-head self-attention
layer that slots into the same layer zoo as LSTM, with three execution paths:
dense O(T²) for short sequences, blockwise flash recurrence for long
sequences on one chip, and ring attention over a sequence-parallel mesh axis
(``parallel/sequence_parallel.py``) when run under shard_map.

Layout: [batch, time, size] (Recurrent InputType), mask [batch, time] — the
same contracts the LSTM layers use, so attention composes with masking,
tBPTT-style segmenting and RnnOutputLayer unchanged.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.input_type import Recurrent
from deeplearning4j_tpu.nn.layers.base import BaseLayer, register_layer


@register_layer
@dataclass
class SelfAttentionLayer(BaseLayer):
    """Multi-head self-attention: LayerNorm-free, projection + softmax(QKᵀ)V +
    output projection; residual optional. ``block_size`` switches the
    blockwise (flash) path; ``sequence_axis`` names a mesh axis for ring
    attention when the model runs inside shard_map."""

    # attention output wants no squashing by default — override the global
    # cascade (which would impose sigmoid)
    activation: Optional[str] = "identity"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    n_heads: int = 1
    causal: bool = False
    residual: bool = False
    block_size: Optional[int] = None
    sequence_axis: Optional[str] = None

    def set_input_type(self, input_type):
        if self.n_in is None and isinstance(input_type, Recurrent):
            self.n_in = input_type.size
        if self.n_out is None:
            self.n_out = self.n_in
        if self.residual and self.n_in != self.n_out:
            raise ValueError(
                f"residual=True needs n_in == n_out, got {self.n_in} != {self.n_out}")
        return self.output_type(input_type)

    def output_type(self, input_type):
        t = input_type.timeseries_length if isinstance(input_type, Recurrent) else None
        return Recurrent(self.n_out, t)

    def param_shapes(self):
        return {"Wq": (self.n_in, self.n_out), "Wk": (self.n_in, self.n_out),
                "Wv": (self.n_in, self.n_out), "Wo": (self.n_out, self.n_out),
                "b": (self.n_out,)}

    @property
    def param_order(self):
        return ["Wq", "Wk", "Wv", "Wo", "b"]

    def init_params(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, 4)
        mk = lambda k, shape: self._init_weight(k, shape, dtype=dtype)
        return {"Wq": mk(ks[0], (self.n_in, self.n_out)),
                "Wk": mk(ks[1], (self.n_in, self.n_out)),
                "Wv": mk(ks[2], (self.n_in, self.n_out)),
                "Wo": mk(ks[3], (self.n_out, self.n_out)),
                "b": self._init_bias((self.n_out,), dtype=dtype)}

    def _split_heads(self, x):
        b, t, _ = x.shape
        h = self.n_heads
        return x.reshape(b, t, h, self.n_out // h).transpose(0, 2, 1, 3)

    def _merge_heads(self, x):
        b, h, t, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)

    def _attend(self, q, k, v, mask):
        """Single-chip attention with the accelerated-helper seam: probe the
        registry, gate per call, take the built-in JAX path when the helper
        declines (ConvolutionLayer.java:158's helper pattern). A helper
        that accepted and then raises propagates."""
        from deeplearning4j_tpu.nn import helpers
        from deeplearning4j_tpu.parallel import sequence_parallel as sp
        helper = helpers.get_helper(self)
        if helper is not None and helper.supports(self, mask=mask):
            return helper.attention(q, k, v, causal=self.causal,
                                    block_size=self.block_size)
        if self.block_size is not None:
            return sp.blockwise_attention(q, k, v, causal=self.causal,
                                          block_size=self.block_size, mask=mask)
        return sp.dense_attention(q, k, v, causal=self.causal, mask=mask)

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.parallel import sequence_parallel as sp
        if self.n_out % self.n_heads != 0:
            raise ValueError(f"n_out={self.n_out} not divisible by "
                             f"n_heads={self.n_heads}")
        x = self.apply_dropout(x, train=train, rng=rng)
        q = self._split_heads(x @ params["Wq"])
        k = self._split_heads(x @ params["Wk"])
        v = self._split_heads(x @ params["Wv"])
        if self.sequence_axis is not None:
            # under shard_map the mask arrives as the local sequence shard and
            # rotates around the ring together with K/V
            out = sp.ring_attention(q, k, v, axis_name=self.sequence_axis,
                                    causal=self.causal, mask=mask)
        else:
            out = self._attend(q, k, v, mask)
        out = self._merge_heads(out) @ params["Wo"] + params["b"]
        out = self.activation_fn()(out)
        if self.residual:
            if self.n_in != self.n_out:
                raise ValueError(
                    f"residual=True needs n_in == n_out, got "
                    f"{self.n_in} != {self.n_out}")
            out = out + x
        if mask is not None:
            out = out * mask[..., None]
        return out, state
