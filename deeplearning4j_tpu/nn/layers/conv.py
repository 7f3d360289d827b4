"""Convolutional layers: Convolution, Subsampling (pooling), ZeroPadding.

Parity surface: ``nn/layers/convolution/ConvolutionLayer.java`` (im2col+GEMM
forward :230-299), ``convolution/subsampling/SubsamplingLayer.java`` (MAX/AVG/
SUM/PNORM, ``PoolingType.java``), ``nn/conf/layers/ZeroPaddingLayer.java``.

TPU-first: the reference lowers conv to im2col+GEMM by hand; here it is a single
``lax.conv_general_dilated`` in NHWC/HWIO layout, which XLA maps directly onto
the MXU (the cuDNN-helper role of ``CudnnConvolutionHelper.java:49`` is played by
the XLA compiler itself — no plug-in seam needed, no descriptor cache: compiled
executables are cached per shape by jit).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.input_type import Convolutional, InputType
from deeplearning4j_tpu.nn.layers.base import BaseLayer, register_layer


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)  # graftlint: disable=G001 -- host config ints (kernel/stride pair)
    return (int(v), int(v))  # graftlint: disable=G001 -- host config ints (kernel/stride pair)


def conv_out_size(size, kernel, stride, pad, mode="truncate"):
    if mode == "same":
        return -(-size // stride)
    return (size + 2 * pad - kernel) // stride + 1


@register_layer
@dataclass
class ConvolutionLayer(BaseLayer):
    """2-D convolution. kernel/stride/padding are (h, w) pairs or ints."""

    n_in: Optional[int] = None    # input channels
    n_out: Optional[int] = None   # output channels
    kernel_size: tuple = (5, 5)
    stride: tuple = (1, 1)
    padding: tuple = (0, 0)
    convolution_mode: str = "truncate"  # "truncate" (explicit pad) or "same"
    cudnn_algo_mode: Optional[str] = None  # accepted for config parity; XLA picks algos
    has_bias: bool = True   # False for conv->BN blocks: beta absorbs the bias,
                            # saving a full-activation add + its gradient reduce

    def set_input_type(self, input_type):
        if not isinstance(input_type, Convolutional):
            raise ValueError(f"ConvolutionLayer expects CNN input, got {input_type}")
        if self.n_in is None:
            self.n_in = input_type.channels
        return self.output_type(input_type)

    def output_type(self, input_type):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        oh = conv_out_size(input_type.height, kh, sh, ph, self.convolution_mode)
        ow = conv_out_size(input_type.width, kw, sw, pw, self.convolution_mode)
        if oh <= 0 or ow <= 0:
            raise ValueError(
                f"Invalid conv configuration: input {input_type.height}x{input_type.width}, "
                f"kernel {kh}x{kw}, stride {sh}x{sw}, padding {ph}x{pw} gives output {oh}x{ow}")
        return Convolutional(oh, ow, self.n_out)

    def param_shapes(self):
        kh, kw = _pair(self.kernel_size)
        shapes = {"W": (kh, kw, self.n_in, self.n_out)}   # HWIO
        if self.has_bias:
            shapes["b"] = (self.n_out,)
        return shapes

    @property
    def param_order(self):
        return ["W", "b"] if self.has_bias else ["W"]

    def init_params(self, key, dtype=jnp.float32):
        kh, kw = _pair(self.kernel_size)
        params = {"W": self._init_weight(
            key, (kh, kw, self.n_in, self.n_out), dtype=dtype)}
        if self.has_bias:
            params["b"] = self._init_bias((self.n_out,), dtype=dtype)
        return params

    def pre_output(self, params, x):
        # accelerated-helper probe (the CudnnConvolutionHelper seam,
        # ConvolutionLayer.java:69-76,158): helper algorithm when supported,
        # built-in direct conv when it declines
        from deeplearning4j_tpu.nn import helpers as _helpers
        helper = _helpers.get_helper(self)
        if helper is not None and helper.supports(self):
            return helper.pre_output(self, params, x)
        return self._pre_output_builtin(params, x)

    def _pre_output_builtin(self, params, x):
        sh, sw = _pair(self.stride)
        if self.convolution_mode == "same":
            padding = "SAME"
        else:
            ph, pw = _pair(self.padding)
            padding = [(ph, ph), (pw, pw)]
        z = lax.conv_general_dilated(
            x, params["W"], window_strides=(sh, sw), padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return z + params["b"] if self.has_bias else z

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        x = self.apply_dropout(x, train=train, rng=rng)
        return self.activation_fn()(self.pre_output(params, x)), state


@register_layer
@dataclass
class SubsamplingLayer(BaseLayer):
    """Pooling: MAX / AVG / SUM / PNORM (SubsamplingLayer.java, PoolingType.java)."""

    pooling_type: str = "max"
    kernel_size: tuple = (2, 2)
    stride: tuple = (2, 2)
    padding: tuple = (0, 0)
    pnorm: int = 2
    convolution_mode: str = "truncate"

    def set_input_type(self, input_type):
        if not isinstance(input_type, Convolutional):
            raise ValueError(f"SubsamplingLayer expects CNN input, got {input_type}")
        return self.output_type(input_type)

    def output_type(self, input_type):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        ph, pw = _pair(self.padding)
        oh = conv_out_size(input_type.height, kh, sh, ph, self.convolution_mode)
        ow = conv_out_size(input_type.width, kw, sw, pw, self.convolution_mode)
        if oh <= 0 or ow <= 0:
            raise ValueError(f"Invalid pooling configuration: output {oh}x{ow}")
        return Convolutional(oh, ow, input_type.channels)

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        kh, kw = _pair(self.kernel_size)
        sh, sw = _pair(self.stride)
        if self.convolution_mode == "same":
            padding = "SAME"
        else:
            ph, pw = _pair(self.padding)
            padding = [(0, 0), (ph, ph), (pw, pw), (0, 0)]
        dims = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pt = self.pooling_type.lower()
        if pt == "max":
            out = lax.reduce_window(x, -jnp.inf, lax.max, dims, strides, padding)
        elif pt in ("avg", "average"):
            s = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
            out = s / (kh * kw)
        elif pt == "sum":
            out = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
        elif pt == "pnorm":
            p = float(self.pnorm)  # graftlint: disable=G001 -- host config float (pnorm exponent)
            s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, dims, strides, padding)
            out = s ** (1.0 / p)
        else:
            raise ValueError(f"Unknown pooling type {self.pooling_type!r}")
        return out, state


@register_layer
@dataclass
class ZeroPaddingLayer(BaseLayer):
    """Zero padding in H/W (nn/conf/layers/ZeroPaddingLayer.java)."""

    padding: tuple = (1, 1)  # (h, w) or ((top,bottom),(left,right))

    def _pads(self):
        p = self.padding
        if isinstance(p, (list, tuple)) and len(p) == 2 and isinstance(p[0], (list, tuple)):
            (pt, pb), (pl, pr) = p
        else:
            ph, pw = _pair(p)
            pt = pb = ph
            pl = pr = pw
        return pt, pb, pl, pr

    def set_input_type(self, input_type):
        return self.output_type(input_type)

    def output_type(self, input_type):
        pt, pb, pl, pr = self._pads()
        return Convolutional(input_type.height + pt + pb, input_type.width + pl + pr,
                             input_type.channels)

    def forward(self, params, x, state, *, train=False, rng=None, mask=None):
        pt, pb, pl, pr = self._pads()
        return jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0))), state
