"""Accelerated-layer helper seam (the cuDNN helper plug-in mechanism).

Parity surface: the reference's layers probe for an optional accelerated
implementation at construction (``ConvolutionLayer.java:69-76`` does
``Class.forName("...CudnnConvolutionHelper")``) and fall back per call when
the helper declines (``if helper != null && dtype != HALF`` —
``ConvolutionLayer.java:158,265,309``). Here the registry maps layer class
names to helper objects; a helper's ``supports(layer, **ctx)`` gates each
call, and declining there is the whole fallback contract: the layer then
takes its built-in JAX path. A helper that accepted a call and then raises
is a defect and the exception propagates — on the chip a swallowed kernel
failure would silently become the slower route.

Shipped tenants (all user-facing layers exercise register/supports/decline):
- ``AcceleratedLSTMHelper`` — the SURVEY §2.8 accelerated LSTM (the role a
  later ``CudnnLSTMHelper`` plays): the same recurrence compiled with an
  unrolled ``lax.scan`` body, amortizing XLA while-loop per-step overhead.
- ``Im2ColConvolutionHelper`` — conv forward as im2col + one MXU GEMM (the
  alternative algorithm the reference's own CPU path uses,
  ``ConvolutionLayer.java:230-299``); ``supports`` gates on small kernels.
- ``FlashAttentionHelper`` — SelfAttentionLayer through the Pallas flash
  kernel on TPU (``ops/pallas_kernels.py``).

Disable all helpers with ``DL4J_TPU_DISABLE_HELPERS=1`` (the reference's
"remove cudnn from the classpath").
"""

from __future__ import annotations

from deeplearning4j_tpu.config import env_flag

_REGISTRY: dict[str, object] = {}


def _pair(v):
    """(a, b) from a scalar, tuple, or list (configs round-trip via JSON,
    where tuples become lists)."""
    if isinstance(v, (tuple, list)):
        return int(v[0]), int(v[1])  # graftlint: disable=G001 -- host config ints (kernel/stride pair), not device values
    return int(v), int(v)  # graftlint: disable=G001 -- host config ints (kernel/stride pair), not device values


def register_helper(layer_cls_name: str, helper):
    _REGISTRY[layer_cls_name] = helper
    return helper


def unregister_helper(layer_cls_name: str):
    _REGISTRY.pop(layer_cls_name, None)


def get_helper(layer):
    """The registered helper for this layer instance, or None
    (the reflective Class.forName probe, minus reflection)."""
    if env_flag("DL4J_TPU_DISABLE_HELPERS"):
        return None
    return _REGISTRY.get(type(layer).__name__)


class LayerHelper:
    """Helper contract (nn/layers/convolution/ConvolutionHelper.java role)."""

    def supports(self, layer, **ctx) -> bool:
        return False


class AcceleratedLSTMHelper(LayerHelper):
    """Accelerated LSTM scan (SURVEY §2.8; the CudnnLSTMHelper role).

    Same math as ``LSTM._scan`` — batched input projection, per-step
    recurrent gemm — but the scan body is UNROLLED so XLA fuses ``unroll``
    timesteps per while-loop iteration, cutting loop-bookkeeping overhead on
    short-ish sequences. Numerics are identical ops in the same order, so
    forced-helper gradient checks hold to builtin tolerances."""

    def __init__(self, unroll: int = 8):
        self.unroll = unroll

    def supports(self, layer, *, mask=None, seq_len=None, **ctx):
        # unrolling pays off when the loop runs more than one unrolled block
        return seq_len is None or seq_len >= self.unroll

    def scan(self, layer, params, x, h0, c0, mask, reverse=False):
        import jax
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers.recurrent import _lstm_gates
        from deeplearning4j_tpu.ops import activations as activations_mod
        n_out = layer.n_out
        cell_act = (layer.activation_fn() if layer.activation
                    else activations_mod.get("tanh"))
        gate_act = activations_mod.get(layer.gate_activation)
        peep = params.get("P")
        b, t, _ = x.shape
        zx = (x.reshape(b * t, -1) @ params["W"]
              + params["b"]).reshape(b, t, 4 * n_out)
        zx_t = jnp.swapaxes(zx, 0, 1)
        mask_t = None if mask is None else jnp.swapaxes(mask, 0, 1)[..., None]

        def step(carry, inp):
            h_prev, c_prev = carry
            z_t = inp if mask is None else inp[0]
            z = z_t + h_prev @ params["RW"]
            h, c = _lstm_gates(z, c_prev, peep, cell_act, gate_act, n_out)
            if mask is not None:
                m_t = inp[1]
                h = jnp.where(m_t > 0, h, h_prev)
                c = jnp.where(m_t > 0, c, c_prev)
                return (h, c), h * (m_t > 0)
            return (h, c), h

        xs = zx_t if mask is None else (zx_t, mask_t)
        (h_f, c_f), out = jax.lax.scan(
            step, (h0, c0), xs, reverse=reverse,
            unroll=min(self.unroll, t))
        return jnp.swapaxes(out, 0, 1), (h_f, c_f)


class Im2ColConvolutionHelper(LayerHelper):
    """Conv forward as im2col + one (B·OH·OW, KH·KW·C)x(KH·KW·C, F) MXU GEMM
    — the reference's own CPU algorithm (``ConvolutionLayer.java:230-299``,
    ``Convolution.im2col``) recast as a single big matmul; an alternative to
    XLA's direct convolution that can win when the kernel volume is small."""

    def __init__(self, max_kernel_elems: int = 25, max_in_channels: int = 4):
        # conservative default gate: im2col's GEMM only plausibly beats
        # XLA's direct conv on small-kernel, few-channel layers (the
        # MXU-underfed first conv of image nets); everything else declines,
        # mirroring cuDNN AlgoMode selection keeping the best algorithm
        self.max_kernel_elems = max_kernel_elems
        self.max_in_channels = max_in_channels

    def supports(self, layer, **ctx):
        kh, kw = _pair(layer.kernel_size)
        n_in = layer.n_in or 0
        return kh * kw <= self.max_kernel_elems and \
            0 < n_in <= self.max_in_channels

    def pre_output(self, layer, params, x):
        import jax.numpy as jnp
        from jax import lax
        kh, kw = _pair(layer.kernel_size)
        sh, sw = _pair(layer.stride)
        if layer.convolution_mode == "same":
            oh = -(-x.shape[1] // sh)
            ow = -(-x.shape[2] // sw)
            pad_h = max((oh - 1) * sh + kh - x.shape[1], 0)
            pad_w = max((ow - 1) * sw + kw - x.shape[2], 0)
            pads = ((pad_h // 2, pad_h - pad_h // 2),
                    (pad_w // 2, pad_w - pad_w // 2))
        else:
            ph, pw = _pair(layer.padding)
            pads = ((ph, ph), (pw, pw))
        xp = jnp.pad(x, ((0, 0), pads[0], pads[1], (0, 0)))
        b, H, W, c = xp.shape
        oh = (H - kh) // sh + 1
        ow = (W - kw) // sw + 1
        # im2col via patch gather: (B, OH, OW, KH, KW, C)
        patches = lax.conv_general_dilated_patches(
            xp, (kh, kw), (sh, sw), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        # patches: (B, OH, OW, C*KH*KW) in (C, KH, KW) minor order
        cols = patches.reshape(b * oh * ow, c, kh * kw)
        cols = jnp.swapaxes(cols, 1, 2).reshape(b * oh * ow, kh * kw * c)
        wmat = params["W"].reshape(kh * kw * c, -1)    # HWIO → (KH·KW·C, F)
        z = (cols @ wmat).reshape(b, oh, ow, -1)
        return z + params["b"] if getattr(layer, "has_bias", True) else z


class FlashAttentionHelper(LayerHelper):
    """Pallas flash-attention forward for SelfAttentionLayer
    (plays the CudnnConvolutionHelper role for the attention hot loop)."""

    def supports(self, layer, *, mask=None, **ctx):
        from deeplearning4j_tpu.ops import pallas_kernels
        # key-validity masks are not fused into the kernel — decline and let
        # the built-in path handle them (the reference's per-call fallback)
        return mask is None and pallas_kernels.pallas_supported()

    def attention(self, q, k, v, *, causal, block_size=None):
        from deeplearning4j_tpu.ops import pallas_kernels
        bs = block_size or 512
        return pallas_kernels.flash_attention(q, k, v, causal=causal,
                                              block_q=bs, block_k=bs)


register_helper("SelfAttentionLayer", FlashAttentionHelper())
# the accelerated LSTM covers the whole LSTM family (shared _scan)
_lstm_helper = AcceleratedLSTMHelper()
register_helper("LSTM", _lstm_helper)
register_helper("GravesLSTM", _lstm_helper)
register_helper("GravesBidirectionalLSTM", _lstm_helper)
register_helper("ConvolutionLayer", Im2ColConvolutionHelper())
