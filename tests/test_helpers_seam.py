"""Accelerated-helper seam tests (the CuDNNGradientChecks pattern:
``deeplearning4j-cuda/src/test/.../CuDNNGradientChecks.java:66`` forces the
helper path and gradient-checks it; ``TestConvolution.java:118`` asserts
helper-vs-builtin output equality).

Covers the SURVEY §2.8 accelerated LSTM and the conv tenant: register /
supports / decline are exercised by user-facing layers.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import NeuralNetConfiguration
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn import helpers
from deeplearning4j_tpu.nn.conf.input_type import InputType
from deeplearning4j_tpu.nn.layers import (ConvolutionLayer, DenseLayer, LSTM,
                                          OutputLayer, RnnOutputLayer,
                                          SelfAttentionLayer)
from deeplearning4j_tpu.nn.layers.conv import ConvolutionLayer as ConvCls


@pytest.fixture
def conv_layer_and_input(rng):
    layer = ConvolutionLayer(n_in=3, n_out=4, kernel_size=(3, 3),
                             stride=(1, 1), convolution_mode="same")
    params = {"W": jnp.asarray(rng.normal(size=(3, 3, 3, 4)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(4,)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
    return layer, params, x


class _Broken(helpers.LayerHelper):
    """Accepts every call, then fails in whichever entry point the layer
    uses: a defect, which the seam must not turn into the built-in path."""

    def supports(self, layer, **ctx):
        return True

    def _explode(self, *a, **kw):
        raise RuntimeError("helper exploded")

    pre_output = attention = scan = _explode


def _call_conv():
    layer = ConvolutionLayer(n_in=3, n_out=4, kernel_size=(3, 3))
    params = layer.init_params(jax.random.PRNGKey(0))
    layer.pre_output(params, jnp.zeros((2, 8, 8, 3), jnp.float32))


def _call_attention():
    layer = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2)
    q = jnp.zeros((2, 2, 4, 4), jnp.float32)
    layer._attend(q, q, q, None)


def _call_lstm():
    layer = LSTM(n_in=4, n_out=6)
    params = layer.init_params(jax.random.PRNGKey(0))
    z = jnp.zeros((2, 6), jnp.float32)
    layer._scan(params, jnp.zeros((2, 12, 4), jnp.float32), z, z, None)


@pytest.mark.parametrize("layer_name,call", [
    ("ConvolutionLayer", _call_conv),
    ("SelfAttentionLayer", _call_attention),
    ("LSTM", _call_lstm),
])
def test_failing_helper_propagates(layer_name, call):
    """``supports()`` declining is the fallback contract; a helper that
    accepted and then raised reaches the caller (it used to be swallowed,
    which on the chip hides a kernel the compiler refused)."""
    old = helpers._REGISTRY.get(layer_name)
    helpers.register_helper(layer_name, _Broken())
    try:
        with pytest.raises(RuntimeError, match="helper exploded"):
            call()
    finally:
        helpers.register_helper(layer_name, old)


class TestConvHelperSeam:
    def test_helper_matches_builtin(self, conv_layer_and_input):
        """TestConvolution.java:118 pattern: helper output == builtin."""
        layer, params, x = conv_layer_and_input
        builtin = layer._pre_output_builtin(params, x)
        helper = helpers.Im2ColConvolutionHelper()
        np.testing.assert_allclose(np.asarray(helper.pre_output(layer, params, x)),
                                   np.asarray(builtin), atol=1e-4)

    def test_helper_matches_builtin_bias_free(self, rng):
        """has_bias=False conv (conv->BN blocks) must go through the helper,
        not silently fall back via a swallowed KeyError."""
        import jax.numpy as jnp
        from deeplearning4j_tpu.nn.layers.conv import ConvolutionLayer
        layer = ConvolutionLayer(n_in=3, n_out=4, kernel_size=(3, 3),
                                 stride=(1, 1), padding=(1, 1),
                                 has_bias=False)
        params = layer.init_params(__import__("jax").random.PRNGKey(0))
        assert "b" not in params
        x = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
        builtin = layer._pre_output_builtin(params, x)
        helper = helpers.Im2ColConvolutionHelper()
        assert helper.supports(layer)
        np.testing.assert_allclose(
            np.asarray(helper.pre_output(layer, params, x)),
            np.asarray(builtin), atol=1e-4)

    def test_registered_helper_used_and_disable_env(self, conv_layer_and_input,
                                                    monkeypatch):
        layer, params, x = conv_layer_and_input

        class Spy(helpers.Im2ColConvolutionHelper):
            calls = 0

            def pre_output(self, *a, **kw):
                Spy.calls += 1
                return super().pre_output(*a, **kw)

        old = helpers._REGISTRY.get("ConvolutionLayer")
        helpers.register_helper("ConvolutionLayer", Spy())
        try:
            layer.pre_output(params, x)
            assert Spy.calls == 1
            monkeypatch.setenv("DL4J_TPU_DISABLE_HELPERS", "1")
            layer.pre_output(params, x)
            assert Spy.calls == 1   # env kill-switch: builtin path
        finally:
            helpers.register_helper("ConvolutionLayer", old)

    def test_supports_gate_declines_large_kernels_and_channels(self):
        h = helpers.Im2ColConvolutionHelper(max_kernel_elems=8)
        small = ConvolutionLayer(n_in=1, n_out=1, kernel_size=(2, 2))
        large = ConvolutionLayer(n_in=1, n_out=1, kernel_size=(5, 5))
        deep = ConvolutionLayer(n_in=64, n_out=1, kernel_size=(2, 2))
        assert h.supports(small)
        assert not h.supports(large)      # kernel too big
        assert not h.supports(deep)       # channels too deep for im2col win

    def test_forced_helper_gradient_check(self, rng):
        """CuDNNGradientChecks.java:66 pattern: numeric-vs-analytic gradients
        with the helper path forced on a real net."""
        from deeplearning4j_tpu.gradientcheck.gradient_check_util import (
            check_gradients)
        conf = (NeuralNetConfiguration.Builder().seed(3).list()
                .layer(ConvolutionLayer(n_out=3, kernel_size=(2, 2),
                                        activation="tanh"))
                .layer(OutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.convolutional(5, 5, 1))
                .build())
        net = MultiLayerNetwork(conf).init()
        X = rng.normal(size=(3, 5, 5, 1)).astype(np.float32)
        Y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 3)]
        assert helpers.get_helper(net.layers[0]) is not None  # helper live
        ok, max_err, _ = check_gradients(net, X, Y)
        assert ok, f"forced-helper conv gradient check failed ({max_err})"


class TestLSTMHelperSeam:
    def _lstm_layer(self, rng):
        layer = LSTM(n_in=4, n_out=6)
        import jax
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.normal(size=(2, 12, 4)), jnp.float32)
        h0 = jnp.zeros((2, 6), jnp.float32)
        c0 = jnp.zeros((2, 6), jnp.float32)
        return layer, params, x, h0, c0

    def test_helper_matches_builtin_scan(self, rng):
        layer, params, x, h0, c0 = self._lstm_layer(rng)
        out_b, (hb, cb) = layer._scan_builtin(params, x, h0, c0, None)
        h = helpers.AcceleratedLSTMHelper()
        out_h, (hh, ch) = h.scan(layer, params, x, h0, c0, None)
        np.testing.assert_allclose(np.asarray(out_h), np.asarray(out_b),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(hh), np.asarray(hb), atol=1e-5)

    def test_helper_matches_builtin_with_mask(self, rng):
        layer, params, x, h0, c0 = self._lstm_layer(rng)
        mask = jnp.asarray((rng.rand(2, 12) > 0.3), jnp.float32)
        out_b, _ = layer._scan_builtin(params, x, h0, c0, mask)
        out_h, _ = helpers.AcceleratedLSTMHelper().scan(
            layer, params, x, h0, c0, mask)
        np.testing.assert_allclose(np.asarray(out_h), np.asarray(out_b),
                                   atol=1e-5)

    def test_supports_declines_short_sequences(self):
        h = helpers.AcceleratedLSTMHelper(unroll=8)
        layer = LSTM(n_in=2, n_out=2)
        assert h.supports(layer, seq_len=16)
        assert not h.supports(layer, seq_len=4)

    def test_forced_helper_gradient_check(self, rng):
        from deeplearning4j_tpu.gradientcheck.gradient_check_util import (
            check_gradients)
        conf = (NeuralNetConfiguration.Builder().seed(4).list()
                .layer(LSTM(n_in=3, n_out=5))
                .layer(RnnOutputLayer(n_in=5, n_out=2, activation="softmax",
                                      loss="mcxent"))
                .build())
        net = MultiLayerNetwork(conf).init()
        X = rng.normal(size=(2, 10, 3)).astype(np.float32)
        Y = np.zeros((2, 10, 2), np.float32)
        Y[..., 0] = 1.0
        assert helpers.get_helper(net.layers[0]) is not None
        ok, max_err, _ = check_gradients(net, X, Y)
        assert ok, f"forced-helper LSTM gradient check failed ({max_err})"
