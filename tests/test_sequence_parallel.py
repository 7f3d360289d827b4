"""Long-context attention tests: blockwise (flash) recurrence and ring
attention over the 8-device CPU mesh must match dense attention exactly;
SelfAttentionLayer integrates with the layer zoo (JSON round-trip, gradient
check, masked training)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from deeplearning4j_tpu.parallel.sequence_parallel import (
    blockwise_attention, dense_attention, ring_attention,
    sequence_parallel_attention)


class TestBlockwiseAttention:
    def test_matches_dense(self, rng):
        q = jnp.asarray(rng.randn(2, 32, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 32, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 32, 8), jnp.float32)
        out = blockwise_attention(q, k, v, block_size=8)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_causal_and_nondivisible_length(self, rng):
        q = jnp.asarray(rng.randn(1, 37, 4), jnp.float32)
        k, v = q + 1.0, q - 0.5
        out = blockwise_attention(q, k, v, causal=True, block_size=16)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_multihead_and_mask(self, rng):
        q = jnp.asarray(rng.randn(2, 4, 24, 8), jnp.float32)  # [b, h, t, d]
        k = jnp.asarray(rng.randn(2, 4, 24, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 4, 24, 8), jnp.float32)
        mask = np.ones((2, 24), np.float32)
        mask[:, 18:] = 0.0
        mask = jnp.asarray(mask)
        out = blockwise_attention(q, k, v, block_size=8, mask=mask)
        ref = dense_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_gradients_match_dense(self, rng):
        q = jnp.asarray(rng.randn(1, 16, 4), jnp.float32)
        k = jnp.asarray(rng.randn(1, 16, 4), jnp.float32)
        v = jnp.asarray(rng.randn(1, 16, 4), jnp.float32)

        g1 = jax.grad(lambda a: blockwise_attention(a, k, v, block_size=4).sum())(q)
        g2 = jax.grad(lambda a: dense_attention(a, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


class TestRingAttention:
    def _mesh(self):
        from deeplearning4j_tpu.parallel.parallel_wrapper import data_parallel_mesh
        return data_parallel_mesh(jax.devices()[:8], axis="seq")

    def test_matches_dense_full_sequence(self, rng):
        mesh = self._mesh()
        T = 64  # 8 per device
        q = jnp.asarray(rng.randn(2, T, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, T, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, T, 8), jnp.float32)
        out = sequence_parallel_attention(q, k, v, mesh)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_causal_matches_dense(self, rng):
        mesh = self._mesh()
        T = 32
        q = jnp.asarray(rng.randn(1, T, 4), jnp.float32)
        k = jnp.asarray(rng.randn(1, T, 4), jnp.float32)
        v = jnp.asarray(rng.randn(1, T, 4), jnp.float32)
        out = sequence_parallel_attention(q, k, v, mesh, causal=True)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_ring_mask_matches_dense(self, rng):
        mesh = self._mesh()
        from jax.sharding import PartitionSpec as P
        import functools
        T = 32
        q = jnp.asarray(rng.randn(2, T, 4), jnp.float32)
        k = jnp.asarray(rng.randn(2, T, 4), jnp.float32)
        v = jnp.asarray(rng.randn(2, T, 4), jnp.float32)
        mask = np.ones((2, T), np.float32)
        mask[:, 20:] = 0.0
        mask = jnp.asarray(mask)
        spec = P(None, "seq", None)
        mspec = P(None, "seq")
        ring = jax.jit(shard_map(
            lambda a, b, c, m: ring_attention(a, b, c, axis_name="seq", mask=m),
            mesh=mesh, in_specs=(spec, spec, spec, mspec), out_specs=spec))
        out = ring(q, k, v, mask)
        ref = dense_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(np.asarray(out)[:, :20],
                                   np.asarray(ref)[:, :20], atol=1e-5)

    def test_differentiable_through_ring(self, rng):
        mesh = self._mesh()
        from jax.sharding import PartitionSpec as P
        import functools
        T = 32
        q = jnp.asarray(rng.randn(1, T, 4), jnp.float32)
        k, v = q * 0.5, q * 2.0
        spec = P(None, "seq", None)

        ring = shard_map(
            functools.partial(ring_attention, axis_name="seq"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        g1 = jax.grad(lambda a: ring(a, k, v).sum())(q)
        g2 = jax.grad(lambda a: dense_attention(a, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


class TestSelfAttentionLayer:
    def _conf(self, **kw):
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import RnnOutputLayer, SelfAttentionLayer
        return (NeuralNetConfiguration.Builder().seed(3).learning_rate(0.05)
                .updater("adam").list()
                .layer(SelfAttentionLayer(n_in=6, n_out=6, n_heads=2, **kw))
                .layer(RnnOutputLayer(n_in=6, n_out=3, activation="softmax",
                                      loss="mcxent"))
                .build())

    def test_json_roundtrip(self):
        from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
        conf = self._conf(causal=True, block_size=16)
        back = MultiLayerConfiguration.from_json(conf.to_json())
        layer = back.layers[0]
        assert layer.n_heads == 2 and layer.causal and layer.block_size == 16

    def test_gradient_check(self, rng):
        from deeplearning4j_tpu.gradientcheck.gradient_check_util import \
            check_gradients
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        net = MultiLayerNetwork(self._conf()).init()
        x = rng.randn(2, 5, 6).astype(np.float64)
        y = np.eye(3)[rng.randint(0, 3, (2, 5))].astype(np.float64)
        ok, max_rel, failures = check_gradients(net, x, y)
        assert ok, f"max rel error {max_rel}: {failures[:5]}"

    def test_training_reduces_loss(self, rng):
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        n, t = 32, 8
        cls = rng.randint(0, 3, n)
        x = rng.randn(n, t, 6).astype(np.float32) * 0.1
        x[np.arange(n), 0, cls] += 2.0  # class signal at t=0 → attention must move it
        y = np.zeros((n, t, 3), np.float32)
        y[np.arange(n)[:, None], np.arange(t)[None, :], cls[:, None]] = 1.0
        net = MultiLayerNetwork(self._conf()).init()
        first = None
        for _ in range(60):
            net.fit_batch(x, y)
            first = first or net.score_
        assert net.score_ < first * 0.5, (first, net.score_)

    def test_blockwise_path_matches_dense_path(self, rng):
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        x = rng.randn(2, 32, 6).astype(np.float32)
        net_d = MultiLayerNetwork(self._conf()).init()
        net_b = MultiLayerNetwork(self._conf(block_size=8)).init()
        net_b.set_params(np.asarray(net_d.params()))
        np.testing.assert_allclose(np.asarray(net_d.output(x)),
                                   np.asarray(net_b.output(x)), atol=1e-5)

    def test_layer_sequence_axis_path(self, rng):
        """The layer's ring-attention branch must run under shard_map and
        match the dense branch (regression: NameError on the sp import)."""
        import functools
        import jax
        from jax.sharding import PartitionSpec as P
        from deeplearning4j_tpu.nn.layers import SelfAttentionLayer
        from deeplearning4j_tpu.parallel.parallel_wrapper import data_parallel_mesh

        mesh = data_parallel_mesh(jax.devices()[:8], axis="seq")
        layer_sp = SelfAttentionLayer(n_in=6, n_out=6, n_heads=2, causal=True,
                                      sequence_axis="seq").apply_global_defaults({})
        layer_d = SelfAttentionLayer(n_in=6, n_out=6, n_heads=2,
                                     causal=True).apply_global_defaults({})
        params = layer_sp.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rng.randn(2, 32, 6), jnp.float32)

        spec = P(None, "seq", None)
        fwd = jax.jit(shard_map(
            lambda p, a: layer_sp.forward(p, a, {})[0],
            mesh=mesh, in_specs=(P(), spec), out_specs=spec))
        out_sp = fwd(params, x)
        out_d, _ = layer_d.forward(params, x, {})
        np.testing.assert_allclose(np.asarray(out_sp), np.asarray(out_d),
                                   atol=1e-5)

    def test_mask_zeroes_padded_steps(self, rng):
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        x = rng.randn(2, 6, 6).astype(np.float32)
        mask = np.ones((2, 6), np.float32)
        mask[:, 4:] = 0.0
        net = MultiLayerNetwork(self._conf()).init()
        out = np.asarray(net.output(x, fmask=mask))
        # attention must not attend to masked steps: changing masked input
        # must not change unmasked outputs
        x2 = x.copy()
        x2[:, 4:] += 100.0
        out2 = np.asarray(net.output(x2, fmask=mask))
        np.testing.assert_allclose(out[:, :4], out2[:, :4], atol=1e-5)


class TestUlyssesAttention:
    """All-to-all context parallelism: sequence→heads reshard, local dense
    attention, inverse reshard — must match dense exactly (it IS dense,
    repartitioned)."""

    def _mesh(self):
        from deeplearning4j_tpu.parallel.parallel_wrapper import data_parallel_mesh
        return data_parallel_mesh(jax.devices()[:8], axis="seq")

    def test_matches_dense(self, rng):
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            dense_attention, ulysses_attention)
        mesh = self._mesh()
        q = jnp.asarray(rng.randn(2, 32, 8, 4), jnp.float32)  # [B,T,H,D]
        k = jnp.asarray(rng.randn(2, 32, 8, 4), jnp.float32)
        v = jnp.asarray(rng.randn(2, 32, 8, 4), jnp.float32)
        out = ulysses_attention(q, k, v, mesh)
        # oracle: per-head dense over [B,H,T,D]
        ref = dense_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                              jnp.swapaxes(v, 1, 2))
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.swapaxes(ref, 1, 2)),
                                   atol=1e-5)

    def test_causal_matches_dense(self, rng):
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            dense_attention, ulysses_attention)
        mesh = self._mesh()
        q = jnp.asarray(rng.randn(1, 16, 8, 4), jnp.float32)
        out = ulysses_attention(q, q, q, mesh, causal=True)
        ref = dense_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(q, 1, 2),
                              jnp.swapaxes(q, 1, 2), causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(jnp.swapaxes(ref, 1, 2)),
                                   atol=1e-5)

    def test_indivisible_heads_rejected(self, rng):
        from deeplearning4j_tpu.parallel.sequence_parallel import ulysses_attention
        mesh = self._mesh()
        q = jnp.asarray(rng.randn(1, 16, 6, 4), jnp.float32)  # 6 heads, 8 devs
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, q, q, mesh)

    def test_indivisible_sequence_rejected(self, rng):
        from deeplearning4j_tpu.parallel.sequence_parallel import ulysses_attention
        mesh = self._mesh()
        q = jnp.asarray(rng.randn(1, 30, 8, 4), jnp.float32)  # T=30, 8 devs
        with pytest.raises(ValueError, match="sequence length"):
            ulysses_attention(q, q, q, mesh)

    def test_repeated_calls_hit_compile_cache(self, rng):
        from deeplearning4j_tpu.parallel import sequence_parallel as sp
        mesh = self._mesh()
        q = jnp.asarray(rng.randn(1, 16, 8, 4), jnp.float32)
        sp.ulysses_attention(q, q, q, mesh)
        n = len(sp._ULYSSES_CACHE)
        sp.ulysses_attention(q + 1, q, q, mesh)
        assert len(sp._ULYSSES_CACHE) == n   # same (mesh, axis, causal) key
