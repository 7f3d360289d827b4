"""Pretrain layer tests: AutoEncoder, RBM, VariationalAutoencoder — mirroring
the reference's VaeGradientCheckTests + RBM/AutoEncoder pretrain behavior tests
(SURVEY §4.1/4.2)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import enable_x64

from deeplearning4j_tpu import NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator, DataSet
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.layers import (
    AutoEncoder, DenseLayer, OutputLayer, RBM, VariationalAutoencoder,
)


def binary_data(n=64, d=12, seed=0):
    rng = np.random.RandomState(seed)
    # correlated binary patterns (3 prototypes + noise)
    protos = rng.rand(3, d) > 0.5
    idx = rng.randint(0, 3, n)
    X = protos[idx] ^ (rng.rand(n, d) < 0.05)
    return X.astype(np.float32)


class TestAutoEncoder:
    def test_pretrain_reduces_reconstruction_error(self):
        X = binary_data()
        conf = (NeuralNetConfiguration.Builder()
                .seed(1).learning_rate(0.5).updater("sgd").activation("sigmoid")
                .list()
                .layer(AutoEncoder(n_in=12, n_out=6, corruption_level=0.2, loss="mse"))
                .layer(OutputLayer(n_in=6, n_out=3, activation="softmax", loss="mcxent"))
                .pretrain(True)
                .build())
        net = MultiLayerNetwork(conf).init()
        ae = net.layers[0]
        loss0 = float(ae.pretrain_loss(net.params_list[0], jnp.asarray(X), None))
        it = ArrayDataSetIterator(X, X, batch_size=16)
        net.pretrain_layer(0, it, epochs=30)
        loss1 = float(ae.pretrain_loss(net.params_list[0], jnp.asarray(X), None))
        assert loss1 < loss0 * 0.9

    def test_autoencoder_gradient_matches_numeric(self):
        """AE pretrain loss: autodiff vs central difference (no corruption)."""
        with enable_x64(True):
            ae = AutoEncoder(n_in=5, n_out=3, corruption_level=0.0, loss="mse",
                             activation="sigmoid", weight_init="xavier")
            ae.apply_global_defaults({})
            params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float64),
                                  ae.init_params(jax.random.PRNGKey(0)))
            x = jnp.asarray(np.random.RandomState(0).rand(4, 5), jnp.float64)
            grads = jax.grad(lambda p: ae.pretrain_loss(p, x, None))(params)
            eps = 1e-6
            for name in ["W", "b", "vb"]:
                flatidx = (0,) * params[name].ndim
                p_plus = dict(params)
                p_plus[name] = params[name].at[flatidx].add(eps)
                p_minus = dict(params)
                p_minus[name] = params[name].at[flatidx].add(-eps)
                numeric = (float(ae.pretrain_loss(p_plus, x, None))
                           - float(ae.pretrain_loss(p_minus, x, None))) / (2 * eps)
                analytic = float(grads[name][flatidx])
                assert abs(analytic - numeric) < 1e-6, name


class TestRBM:
    def test_cd_reduces_reconstruction_error(self):
        X = binary_data(n=96)
        conf = (NeuralNetConfiguration.Builder()
                .seed(3).learning_rate(0.2).updater("sgd").activation("sigmoid")
                .list()
                .layer(RBM(n_in=12, n_out=8, k=1))
                .layer(OutputLayer(n_in=8, n_out=3, activation="softmax", loss="mcxent"))
                .pretrain(True)
                .build())
        net = MultiLayerNetwork(conf).init()
        rbm = net.layers[0]

        def recon_err(params):
            h = rbm.prop_up(params, jnp.asarray(X))
            v = rbm.prop_down(params, h)
            return float(jnp.mean((jnp.asarray(X) - v) ** 2))

        err0 = recon_err(net.params_list[0])
        it = ArrayDataSetIterator(X, X, batch_size=24)
        net.pretrain_layer(0, it, epochs=40)
        err1 = recon_err(net.params_list[0])
        assert err1 < err0 * 0.8

    def test_param_shapes_include_visible_bias(self):
        rbm = RBM(n_in=4, n_out=3)
        assert rbm.param_shapes() == {"W": (4, 3), "b": (3,), "vb": (4,)}
        assert rbm.param_order == ["W", "b", "vb"]


class TestVAE:
    def test_param_names_mirror_reference(self):
        vae = VariationalAutoencoder(n_in=10, n_out=4, encoder_layer_sizes=(8, 6),
                                     decoder_layer_sizes=(6, 8))
        names = set(vae.param_shapes())
        assert {"e0W", "e0b", "e1W", "e1b", "pZXMeanW", "pZXMeanb",
                "pZXLogStd2W", "pZXLogStd2b", "d0W", "d0b", "d1W", "d1b",
                "pXZW", "pXZb"} == names

    def test_elbo_decreases_with_pretraining(self):
        X = binary_data(n=96)
        conf = (NeuralNetConfiguration.Builder()
                .seed(5).learning_rate(0.05).updater("adam").activation("tanh")
                .list()
                .layer(VariationalAutoencoder(
                    n_in=12, n_out=3, encoder_layer_sizes=(16,),
                    decoder_layer_sizes=(16,),
                    reconstruction_distribution="bernoulli"))
                .layer(OutputLayer(n_in=3, n_out=3, activation="softmax", loss="mcxent"))
                .pretrain(True)
                .build())
        net = MultiLayerNetwork(conf).init()
        vae = net.layers[0]
        self_rng = jax.random.PRNGKey(42)
        loss0 = float(vae.pretrain_loss(net.params_list[0], jnp.asarray(X), self_rng))
        it = ArrayDataSetIterator(X, X, batch_size=32)
        net.pretrain_layer(0, it, epochs=60)
        loss1 = float(vae.pretrain_loss(net.params_list[0], jnp.asarray(X), self_rng))
        assert loss1 < loss0

    @pytest.mark.parametrize("dist,act", [("bernoulli", "sigmoid"),
                                          ("gaussian", "identity"),
                                          ("gaussian", "tanh")])
    def test_vae_gradient_check(self, dist, act):
        """ELBO gradient (deterministic z = mean) vs numeric — the
        VaeGradientCheckTests pattern."""
        with enable_x64(True):
            vae = VariationalAutoencoder(
                n_in=4, n_out=3, encoder_layer_sizes=(5,), decoder_layer_sizes=(5,),
                reconstruction_distribution=dist, reconstruction_activation=act,
                activation="tanh", weight_init="xavier")
            vae.apply_global_defaults({})
            params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float64),
                                  vae.init_params(jax.random.PRNGKey(7)))
            rng = np.random.RandomState(1)
            x = jnp.asarray(rng.rand(3, 4) if dist == "bernoulli"
                            else rng.randn(3, 4), jnp.float64)
            loss = lambda p: vae.pretrain_loss(p, x, None)
            grads = jax.grad(loss)(params)
            eps = 1e-6
            failures = []
            for name in sorted(params):
                idx = (0,) * params[name].ndim
                pp = dict(params)
                pp[name] = params[name].at[idx].add(eps)
                pm = dict(params)
                pm[name] = params[name].at[idx].add(-eps)
                numeric = (float(loss(pp)) - float(loss(pm))) / (2 * eps)
                analytic = float(grads[name][idx])
                denom = abs(analytic) + abs(numeric)
                rel = 0.0 if denom == 0 else abs(analytic - numeric) / denom
                if rel > 1e-4 and abs(analytic - numeric) > 1e-8:
                    failures.append((name, analytic, numeric, rel))
            assert not failures, failures

    def test_supervised_forward_uses_latent_mean(self):
        vae = VariationalAutoencoder(n_in=6, n_out=2, encoder_layer_sizes=(4,),
                                     decoder_layer_sizes=(4,), activation="tanh",
                                     weight_init="xavier")
        vae.apply_global_defaults({})
        params = vae.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.RandomState(0).randn(5, 6), jnp.float32)
        out, _ = vae.forward(params, x, {})
        mean, _ = vae._encode(params, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(mean))
        assert out.shape == (5, 2)

    def test_generate_from_latent(self):
        vae = VariationalAutoencoder(n_in=6, n_out=2, encoder_layer_sizes=(4,),
                                     decoder_layer_sizes=(4,), activation="tanh",
                                     weight_init="xavier")
        vae.apply_global_defaults({})
        params = vae.init_params(jax.random.PRNGKey(0))
        z = np.random.RandomState(0).randn(3, 2).astype(np.float32)
        x_mean = vae.generate_at_mean_given_z(params, z)
        assert x_mean.shape == (3, 6)
        assert np.all(np.asarray(x_mean) >= 0) and np.all(np.asarray(x_mean) <= 1)


COMPOSITE = [{"dist": "bernoulli", "size": 2, "activation": "sigmoid"},
             {"dist": "gaussian", "size": 2, "activation": "identity"}]
LOSS_WRAPPED = {"loss": "mse", "activation": "sigmoid"}


class TestVAEReconstructionSpecs:
    """CompositeReconstructionDistribution.java:27 + LossFunctionWrapper.java:23."""

    def _vae(self, dist, n_in=4):
        vae = VariationalAutoencoder(
            n_in=n_in, n_out=3, encoder_layer_sizes=(5,),
            decoder_layer_sizes=(5,), reconstruction_distribution=dist,
            activation="tanh", weight_init="xavier")
        vae.apply_global_defaults({})
        return vae

    def test_composite_param_count_and_slice_equivalence(self):
        """Composite log p(x|z) must equal the sum of its parts computed on
        the matching feature/param slices."""
        from deeplearning4j_tpu.nn.layers.pretrain import (
            _recon_log_prob, _recon_param_count)
        assert _recon_param_count(COMPOSITE, 4) == 2 + 4  # bern 2 + gauss 2*2
        rng = np.random.RandomState(0)
        x = jnp.asarray(np.concatenate(
            [rng.rand(3, 2), rng.randn(3, 2)], axis=1), jnp.float32)
        dp = jnp.asarray(rng.randn(3, 6), jnp.float32)
        whole = _recon_log_prob(COMPOSITE, None, x, dp)
        bern = _recon_log_prob("bernoulli", "sigmoid", x[:, :2], dp[:, :2])
        gauss = _recon_log_prob("gaussian", "identity", x[:, 2:], dp[:, 2:])
        np.testing.assert_allclose(np.asarray(whole), np.asarray(bern + gauss),
                                   rtol=1e-6)

    def test_composite_size_mismatch_is_an_error(self):
        from deeplearning4j_tpu.nn.layers.pretrain import _recon_param_count
        with pytest.raises(ValueError, match="sum to 3"):
            _recon_param_count([{"dist": "bernoulli", "size": 3}], 4)

    @pytest.mark.parametrize("dist", [COMPOSITE, LOSS_WRAPPED,
                                      [{"dist": LOSS_WRAPPED, "size": 2},
                                       {"dist": "bernoulli", "size": 2}]])
    def test_gradient_check(self, dist):
        """VaeGradientCheckTests pattern for the composite/loss-wrapper specs."""
        with enable_x64(True):
            vae = self._vae(dist)
            params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float64),
                                  vae.init_params(jax.random.PRNGKey(7)))
            x = jnp.asarray(np.random.RandomState(1).rand(3, 4), jnp.float64)
            loss = lambda p: vae.pretrain_loss(p, x, None)
            grads = jax.grad(loss)(params)
            eps = 1e-6
            failures = []
            for name in sorted(params):
                idx = (0,) * params[name].ndim
                pp = dict(params)
                pp[name] = params[name].at[idx].add(eps)
                pm = dict(params)
                pm[name] = params[name].at[idx].add(-eps)
                numeric = (float(loss(pp)) - float(loss(pm))) / (2 * eps)
                analytic = float(grads[name][idx])
                denom = abs(analytic) + abs(numeric)
                rel = 0.0 if denom == 0 else abs(analytic - numeric) / denom
                if rel > 1e-4 and abs(analytic - numeric) > 1e-8:
                    failures.append((name, analytic, numeric, rel))
            assert not failures, failures

    def test_loss_wrapper_error_vs_log_probability(self):
        """hasLossFunction semantics: reconstruction_error works, log prob
        raises — and vice versa for probabilistic specs."""
        vae = self._vae(LOSS_WRAPPED)
        params = vae.init_params(jax.random.PRNGKey(0))
        x = np.random.RandomState(0).rand(5, 4).astype(np.float32)
        assert vae.has_loss_function()
        err = vae.reconstruction_error(params, x)
        assert err.shape == (5,)
        assert np.all(np.asarray(err) >= 0)   # mse is non-negative
        with pytest.raises(ValueError, match="reconstruction_error"):
            vae.reconstruction_log_probability(params, x, rng=jax.random.PRNGKey(1))
        prob_vae = self._vae("bernoulli")
        assert not prob_vae.has_loss_function()
        with pytest.raises(ValueError, match="loss-function"):
            prob_vae.reconstruction_error(params, x)
        # mixed composite: not all leaves are losses -> probabilistic API
        mixed = self._vae([{"dist": LOSS_WRAPPED, "size": 2},
                           {"dist": "bernoulli", "size": 2}])
        assert not mixed.has_loss_function()

    def test_pretrain_decreases_loss_with_loss_wrapper(self):
        X = binary_data(n=64, d=12)
        conf = (NeuralNetConfiguration.Builder()
                .seed(5).learning_rate(0.05).updater("adam").activation("tanh")
                .list()
                .layer(VariationalAutoencoder(
                    n_in=12, n_out=3, encoder_layer_sizes=(16,),
                    decoder_layer_sizes=(16,),
                    reconstruction_distribution={"loss": "mse",
                                                 "activation": "sigmoid"}))
                .layer(OutputLayer(n_in=3, n_out=3, activation="softmax",
                                   loss="mcxent"))
                .pretrain(True)
                .build())
        net = MultiLayerNetwork(conf).init()
        vae = net.layers[0]
        key = jax.random.PRNGKey(42)
        loss0 = float(vae.pretrain_loss(net.params_list[0], jnp.asarray(X), key))
        it = ArrayDataSetIterator(X, X, batch_size=32)
        net.pretrain_layer(0, it, epochs=40)
        loss1 = float(vae.pretrain_loss(net.params_list[0], jnp.asarray(X), key))
        assert loss1 < loss0

    def test_composite_generate_at_mean_and_json_roundtrip(self):
        from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
        conf = (NeuralNetConfiguration.Builder()
                .seed(3).activation("tanh")
                .list()
                .layer(VariationalAutoencoder(
                    n_in=4, n_out=2, encoder_layer_sizes=(5,),
                    decoder_layer_sizes=(5,),
                    reconstruction_distribution=COMPOSITE))
                .layer(OutputLayer(n_in=2, n_out=2, activation="softmax",
                                   loss="mcxent"))
                .build())
        back = MultiLayerConfiguration.from_json(conf.to_json())
        vae2 = back.layers[0]
        assert _as_plain(vae2.reconstruction_distribution) == COMPOSITE
        params = vae2.init_params(jax.random.PRNGKey(0))
        z = np.random.RandomState(0).randn(3, 2).astype(np.float32)
        out = np.asarray(vae2.generate_at_mean_given_z(params, z))
        assert out.shape == (3, 4)
        # bernoulli slice in [0,1]; gaussian slice unconstrained
        assert np.all(out[:, :2] >= 0) and np.all(out[:, :2] <= 1)


def _as_plain(spec):
    if isinstance(spec, (list, tuple)):
        return [dict(c) for c in spec]
    return spec


class TestPretrainInFit:
    def test_pretrain_then_finetune_end_to_end(self):
        """conf.pretrain(True) + fit() runs unsupervised pass then supervised
        (MultiLayerNetwork.fit:932) and the classifier learns."""
        X = binary_data(n=120)
        y_idx = np.argmax(X[:, :3], axis=1)
        Y = np.eye(3, dtype=np.float32)[y_idx]
        conf = (NeuralNetConfiguration.Builder()
                .seed(9).learning_rate(0.1).updater("sgd").activation("sigmoid")
                .list()
                .layer(AutoEncoder(n_in=12, n_out=8, corruption_level=0.1, loss="mse"))
                .layer(OutputLayer(n_in=8, n_out=3, activation="softmax", loss="mcxent"))
                .pretrain(True)
                .build())
        net = MultiLayerNetwork(conf).init()
        it = ArrayDataSetIterator(X, Y, batch_size=30)
        net.fit(it, epochs=40)
        preds = np.argmax(net.output(X), axis=1)
        assert (preds == y_idx).mean() > 0.8

    def test_json_roundtrip_pretrain_layers(self):
        from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
        conf = (NeuralNetConfiguration.Builder()
                .seed(2).learning_rate(0.1)
                .list()
                .layer(VariationalAutoencoder(n_in=6, n_out=2,
                                              encoder_layer_sizes=(4,),
                                              decoder_layer_sizes=(4,)))
                .layer(RBM(n_in=2, n_out=2))
                .layer(AutoEncoder(n_in=2, n_out=2))
                .layer(OutputLayer(n_in=2, n_out=2, activation="softmax",
                                   loss="mcxent"))
                .build())
        s = conf.to_json()
        conf2 = MultiLayerConfiguration.from_json(s)
        assert [type(l).__name__ for l in conf2.layers] == [
            "VariationalAutoencoder", "RBM", "AutoEncoder", "OutputLayer"]
        net = MultiLayerNetwork(conf2).init()
        assert net.num_params() == MultiLayerNetwork(conf).init().num_params()


class TestGraphPretrain:
    def test_graph_pretrain_flag_runs_unsupervised_pass(self):
        """GraphBuilder.pretrain(True) + fit() pretrains AE vertices
        (ComputationGraph.pretrain:529-534)."""
        from deeplearning4j_tpu.models.computation_graph import ComputationGraph
        X = binary_data(n=96)
        y_idx = np.argmax(X[:, :3], axis=1)
        Y = np.eye(3, dtype=np.float32)[y_idx]
        conf = (NeuralNetConfiguration.Builder()
                .seed(4).learning_rate(0.3).updater("sgd").activation("sigmoid")
                .graph_builder()
                .add_inputs("in")
                .add_layer("ae", AutoEncoder(n_in=12, n_out=8, corruption_level=0.0,
                                             loss="mse"), "in")
                .add_layer("out", OutputLayer(n_in=8, n_out=3, activation="softmax",
                                              loss="mcxent"), "ae")
                .set_outputs("out")
                .pretrain(True)
                .build())
        g = ComputationGraph(conf).init()
        ae = conf.vertices["ae"].layer
        p0 = np.array(g.params())
        loss0 = float(ae.pretrain_loss(g.params_map["ae"], jnp.asarray(X), None))
        g.pretrain(DataSet(X, Y), epochs=30)
        loss1 = float(ae.pretrain_loss(g.params_map["ae"], jnp.asarray(X), None))
        assert loss1 < loss0
        assert not np.allclose(p0, g.params())
        # fit() triggers it automatically via the flag
        g2 = ComputationGraph(conf).init()
        g2.fit(DataSet(X, Y))
        assert g2._pretrained

    def test_vae_reconstruction_log_probability(self):
        """Importance-sampled log p(x): finite, higher for in-distribution data
        after training (reference reconstructionLogProbability)."""
        X = binary_data(n=64)
        vae = VariationalAutoencoder(
            n_in=12, n_out=3, encoder_layer_sizes=(16,), decoder_layer_sizes=(16,),
            reconstruction_distribution="bernoulli", activation="tanh",
            weight_init="xavier", updater="adam", learning_rate=0.05)
        vae.apply_global_defaults({})
        params = vae.init_params(jax.random.PRNGKey(0))
        lp = vae.reconstruction_log_probability(params, X, jax.random.PRNGKey(1),
                                                num_samples=8)
        assert lp.shape == (64,)
        assert np.all(np.isfinite(np.asarray(lp)))
        # num_samples argument is honored (different sample counts differ)
        lp1 = vae.reconstruction_log_probability(params, X, jax.random.PRNGKey(1),
                                                 num_samples=1)
        assert not np.allclose(np.asarray(lp), np.asarray(lp1))


class TestDuplicateToTimeSeriesNamedInput:
    def test_single_wired_input_with_ts_input_name(self):
        """Reference wiring: one wired input; time length from the named
        network input (DuplicateToTimeSeriesVertex.java)."""
        from deeplearning4j_tpu.models.computation_graph import ComputationGraph
        from deeplearning4j_tpu.nn.conf.graph import (
            DuplicateToTimeSeriesVertex, LastTimeStepVertex, MergeVertex,
        )
        from deeplearning4j_tpu.nn.layers import GravesLSTM, RnnOutputLayer
        rng = np.random.RandomState(0)
        Xseq = rng.randn(8, 5, 3).astype(np.float32)
        Xff = rng.randn(8, 4).astype(np.float32)
        Yseq = np.eye(2, dtype=np.float32)[rng.randint(0, 2, (8, 5))]
        conf = (NeuralNetConfiguration.Builder()
                .seed(3).learning_rate(0.05).updater("sgd").activation("tanh")
                .graph_builder()
                .add_inputs("seq", "ff")
                .add_vertex("dup", DuplicateToTimeSeriesVertex(ts_input_name="seq"),
                            "ff")
                .add_vertex("merged", MergeVertex(), "seq", "dup")
                .add_layer("lstm", GravesLSTM(n_in=7, n_out=6), "merged")
                .add_layer("out", RnnOutputLayer(n_in=6, n_out=2, activation="softmax",
                                                 loss="mcxent"), "lstm")
                .set_outputs("out")
                .build())
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet
        g = ComputationGraph(conf).init()
        g.fit(MultiDataSet([Xseq, Xff], [Yseq]))
        out = g.output(Xseq, Xff)
        assert out.shape == (8, 5, 2)
