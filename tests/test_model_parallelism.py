"""Model-parallelism tests: tensor, pipeline, expert, and fully-sharded
(ZeRO-3) parallelism over the 8-device CPU mesh.

All four are beyond-reference capabilities (SURVEY §2.4 lists none), so
the oracle is internal consistency: the tensor-parallel MLP must train
bit-consistently with the single-device computation, the GPipe pipeline
must be math-preserving (pipelined loss == unpipelined loss on the same
params), the sharded MoE with lossless capacity must match its dense
single-device routing, and FSDP must equal unsharded full-batch SGD while
holding 1/N of the parameters per device at rest.
"""

import jax
import numpy as np
import pytest
from jax import shard_map

from deeplearning4j_tpu.parallel.expert_parallel import ExpertParallelMoE, ep_mesh
from deeplearning4j_tpu.parallel.pipeline_parallel import (
    PipelineParallelNet, pp_mesh)


class TestPipelineParallel:
    def _net(self, n_data, n_pipe, n_micro=4, **kw):
        mesh = pp_mesh(n_data, n_pipe, jax.devices()[:n_data * n_pipe])
        return PipelineParallelNet(mesh, n_in=6, d=16, n_out=3,
                                   n_micro=n_micro, **kw)

    def test_pipelined_loss_matches_unpipelined(self, rng):
        """GPipe is math-preserving: the microbatched pipelined step must
        compute exactly the loss a single-device forward computes."""
        net = self._net(1, 4, n_micro=4)
        x = rng.randn(32, 6).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 32)]
        want = net.reference_loss(x, y)   # BEFORE the update
        got = net.fit_batch(x, y)
        assert got == pytest.approx(want, rel=1e-4)

    def test_composes_with_data_parallel(self, rng):
        net = self._net(2, 4, n_micro=2)
        x = rng.randn(24, 6).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 24)]
        want = net.reference_loss(x, y)
        got = net.fit_batch(x, y)
        assert got == pytest.approx(want, rel=1e-4)

    def test_training_decreases_loss(self, rng):
        net = self._net(1, 4, n_micro=4, lr=0.5, seed=1)
        x = rng.randn(16, 6).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 16)]
        losses = [net.fit_batch(x, y) for _ in range(30)]
        assert losses[-1] < 0.5 * losses[0]
        assert np.isfinite(losses[-1])

    def test_pp_equals_single_stage_training(self, rng):
        """The pipeline schedule must not change the math: training curves
        for S=4 pipeline vs the same network trained without microbatching
        (n_micro=1, S stages still applied in sequence) coincide."""
        x = rng.randn(16, 6).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 16)]
        a = self._net(1, 4, n_micro=4, lr=0.2, seed=3)
        b = self._net(1, 4, n_micro=1, lr=0.2, seed=3)
        la = [a.fit_batch(x, y) for _ in range(5)]
        lb = [b.fit_batch(x, y) for _ in range(5)]
        np.testing.assert_allclose(la, lb, rtol=1e-4)

    def test_batch_validation(self, rng):
        net = self._net(2, 4, n_micro=3)
        with pytest.raises(ValueError, match="multiple"):
            net.fit_batch(np.zeros((8, 6), np.float32),
                          np.zeros((8, 3), np.float32))


class TestExpertParallel:
    def _moe(self, E=4, **kw):
        return ExpertParallelMoE(ep_mesh(E, jax.devices()[:E]),
                                 d=8, hidden=16, n_out=3, **kw)

    def test_sharded_forward_matches_dense_oracle(self, rng):
        """With lossless capacity, all_to_all dispatch must reproduce the
        dense per-token routing exactly."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        moe = self._moe(4)
        x = rng.randn(32, 8).astype(np.float32)
        want = moe.reference_forward(x)

        # run just the forward through the sharded block
        cap = 32 // 4
        E = moe.E

        def fwd(params, xl):
            out = xl + ExpertParallelMoE._moe_block(params, xl, E, cap)
            return jax.nn.softmax(out @ params["head"], axis=-1)

        specs = {"gate": P(), "W1": P("expert", None, None),
                 "W2": P("expert", None, None), "head": P()}
        sharded = shard_map(
            fwd, mesh=moe.mesh, in_specs=(specs, P("expert", None)),
            out_specs=P("expert", None), check_vma=False)
        xs = jax.device_put(jnp.asarray(x),
                            NamedSharding(moe.mesh, P("expert", None)))
        got = np.asarray(sharded(moe.params, xs))
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_training_decreases_loss(self, rng):
        moe = self._moe(4, lr=0.5, seed=1)
        x = rng.randn(32, 8).astype(np.float32)
        # labels correlated with input so there is signal to learn
        y = np.eye(3, dtype=np.float32)[
            (x[:, 0] > 0).astype(int) + (x[:, 1] > 0).astype(int)]
        losses = [moe.fit_batch(x, y) for _ in range(40)]
        assert losses[-1] < 0.7 * losses[0]
        assert np.isfinite(losses[-1])

    def test_capacity_overflow_drops_to_residual(self, rng):
        """With capacity 1 and adversarial identical tokens, overflow must
        pass through as residual (zero expert contribution), not corrupt."""
        moe = self._moe(2, capacity=1)
        x = np.tile(rng.randn(1, 8).astype(np.float32), (8, 1))
        y = np.eye(3, dtype=np.float32)[np.zeros(8, int)]
        loss = moe.fit_batch(x, y)
        assert np.isfinite(loss)

    def test_batch_validation(self, rng):
        moe = self._moe(4)
        with pytest.raises(ValueError, match="multiple"):
            moe.fit_batch(np.zeros((6, 8), np.float32),
                          np.zeros((6, 3), np.float32))


class TestTensorParallel:
    """Tensor parallelism (beyond-reference; SURVEY §2.4 notes the reference
    has none): column→row parallel MLP over a (data, model) mesh must train
    bit-consistently with the single-device computation."""

    def test_tp_matches_single_device_training(self, rng):
        from deeplearning4j_tpu.parallel.tensor_parallel import (
            TensorParallelMLP, tp_mesh)
        mesh = tp_mesh(2, 4)
        X = rng.normal(size=(64, 12)).astype(np.float32)
        W = rng.normal(size=(12, 3)).astype(np.float32)
        Y = np.eye(3, dtype=np.float32)[np.argmax(X @ W, 1)]
        tp = TensorParallelMLP(mesh, 12, 32, 3, lr=0.5, seed=1)
        init = {k: np.asarray(v) for k, v in tp.params.items()}

        def ref_train(p, steps):
            p = {k: v.copy() for k, v in p.items()}
            for _ in range(steps):
                h = np.tanh(X @ p["W1"] + p["b1"])
                logits = h @ p["W2"] + p["b2"]
                e = np.exp(logits - logits.max(-1, keepdims=True))
                probs = e / e.sum(-1, keepdims=True)
                dlogits = (probs - Y) / X.shape[0]
                gW2, gb2 = h.T @ dlogits, dlogits.sum(0)
                dh = dlogits @ p["W2"].T * (1 - h ** 2)
                p = {"W1": p["W1"] - 0.5 * (X.T @ dh),
                     "b1": p["b1"] - 0.5 * dh.sum(0),
                     "W2": p["W2"] - 0.5 * gW2,
                     "b2": p["b2"] - 0.5 * gb2}
            return p

        ref = ref_train(init, 10)
        for _ in range(10):
            tp.fit_batch(X, Y)
        for k in ("W1", "b1", "W2", "b2"):
            np.testing.assert_allclose(np.asarray(tp.params[k]), ref[k],
                                       atol=2e-4)

    def test_tp_trains_to_high_accuracy(self, rng):
        from deeplearning4j_tpu.parallel.tensor_parallel import (
            TensorParallelMLP, tp_mesh)
        mesh = tp_mesh(4, 2)
        X = rng.normal(size=(64, 10)).astype(np.float32)
        W = rng.normal(size=(10, 4)).astype(np.float32)
        Y = np.eye(4, dtype=np.float32)[np.argmax(X @ W, 1)]
        tp = TensorParallelMLP(mesh, 10, 24, 4, lr=0.5, seed=3)
        first = float(tp.fit_batch(X, Y))
        for _ in range(80):
            tp.fit_batch(X, Y)
        assert float(tp.fit_batch(X, Y)) < 0.3 * first
        acc = (np.argmax(tp.predict(X), 1) == np.argmax(Y, 1)).mean()
        assert acc > 0.95


class TestFSDP:
    """ZeRO-3-style fully-sharded DP (beyond-reference): params at rest are
    1/N per device; the all_gather transpose reduce-scatters gradients; the
    math must equal unsharded full-batch SGD (N=1 oracle)."""

    def _net(self, n_dev, **kw):
        from deeplearning4j_tpu.parallel.fsdp import FSDPMLP
        from deeplearning4j_tpu.parallel.parallel_wrapper import data_parallel_mesh
        mesh = data_parallel_mesh(jax.devices()[:n_dev])
        return FSDPMLP(mesh, n_in=12, hidden=64, n_out=4, n_layers=3, **kw)

    def test_at_rest_memory_is_one_over_n(self):
        net = self._net(8)
        assert net.shard_fraction() == pytest.approx(1 / 8, rel=1e-6)

    def test_matches_unsharded_training(self, rng):
        X = rng.randn(32, 12).astype(np.float32)
        Y = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 32)]
        a = self._net(8, lr=0.3, seed=5)
        b = self._net(1, lr=0.3, seed=5)
        for _ in range(10):
            la = a.fit_batch(X, Y)
            lb = b.fit_batch(X, Y)
        assert la == pytest.approx(lb, rel=1e-4)
        pa, pb = a.gathered_params(), b.gathered_params()
        for k in pa:
            np.testing.assert_allclose(pa[k], pb[k], atol=2e-5)

    def test_trains_to_high_accuracy(self, rng):
        X = rng.randn(64, 12).astype(np.float32)
        W = rng.randn(12, 4).astype(np.float32)
        Y = np.eye(4, dtype=np.float32)[np.argmax(X @ W, 1)]
        net = self._net(8, lr=0.5, seed=1)
        first = net.fit_batch(X, Y)
        for _ in range(100):
            last = net.fit_batch(X, Y)
        acc = (np.argmax(net.predict(X), 1) == np.argmax(Y, 1)).mean()
        assert last < 0.3 * first and acc > 0.95

    def test_batch_validation(self):
        net = self._net(8)
        with pytest.raises(ValueError, match="multiple"):
            net.fit_batch(np.zeros((9, 12), np.float32),
                          np.zeros((9, 4), np.float32))

    def test_label_row_mismatch_raises(self):
        net = self._net(8)
        with pytest.raises(ValueError, match="labels"):
            net.fit_batch(np.zeros((16, 12), np.float32),
                          np.zeros((8, 4), np.float32))


class TestTPTransformer:
    """Megatron-partitioned TransformerLM: N-way tensor parallelism must
    reproduce single-device training (same seed, same init, same math)."""

    def _conf(self, **kw):
        from deeplearning4j_tpu.models.transformer import TransformerConfig
        base = dict(vocab_size=40, max_len=32, d_model=32, n_heads=4,
                    n_layers=2, d_ff=64, learning_rate=1e-3, seed=0)
        base.update(kw)
        return TransformerConfig(**base)

    def _mesh(self, n):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:n]), ("model",))

    @pytest.mark.parametrize("tp", [2, 4])
    def test_matches_single_device_training(self, tp):
        from deeplearning4j_tpu.models.transformer import TransformerLM
        from deeplearning4j_tpu.parallel.tp_transformer import TPTransformerLM
        conf = self._conf()
        ref = TransformerLM(conf).init()
        tpm = TPTransformerLM(self._mesh(tp), conf)
        rng = np.random.RandomState(0)
        toks = rng.randint(0, 40, (8, 17))
        for step in range(3):
            lr = float(ref.fit_batch(toks))
            lt = tpm.fit_batch(toks)
            assert abs(lr - lt) < 1e-4, f"step {step}: {lr} vs {lt}"
        # logits parity after training
        got = tpm.gathered_logits(toks[:, :-1])
        want = np.asarray(ref.output(toks[:, :-1]))
        np.testing.assert_allclose(got, want, atol=5e-4)

    def test_params_actually_sharded(self):
        from deeplearning4j_tpu.parallel.tp_transformer import TPTransformerLM
        tpm = TPTransformerLM(self._mesh(4), self._conf())
        frac = tpm.shard_fraction()
        # sharded matmuls dominate; fraction must sit well below 1 and
        # above the pure-1/N floor (embeddings/norms are replicated)
        assert 0.25 < frac < 0.8, frac

    def test_head_alignment_enforced(self):
        from deeplearning4j_tpu.parallel.tp_transformer import TPTransformerLM
        with pytest.raises(ValueError, match="head"):
            TPTransformerLM(self._mesh(8), self._conf(n_heads=4))

    def test_dropout_rejected(self):
        from deeplearning4j_tpu.parallel.tp_transformer import TPTransformerLM
        with pytest.raises(ValueError, match="dropout"):
            TPTransformerLM(self._mesh(2), self._conf(dropout=0.1))

    def test_tp_dp_2d_mesh_matches_single_device(self):
        """TP×DP on a (data=2, model=2) mesh: batch sharded over data,
        matmuls over model — still exactly the single-device math."""
        from deeplearning4j_tpu.models.transformer import TransformerLM
        from deeplearning4j_tpu.parallel.parallel_wrapper import mesh_2d
        from deeplearning4j_tpu.parallel.tp_transformer import TPTransformerLM
        conf = self._conf()
        ref = TransformerLM(conf).init()
        tpm = TPTransformerLM(
            mesh_2d(2, 2, ("data", "model"), jax.devices()[:4]), conf)
        assert tpm.n_data == 2
        toks = np.random.RandomState(3).randint(0, 40, (8, 17))
        for step in range(3):
            lr = float(ref.fit_batch(toks))
            lt = tpm.fit_batch(toks)
            assert abs(lr - lt) < 1e-4, f"step {step}: {lr} vs {lt}"
        with pytest.raises(ValueError, match="multiple"):
            tpm.fit_batch(np.zeros((5, 9), np.int32))

    def test_bf16_and_cosine_schedule_match_single_device(self):
        """compute_dtype and the lr schedule must not be silently dropped:
        a bf16+cosine TP run tracks the identically-configured 1-chip
        model."""
        from deeplearning4j_tpu.models.transformer import TransformerLM
        from deeplearning4j_tpu.parallel.tp_transformer import TPTransformerLM
        conf = self._conf(compute_dtype="bfloat16", lr_schedule="cosine",
                          warmup_steps=2, total_steps=10)
        ref = TransformerLM(conf).init()
        tpm = TPTransformerLM(self._mesh(2), conf)
        toks = np.random.RandomState(1).randint(0, 40, (8, 17))
        for step in range(4):
            lr = float(ref.fit_batch(toks))
            lt = tpm.fit_batch(toks)
            assert abs(lr - lt) < 5e-2, f"step {step}: {lr} vs {lt}"

    def test_block_size_rejected(self):
        from deeplearning4j_tpu.parallel.tp_transformer import TPTransformerLM
        with pytest.raises(ValueError, match="block_size"):
            TPTransformerLM(self._mesh(2), self._conf(block_size=16))

    def test_misnamed_mesh_axes_rejected(self):
        from deeplearning4j_tpu.parallel.parallel_wrapper import mesh_2d
        from deeplearning4j_tpu.parallel.tp_transformer import TPTransformerLM
        # extra unrecognized axis
        with pytest.raises(ValueError, match="neither"):
            TPTransformerLM(
                mesh_2d(2, 2, ("batch", "model"), jax.devices()[:4]),
                self._conf())
        # the model axis itself misnamed
        with pytest.raises(ValueError, match="model axis"):
            TPTransformerLM(
                mesh_2d(2, 2, ("data", "tensor"), jax.devices()[:4]),
                self._conf())


class TestPPTransformer:
    """GPipe-scheduled TransformerLM: S-stage pipelining is math-preserving
    and must reproduce single-device training exactly."""

    def _conf(self, **kw):
        from deeplearning4j_tpu.models.transformer import TransformerConfig
        base = dict(vocab_size=40, max_len=32, d_model=32, n_heads=4,
                    n_layers=4, d_ff=64, learning_rate=1e-3, seed=0)
        base.update(kw)
        return TransformerConfig(**base)

    def _mesh(self, n):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:n]), ("pipe",))

    @pytest.mark.parametrize("stages,micro", [(2, 4), (4, 2)])
    def test_matches_single_device_training(self, stages, micro):
        from deeplearning4j_tpu.models.transformer import TransformerLM
        from deeplearning4j_tpu.parallel.pp_transformer import PPTransformerLM
        conf = self._conf()
        ref = TransformerLM(conf).init()
        ppm = PPTransformerLM(self._mesh(stages), conf, n_micro=micro)
        toks = np.random.RandomState(0).randint(0, 40, (8, 17))
        for step in range(3):
            lr = float(ref.fit_batch(toks))
            lp = ppm.fit_batch(toks)
            assert abs(lr - lp) < 1e-4, f"step {step}: {lr} vs {lp}"

    def test_block_params_actually_sharded(self):
        from deeplearning4j_tpu.parallel.pp_transformer import PPTransformerLM
        ppm = PPTransformerLM(self._mesh(4), self._conf(), n_micro=2)
        assert 0.25 < ppm.shard_fraction() < 0.8

    def test_remat_bf16_blockwise_variant_matches(self):
        """The memory-saving knobs users reach for with pipelining —
        remat, bf16 compute, blockwise attention — must not be silently
        dropped: the PP run tracks the identically-configured 1-chip
        model."""
        from deeplearning4j_tpu.models.transformer import TransformerLM
        from deeplearning4j_tpu.parallel.pp_transformer import PPTransformerLM
        conf = self._conf(remat=True, compute_dtype="bfloat16",
                          block_size=16)
        ref = TransformerLM(conf).init()
        ppm = PPTransformerLM(self._mesh(2), conf, n_micro=2)
        toks = np.random.RandomState(2).randint(0, 40, (4, 17))
        for step in range(3):
            lr = float(ref.fit_batch(toks))
            lp = ppm.fit_batch(toks)
            assert abs(lr - lp) < 5e-2, f"step {step}: {lr} vs {lp}"

    def test_layer_stage_divisibility_enforced(self):
        from deeplearning4j_tpu.parallel.pp_transformer import PPTransformerLM
        with pytest.raises(ValueError, match="stages"):
            PPTransformerLM(self._mesh(3), self._conf(n_layers=4), n_micro=2)

    def test_batch_microbatch_divisibility_enforced(self):
        from deeplearning4j_tpu.parallel.pp_transformer import PPTransformerLM
        ppm = PPTransformerLM(self._mesh(2), self._conf(), n_micro=3)
        with pytest.raises(ValueError, match="multiple"):
            ppm.fit_batch(np.zeros((8, 17), np.int32))


class TestSPTransformer:
    """Ring-attention sequence parallelism: sharding the SEQUENCE axis
    must reproduce single-device training exactly (the ring is exact)."""

    def _conf(self, **kw):
        from deeplearning4j_tpu.models.transformer import TransformerConfig
        base = dict(vocab_size=40, max_len=32, d_model=32, n_heads=4,
                    n_layers=2, d_ff=64, learning_rate=1e-3, seed=0)
        base.update(kw)
        return TransformerConfig(**base)

    def _mesh(self, n):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:n]), ("seq",))

    @pytest.mark.parametrize("sp", [2, 4])
    def test_matches_single_device_training(self, sp):
        from deeplearning4j_tpu.models.transformer import TransformerLM
        from deeplearning4j_tpu.parallel.sp_transformer import SPTransformerLM
        conf = self._conf()
        ref = TransformerLM(conf).init()
        spm = SPTransformerLM(self._mesh(sp), conf)
        toks = np.random.RandomState(0).randint(0, 40, (4, 17))
        for step in range(3):
            lr = float(ref.fit_batch(toks))
            lp = spm.fit_batch(toks)
            assert abs(lr - lp) < 1e-4, f"step {step}: {lr} vs {lp}"

    def test_remat_bf16_variant_matches(self):
        from deeplearning4j_tpu.models.transformer import TransformerLM
        from deeplearning4j_tpu.parallel.sp_transformer import SPTransformerLM
        conf = self._conf(remat=True, compute_dtype="bfloat16")
        ref = TransformerLM(conf).init()
        spm = SPTransformerLM(self._mesh(2), conf)
        toks = np.random.RandomState(2).randint(0, 40, (4, 17))
        for step in range(3):
            lr = float(ref.fit_batch(toks))
            lp = spm.fit_batch(toks)
            assert abs(lr - lp) < 5e-2, f"step {step}: {lr} vs {lp}"

    def test_seq_divisibility_enforced(self):
        from deeplearning4j_tpu.parallel.sp_transformer import SPTransformerLM
        spm = SPTransformerLM(self._mesh(4), self._conf())
        with pytest.raises(ValueError, match="multiple"):
            spm.fit_batch(np.zeros((2, 18), np.int32))   # T=17 % 4 != 0

    def test_dropout_and_block_size_rejected(self):
        from deeplearning4j_tpu.parallel.sp_transformer import SPTransformerLM
        with pytest.raises(ValueError, match="dropout"):
            SPTransformerLM(self._mesh(2), self._conf(dropout=0.1))
        with pytest.raises(ValueError, match="block_size"):
            SPTransformerLM(self._mesh(2), self._conf(block_size=16))


class TestEPTransformer:
    """Expert-parallel MoE LM: all_to_all switch dispatch must reproduce
    the densely-routed single-device MoE oracle exactly (lossless
    capacity, aux_weight=0 where the math must be exact)."""

    def _conf(self, **kw):
        from deeplearning4j_tpu.models.moe_transformer import (
            MoETransformerConfig)
        base = dict(vocab_size=40, max_len=32, d_model=32, n_heads=4,
                    n_layers=2, d_ff=64, n_experts=4, moe_every=2,
                    aux_weight=0.0, learning_rate=1e-3, seed=0)
        base.update(kw)
        return MoETransformerConfig(**base)

    def _mesh(self, n):
        from jax.sharding import Mesh
        return Mesh(np.array(jax.devices()[:n]), ("expert",))

    def test_matches_dense_moe_training(self):
        from deeplearning4j_tpu.models.moe_transformer import MoETransformerLM
        from deeplearning4j_tpu.parallel.ep_transformer import EPTransformerLM
        conf = self._conf()
        ref = MoETransformerLM(conf).init()
        epm = EPTransformerLM(self._mesh(4), conf)
        toks = np.random.RandomState(0).randint(0, 40, (8, 17))
        for step in range(3):
            lr = float(ref.fit_batch(toks))
            le = epm.fit_batch(toks)
            assert abs(lr - le) < 1e-4, f"step {step}: {lr} vs {le}"

    def test_top2_matches_dense_moe_training(self):
        """GShard top-2: the k-round all_to_all combine must reproduce the
        dense top-2 oracle exactly (lossless capacity, aux off)."""
        from deeplearning4j_tpu.models.moe_transformer import MoETransformerLM
        from deeplearning4j_tpu.parallel.ep_transformer import EPTransformerLM
        conf = self._conf(router_top_k=2)
        ref = MoETransformerLM(conf).init()
        epm = EPTransformerLM(self._mesh(4), conf)
        toks = np.random.RandomState(3).randint(0, 40, (8, 17))
        for step in range(3):
            lr = float(ref.fit_batch(toks))
            le = epm.fit_batch(toks)
            assert abs(lr - le) < 1e-4, f"step {step}: {lr} vs {le}"

    def test_top2_differs_from_top1_and_validates(self):
        from deeplearning4j_tpu.models.moe_transformer import MoETransformerLM
        toks = np.random.RandomState(4).randint(0, 40, (4, 17))
        a = MoETransformerLM(self._conf()).init()
        b = MoETransformerLM(self._conf(router_top_k=2)).init()
        la, lb = float(a.fit_batch(toks)), float(b.fit_batch(toks))
        assert np.isfinite(lb) and abs(la - lb) > 1e-6
        with pytest.raises(ValueError, match="router_top_k"):
            self._conf(router_top_k=5)   # > n_experts

    def test_aux_loss_trains_finite_and_expert_shards(self):
        from deeplearning4j_tpu.parallel.ep_transformer import EPTransformerLM
        epm = EPTransformerLM(self._mesh(4), self._conf(aux_weight=0.01))
        toks = np.random.RandomState(1).randint(0, 40, (8, 17))
        for _ in range(3):
            loss = epm.fit_batch(toks)
        assert np.isfinite(loss)
        # expert leaves sharded 1/E per device, everything else replicated
        w1 = epm.params["b1"]["W1"]
        assert w1.sharding.shard_shape(w1.shape)[0] == 1
        gate = epm.params["b1"]["gate"]
        assert gate.sharding.shard_shape(gate.shape) == gate.shape

    def test_capacity_overflow_drops_to_residual(self):
        """Tiny capacity: overflowed tokens ride the residual (finite
        loss), the Switch drop semantics."""
        from deeplearning4j_tpu.parallel.ep_transformer import EPTransformerLM
        epm = EPTransformerLM(self._mesh(4), self._conf(), capacity=1)
        toks = np.random.RandomState(2).randint(0, 40, (8, 17))
        assert np.isfinite(epm.fit_batch(toks))

    def test_expert_axis_size_enforced(self):
        from deeplearning4j_tpu.parallel.ep_transformer import EPTransformerLM
        with pytest.raises(ValueError, match="n_experts"):
            EPTransformerLM(self._mesh(2), self._conf(n_experts=4))

    def test_batch_divisibility_enforced(self):
        from deeplearning4j_tpu.parallel.ep_transformer import EPTransformerLM
        epm = EPTransformerLM(self._mesh(4), self._conf())
        with pytest.raises(ValueError, match="multiple"):
            epm.fit_batch(np.zeros((6, 17), np.int32))
