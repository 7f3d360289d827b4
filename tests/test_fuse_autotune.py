"""First-compile fusion autotuner + adaptive-grouping acceptance tests.

ISSUE 9 contract under test:

- ``DL4J_TPU_FUSE_AUTOTUNE=1`` with ``DL4J_TPU_FUSE_STEPS`` unset probes
  the ``DL4J_TPU_FUSE_PROBE_KS`` ladder ONCE per (model, bucket shape,
  backend) with zero-weight identity dispatches, picks the steady-state
  winner, evicts loser signatures (homogeneous streams keep ONE train
  signature and 0 in-fit compiles after the first), and persists the
  decision to ``DL4J_TPU_TUNE_CACHE_DIR`` via the atomic_io protocol so
  a restarted process never probes again.
- Probing is invisible to training: an autotuned fit trains bit-identical
  to a fit with the winner pinned via ``DL4J_TPU_FUSE_STEPS``.
- The unfused (FUSE_STEPS=1) per-batch path bucket-pads ragged trailers
  (ew contract) so it too holds one train signature per run.
"""

import json
import os

import numpy as np
import pytest

from deeplearning4j_tpu import NeuralNetConfiguration, obs
from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.tuning import autotuner


def make_data(n=256, d=4, c=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    yi = rng.integers(0, c, n)
    return X, np.eye(c, dtype=np.float32)[yi]


def mlp(seed=1):
    conf = (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.1)
            .updater("sgd").list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def fused_sigs(net):
    return [sig for sig in net._jit_train
            if isinstance(sig, tuple) and sig and sig[0] == "fused"]


def probes_total():
    return obs.metrics.value("fuse.autotune_probes_total")


@pytest.fixture
def tuned_env(monkeypatch, tmp_path):
    """Arm the tuner with a small ladder and an isolated disk cache; the
    in-memory decision state is reset on both sides of the test."""
    monkeypatch.delenv("DL4J_TPU_FUSE_STEPS", raising=False)
    monkeypatch.setenv("DL4J_TPU_FUSE_AUTOTUNE", "1")
    monkeypatch.setenv("DL4J_TPU_FUSE_PROBE_KS", "1,2,4")
    monkeypatch.setenv("DL4J_TPU_TUNE_CACHE_DIR", str(tmp_path))
    autotuner._reset_for_tests()
    yield tmp_path
    autotuner._reset_for_tests()


class TestActivation:
    def test_explicit_fuse_steps_wins_over_autotune(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FUSE_AUTOTUNE", "1")
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "8")
        assert not autotuner.autotune_active()
        monkeypatch.delenv("DL4J_TPU_FUSE_STEPS")
        assert autotuner.autotune_active()
        monkeypatch.setenv("DL4J_TPU_FUSE_AUTOTUNE", "0")
        assert not autotuner.autotune_active()

    def test_ladder_parses_sorts_dedupes_and_survives_garbage(
            self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FUSE_PROBE_KS", "8,2,2,4")
        assert autotuner.candidate_ladder() == (2, 4, 8)
        assert autotuner.probe_group_steps() == 8
        monkeypatch.setenv("DL4J_TPU_FUSE_PROBE_KS", "banana")
        with pytest.warns(UserWarning, match="FUSE_PROBE_KS"):
            assert autotuner.candidate_ladder() == (1, 4, 8, 16)


class TestProbeAndDecide:
    def test_probe_decides_persists_and_keeps_one_signature(self, tuned_env):
        X, Y = make_data()   # 8 batches of 32; probe group = 4
        p0 = probes_total()
        net = mlp()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32))
        assert probes_total() - p0 == 3          # ladder 1/2/4, once each
        assert net.iteration == 8                # probing skipped no batches
        sigs = fused_sigs(net)
        assert len(sigs) == 1 and len(net._jit_train) == 1
        selected = sigs[0][1][0]                 # K of the stacked shape
        assert selected in (1, 2, 4)
        # persisted via atomic_io: one committed JSON, decision readable
        files = os.listdir(tuned_env)
        assert len(files) == 1 and files[0].endswith("_cpu.json")
        doc = json.loads((tuned_env / files[0]).read_text())
        (entry,) = doc["decisions"].values()
        assert entry["k"] == selected
        assert obs.metrics.value("fuse.selected_k") == selected

    def test_cache_roundtrip_restarted_process_skips_probe(self, tuned_env):
        X, Y = make_data()
        net = mlp()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32))
        (sig,) = fused_sigs(net)
        p0 = probes_total()
        # simulated restart: in-memory decisions dropped, disk cache kept
        autotuner._reset_for_tests()
        net2 = mlp(seed=9)
        net2.fit(ArrayDataSetIterator(X, Y, batch_size=32))
        assert probes_total() == p0              # cache hit: zero probes
        assert fused_sigs(net2) == [sig]         # same K, one signature

    def test_autotuned_fit_bitwise_equals_pinned_winner(self, tuned_env,
                                                        monkeypatch):
        X, Y = make_data()
        a = mlp(seed=5)
        a.fit(ArrayDataSetIterator(X, Y, batch_size=32))
        (sig,) = fused_sigs(a)
        winner = sig[1][0]
        # same model/data with the winner pinned the PR-1 way: the probe's
        # zero-weight identity dispatches must have left NO trace on
        # params/updater/rng — bit-for-bit
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", str(winner))
        monkeypatch.setenv("DL4J_TPU_FUSE_AUTOTUNE", "0")
        b = mlp(seed=5)
        b.fit(ArrayDataSetIterator(X, Y, batch_size=32))
        np.testing.assert_array_equal(a.params(), b.params())
        assert np.array_equal(np.asarray(a._rng), np.asarray(b._rng))

    def test_homogeneous_stream_zero_infit_compiles_after_first(
            self, tuned_env):
        from tools.compile_counter import CompileCounter

        X, Y = make_data()
        net = mlp()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32))   # probe + compile
        with CompileCounter() as cc:
            net.fit(ArrayDataSetIterator(X, Y, batch_size=32), epochs=2)
        assert cc.count == 0
        assert len(net._jit_train) == 1

    def test_corrupt_cache_file_is_ignored_and_rewritten(self, tuned_env):
        X, Y = make_data()
        net = mlp()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=32))
        (path,) = [tuned_env / f for f in os.listdir(tuned_env)]
        path.write_text("{ not json")
        autotuner._reset_for_tests()
        p0 = probes_total()
        with pytest.warns(UserWarning, match="fuse-tune cache"):
            net2 = mlp(seed=3)
            net2.fit(ArrayDataSetIterator(X, Y, batch_size=32))
        assert probes_total() - p0 == 3          # re-probed, not crashed
        assert json.loads(path.read_text())["decisions"]   # rewritten

    def test_inflight_probe_group_rechunked_to_decided_k(self, tuned_env):
        """plan_fused on a probe-size group AFTER a decision k < group K
        splits into winner-K chunks (already-compiled signature), the
        remainder padded with zero-weight steps; real-step counts split
        accordingly."""
        import jax.numpy as jnp

        net = mlp()
        X, Y = make_data(n=4 * 8, seed=2)
        xs = jnp.asarray(np.stack([X[i * 8:(i + 1) * 8] for i in range(4)]))
        ys = jnp.asarray(np.stack([Y[i * 8:(i + 1) * 8] for i in range(4)]))
        ews = jnp.ones((4, 8), jnp.float32)
        mk = autotuner.model_key(net)
        bkey = autotuner._stacked_bucket_key(xs, ys)
        autotuner.record_decision(mk, "cpu", bkey, 3, {3: 1e-3})
        import jax
        assert jax.default_backend() == "cpu"
        plan = autotuner.plan_fused(net, xs, ys, ews, 4, True)
        assert [c[3] for c in plan] == [3, 1]       # real steps per chunk
        assert all(c[0].shape == (3, 8, 4) for c in plan)
        # remainder chunk: step 4 is real, steps 5-6 zero-weight padding
        tail = plan[1]
        w = np.asarray(tail[2])
        assert w[0].min() == 1.0 and w[1:].max() == 0.0
        # an adaptive partial SMALLER than the decision passes through
        # untouched — padding it back up to K would undo adaptive grouping
        small = autotuner.plan_fused(net, xs[:2], ys[:2], ews[:2], 2, True)
        assert len(small) == 1 and small[0][0].shape == (2, 8, 4)
        assert small[0][3] == 2


class TestCompileCachePlacement:
    def test_compile_cache_dir_placed_from_outside(self, tmp_path):
        """The persistent XLA compilation cache is placed from outside:
        with JAX_COMPILATION_CACHE_DIR set JAX reads it itself and the
        package sets no directory; unset, the package falls back to the
        fixed ``<checkout>/.jax_cache``. Subprocesses: the rule runs at
        import time, which already happened in this process."""
        import subprocess
        import sys

        code = (
            "import os, sys\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import deeplearning4j_tpu, jax, jax.numpy as jnp\n"
            "d = jax.config.jax_compilation_cache_dir\n"
            "print(d)\n"
            "if os.environ.get('JAX_COMPILATION_CACHE_DIR'):\n"
            "    jax.jit(lambda x: x * 2 + 1)(jnp.ones((32, 32)))"
            ".block_until_ready()\n"
            "    print(len(os.listdir(d)))"
        )

        def run(cache_dir):
            env = dict(os.environ)
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
            if cache_dir:
                env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
            out = subprocess.run([sys.executable, "-c", code], env=env,
                                 capture_output=True, text=True, timeout=240)
            assert out.returncode == 0, out.stderr[-2000:]
            return out.stdout.strip().splitlines()

        placed, n_entries = run(str(tmp_path))[-2:]
        assert placed == str(tmp_path)
        assert int(n_entries) > 0   # cache wrote
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert run(None)[-1] == os.path.join(repo, ".jax_cache")


class TestUnfusedBucketing:
    """ISSUE 9 satellite: the per-batch (FUSE_STEPS=1) path bucket-pads
    ragged trailers with zero example weights, so unfused runs hold ONE
    train signature too (the pre-existing 'unfused=2 compiles' bench
    line — actually staged-slice recompiles plus ragged-trailer
    signatures — goes to zero)."""

    def test_unfused_ragged_trailer_one_signature_and_parity(
            self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "1")
        X, Y = make_data(n=120)   # 3 full batches of 32 + ragged 24
        a = mlp(seed=4)
        for s in range(0, 120, 32):
            a.fit_batch(X[s:s + 32], Y[s:s + 32])
        b = mlp(seed=4)
        b.fit(ArrayDataSetIterator(X, Y, batch_size=32))
        assert len(b._jit_train) == 1             # ew program, trailer incl.
        assert b.iteration == a.iteration == 4
        np.testing.assert_allclose(a.params(), b.params(), atol=1e-6)

    def test_unfused_refit_zero_compiles_across_stream_lengths(
            self, monkeypatch):
        """The staged super-batch slicing programs compile once per bucket
        — a later fit with a DIFFERENT number of trailing batches (the
        old '2 in-fit compiles' trigger: partial concats minted novel
        slice shapes) compiles nothing."""
        from tools.compile_counter import CompileCounter

        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "1")
        monkeypatch.setenv("DL4J_TPU_TRANSFER_STAGE", "4")
        net = mlp(seed=6)
        X, Y = make_data(n=6 * 8, seed=1)
        net.fit(ArrayDataSetIterator(X, Y, batch_size=8))   # 4 full + 2 tail
        X2, Y2 = make_data(n=7 * 8, seed=2)
        with CompileCounter() as cc:
            net.fit(ArrayDataSetIterator(X2, Y2, batch_size=8))  # 3-batch tail
        assert cc.count == 0
        assert len(net._jit_train) == 1
