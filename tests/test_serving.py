"""ISSUE 14: the continuous-batching inference serving tier.

Covers the batcher (bucketed dispatch through the blessed
``_output_signature`` cache, padding, backpressure), the continuous
decoder (greedy parity with ``generate``, mid-decode admission into
freed KV slots, 0 steady-state compiles / 2 signatures), the
decode-width autotuner (probe -> persist -> restart cache hit), server
warm-start over the persistent XLA compile cache (subprocess: second
boot compiles NOTHING — every compile request is a cache hit), the
blessed+bounded ``_jit_gen`` sampler cache, the serving chaos sites
(typed errors, no wedged threads — this file runs in ``make chaos``
under lockwatch), and the ``serve.*`` metric family on ``GET /metrics``
(parametrized p50/p99 scrape from the Prometheus text).
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import NeuralNetConfiguration, obs
from deeplearning4j_tpu.errors import (ServeQueueFullError,
                                       ServeStoppedError)
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import (ContinuousLM, InferenceServer,
                                        serve_buckets, slots_ladder)
from deeplearning4j_tpu.serving.decode import kv_ladder, prefill_ladder
from deeplearning4j_tpu.testing import faults
from tools.compile_counter import CompileCounter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def small_mln(seed=1, n_in=12, n_out=4):
    conf = (NeuralNetConfiguration.Builder().seed(seed).list()
            .layer(DenseLayer(n_in=n_in, n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=n_out, activation="softmax",
                               loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def small_lm(seed=3, pos_embed="learned", max_len=64):
    return TransformerLM(TransformerConfig(
        vocab_size=50, max_len=max_len, d_model=16, n_heads=2, n_layers=2,
        d_ff=32, pos_embed=pos_embed, seed=seed)).init()


def rows(n, n_in=12):
    return [np.random.RandomState(i).rand(n_in).astype(np.float32)
            for i in range(n)]


def prompts(sizes):
    return [np.arange(1, 1 + n, dtype=np.int32) % 49 + 1 for n in sizes]


@pytest.fixture(autouse=True)
def _clean_metrics():
    obs.reset_metrics()
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# the batcher: bucketed output() serving
# ---------------------------------------------------------------------------
class TestBatcher:
    def test_bucketed_dispatch_parity_zero_steady_compiles(self):
        net = small_mln()
        srv = InferenceServer(net, buckets=(4, 8))
        srv.warm_start([(12,)])
        assert len(srv.signatures()) == 2     # one per bucket, fixed set
        xs = rows(11)
        with CompileCounter() as cc:
            futs = [srv.submit(x) for x in xs]
            got = [f.result(30) for f in futs]
        assert cc.count == 0                  # 0 steady-state compiles
        assert srv.signatures() == srv.warm_start([(12,)])   # still fixed
        ref = net.output(np.stack(xs))
        for i, g in enumerate(got):
            assert np.allclose(g, ref[i], atol=1e-6)
        srv.stop()
        assert obs.metrics.value("serve.requests_total") == 11
        assert obs.metrics.value("serve.batches_total") >= 2

    def test_partial_batch_pads_to_bucket(self):
        net = small_mln()
        srv = InferenceServer(net, buckets=(4,), wait_s=0.0)
        srv.warm_start([(12,)])
        out = srv.infer(rows(1)[0])
        assert out.shape == (4,)
        srv.stop()
        # 1 real row rode a 4-row bucket: 3 padding rows, occupancy 0.25
        assert obs.metrics.value("serve.padded_rows_total") == 3
        h = obs.metrics.metrics_snapshot()["histograms"]
        assert h["serve.batch_occupancy"]["count"] == 1
        assert h["serve.batch_occupancy"]["min"] == 0.25

    def test_queue_overflow_backpressure_typed(self):
        net = small_mln()
        srv = InferenceServer(net, buckets=(4,))
        with faults.inject("queue-overflow@0"):
            with pytest.raises(ServeQueueFullError):
                srv.submit(rows(1)[0])
        assert obs.metrics.value("serve.rejected_total") == 1
        # the queue recovers: the next submit serves normally
        assert srv.infer(rows(1)[0]).shape == (4,)
        srv.stop()

    def test_real_capacity_backpressure(self):
        net = small_mln()
        srv = InferenceServer(net, buckets=(4,), queue_cap=0)
        with pytest.raises(ServeQueueFullError):
            srv.submit(rows(1)[0])
        srv.stop()

    def test_client_disconnect_discards_and_serves_on(self):
        net = small_mln()
        srv = InferenceServer(net, buckets=(2,), wait_s=0.0)
        srv.warm_start([(12,)])
        with faults.inject("client-disconnect@0"):
            f1 = srv.submit(rows(1)[0])
            # f1's result is discarded (caller gone); the loop must not
            # wedge — later requests still serve
            out = srv.infer(rows(2)[1], timeout=30)
            assert out.shape == (4,)
        assert f1.cancelled()
        assert obs.metrics.value("serve.disconnects_total") == 1
        srv.stop()

    def test_slow_request_lands_in_latency_histogram(self):
        net = small_mln()
        srv = InferenceServer(net, buckets=(2,), wait_s=0.0)
        srv.warm_start([(12,)])
        with faults.inject("slow-request@0:0.2"):
            srv.infer(rows(1)[0], timeout=30)
        h = obs.metrics.metrics_snapshot()["histograms"]
        assert h["serve.request_seconds"]["max"] >= 0.2
        srv.stop()

    def test_stop_drains_pending_typed_and_refuses_submits(self):
        net = small_mln()
        srv = InferenceServer(net, buckets=(2,), wait_s=0.0)
        srv.warm_start([(12,)])
        with faults.inject("slow-request@0:0.5"):
            f1 = srv.submit(rows(1)[0])      # held in dispatch by the sleep
            time.sleep(0.1)                  # loop is now inside the sleep
            f2 = srv.submit(rows(2)[1])      # still queued
            srv.stop()
        assert isinstance(f2.exception(timeout=5), ServeStoppedError)
        with pytest.raises(ServeStoppedError):
            srv.submit(rows(1)[0])
        # the in-flight one finished normally before the loop exited
        assert f1.result(5).shape == (4,)

    def test_batcher_serves_every_output_model_family(self):
        """Review regression: the docstring promises ComputationGraph and
        TransformerLM too — the signature provenance must route through
        each family's own blessed builder (CG: _cache_signature) or the
        uniform fallback tuple (LM logits), not MLN's method."""
        from deeplearning4j_tpu.models.computation_graph import \
            ComputationGraph
        cg_conf = (NeuralNetConfiguration.Builder()
                   .seed(5).learning_rate(0.1).updater("sgd")
                   .graph_builder()
                   .add_inputs("in")
                   .add_layer("dense", DenseLayer(n_in=6, n_out=10), "in")
                   .add_layer("out", OutputLayer(n_in=10, n_out=3,
                                                 activation="softmax",
                                                 loss="mcxent"), "dense")
                   .set_outputs("out").build())
        cg = ComputationGraph(cg_conf).init()
        srv = InferenceServer(cg, buckets=(4,), wait_s=0.0)
        srv.warm_start([(6,)])
        x = rows(3, n_in=6)
        got = [f.result(30) for f in [srv.submit(v) for v in x]]
        ref = cg.output(np.stack(x))
        assert all(np.allclose(g, ref[i], atol=1e-6)
                   for i, g in enumerate(got))
        assert srv.signatures() and "'out'" in srv.signatures()[0]
        srv.stop()

        lm = small_lm()
        srv = InferenceServer(lm, buckets=(2,), wait_s=0.0)
        toks = np.arange(1, 9, dtype=np.int32)
        got = srv.infer(toks, timeout=60)
        ref = lm.output(toks[None, :])[0]
        assert np.allclose(np.asarray(got), np.asarray(ref), atol=1e-6)
        srv.stop()

    def test_explicit_start_reenables_a_stopped_server(self):
        """stop() is final for submit() (typed error), but an EXPLICIT
        start() — the only call that clears the flag — brings the front
        end back."""
        net = small_mln()
        srv = InferenceServer(net, buckets=(2,), wait_s=0.0)
        srv.stop()
        with pytest.raises(ServeStoppedError):
            srv.submit(rows(1)[0])
        srv.start()
        assert srv.infer(rows(1)[0], timeout=30).shape == (4,)
        srv.stop()

    def test_buckets_knob_garbage_warns_and_falls_back(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_SERVE_BUCKETS", "4,banana")
        with pytest.warns(UserWarning, match="SERVE_BUCKETS"):
            assert serve_buckets() == (8,)
        monkeypatch.setenv("DL4J_TPU_SERVE_BUCKETS", "16, 2,4")
        assert serve_buckets() == (2, 4, 16)


# ---------------------------------------------------------------------------
# continuous batching: the KV slot pool decoder
# ---------------------------------------------------------------------------
class TestContinuousDecode:
    @pytest.mark.parametrize("pos_embed", ["learned", "rope"])
    def test_greedy_parity_with_generate_across_slot_reuse(self, pos_embed):
        """More requests than slots: completions free cache rows that the
        scheduler re-admits into MID-DECODE; every row must equal the
        per-request generate() greedy output exactly."""
        lm = small_lm(pos_embed=pos_embed)
        srv = ContinuousLM(lm, slots=2, chunk=4)
        ps = prompts((5, 3, 7, 2, 6, 4))
        futs = [srv.submit(p, 6) for p in ps]
        got = [f.result(120) for f in futs]
        srv.stop()
        for p, g in zip(ps, got):
            ref = lm.generate(p[None, :], 6, temperature=0.0)[0]
            assert np.array_equal(g, ref)

    def test_zero_steady_state_compiles_fixed_signatures(self):
        """warm_start pre-compiles the whole rung inventory — one admit,
        one decode program per KV rung, one prefill program per prefill
        rung — and a mixed pool never compiles again (ISSUE 16: the set
        is bounded by len(kv_ladder) + len(prefill_ladder) + admit)."""
        lm = small_lm()                              # max_len=64
        srv = ContinuousLM(lm, slots=2, chunk=4)
        srv.warm_start()
        srv.generate(prompts((4,))[0], 4)            # pool fully warm
        sigs = sorted(lm._jit_decode)
        assert sigs == [("admit", 2),
                        ("decode", 2, 4, 32), ("decode", 2, 4, 64),
                        ("prefill", 2, 16), ("prefill", 2, 64)]
        with CompileCounter() as cc:
            futs = [srv.submit(p, 5) for p in prompts((3, 5, 4, 6))]
            for f in futs:
                f.result(120)
        assert cc.count == 0
        assert sorted(lm._jit_decode) == sigs        # fixed signature set
        srv.stop()
        assert obs.metrics.value("serve.tokens_total") >= 4 * 5

    def test_mid_decode_admission(self):
        """A request submitted while another is decoding joins the SAME
        compiled step at the next chunk boundary (no second program, no
        restart of the in-flight row)."""
        lm = small_lm(max_len=64)
        srv = ContinuousLM(lm, slots=2, chunk=2)
        long_f = srv.submit(prompts((4,))[0], 30)
        time.sleep(0.05)                 # the long row is mid-decode now
        short = srv.generate(prompts((3,))[0], 4, timeout=120)
        long_out = long_f.result(120)
        srv.stop()
        assert np.array_equal(
            short, lm.generate(prompts((3,))[0][None, :], 4,
                               temperature=0.0)[0])
        assert np.array_equal(
            long_out, lm.generate(prompts((4,))[0][None, :], 30,
                                  temperature=0.0)[0])

    def test_sampled_serving_stays_in_vocab(self):
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4)
        out = srv.generate(prompts((4,))[0], 8, temperature=1.0, seed=7,
                           timeout=120)
        srv.stop()
        assert out.shape == (12,)
        assert (out >= 0).all() and (out < lm.conf.vocab_size).all()

    def test_submit_validation(self):
        lm = small_lm(max_len=16)
        srv = ContinuousLM(lm, slots=2, chunk=2)
        with pytest.raises(ValueError):
            srv.submit(np.zeros(0, np.int32), 4)
        with pytest.raises(ValueError):
            srv.submit(prompts((4,))[0], 0)
        with pytest.raises(ValueError):
            srv.submit(prompts((10,))[0], 10)    # P+n_new > max_len
        srv.stop()

    def test_overflow_and_disconnect_sites(self):
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4)
        with faults.inject("queue-overflow@0"):
            with pytest.raises(ServeQueueFullError):
                srv.submit(prompts((4,))[0], 4)
        with faults.inject("client-disconnect@0"):
            f1 = srv.submit(prompts((4,))[0], 4)
            f2 = srv.submit(prompts((3,))[0], 4)
            r2 = f2.result(120)
        assert r2.shape == (7,)
        assert f1.cancelled()                    # caller gone, discarded
        # the pool keeps serving after both faults
        assert srv.generate(prompts((5,))[0], 4, timeout=120).shape == (9,)
        srv.stop()

    def test_stop_fails_inflight_typed(self):
        lm = small_lm(max_len=64)
        srv = ContinuousLM(lm, slots=2, chunk=2)
        p = prompts((4,))[0]
        f = srv.submit(p, 40)                    # long generation
        time.sleep(0.05)
        srv.stop()
        # the contract: either it finished before stop() landed (a valid
        # full result) or it failed with the TYPED stop error — a raw
        # exception or a silently dropped future is a regression
        exc = f.exception(timeout=5)
        if exc is None:
            assert f.result().shape == (4 + 40,)
        else:
            assert isinstance(exc, ServeStoppedError), exc
        with pytest.raises(ServeStoppedError):
            srv.submit(p, 4)

    def test_restart_after_stop_rebuilds_full_capacity(self):
        """Review regression: stop() with requests in flight leaves their
        device rows active and out of the free list — an explicit
        start() must rebuild a FRESH pool at full capacity, not spin on
        an empty free list or serve at reduced width."""
        lm = small_lm(max_len=64)
        srv = ContinuousLM(lm, slots=2, chunk=2)
        inflight = [srv.submit(p, 40) for p in prompts((4, 3))]  # both slots
        time.sleep(0.05)                        # mid-decode
        srv.stop()
        for f in inflight:
            assert isinstance(f.exception(timeout=5), ServeStoppedError) \
                or f.done()
        srv.start()
        # more requests than slots: full capacity must be back
        ps = prompts((3, 5, 4, 6))
        got = [f.result(120) for f in [srv.submit(p, 4) for p in ps]]
        srv.stop()
        for p, g in zip(ps, got):
            assert np.array_equal(
                g, lm.generate(p[None, :], 4, temperature=0.0)[0])

    def test_ladder_knob_garbage_warns_and_falls_back(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_SERVE_SLOTS_LADDER", "2,x")
        with pytest.warns(UserWarning, match="SLOTS_LADDER"):
            assert slots_ladder() == (2, 4, 8)


# ---------------------------------------------------------------------------
# ISSUE 16: paged decode attention, chunked prefill, prefix-shared KV
# ---------------------------------------------------------------------------
class TestPagedPrefill:
    """The rung-ladder serving model: decode attends over the smallest
    KV window rung covering the pool, prompts prefill in whole windows
    interleaved with decode chunks, repeated prefixes inject cached KV
    pages — all bit-equal to ``generate(temperature=0)`` and all inside
    the fixed blessed-signature set."""

    def test_kv_ladder_derivation_and_off(self, monkeypatch):
        assert kv_ladder(64, 4) == (32, 64)
        assert kv_ladder(64, 4, "off") == (64,)
        assert kv_ladder(256, 8, (16, 48, 128)) == (16, 48, 128, 256)
        assert kv_ladder(2048, 8)[-1] == 2048
        assert prefill_ladder(64) == (16, 64)
        assert prefill_ladder(64, "off") == ()
        assert prefill_ladder(300) == (16, 64, 256)
        monkeypatch.setenv("DL4J_TPU_SERVE_KV_LADDER", "32,x")
        with pytest.warns(UserWarning, match="KV_LADDER"):
            assert kv_ladder(64, 4) == (32, 64)   # garbage -> derived

    @pytest.mark.parametrize("pos_embed", ["learned", "rope"])
    def test_greedy_parity_every_rung(self, pos_embed):
        """Prompt sizes chosen so the pool crosses EVERY decode rung and
        both prefill rungs; each row must bit-equal generate()."""
        lm = small_lm(pos_embed=pos_embed)
        srv = ContinuousLM(lm, slots=2, chunk=4, kv_ladder=(16, 32, 64),
                           prefill_ladder=(8, 16), prefix_cache_mb=8)
        try:
            ps = prompts((3, 9, 17, 30))
            futs = [srv.submit(p, 8) for p in ps]
            got = [f.result(240) for f in futs]
        finally:
            srv.stop()
        for p, g in zip(ps, got):
            ref = lm.generate(p[None, :], 8, temperature=0.0)[0]
            assert np.array_equal(g, ref)
        assert sorted(lm._jit_decode) == [
            ("admit", 2),
            ("decode", 2, 4, 16), ("decode", 2, 4, 32),
            ("decode", 2, 4, 64),
            ("prefill", 2, 8), ("prefill", 2, 16)]

    def test_prefix_hit_bit_equals_cold(self):
        """The same prompt twice: the second admission injects cached KV
        pages instead of recomputing them — identical output, hits
        counted."""
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4, kv_ladder=(32, 64),
                           prefill_ladder=(8, 16), prefix_cache_mb=8)
        try:
            p = prompts((33,))[0]
            cold = srv.generate(p, 6, timeout=240)
            assert obs.metrics.value("serve.prefix_hits_total") == 0
            warm = srv.generate(p, 6, timeout=240)
        finally:
            srv.stop()
        assert obs.metrics.value("serve.prefix_hits_total") > 0
        assert np.array_equal(cold, warm)
        assert np.array_equal(
            cold, lm.generate(p[None, :], 6, temperature=0.0)[0])

    def test_mixed_long_short_pool_zero_compiles(self):
        """Long prompts (prefill windows interleaved at chunk boundaries)
        and short prompts (direct admit) share one warm pool: zero
        steady-state compiles, signature count bounded by
        len(kv_ladder) + len(prefill_ladder) + admit."""
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4)
        sizes = (40, 3, 25, 2, 33)
        try:
            srv.warm_start()
            with CompileCounter() as cc:
                futs = [srv.submit(p, 5) for p in prompts(sizes)]
                got = [f.result(240) for f in futs]
        finally:
            srv.stop()
        assert cc.count == 0
        kl = kv_ladder(lm.conf.max_len, 4)
        pl = prefill_ladder(lm.conf.max_len)
        assert len(lm._jit_decode) <= len(kl) + len(pl) + 1
        for p, g in zip(prompts(sizes), got):
            ref = lm.generate(p[None, :], 5, temperature=0.0)[0]
            assert np.array_equal(g, ref)

    def test_ladder_decision_persists_and_restart_adopts(
            self, monkeypatch, tmp_path):
        """With autotune ARMED, a non-default ladder is recorded beside
        the K/slot decisions; a restarted server with no explicit ladder
        adopts it. Unarmed servers never write the shared tune cache."""
        from deeplearning4j_tpu.tuning import autotuner
        monkeypatch.setenv("DL4J_TPU_TUNE_CACHE_DIR", str(tmp_path))
        monkeypatch.delenv("DL4J_TPU_SERVE_KV_LADDER", raising=False)
        lm = small_lm()
        try:
            # unarmed: the explicit ladder stays this server's choice
            srv = ContinuousLM(lm, slots=2, chunk=4, kv_ladder=(16, 64))
            srv.generate(prompts((4,))[0], 4, timeout=120)
            srv.stop()
            assert os.listdir(tmp_path) == []
            monkeypatch.setenv("DL4J_TPU_SERVE_AUTOTUNE", "1")
            srv = ContinuousLM(lm, slots=2, chunk=4, kv_ladder=(16, 64))
            srv.generate(prompts((4,))[0], 4, timeout=120)
            srv.stop()
            autotuner._reset_for_tests()
            lm2 = small_lm()
            srv2 = ContinuousLM(lm2, slots=2, chunk=4)
            srv2.generate(prompts((4,))[0], 4, timeout=120)
            srv2.stop()
            assert srv2._kv_ladder == (16, 64)
        finally:
            # the decisions live in autotuner memory keyed by a model
            # key EVERY small_lm() shares — drop them or later tests
            # adopt this test's ladder
            autotuner._reset_for_tests()

    def test_prefill_and_ttft_metrics_recorded(self):
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4)
        try:
            srv.generate(prompts((33,))[0], 4, timeout=240)
        finally:
            srv.stop()
        h = obs.metrics.metrics_snapshot()["histograms"]
        assert h["serve.prefill_seconds"]["count"] >= 1
        assert h["serve.ttft_seconds"]["count"] >= 1
        assert obs.metrics.value("serve.prefill_windows_total") >= 1
        assert obs.metrics.value("serve.kv_window") in (32, 64)

    def test_stop_mid_prefill_fails_typed(self):
        """stop() with a request still in its prefill plan: either it
        finished (valid full row) or it failed with the TYPED stop error
        — a wedged future is a regression (chaos-lane coverage for the
        prefill interleaving state)."""
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4, kv_ladder=(64,),
                           prefill_ladder=(8,))
        p = prompts((60,))[0]          # 59-token span: 8 prefill windows
        f = srv.submit(p, 4)
        srv.stop()
        exc = f.exception(timeout=5)
        if exc is None:
            assert f.result().shape == (64,)
        else:
            assert isinstance(exc, ServeStoppedError), exc
        with pytest.raises(ServeStoppedError):
            srv.submit(p, 4)


# ---------------------------------------------------------------------------
# satellite: the blessed + knob-bounded compiled-sampler cache
# ---------------------------------------------------------------------------
class TestPerRequestSampling:
    """ISSUE 15 satellite: per-request ``top_k``/``top_p`` ride the slot
    state as device vectors — every sampler mix shares the ONE compiled
    chunk signature, and the filter math is the same function family
    ``generate()`` uses (parity pinned below)."""

    def test_top_k1_parity_with_greedy_generate(self):
        # top_k=1 keeps exactly the argmax token, so SAMPLING at
        # temperature 1 must reproduce generate()'s greedy row bit-exactly
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4)
        try:
            ps = prompts((5, 3, 7, 2))
            futs = [srv.submit(p, 6, temperature=1.0, top_k=1,
                               seed=11 + i) for i, p in enumerate(ps)]
            got = [f.result(120) for f in futs]
        finally:
            srv.stop()   # a timed-out result must not leak the scheduler
        for p, g in zip(ps, got):
            ref = lm.generate(p[None, :], 6, temperature=0.0)[0]
            assert np.array_equal(g, ref)

    def test_tiny_top_p_parity_with_greedy(self):
        # a nucleus that can only ever hold the first sorted token is
        # greedy by construction
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4)
        try:
            p = prompts((5,))[0]
            out = srv.generate(p, 6, temperature=1.0, top_p=1e-9, seed=3,
                               timeout=120)
        finally:
            srv.stop()
        assert np.array_equal(
            out, lm.generate(p[None, :], 6, temperature=0.0)[0])

    def test_mixed_sampler_chunk_no_new_signatures(self):
        """Greedy, top-k, top-p and unfiltered sampling requests decode
        CONCURRENTLY in one pool: zero steady-state compiles, the fixed
        two-signature set, and the deterministic rows still match
        generate()."""
        lm = small_lm()
        srv = ContinuousLM(lm, slots=4, chunk=4)
        try:
            srv.warm_start()
            srv.generate(prompts((4,))[0], 4, timeout=120)   # pool warm
            sigs = sorted(lm._jit_decode)
            ps = prompts((5, 3, 6, 4))
            with CompileCounter() as cc:
                futs = [
                    srv.submit(ps[0], 5),                           # greedy
                    srv.submit(ps[1], 5, temperature=1.0,
                               top_k=1),                            # =greedy
                    srv.submit(ps[2], 5, temperature=0.9, top_k=3,
                               top_p=0.8, seed=5),                  # sampled
                    srv.submit(ps[3], 5, temperature=1.2, seed=9),  # sampled
                ]
                got = [f.result(120) for f in futs]
        finally:
            srv.stop()
        assert cc.count == 0
        assert sorted(lm._jit_decode) == sigs
        for i in (0, 1):
            ref = lm.generate(ps[i][None, :], 5, temperature=0.0)[0]
            assert np.array_equal(got[i], ref)
        for g in got[2:]:
            assert (g >= 0).all() and (g < lm.conf.vocab_size).all()

    def test_filter_rows_matches_generate_filter(self):
        """The per-row filter is numerically the same as the scalar
        ``_filter_logits`` generate() compiles, row for row."""
        import jax.numpy as jnp
        rng = np.random.RandomState(0)
        logits = jnp.asarray(rng.randn(4, 50).astype(np.float32))
        ks = np.array([1, 5, 50, 12], np.int32)
        pps = np.array([1.0, 0.7, 0.35, 1.0], np.float32)
        rowed = TransformerLM._filter_logits_rows(
            logits, jnp.asarray(ks), jnp.asarray(pps))
        for i in range(4):
            ref = TransformerLM._filter_logits(
                logits[i:i + 1], int(ks[i]) if ks[i] < 50 else None,
                float(pps[i]) if pps[i] < 1.0 else None)
            assert np.allclose(np.asarray(rowed[i]), np.asarray(ref[0]))

    def test_sampler_validation(self):
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=2)
        try:
            with pytest.raises(ValueError):
                srv.submit(prompts((4,))[0], 4, top_k=0)
            with pytest.raises(ValueError):
                srv.submit(prompts((4,))[0], 4,
                           top_k=lm.conf.vocab_size + 1)
            with pytest.raises(ValueError):
                srv.submit(prompts((4,))[0], 4, top_p=0.0)
            with pytest.raises(ValueError):
                srv.submit(prompts((4,))[0], 4, top_p=1.5)
        finally:
            srv.stop()


class TestServingTeardown:
    """ISSUE 15: the serving teardown contract under the runtime leak
    watcher — stop() leaves no thread, socket or file behind."""

    def test_stop_releases_everything_leakwatch_clean(self):
        from deeplearning4j_tpu.testing import leakwatch
        lm = small_lm()
        with leakwatch.watch() as lw:
            snap = lw.snapshot()
            srv = ContinuousLM(lm, slots=2, chunk=4)
            batcher = None
            try:
                # a long prompt takes the prefill path and leaves pages
                # in the prefix cache — stop() must free those too
                srv.generate(prompts((33,))[0], 4, timeout=120)
                batcher = InferenceServer(small_mln(), buckets=(4,))
                batcher.infer(rows(1)[0], timeout=60)
            finally:
                if batcher is not None:
                    batcher.stop()
                srv.stop()
            lw.assert_clean(since=snap)

    def test_double_stop_is_idempotent(self):
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4)
        srv.generate(prompts((4,))[0], 4, timeout=120)
        srv.stop()
        srv.stop()   # second stop must not wedge or raise


class TestGenCacheBlessed:
    def test_gen_cache_bounded_by_knob(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_SERVE_GEN_CACHE", "2")
        lm = small_lm()
        for p_len in (3, 4, 5):
            lm.generate(prompts((p_len,))[0][None, :], 3, temperature=0.0)
        assert len(lm._jit_gen) <= 2
        # keys come from the blessed builder
        for sig in lm._jit_gen:
            assert sig[0] in ("sample", "beam") and isinstance(sig, tuple)

    def test_beam_rides_the_same_bounded_cache(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_SERVE_GEN_CACHE", "2")
        lm = small_lm()
        lm.generate(prompts((3,))[0][None, :], 3, temperature=0.0)
        lm.beam_search(prompts((3,))[0][None, :], 3, beams=2)
        lm.beam_search(prompts((4,))[0][None, :], 3, beams=2)
        assert len(lm._jit_gen) <= 2
        assert any(s[0] == "beam" for s in lm._jit_gen)


# ---------------------------------------------------------------------------
# satellite: first-request decode-width autotuner
# ---------------------------------------------------------------------------
class TestSlotsAutotune:
    def test_explicit_knob_always_wins(self, monkeypatch, tmp_path):
        monkeypatch.setenv("DL4J_TPU_SERVE_AUTOTUNE", "1")
        monkeypatch.setenv("DL4J_TPU_SERVE_SLOTS", "3")
        monkeypatch.setenv("DL4J_TPU_TUNE_CACHE_DIR", str(tmp_path))
        lm = small_lm()
        srv = ContinuousLM(lm, chunk=4)
        srv.generate(prompts((4,))[0], 4, timeout=120)
        srv.stop()
        assert obs.metrics.value("serve.autotune_probes_total") == 0
        assert obs.metrics.value("serve.slots") == 3

    def test_probe_persists_and_restart_skips(self, monkeypatch, tmp_path):
        from deeplearning4j_tpu.tuning import autotuner
        monkeypatch.setenv("DL4J_TPU_SERVE_AUTOTUNE", "1")
        monkeypatch.setenv("DL4J_TPU_SERVE_SLOTS_LADDER", "1,2")
        monkeypatch.setenv("DL4J_TPU_TUNE_CACHE_DIR", str(tmp_path))
        monkeypatch.delenv("DL4J_TPU_SERVE_SLOTS", raising=False)
        lm = small_lm()
        srv = ContinuousLM(lm, chunk=2)
        futs = [srv.submit(p, 4) for p in prompts((4, 3, 5))]
        for f in futs:
            f.result(120)
        srv.stop()
        assert obs.metrics.value("serve.autotune_probes_total") == 2
        winner = obs.metrics.value("serve.slots")
        assert winner in (1, 2)
        # losers evicted: only the winner's programs stay — the probe's
        # top-rung decode plus whatever the served requests compiled
        # (the 32 rung; these prompts sit below the smallest prefill
        # window, so they teacher-force and compile no prefill program)
        assert sorted(lm._jit_decode) == [
            ("admit", winner), ("decode", winner, 2, 32),
            ("decode", winner, 2, 64)]
        assert len(os.listdir(tmp_path)) == 1    # atomic cache committed
        # "restart": drop in-memory decisions, fresh model/server — the
        # persisted decision is read back, zero probes
        autotuner._reset_for_tests()
        obs.reset_metrics()
        lm2 = small_lm()
        srv2 = ContinuousLM(lm2, chunk=2)
        srv2.generate(prompts((4,))[0], 4, timeout=120)
        srv2.stop()
        assert obs.metrics.value("serve.autotune_probes_total") == 0
        assert obs.metrics.value("serve.slots") == winner

    def test_warm_start_refused_on_a_live_scheduler(self):
        """Review regression: the slot pool is scheduler-owned once
        submits flow — warm_start on a live server must refuse instead
        of racing the loop thread."""
        lm = small_lm(max_len=64)
        srv = ContinuousLM(lm, slots=2, chunk=2)
        f = srv.submit(prompts((4,))[0], 30)
        with pytest.raises(RuntimeError, match="before serving starts"):
            srv.warm_start()
        assert f.result(120).shape == (34,)     # request unharmed
        srv.stop()

    def test_warm_start_pins_the_actually_served_lm_signatures(self):
        """Review regression: LM token inputs are int32 — the family-
        aware warm dtype must pre-compile the signatures real submits
        hit, keeping the set FIXED after warmup."""
        lm = small_lm()
        srv = InferenceServer(lm, buckets=(2,), wait_s=0.0)
        warm = srv.warm_start([(8,)])
        assert "'int32'" in warm[0]
        srv.infer(np.arange(1, 9, dtype=np.int32), timeout=60)
        assert srv.signatures() == warm          # no new signature
        srv.stop()

    def test_model_key_ignores_value_only_config_fields(self):
        """Review regression: two architecturally identical LMs that
        differ only in seed/lr/decay share one persisted decision slot;
        a real architecture change does not."""
        from deeplearning4j_tpu.tuning.autotuner import model_key
        a = small_lm(seed=1)
        b = small_lm(seed=2)
        b.conf.learning_rate = 9.9
        c = TransformerLM(TransformerConfig(
            vocab_size=50, max_len=64, d_model=32, n_heads=2, n_layers=2,
            d_ff=32, seed=1)).init()
        assert model_key(a) == model_key(b)
        assert model_key(a) != model_key(c)

    def test_unarmed_uses_memory_derived_default_without_probe(
            self, monkeypatch, tmp_path, caplog):
        """ISSUE 16 satellite: with no knob, no persisted decision and no
        armed probe, the slot width is DERIVED from the memory budget —
        memlint's decode-row kv_cache bytes per slot against half the
        budget after params — and the derivation is logged."""
        import logging

        import jax
        monkeypatch.delenv("DL4J_TPU_SERVE_AUTOTUNE", raising=False)
        monkeypatch.delenv("DL4J_TPU_SERVE_SLOTS", raising=False)
        monkeypatch.setenv("DL4J_TPU_TUNE_CACHE_DIR", str(tmp_path))
        lm = small_lm()
        params_b = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(lm.params))
        # 2*L*kv_heads*max_len*hd*4 — the decode-row formula for small_lm
        kv_slot = 2 * 2 * 2 * 64 * 8 * 4
        # budget chosen so (budget/2 - params) holds exactly 3 slots
        monkeypatch.setenv("DL4J_TPU_MEM_BUDGET",
                           str(2 * (params_b + 3 * kv_slot)))
        with caplog.at_level(logging.INFO,
                             logger="deeplearning4j_tpu.serving.decode"):
            srv = ContinuousLM(lm, chunk=4)
            srv.generate(prompts((4,))[0], 4, timeout=120)
            srv.stop()
        assert obs.metrics.value("serve.autotune_probes_total") == 0
        assert obs.metrics.value("serve.slots") == 3
        assert any("derived from memory" in r.message
                   for r in caplog.records)


# ---------------------------------------------------------------------------
# satellite: server warm-start over the persistent XLA compile cache
# ---------------------------------------------------------------------------
_WARM_BOOT = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from tools.compile_counter import CompileCacheCounter
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import ContinuousLM, InferenceServer

conf = (NeuralNetConfiguration.Builder().seed(1).list()
        .layer(DenseLayer(n_in=8, n_out=8, activation="relu"))
        .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
lm = TransformerLM(TransformerConfig(
    vocab_size=40, max_len=32, d_model=16, n_heads=2, n_layers=1,
    d_ff=32, seed=0)).init()
with CompileCacheCounter() as cc:
    InferenceServer(net, buckets=(2, 4)).warm_start([(8,)])
    ContinuousLM(lm, slots=2, chunk=2).warm_start()
print("HITS", cc.hits, "MISSES", cc.misses)
"""


class TestWarmStart:
    def test_second_boot_compiles_nothing(self, tmp_path):
        """Serving startup pre-compiles the blessed inference signatures;
        with a persistent cache (JAX_COMPILATION_CACHE_DIR) the SECOND boot serves
        every compile request from the persistent cache — zero misses
        (backend_compile events still fire on hits on current jax, so
        the cache counter, not CompileCounter, is the oracle)."""
        env = dict(os.environ)
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        env.pop("DL4J_TPU_FAULT_SPEC", None)

        def boot():
            r = subprocess.run([sys.executable, "-c", _WARM_BOOT],
                               env=env, capture_output=True, text=True,
                               timeout=300, cwd=REPO)
            assert r.returncode == 0, r.stderr[-2000:]
            line = [l for l in r.stdout.splitlines()
                    if l.startswith("HITS")][-1].split()
            return int(line[1]), int(line[3])

        hits1, misses1 = boot()
        assert misses1 > 0                  # cold boot really compiled
        hits2, misses2 = boot()
        assert misses2 == 0                 # warm restart: all from cache
        assert hits2 >= misses1


# ---------------------------------------------------------------------------
# serve.* on GET /metrics (the Prometheus scrape contract)
# ---------------------------------------------------------------------------
def _prom_quantile(text, pname, q):
    """histogram_quantile over the cumulative buckets in the exposition
    text — what a Prometheus dashboard computes from this scrape."""
    buckets = []
    for line in text.splitlines():
        if line.startswith(f"{pname}_bucket"):
            le = line.split('le="')[1].split('"')[0]
            buckets.append((float("inf") if le == "+Inf" else float(le),
                            int(float(line.rsplit(" ", 1)[1]))))
    total = buckets[-1][1]
    assert total > 0
    rank = q * total
    prev_le, prev_c = 0.0, 0
    for le, c in buckets:
        if c >= rank:
            if le == float("inf"):
                return prev_le
            frac = (rank - prev_c) / max(c - prev_c, 1)
            return prev_le + (le - prev_le) * frac
        prev_le, prev_c = le, c
    return prev_le


class TestMetricsEndpoint:
    @pytest.fixture
    def served_ui(self):
        from deeplearning4j_tpu.ui.server import UIServer
        lm = small_lm()
        srv = ContinuousLM(lm, slots=2, chunk=4)
        futs = [srv.submit(p, 5) for p in prompts((4, 3, 5, 6))]
        for f in futs:
            f.result(120)
        srv.stop()
        ui = UIServer(port=0).start()
        yield ui
        ui.stop()

    @pytest.mark.parametrize("q", [0.5, 0.99])
    def test_scrape_request_latency_percentiles(self, served_ui, q):
        """The acceptance scrape: p50/p99 of serve.request_seconds come
        OUT of the Prometheus text. A dashboard's histogram_quantile
        lerps to the bucket's upper edge while the registry clamps to
        the observed max, so the two estimates agree at BUCKET
        resolution (same or adjacent bucket), not bitwise."""
        import bisect
        from deeplearning4j_tpu.obs.metrics import TIME_BUCKETS
        with urllib.request.urlopen(
                f"http://127.0.0.1:{served_ui.port}/metrics",
                timeout=5) as r:
            text = r.read().decode()
        assert "# TYPE dl4j_tpu_serve_request_seconds histogram" in text
        got = _prom_quantile(text, "dl4j_tpu_serve_request_seconds", q)
        want = obs.metrics._REGISTRY["serve.request_seconds"].quantile(q)
        assert got > 0 and want > 0
        b = lambda v: bisect.bisect_left(TIME_BUCKETS, v)
        assert abs(b(got) - b(want)) <= 1, (got, want)

    def test_serve_family_exported_and_serve_data_slice(self, served_ui):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{served_ui.port}/metrics",
                timeout=5) as r:
            text = r.read().decode()
        for name in ("dl4j_tpu_serve_queue_depth",
                     "dl4j_tpu_serve_tokens_total",
                     "dl4j_tpu_serve_batch_occupancy",
                     "dl4j_tpu_serve_slots"):
            assert name in text, name
        with urllib.request.urlopen(
                f"http://127.0.0.1:{served_ui.port}/serve/data",
                timeout=5) as r:
            data = json.loads(r.read())
        names = [n for kind in data.values() for n in kind]
        assert names and all(n.startswith(("serve.", "infer."))
                             for n in names)
        assert "serve.tokens_total" in data["counters"]
