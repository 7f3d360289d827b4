"""Unified observability layer (deeplearning4j_tpu/obs/): metric registry,
trace spans, instrumentation through the training stack, export surfaces.

The acceptance contract under test (ISSUE 6, re-stated by ISSUE 26): with
DL4J_TPU_METRICS=1 and a profiler session running, a fused fit still compiles
0 programs in-fit against 1 train signature (instrumentation adds no
recompiles or hot-path syncs), the session's one ``.xplane.pb`` holds the
program's spans (``dl4j:<name>``) from >=2 distinct threads, and the PR-3 fuse
telemetry counts identically through its migrated registry mirror.
"""

import contextlib
import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import NeuralNetConfiguration, obs
from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator, DataSet
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.obs import metrics as obs_metrics


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_data(n=120, d=4, c=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    return X, np.eye(c, dtype=np.float32)[rng.integers(0, c, n)]


def mlp(seed=1):
    conf = (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.1)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture(autouse=True)
def _clean_registry():
    obs.reset_metrics()
    yield
    obs.reset_metrics()


@contextlib.contextmanager
def profiler_session(directory):
    """A CPU profiler capture with the options ProfilerListener and the
    benchmark use; yields a function that reads the program's spans back
    out of the session's one file once the block has ended."""
    import jax

    from benchmark import scope_reduce

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(directory), profiler_options=options)

    def spans():
        found = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                          recursive=True)
        assert len(found) == 1
        return scope_reduce.load(found[0])[1]

    try:
        yield spans
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# registry units
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        c = obs.counter("t.obs.c", "a counter")
        c.inc()
        c.inc(4)
        assert obs_metrics.value("t.obs.c") == 5
        g = obs.gauge("t.obs.g")
        g.set(3)
        g.set(7)
        assert obs_metrics.value("t.obs.g") == 7
        h = obs.histogram("t.obs.h_seconds")
        h.record(0.004)
        h.record(0.004)
        h.record(40.0)
        snap = h.snapshot()
        assert snap["count"] == 3
        assert snap["min"] == 0.004 and snap["max"] == 40.0
        assert snap["sum"] == pytest.approx(40.008)
        # bucket counts are per-bucket in the snapshot, cumulative only in
        # the Prometheus exposition
        by_bound = dict((str(b), n) for b, n in snap["buckets"])
        assert by_bound["0.005"] == 2
        assert by_bound["60.0"] == 1

    def test_same_name_returns_same_object_and_kind_is_checked(self):
        assert obs.counter("t.obs.same") is obs.counter("t.obs.same")
        with pytest.raises(ValueError, match="already registered"):
            obs.gauge("t.obs.same")

    def test_timer_records_into_histogram(self):
        with obs.timer("t.obs.timed_seconds"):
            pass
        h = obs.histogram("t.obs.timed_seconds")
        assert h.count == 1 and 0 <= h.sum < 1.0

    def test_quantile_estimates_are_clamped_to_observations(self):
        h = obs.histogram("t.obs.q_seconds")
        for _ in range(100):
            h.record(0.002)
        assert h.quantile(0.5) == pytest.approx(0.002, abs=0.001)
        # lerp inside the (0.001, 0.0025] bucket must not exceed the max
        assert h.quantile(0.99) <= h.snapshot()["max"]
        assert obs.histogram("t.obs.empty").quantile(0.5) is None

    def test_disabled_knob_makes_records_no_ops(self, monkeypatch):
        c = obs.counter("t.obs.gated")
        h = obs.histogram("t.obs.gated_seconds")
        monkeypatch.setenv("DL4J_TPU_METRICS", "0")
        c.inc()
        h.record(1.0)
        with h.time():
            pass
        assert c.value == 0 and h.count == 0
        snap = obs.metrics_snapshot()
        assert snap["enabled"] is False
        monkeypatch.setenv("DL4J_TPU_METRICS", "1")
        c.inc()
        assert c.value == 1   # call-time knob: flips back on without rebuild

    def test_thread_safety_of_counter_increments(self):
        c = obs.counter("t.obs.mt")

        def work():
            for _ in range(1000):
                c.inc()

        ts = [threading.Thread(target=work) for _ in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.value == 8000

    def test_snapshot_is_json_able_and_summary_compact(self):
        obs.counter("t.obs.c2").inc(2)
        h = obs.histogram("t.obs.h2_seconds")
        h.record(0.01)
        snap = obs.metrics_snapshot()
        json.dumps(snap)   # must not raise
        assert snap["counters"]["t.obs.c2"] == 2
        assert snap["histograms"]["t.obs.h2_seconds"]["count"] == 1
        # the log of compiled programs rides under a key of its own
        assert snap["compiles"] == obs.compiles()
        assert not hasattr(obs, "metrics_summary")

    def test_prometheus_exposition_format(self):
        obs.counter("t.obs.prom", "events seen").inc(3)
        h = obs.histogram("t.obs.prom_seconds", buckets=(0.1, 1.0))
        h.record(0.05)
        h.record(5.0)
        text = obs.prometheus_text()
        assert "# TYPE dl4j_tpu_t_obs_prom counter" in text
        assert "dl4j_tpu_t_obs_prom 3" in text
        assert "# HELP dl4j_tpu_t_obs_prom events seen" in text
        # histogram: cumulative buckets + _sum/_count
        assert 'dl4j_tpu_t_obs_prom_seconds_bucket{le="0.1"} 1' in text
        assert 'dl4j_tpu_t_obs_prom_seconds_bucket{le="1.0"} 1' in text
        assert 'dl4j_tpu_t_obs_prom_seconds_bucket{le="+Inf"} 2' in text
        assert "dl4j_tpu_t_obs_prom_seconds_count 2" in text


# ---------------------------------------------------------------------------
# trace spans
# ---------------------------------------------------------------------------
class TestTracing:
    def test_outside_a_session_a_span_writes_nothing(self, tmp_path,
                                                     monkeypatch):
        """No switch, no buffer, no file of its own: with no profiler
        session a span is a no-op that touches nothing on disk."""
        monkeypatch.chdir(tmp_path)
        monkeypatch.setenv("TMPDIR", str(tmp_path))
        with obs.span("t.nothing", items=3):
            pass
        assert os.listdir(tmp_path) == []
        assert not hasattr(obs, "flush_trace")
        assert not hasattr(obs, "add_span")

    def test_spans_of_two_threads_lie_on_separate_lines_of_the_host_plane(
            self, tmp_path):
        with obs.span("t.before_the_session"):
            pass
        with profiler_session(tmp_path) as spans:
            def worker():
                with obs.span("t.worker_phase", items=3):
                    pass

            t = threading.Thread(target=worker, name="obs-test-worker")
            t.start()
            t.join()
            with obs.span("t.main_phase"):
                with obs.span("t.inner"):
                    pass
        got = spans()
        by_name = {name: (thread, start, dur)
                   for thread, name, start, dur in got}
        # the annotation's arguments are no part of the span's name
        assert set(by_name) == {"t.worker_phase", "t.main_phase", "t.inner"}
        assert by_name["t.worker_phase"][0] != by_name["t.main_phase"][0]
        assert by_name["t.inner"][0] == by_name["t.main_phase"][0]
        _, start, dur = by_name["t.main_phase"]
        _, inner_start, inner_dur = by_name["t.inner"]
        assert start <= inner_start and inner_start + inner_dur <= start + dur

    def test_the_raw_event_carries_the_prefix_and_the_arguments(
            self, tmp_path):
        from jax.profiler import ProfileData
        with profiler_session(tmp_path) as spans:
            with obs.span("t.group", steps=4):
                pass
        assert [n for _, n, _, _ in spans()] == ["t.group"]
        path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        events = {e.name: dict(e.stats)
                  for plane in ProfileData.from_file(path).planes
                  if plane.name == "/host:CPU"
                  for line in plane.lines for e in line.events}
        assert events["dl4j:t.group"]["steps"] == 4


# ---------------------------------------------------------------------------
# the compile log: one jax.monitoring registration, owners, the cache (ISSUE 36)
# ---------------------------------------------------------------------------
def tiny_lm(seed=0, **changes):
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    conf = dict(vocab_size=40, max_len=32, d_model=16, n_heads=2, n_layers=1,
                d_ff=32, seed=seed)
    conf.update(changes)
    return TransformerLM(TransformerConfig(**conf)).init()


TOKENS = np.random.default_rng(0).integers(0, 40, (2, 17)).astype(np.int32)

_BOOT = """
import json, os
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
lm = TransformerLM(TransformerConfig(
    vocab_size=40, max_len=32, d_model=16, n_heads=2, n_layers=1,
    d_ff=32, seed=0)).init()
lm.fit_batch(np.arange(34, dtype=np.int32).reshape(2, 17))
print("BOOT", json.dumps({
    "step": [e for e in obs.compiles() if e["owner"] == "lm.step"],
    "hits": obs.metrics.value("compile.cache_hits_total"),
    "writes": obs.metrics.value("compile.cache_writes_total")}))
"""


def since(t0):
    return [e for e in obs.compiles() if e["end"] >= t0]


class TestCompileLog:
    @pytest.mark.parametrize("kernels", ["dense", "pallas_interpreter"])
    def test_first_fit_batch_leaves_one_entry_owned_by_lm_step(
            self, kernels, monkeypatch):
        """Under the Pallas interpreter the kernels' lowering rules trace
        jitted functions of their own: those seconds lie inside the lowering
        event's and are counted once."""
        changes = {}
        if kernels == "pallas_interpreter":
            monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
            monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
            changes = {"block_size": 8}
        lm = tiny_lm(**changes)
        t0 = time.perf_counter()
        lm.fit_batch(TOKENS)
        owned = [e for e in since(t0) if e["owner"] == "lm.step"]
        assert len(owned) == 1
        e = owned[0]
        assert e["fun_name"] == "jit(step)"
        parts = (e["trace_seconds"], e["lower_seconds"],
                 e["backend_seconds"])
        assert min(parts) > 0
        assert obs.metrics.value("lm.step.build_seconds") >= sum(parts)
        assert e["end"] <= time.perf_counter()
        assert obs.metrics.value("compile.programs_total") >= 1
        assert obs.metrics.value("compile.trace_seconds_total") \
            >= e["trace_seconds"]
        json.dumps(obs.compiles())

    def test_second_fit_batch_adds_no_entry_and_stays_out_of_the_bracket(
            self, monkeypatch):
        lm = tiny_lm()
        lm.fit_batch(TOKENS)
        built = obs.metrics.value("lm.step.build_seconds")
        entered = []
        real = obs.building
        monkeypatch.setattr(obs, "building",
                            lambda owner: entered.append(owner) or real(owner))
        t0 = time.perf_counter()
        lm.fit_batch(TOKENS)
        assert since(t0) == [] and entered == []
        assert obs.metrics.value("lm.step.build_seconds") == built
        # a new step program (here: the old one dropped) is a new build
        lm._step = None
        lm.fit_batch(TOKENS)
        assert entered == ["lm.step"]
        assert [e["owner"] for e in since(t0)] == ["lm.step"]

    def test_second_boot_is_served_by_the_persistent_cache(self, tmp_path):
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        env.pop("DL4J_TPU_FAULT_SPEC", None)
        env.pop("DL4J_TPU_METRICS", None)

        def boot():
            r = subprocess.run([sys.executable, "-c", _BOOT], env=env,
                               capture_output=True, text=True, timeout=300,
                               cwd=REPO)
            assert r.returncode == 0, r.stderr[-2000:]
            line = [l for l in r.stdout.splitlines()
                    if l.startswith("BOOT ")][-1]
            return json.loads(line[5:])

        cold, warm = boot(), boot()
        (c,), (w,) = cold["step"], warm["step"]
        assert c["cache_asked"] and not c["cache_served"] \
            and c["cache_written"]
        assert cold["hits"] == 0 and cold["writes"] > 0
        assert w["cache_asked"] and w["cache_served"] \
            and not w["cache_written"]
        assert w["retrieval_seconds"] > 0
        assert warm["hits"] >= cold["writes"] and warm["writes"] == 0
        # trace and lowering are paid on the warm boot too
        assert w["trace_seconds"] > 0 and w["lower_seconds"] > 0

    def test_install_twice_registers_once(self):
        from jax._src import monitoring

        from deeplearning4j_tpu.obs import compilation
        compilation.install()
        compilation.install()
        tiny_lm()           # the constructors install too
        assert monitoring.get_event_duration_listeners().count(
            compilation._on_duration) == 1
        assert monitoring.get_event_listeners().count(
            compilation._on_event) == 1

    def test_metrics_off_keeps_the_registry_silent_and_the_counters_counting(
            self, monkeypatch):
        import jax

        from tools.compile_counter import CompileCacheCounter, CompileCounter
        monkeypatch.setenv("DL4J_TPU_METRICS", "0")
        t0 = time.perf_counter()
        with CompileCounter() as cc, CompileCacheCounter() as cache:
            with obs.building("t.obs.off"):
                jax.jit(lambda x: x * 2 + 1)(np.ones(3, np.float32))
        assert cc.count == 1 and cc.seconds > 0
        assert cache.hits + cache.misses == 1
        snap = obs.metrics_snapshot()
        assert all(v == 0 for k, v in snap["counters"].items()
                   if k.startswith("compile."))
        assert snap["gauges"]["t.obs.off.build_seconds"] == 0
        # the log is kept whatever the knob says
        assert [e["owner"] for e in since(t0)] == ["t.obs.off"]

    def test_building_nests_and_is_per_thread(self):
        import jax

        def program(k):
            return jax.jit(lambda x: x + k)(np.ones(2, np.float32))

        other = {}

        def elsewhere():
            t0 = time.perf_counter()
            program(12.5)
            other["entries"] = since(t0)

        t0 = time.perf_counter()
        with obs.building("t.obs.outer"):
            program(1.5)
            with obs.building("t.obs.inner"):
                program(2.5)
                t = threading.Thread(target=elsewhere)
                t.start()
                t.join(timeout=60)
                assert not t.is_alive()
            program(3.5)
        program(4.5)
        mine = [e["owner"] for e in since(t0)
                if e not in other["entries"]]
        assert mine == ["t.obs.outer", "t.obs.inner", "t.obs.outer", None]
        # the other thread compiled inside no bracket of its own
        assert [e["owner"] for e in other["entries"]] == [None]
        assert obs.metrics.value("t.obs.outer.build_seconds") \
            >= obs.metrics.value("t.obs.inner.build_seconds") > 0

    def test_nested_jits_trace_time_is_counted_once(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def inner(x):
            return jnp.sin(x) * 2

        @jax.jit
        def outer(x):
            y = x
            for _ in range(20):
                y = inner(y + 1)
            return jax.checkpoint(lambda z: inner(z).sum())(y)

        t0 = time.perf_counter()
        outer(np.ones((4, 4), np.float32))
        wall = time.perf_counter() - t0
        (e,) = [e for e in since(t0) if e["fun_name"] == "jit(outer)"]
        assert 0 < e["trace_seconds"] + e["lower_seconds"] \
            + e["backend_seconds"] <= wall

    def test_lowered_and_never_compiled_is_no_program(self):
        import jax

        def only_lowered(x):
            return x * 3

        t0 = time.perf_counter()
        jax.jit(only_lowered).lower(np.ones(3, np.float32))
        assert all("only_lowered" not in e["fun_name"] for e in since(t0))
        jax.jit(lambda x: x - 7)(np.ones(3, np.float32))
        (e,) = [e for e in since(t0) if e["fun_name"] == "jit(<lambda>)"]
        assert e["lower_seconds"] > 0

    def test_a_subscriber_runs_on_the_compiling_thread_with_its_stack(self):
        import jax

        from deeplearning4j_tpu.obs import compilation
        seen = []

        def callback(entry):
            seen.append((entry["fun_name"], threading.get_ident(),
                         sys._getframe(1).f_code.co_filename))

        compilation.subscribe(callback)
        compilation.subscribe(callback)      # once a callback
        try:
            jax.jit(lambda x: x * 5 - 2)(np.ones(3, np.float32))
        finally:
            compilation._subscribers.remove(callback)
        assert seen == [("jit(<lambda>)", threading.get_ident(),
                         compilation.__file__)]

    def test_warm_start_logs_one_owned_row_a_program_of_the_ladder(self):
        from deeplearning4j_tpu.serving import ContinuousLM
        lm = tiny_lm()
        t0 = time.perf_counter()
        srv = ContinuousLM(lm, slots=2, chunk=2)
        srv.warm_start()
        # the eager helpers a rung compiles beside its program (an iota, a
        # broadcast) are its rows too; the ladder's own are these three
        owned = {e["owner"]: e["fun_name"] for e in since(t0)
                 if e["fun_name"] in ("jit(admit)", "jit(chunk_run)",
                                      "jit(prefill)")}
        want = {"serve.warm.admit": "jit(admit)"}
        want.update({f"serve.warm.decode.w{w}": "jit(chunk_run)"
                     for w in srv._kv_ladder})
        want.update({f"serve.warm.prefill.w{w}": "jit(prefill)"
                     for w in srv._prefill_ladder})
        assert owned == want
        for owner in want:
            assert obs.metrics.value(owner + ".build_seconds") > 0

    def test_prometheus_text_holds_the_compile_counters(self):
        import jax
        jax.jit(lambda x: x / 3)(np.ones(3, np.float32))
        text = obs.prometheus_text()
        assert "# TYPE dl4j_tpu_compile_programs_total counter" in text
        programs = [l for l in text.splitlines()
                    if l.startswith("dl4j_tpu_compile_programs_total ")]
        assert programs and int(programs[0].split()[1]) >= 1
        for name in ("trace_seconds", "lower_seconds", "backend_seconds",
                     "cache_requests", "cache_hits", "cache_writes",
                     "cache_retrieval_seconds", "cache_saved_seconds"):
            assert f"dl4j_tpu_compile_{name}_total " in text

    def test_compile_counters_are_differences_of_the_one_listeners_tallies(
            self):
        import jax

        from tools.compile_counter import CompileCacheCounter, CompileCounter
        f = jax.jit(lambda x: x * 11)
        with CompileCounter() as outer, CompileCacheCounter() as cache:
            f(np.ones(3, np.float32))
            assert outer.count == 1          # live inside the body
            with CompileCounter() as nested:
                f(np.ones(3, np.float32))    # jit cache hit: no event
                f(np.ones(5, np.float32))    # a new shape: one compile
            assert nested.count == 1 and outer.count == 2
        f(np.ones(7, np.float32))
        assert outer.count == 2 and nested.count == 1   # frozen on leaving
        assert outer.seconds > nested.seconds > 0
        assert cache.hits + cache.misses == 2
        assert CompileCounter().count == 0


# ---------------------------------------------------------------------------
# instrumentation through the training stack (the acceptance criteria)
# ---------------------------------------------------------------------------
class TestInstrumentedFit:
    def test_fused_fit_records_and_adds_no_recompiles(
            self, tmp_path, monkeypatch):
        """The tentpole acceptance: metrics on + a profiler session running
        + periodic checkpointing; the instrumented fused fit keeps 0 in-fit
        compiles and ONE train signature, the registry sees the
        groups/steps/commit, and the session's trace holds the program's
        spans from the trainer AND prefetch threads."""
        from tools.compile_counter import CompileCounter

        monkeypatch.setenv("DL4J_TPU_METRICS", "1")
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "4")
        ckdir = tmp_path / "ck"
        X, Y = make_data(120)    # 15 batches of 8 -> 4 groups (one short)
        net = mlp()
        it = ArrayDataSetIterator(X, Y, batch_size=8)
        with profiler_session(tmp_path / "prof") as spans:
            net.fit(it, checkpoint_every=8, checkpoint_dir=str(ckdir))
            assert len(net._jit_train) == 1
            assert obs.metrics.value("train.steps_total") == 15
            assert obs.metrics.value("train.dispatch_groups_total") == 4
            h = obs.histogram("train.dispatch_group_seconds")
            assert h.count == 4 and h.sum > 0
            assert obs.metrics.value("checkpoint.commits_total") >= 1
            assert obs.metrics.value("checkpoint.bytes_written_total") > 0
            assert obs.histogram("checkpoint.commit_seconds").count >= 1
            assert obs.metrics.value("prefetch.fused_groups_total") == 4
            assert obs.histogram("prefetch.consumer_wait_seconds").count > 0
            # second fit, warm cache, the session still running:
            # instrumentation must not compile anything
            with CompileCounter() as cc:
                net.fit(ArrayDataSetIterator(X, Y, batch_size=8))
            assert cc.count == 0
            assert len(net._jit_train) == 1
        got = spans()
        names = {name for _, name, _, _ in got}
        assert {"fit.dispatch_group", "fit.nanguard_sync", "prefetch.pull",
                "prefetch.stack_group", "prefetch.wait",
                "fit.checkpoint_commit", "checkpoint.write"} <= names
        threads = {name: {t for t, n, _, _ in got if n == name}
                   for name in names}
        assert threads["fit.dispatch_group"] == threads["prefetch.wait"]
        assert not threads["fit.dispatch_group"] & threads["prefetch.pull"]
        # two fits of 4 groups each
        assert sum(n == "fit.dispatch_group" for _, n, _, _ in got) == 8

    def test_unfused_fit_records_step_histogram(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "1")
        X, Y = make_data(32)
        net = mlp()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=8))
        assert obs.metrics.value("train.steps_total") == 4
        assert obs.histogram("train.step_seconds").count == 4
        assert obs.metrics.value("train.dispatch_groups_total") == 0

    def test_nonfinite_guard_steps_land_in_registry(self):
        from deeplearning4j_tpu.testing import faults
        X, Y = make_data(32)
        net = mlp()
        with faults.inject("nan-step@0"):   # poison the first fused group
            net.fit(ArrayDataSetIterator(X, Y, batch_size=8))
        assert obs.metrics.value("train.nonfinite_steps_total") == 1

    def test_metrics_off_keeps_fit_working_and_registry_silent(
            self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_METRICS", "0")
        X, Y = make_data(32)
        net = mlp()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=8))
        assert obs.metrics.value("train.steps_total") == 0
        assert obs.histogram("train.dispatch_group_seconds").count == 0


# ---------------------------------------------------------------------------
# PR-3 fuse telemetry migrated onto the registry (satellite)
# ---------------------------------------------------------------------------
class TestFuseTelemetryMigration:
    def test_registry_mirror_counts_identical_on_alternating_stream(self):
        """The 2-shape alternating fixture from PR 3: fuse_stats() (the
        preserved per-iterator view) and the registry mirror must count
        the SAME rebuckets/groups/padded steps."""
        from deeplearning4j_tpu.datasets.async_iterator import (
            AsyncDataSetIterator)

        class AlternatingShapes:
            def __init__(self):
                y = np.eye(3, dtype=np.float32)[np.zeros(8, int)]
                self.batches = []
                for _ in range(3):
                    self.batches.append(
                        DataSet(np.zeros((8, 2), np.float32), y))
                    self.batches.append(
                        DataSet(np.zeros((8, 4), np.float32), y))

            def __iter__(self):
                return iter(list(self.batches))

            def batch_size(self):
                return 8

        mirrors = {"rebucket_flushes": "prefetch.rebucket_flushes_total",
                   "fused_groups": "prefetch.fused_groups_total",
                   "padded_steps": "prefetch.padded_steps_total",
                   "partial_flush_batches":
                       "prefetch.partial_flush_batches_total",
                   "padded_steps_saved": "fuse.padding_steps_saved_total"}
        before = {k: obs.metrics.value(m) for k, m in mirrors.items()}
        it = AsyncDataSetIterator(AlternatingShapes(), fuse=4)
        list(it)
        stats = it.fuse_stats()
        # adaptive grouping (default): lone flushes emit per-batch, both
        # buckets degrade to K=1, zero padding — saved == the 18 dummy
        # steps the PR-1 always-pad contract paid on this fixture
        assert stats == {"rebucket_flushes": 4, "fused_groups": 0,
                         "padded_steps": 0, "partial_flush_batches": 6,
                         "padded_steps_saved": 18}
        deltas = {k: obs.metrics.value(m) - before[k]
                  for k, m in mirrors.items()}
        assert deltas == stats

    def test_per_fit_reset_semantics_preserved(self):
        """PR-3 contract: each model fit wraps a FRESH iterator, so
        _last_fuse_stats covers that fit only even though the registry
        mirror is cumulative across fits."""
        X, Y = make_data(32)
        net = mlp()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=8))
        first = dict(net._last_fuse_stats)
        net.fit(ArrayDataSetIterator(X, Y, batch_size=8))
        assert net._last_fuse_stats == first       # per-fit, not cumulative
        total = obs.metrics.value("prefetch.fused_groups_total")
        assert total == 2 * first["fused_groups"]  # registry: cumulative


# ---------------------------------------------------------------------------
# ProfilerListener hardening (satellite)
# ---------------------------------------------------------------------------
class TestProfilerListenerHardening:
    def test_close_without_start_is_a_no_op(self):
        from deeplearning4j_tpu.optimize.listeners import ProfilerListener
        lst = ProfilerListener("/tmp/nonexistent_profiler_dir")
        lst.close()            # never started: must not raise
        lst.close()            # and stays idempotent
        assert not lst.captured

    def test_capture_is_light_and_holds_the_programs_spans(
            self, tmp_path, monkeypatch):
        """The listener traces without the Python tracer, at host tracer
        level 1 (what keeps the annotations), and its one file is what
        ``python3 -m benchmark.scope_reduce`` reads."""
        import jax

        from benchmark import scope_reduce
        from deeplearning4j_tpu.optimize.listeners import ProfilerListener

        seen = {}
        start = jax.profiler.start_trace

        def spy(directory, **kwargs):
            seen.update(kwargs)
            return start(directory, **kwargs)

        monkeypatch.setattr(jax.profiler, "start_trace", spy)
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "1")
        X, Y = make_data(64)
        net = mlp()
        lst = ProfilerListener(str(tmp_path), start_iteration=2,
                               num_iterations=3, log_fn=lambda *a: None)
        net.set_listeners([lst])
        net.fit(ArrayDataSetIterator(X, Y, batch_size=8))
        assert lst.captured
        options = seen["profiler_options"]
        assert (options.python_tracer_level,
                options.host_tracer_level) == (0, 1)
        path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                          recursive=True)
        names = [n for _, n, _, _ in scope_reduce.load(path)[1]]
        assert names.count("fit.step") == 3

    def test_double_stop_and_stop_without_start_are_no_ops(
            self, tmp_path, monkeypatch):
        """Even if jax raises on stop (no trace running / already
        stopped), close() and __del__ must swallow it — the regression
        was relying on whatever jax.profiler happened to raise."""
        import jax
        from deeplearning4j_tpu.optimize.listeners import ProfilerListener

        calls = []

        def fake_stop():
            calls.append(1)
            raise RuntimeError("No profile started")

        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d, **options: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", fake_stop)
        lst = ProfilerListener(str(tmp_path), start_iteration=0,
                               num_iterations=1, log_fn=lambda *a: None)

        class _M:
            _iter_dev = None
            _score = 0.5
        lst.iteration_done(_M(), 0)     # starts the window
        assert lst._active
        lst.close(_M())                 # stop raises inside: swallowed
        assert not lst._active and not lst.captured
        lst.close(_M())                 # double stop: no second jax call
        assert len(calls) == 1
        lst._active = True              # simulate mid-window teardown
        lst.__del__()                   # raising stop must not escape del
        assert not lst._active

    def test_sync_failure_during_finish_still_stops_the_trace(
            self, tmp_path, monkeypatch):
        """Review regression: _finish flips _active before syncing, so a
        _sync that raises (device error mid-run) must still stop the
        process-global trace — otherwise no later close()/__del__ can."""
        import jax
        from deeplearning4j_tpu.optimize.listeners import ProfilerListener
        stops = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d, **options: None)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: stops.append(1))
        lst = ProfilerListener(str(tmp_path), start_iteration=0,
                               num_iterations=1, log_fn=lambda *a: None)

        class Good:
            _iter_dev = None
            _score = 0.5

        class Poisoned:
            _iter_dev = None

            @property
            def _score(self):
                raise RuntimeError("device poisoned")

        lst.iteration_done(Good(), 0)          # starts the window
        with pytest.raises(RuntimeError, match="device poisoned"):
            lst.close(Poisoned())
        assert stops == [1]                    # trace stopped regardless
        assert not lst._active                 # and no retry path armed

    def test_window_capture_still_reports_when_stop_succeeds(
            self, tmp_path, monkeypatch):
        import jax
        from deeplearning4j_tpu.optimize.listeners import ProfilerListener
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda d, **options: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        logged = []
        lst = ProfilerListener(str(tmp_path), start_iteration=0,
                               num_iterations=1, log_fn=logged.append)

        class _M:
            _iter_dev = None
            _score = 0.5
        lst.iteration_done(_M(), 0)
        lst.iteration_done(_M(), 1)
        assert lst.captured and lst.trace_dir == str(tmp_path)
        assert logged and "captured" in logged[0]


# ---------------------------------------------------------------------------
# export surfaces: UI endpoints
# ---------------------------------------------------------------------------
class TestUIExport:
    @pytest.fixture
    def server(self):
        from deeplearning4j_tpu.ui.server import UIServer
        srv = UIServer(port=0).start()
        yield srv
        srv.stop()

    def _get(self, srv, path):
        with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}{path}",
                                    timeout=5) as r:
            return r.status, r.headers.get("Content-Type", ""), r.read()

    def test_prometheus_and_json_endpoints(self, server):
        obs.counter("train.steps_total").inc(12)
        obs.histogram("train.dispatch_group_seconds").record(0.02)
        status, ctype, body = self._get(server, "/metrics")
        assert status == 200 and ctype.startswith("text/plain")
        text = body.decode()
        assert "dl4j_tpu_train_steps_total 12" in text
        assert "# TYPE dl4j_tpu_train_dispatch_group_seconds histogram" \
            in text
        status, ctype, body = self._get(server, "/train/metrics/data")
        assert status == 200 and ctype.startswith("application/json")
        snap = json.loads(body)
        assert snap["counters"]["train.steps_total"] == 12
        assert snap["histograms"]["train.dispatch_group_seconds"][
            "count"] == 1

    def test_compiles_reach_both_endpoints_after_one_fit_batch(self, server):
        t0 = time.perf_counter()
        tiny_lm(seed=3).fit_batch(TOKENS)
        _, _, body = self._get(server, "/metrics")
        values = {l.split()[0]: float(l.split()[1])
                  for l in body.decode().splitlines()
                  if l.startswith("dl4j_tpu_compile_")}
        assert values["dl4j_tpu_compile_programs_total"] >= 1
        assert values["dl4j_tpu_compile_trace_seconds_total"] > 0
        assert values["dl4j_tpu_compile_lower_seconds_total"] > 0
        assert values["dl4j_tpu_compile_backend_seconds_total"] > 0
        assert values["dl4j_tpu_compile_cache_requests_total"] >= 1
        assert "dl4j_tpu_lm_step_build_seconds" in body.decode()
        _, _, body = self._get(server, "/train/metrics/data")
        rows = [e for e in json.loads(body)["compiles"]
                if e["owner"] == "lm.step" and e["end"] >= t0]
        assert [e["fun_name"] for e in rows] == ["jit(step)"]
