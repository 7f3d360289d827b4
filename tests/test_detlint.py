"""graftlint v7 (detlint) + rngwatch: the RNG-lineage / determinism
analysis and its runtime twin.

Five layers, mirroring test_siglint.py's structure for v6:

- fixture fire/quiet pairs: every rule fires on its defect class at the
  pinned line and stays silent on the blessed twins (a silently-empty
  lineage walker also lints "clean");
- live-tree gate: G028-G030 produce ZERO findings and ZERO suppressions
  on the real package — detlint holds the tree, it doesn't annotate it;
- the ``lint_paths``-vs-``lint_file`` seam: a key spent inside an
  imported helper only the cross-module call graph can see;
- the dynamic twin: rngwatch's generation books, the dual-layer fixture
  (ONE defect, both layers, the SAME file:line), vocabulary sync with
  the static pass, and runtime observed sites ⊆ the static inventory;
- the end-to-end determinism gates: same-seed double runs must be
  BITWISE equal — params/updater/rng/score for MLN + ComputationGraph
  (fused and unfused), sampled TransformerLM generation, and a mixed
  sampled/greedy ContinuousLM slot pool (whose per-row counter-derived
  keys must not depend on scheduler thread timing).
"""

import importlib.util
import os

import numpy as np
import pytest

from deeplearning4j_tpu import NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator
from deeplearning4j_tpu.models.computation_graph import ComputationGraph
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.serving import ContinuousLM
from deeplearning4j_tpu.testing import rngwatch
from deeplearning4j_tpu.utils import flat_params
from tools.graftlint import determinism, lint_file, lint_paths
from tools.graftlint.determinism import (det_report, det_report_md,
                                         rng_inventory_for_paths)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "deeplearning4j_tpu")
TOOLS = os.path.join(REPO, "tools")
FIX = os.path.join(REPO, "tests", "fixtures", "graftlint")
RNGFIX = os.path.join(REPO, "tests", "fixtures", "rngwatch", "reuse.py")
RULES = ("G028", "G029", "G030")


def _hits(res, rule):
    return sorted(f.line for f in res.findings if f.rule_id == rule)


def _det(res):
    return sorted((f.rule_id, f.line) for f in res.findings
                  if f.rule_id in RULES)


def _fixture(name):
    return os.path.join(FIX, name)


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# fixture fire/quiet pairs: every rule fires at its pinned line
# ---------------------------------------------------------------------------
class TestDetlintFixtures:
    def test_g028_fires_on_every_reuse_shape(self):
        res = lint_file(_fixture("g028_bad.py"))
        # sequential reuse, loop without in-loop rebind, split-then-parent,
        # traced-consumer (lax.scan carry) then host sample
        assert _hits(res, "G028") == [14, 21, 27, 36]

    def test_g028_quiet_on_blessed_idioms(self):
        # chained split rebinds, fold_in derivation, branch-exclusive
        # arms, dispatch chains, in-loop rebind, jnp.where select-revert,
        # the carried lazily-seeded self._rng
        res = lint_file(_fixture("g028_good.py"))
        assert _det(res) == []

    def test_g029_fires_on_every_ambient_source(self):
        res = lint_file(_fixture("g029_bad.py"))
        # global np.random draw, unseeded RandomState, stdlib random,
        # time-seeded PRNGKey, np.random.seed
        assert _hits(res, "G029") == [13, 17, 21, 26, 30]

    def test_g029_quiet_on_seeded_generators(self):
        res = lint_file(_fixture("g029_good.py"))
        assert _det(res) == []

    def test_g030_fires_on_every_order_leak(self):
        res = lint_file(_fixture("g030_bad.py"))
        # unsorted listdir accumulate-and-return, glob into instance
        # state, set iteration inside jit, set comprehension into
        # tree_unflatten
        assert _hits(res, "G030") == [19, 24, 30, 37]

    def test_g030_quiet_on_sorted_and_order_insensitive(self):
        res = lint_file(_fixture("g030_good.py"))
        assert _det(res) == []


# ---------------------------------------------------------------------------
# the G009 fold: flow-carried float64 fires like the syntactic form
# ---------------------------------------------------------------------------
class TestDtypeFlowFold:
    def test_flow_carried_f64_fires_without_literals(self):
        """No f64 literal sits inside any traced function in this
        fixture — every finding is the dataflow fold following the
        value: host mint → traced call, flowed dtype object → device
        op, helper summary → traced call, mint → _jit dispatch."""
        res = lint_file(_fixture("g009_flow_bad.py"))
        assert _hits(res, "G009") == [18, 23, 32, 45]

    def test_quiet_on_f32_host_only_and_x64_lane(self):
        res = lint_file(_fixture("g009_flow_good.py"))
        assert _hits(res, "G009") == []

    def test_syntactic_layer_unchanged(self):
        res = lint_file(_fixture("g009_bad.py"))
        assert len(_hits(res, "G009")) == 2

    def test_cross_module_f64_needs_package_mode(self):
        """The seeded regression: f64 minted inside an imported helper
        only exists in the package-scope summaries — lint_paths fires at
        the caller's dispatch, lint_file on the same file cannot."""
        pkg = os.path.join(FIX, "g009_pkg")
        res = lint_paths([pkg])
        hits = [(os.path.basename(f.path), f.line) for f in res.findings
                if f.rule_id == "G009"]
        assert hits == [("user.py", 18)]
        assert _hits(lint_file(os.path.join(pkg, "user.py")), "G009") == []

    def test_live_tree_g009_stays_zero(self):
        """The enable_x64 carve-out holds the gradient-check lane at
        zero WITHOUT suppressions — f64 under x64 is the point there."""
        res = lint_paths([os.path.join(PKG, "gradientcheck")])
        assert _hits(res, "G009") == []


# ---------------------------------------------------------------------------
# the cross-module seam: only package mode sees the helper spend the key
# ---------------------------------------------------------------------------
class TestCrossModuleSeam:
    def test_helper_spend_needs_package_mode(self):
        pkg = os.path.join(FIX, "g028_pkg")
        res = lint_paths([pkg])
        by_file = [(os.path.basename(f.path), f.rule_id, f.line)
                   for f in res.findings if f.rule_id in RULES]
        assert by_file == [("user.py", "G028", 14)]
        # single-file mode cannot resolve sample_with() and must NOT
        # guess: unresolved calls never spend a key
        solo = lint_file(os.path.join(pkg, "user.py"))
        assert _det(solo) == []


# ---------------------------------------------------------------------------
# live-tree gate: the real package holds G028-G030 at zero
# ---------------------------------------------------------------------------
class TestLiveTree:
    @pytest.fixture(scope="class")
    def live(self):
        # replicate the CLI's `make lint` invocation EXACTLY — same cwd,
        # same relative path strings, same cache dir — so this shares the
        # incremental cache's whole-run result entry (the key hashes the
        # path strings): warm after any lint run, the live-tree gate is a
        # single JSON read instead of a ~30s cold analysis, cheap enough
        # for the tier-1 lane on every run
        cwd = os.getcwd()
        os.chdir(REPO)
        try:
            return lint_paths(
                ["deeplearning4j_tpu", "tools", "chip_smoke.py", "examples"],
                cache_dir=".graftlint_cache")
        finally:
            os.chdir(cwd)

    def test_zero_findings_zero_suppressions(self, live):
        assert _det(live) == []
        assert [s for s in live.suppressed if s.rule_id in RULES] == []

    def test_det_report_covers_the_model_zoo(self, live):
        r = det_report([PKG, TOOLS, os.path.join(REPO, "chip_smoke.py"),
                        os.path.join(REPO, "examples")])
        assert r["version"] == 7
        for name in ("MultiLayerNetwork", "ComputationGraph",
                     "TransformerLM"):
            assert name in r["models"], name
        lm = r["models"]["TransformerLM"]
        # the training step rebinds (split) and the carried self._rng is
        # inventoried — an empty lineage would also render "clean"
        assert lm["rebind_sites"] and lm["carried_attrs"]
        md = det_report_md(r)
        assert "| model / module |" in md
        assert "TransformerLM" in md

    def test_inventory_rows_are_absolute_and_kinded(self):
        inv = rng_inventory_for_paths([RNGFIX])
        assert {(os.path.basename(p), ln): k for (p, ln), k in inv.items()
                } == {("reuse.py", 19): "create",
                      ("reuse.py", 20): "consume:normal",
                      ("reuse.py", 21): "consume:uniform",
                      ("reuse.py", 26): "create",
                      ("reuse.py", 27): "split",
                      ("reuse.py", 28): "consume:normal",
                      ("reuse.py", 29): "split",
                      ("reuse.py", 30): "consume:uniform"}
        assert all(os.path.isabs(p) for p, _ in inv)


# ---------------------------------------------------------------------------
# the runtime twin
# ---------------------------------------------------------------------------
class TestRngwatch:
    def test_knob_defaults_off(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_RNGWATCH", raising=False)
        assert not rngwatch.enabled()
        monkeypatch.setenv("DL4J_TPU_RNGWATCH", "1")
        assert rngwatch.enabled()

    def test_vocabulary_sync_with_static_pass(self):
        """The watcher duplicates detlint's op vocabulary deliberately
        (it must import without the tools tree) — this pin is what keeps
        the two copies identical."""
        assert set(rngwatch.CONSUMERS) == set(determinism._SAMPLERS)
        assert set(rngwatch.PRODUCERS) == (determinism._CREATORS
                                           | determinism._SPLITTERS
                                           | determinism._DERIVERS)

    def test_dual_layer_fixture_same_file_same_line(self):
        """ONE defect, both layers, ONE line: G028 flags reuse.py's
        second consumption statically, and running double_draw() under
        the watcher records a violation whose second consumption sits at
        the SAME file:line."""
        static = _hits(lint_file(RNGFIX), "G028")
        assert static == [21]
        reuse = _load("detlint_reuse_fixture", RNGFIX)
        try:
            with rngwatch.watch():
                before = rngwatch.snapshot()
                reuse.double_draw()
                vs = rngwatch.violations(since=before)
            assert len(vs) == 1
            v = vs[0]
            assert v["created"] == (os.path.abspath(RNGFIX), 19)
            assert v["created_by"] == "PRNGKey"
            _, first_site, _ = v["first"]
            _, second_site, _ = v["second"]
            assert first_site == (os.path.abspath(RNGFIX), 20)
            assert second_site == (os.path.abspath(RNGFIX), static[0])
            assert "G028" in rngwatch.report(since=before)
        finally:
            rngwatch.reset()   # keep the chaos-lane session gate clean

    def test_clean_twin_records_no_violation(self):
        reuse = _load("detlint_reuse_fixture2", RNGFIX)
        with rngwatch.watch():
            before = rngwatch.snapshot()
            reuse.clean_draw()
            assert rngwatch.violations(since=before) == []
            rngwatch.assert_clean(since=before)

    def test_observed_sites_subset_of_static_inventory(self):
        """Conformance: every site the watcher attributes must exist in
        the static inventory with a compatible kind — the runtime twin
        never discovers seams the static pass cannot see."""
        inv = rng_inventory_for_paths([RNGFIX])
        reuse = _load("detlint_reuse_fixture3", RNGFIX)
        with rngwatch.watch():
            rngwatch.reset()
            reuse.clean_draw()
            seen = {(p, ln): k for (p, ln), k in
                    rngwatch.observed_sites().items() if p == RNGFIX}
            rngwatch.reset()
        assert seen, "the watcher observed nothing — wrapping is dead"
        for site, kind in seen.items():
            assert site in inv, site
            assert inv[site] == kind, (site, kind, inv[site])

    def test_generation_resets_on_reregistration(self):
        """Same-seed double runs re-mint the same key BITS; re-running
        PRNGKey at the same site must open a fresh generation, not count
        against the first run's consumption."""
        import jax
        with rngwatch.watch():
            rngwatch.reset()
            before = rngwatch.snapshot()
            for _ in range(2):                    # the double-run shape
                k = jax.random.PRNGKey(0)
                jax.random.normal(k, (2,))        # one consumption each
            assert rngwatch.violations(since=before) == []
            rngwatch.reset()

    def test_watch_restores_the_seams(self):
        import jax.random
        before = jax.random.normal
        if rngwatch.installed():
            # chaos lane: the session-wide install owns the seams, and a
            # nested watch() must be a no-op — no re-wrap on entry, no
            # restore on exit (the lane keeps watching after this test)
            with rngwatch.watch():
                assert jax.random.normal is before
            assert jax.random.normal is before
            assert rngwatch.installed()
        else:
            with rngwatch.watch():
                assert jax.random.normal is not before
            assert jax.random.normal is before


# ---------------------------------------------------------------------------
# end-to-end determinism gates: same-seed double runs are BITWISE equal
# ---------------------------------------------------------------------------
def _mln_conf(seed=12):
    return (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
            .updater("adam").list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())


def _graph(seed=12):
    return ComputationGraph(
        (NeuralNetConfiguration.Builder().seed(seed).learning_rate(0.05)
         .updater("adam").graph_builder()
         .add_inputs("in")
         .add_layer("d", DenseLayer(n_in=4, n_out=8, activation="tanh"),
                    "in")
         .add_layer("out", OutputLayer(n_in=8, n_out=3,
                                       activation="softmax", loss="mcxent"),
                    "d")
         .set_outputs("out").build())).init()


def _updater_vec(net):
    if hasattr(net, "params_map"):
        states = [net.updater_states[n] for n in net.layer_names]
    else:
        states = net.updater_states
    return np.asarray(flat_params.updater_state_to_vector(net.layers, states))


def _data(seed=7, n=48):
    r = np.random.RandomState(seed)
    X = r.randn(n, 4).astype(np.float32)
    Y = np.eye(3, dtype=np.float32)[r.randint(0, 3, n)]
    return X, Y


def small_lm(seed=3, max_len=64):
    return TransformerLM(TransformerConfig(
        vocab_size=50, max_len=max_len, d_model=16, n_heads=2, n_layers=2,
        d_ff=32, pos_embed="learned", seed=seed)).init()


class TestDoubleRunParity:
    def _fit_once(self, build):
        X, Y = _data()
        net = build()
        net.fit(ArrayDataSetIterator(X, Y, batch_size=8), epochs=2)
        return net

    @pytest.mark.parametrize("fuse", [1, 4], ids=["unfused", "fused"])
    @pytest.mark.parametrize("build", [
        lambda: MultiLayerNetwork(_mln_conf()).init(), _graph,
    ], ids=["mln", "cg"])
    def test_training_double_run_is_bitwise(self, monkeypatch, build, fuse):
        """Same seed, same data, fresh process state: params, updater
        state, rng and score must match to the BIT — any drift here is a
        G028/G029-class defect escaping the static net."""
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", str(fuse))
        a = self._fit_once(build)
        b = self._fit_once(build)
        np.testing.assert_array_equal(np.asarray(a.params()),
                                      np.asarray(b.params()))
        np.testing.assert_array_equal(_updater_vec(a), _updater_vec(b))
        np.testing.assert_array_equal(np.asarray(a._rng),
                                      np.asarray(b._rng))
        assert float(a.score_) == float(b.score_)
        assert (a.iteration, a.epoch_count) == (b.iteration, b.epoch_count)

    def test_sampled_generate_double_run_is_bitwise(self):
        """generate() threads jax.random.PRNGKey(seed) through the scan
        carry — two calls with the same seed sample identical tokens,
        and a third with another seed proves sampling is live."""
        lm = small_lm()
        p = np.arange(1, 6, dtype=np.int32)[None, :]
        a = lm.generate(p, 8, temperature=1.0, seed=7)
        b = lm.generate(p, 8, temperature=1.0, seed=7)
        np.testing.assert_array_equal(a, b)
        c = lm.generate(p, 8, temperature=1.0, seed=8)
        assert not np.array_equal(a, c), \
            "seed is dead — sampling ignored the rng"

    def _pool_run(self):
        # more requests than slots, mixed prompt lengths (multiple
        # prefill rungs), mixed greedy/sampled rows with per-request
        # seeds: the full scheduler surface
        lm = small_lm(seed=3)
        srv = ContinuousLM(lm, slots=2, chunk=4)
        try:
            reqs = [(4, 0.0, 0), (3, 1.0, 11), (6, 1.0, 12), (2, 0.0, 0),
                    (5, 1.0, 13)]
            futs = [srv.submit(
                (np.arange(n) % lm.conf.vocab_size).astype(np.int32),
                5, temperature=t, seed=s) for n, t, s in reqs]
            return [np.asarray(f.result(180)) for f in futs]
        finally:
            srv.stop()

    def test_mixed_pool_double_run_is_bitwise(self):
        """Sampling keys are counter-derived per row — fold_in(fold_in(
        pool base, request seed), position) — so two fresh pools serving
        the same request mix produce bitwise-identical completions even
        though admits and decode chunks interleave differently run to
        run (a carried pool-wide rng stream failed exactly this gate)."""
        a = self._pool_run()
        b = self._pool_run()
        assert len(a) == len(b) == 5
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
