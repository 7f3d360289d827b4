"""graftlint: per-rule fixtures, suppression semantics, the CLI, and the
tier-1 whole-package gate (zero unsuppressed findings in
deeplearning4j_tpu/).

The fixtures are inline source strings: each rule must FIRE on its bad
snippet and stay SILENT on the good twin — both directions matter, a rule
that fires on idiomatic code would get suppressed into uselessness.
graftlint imports nothing from jax, so this module is cheap enough to run
first in any lane.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO) if REPO not in sys.path else None

from tools.graftlint import (lint_file, lint_paths, lint_source,  # noqa: E402
                             lint_sources)
from tools.graftlint.rules import RULES  # noqa: E402


def ids(result):
    return sorted({f.rule_id for f in result.findings})


def lint_live(paths, rule_ids=None):
    """Whole-tree lint through the CLI's incremental cache: cwd and
    path strings replicate a repo-root invocation so the result key
    matches across runs — warm, a live-tree gate is a JSON read instead
    of a multi-second cold analysis. Tests that ASSERT cold-pass
    properties (the perf budget) must keep calling lint_paths raw."""
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        return lint_paths([os.path.relpath(p, REPO) for p in paths],
                          rule_ids=rule_ids, cache_dir=".graftlint_cache")
    finally:
        os.chdir(cwd)


def check(src, path="mod.py"):
    return lint_source(textwrap.dedent(src), path)


# ---------------------------------------------------------------------------
# G001 host-sync-in-hot-path
# ---------------------------------------------------------------------------
G001_BAD = """
    class Net:
        def fit_batch(self, x):
            out = self._jit_train[("sig",)](x)
            return out.item()
"""

G001_BAD_REACHABLE = """
    import numpy as np

    class Net:
        def fit_batch(self, x):
            score = self._jit_train[("sig",)](x)
            return self._log(score)

        def _log(self, score):
            return float(score)
"""

G001_GOOD = """
    class Net:
        def fit_batch(self, x):
            score = self._jit_train[("sig",)](x)
            self._last_batch_size = int(x.shape[0])   # shape: host metadata
            self.score_ = score                       # device, lazy sync
            return score

        def report(self, score):
            return float(score)   # NOT reachable from the hot path
"""


def test_g001_fires_on_item_in_hot_path():
    r = check(G001_BAD)
    assert ids(r) == ["G001"], r.findings
    assert ".item()" in r.findings[0].message


def test_g001_follows_the_call_graph():
    r = check(G001_BAD_REACHABLE)
    assert ids(r) == ["G001"]
    assert "'_log'" in r.findings[0].message


def test_g001_allows_shape_reads_and_cold_paths():
    assert check(G001_GOOD).findings == []


# ---------------------------------------------------------------------------
# G002 recompile-hazard
# ---------------------------------------------------------------------------
G002_BAD_LOOP = """
    import jax

    def fit(batches):
        for b in batches:
            step = jax.jit(lambda x: x * 2)   # fresh cache every batch
            step(b)
"""

G002_BAD_NO_DONATE = """
    import jax

    def make():
        def train_step(params, states, x):
            return params, states
        return jax.jit(train_step)
"""

G002_GOOD = """
    import jax

    def make():
        def train_step(params, states, x):
            return params, states
        return jax.jit(train_step, donate_argnums=(0, 1))

    def make_out():
        def run(params, x):   # inference: params reused, donation wrong
            return x
        return jax.jit(run)
"""


def test_g002_fires_on_jit_in_loop():
    r = check(G002_BAD_LOOP)
    assert ids(r) == ["G002"]
    assert "inside a loop" in r.findings[0].message


def test_g002_fires_on_undonated_carry():
    r = check(G002_BAD_NO_DONATE)
    assert ids(r) == ["G002"]
    assert "donate_argnums" in r.findings[0].message


def test_g002_good_patterns_pass():
    assert check(G002_GOOD).findings == []


def test_g002_partial_jit_decorator_donation_is_seen():
    r = check("""
        import functools, jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(params, x):
            return params
    """)
    assert r.findings == []
    r = check("""
        import jax

        @jax.jit
        def train_step(params, x):
            return params
    """)
    assert ids(r) == ["G002"]


# ---------------------------------------------------------------------------
# G003 untracked-env-knob
# ---------------------------------------------------------------------------
G003_BAD = """
    import os
    from os import getenv
    FUSE = os.environ.get("DL4J_TPU_FUSE_STEPS", "8")
    STAGE = os.getenv("DL4J_TPU_TRANSFER_STAGE")
    DIR = os.environ["DL4J_TPU_DATA_DIR"]
    BARE = getenv("DL4J_TPU_FUSE_UNROLL")
    DFLT = os.environ.setdefault("DL4J_TPU_LM_ATTN", "scan")  # read+write
"""

G003_GOOD = """
    import os
    from deeplearning4j_tpu.config import env_int
    FUSE = env_int("DL4J_TPU_FUSE_STEPS")
    OTHER = os.environ.get("JAX_PLATFORMS")          # not a DL4J knob
    os.environ["DL4J_TPU_FUSE_STEPS"] = "4"          # write, not read
"""


def test_g003_fires_on_all_read_forms():
    r = check(G003_BAD)
    assert [f.rule_id for f in r.findings] == ["G003"] * 5


def test_g003_allows_registry_and_writes():
    assert check(G003_GOOD).findings == []


def test_g003_exempts_the_registry_itself():
    src = 'import os\nX = os.environ.get("DL4J_TPU_X")\n'
    assert lint_source(src, "deeplearning4j_tpu/config.py").findings == []
    assert lint_source(src, "other.py").findings != []


# ---------------------------------------------------------------------------
# G004 traced-impurity
# ---------------------------------------------------------------------------
G004_BAD = """
    import jax, time, os

    def step(w, x):
        t0 = time.time()              # baked in at trace time
        print("tracing", t0)
        mode = os.environ.get("MODE")
        return w

    train = jax.jit(step)
"""

G004_GOOD = """
    import jax, time

    def step(w, rng, x):
        sub = jax.random.split(rng)   # device RNG: fine
        return w

    train = jax.jit(step)

    def host_loop():
        t0 = time.time()              # host code: fine
        print("done", t0)
"""


def test_g004_fires_inside_traced_functions():
    r = check(G004_BAD)
    assert ids(r) == ["G004"]
    msgs = " ".join(f.message for f in r.findings)
    assert "time.time" in msgs and "print" in msgs and "environment" in msgs


def test_g004_ignores_host_code_and_jax_random():
    assert check(G004_GOOD).findings == []


_G004_REGISTRY_TMPL = """
    KNOBS = {{}}

    def _declare(name, kind, default, doc, trace_time=False):
        KNOBS[name] = (name, kind, default, doc, trace_time)

    _declare("DL4J_TPU_LM_ATTN", "str", "auto", "attention route"{tt})

    def env_str(name):
        import os
        return os.environ.get(name, KNOBS[name][2])
"""

_G004_READER = """
    import jax
    from deeplearning4j_tpu.config import env_str

    def step(w, x):
        mode = env_str("DL4J_TPU_LM_ATTN")
        return w

    train = jax.jit(step)

    def host_setup():
        return env_str("DL4J_TPU_LM_ATTN")   # host code: fine
"""


def _g004_pkg(trace_time):
    return {
        "pkg/deeplearning4j_tpu/config.py": textwrap.dedent(
            _G004_REGISTRY_TMPL.format(
                tt=", trace_time=True" if trace_time else "")),
        "pkg/deeplearning4j_tpu/models/transformer.py":
            textwrap.dedent(_G004_READER),
    }


def test_g004_flags_registry_helpers_in_traced_code():
    """Routing an env read through config.env_* must not hide it from
    G004 — a knob consulted during tracing is still baked in, UNLESS the
    registry declares it trace_time=True (the declaration replaces the
    per-site suppression inventory)."""
    r = lint_sources(_g004_pkg(trace_time=False))
    g4 = [f for f in r.findings if f.rule_id == "G004"]
    assert len(g4) == 1, [f.format() for f in r.findings]
    assert "registry knob read" in g4[0].message
    assert "trace_time=True" in g4[0].message
    assert g4[0].path.endswith("transformer.py")


def test_g004_declared_trace_time_knob_is_allowed():
    """ISSUE 8 satellite: the registry-routed read of a DECLARED
    trace-time knob needs no suppression — the six per-site disables
    (LM_ATTN, W2V_SCATTER, PALLAS_INTERPRET, FLASH_BWD, FUSE_UNROLL,
    DISABLE_HELPERS) are retired by Knob.trace_time."""
    r = lint_sources(_g004_pkg(trace_time=True))
    assert [f for f in r.findings if f.rule_id == "G004"] == [], \
        [f.format() for f in r.findings]


def test_g004_file_scoped_lane_presumes_declared_never_false_positives():
    """Without the registry module in the linted set (the --changed fast
    lane), a constant DL4J_TPU_* helper read cannot be verified: the
    fast lane's contract is to MISS, never false-positive. A computed
    knob name still fires (it could never be declared)."""
    r = check(_G004_READER)
    assert [f for f in r.findings if f.rule_id == "G004"] == [], \
        [f.format() for f in r.findings]
    r = check("""
        import jax
        from deeplearning4j_tpu.config import env_str

        def step(w, x, which):
            mode = env_str(which)       # computed name: unverifiable
            return w

        train = jax.jit(step)
    """)
    assert ids(r) == ["G004"]
    assert "registry knob read" in r.findings[0].message


def test_g004_live_trace_time_reads_need_no_suppressions():
    """Seeded on the live tree: the real trace-time knob sites
    (transformer LM_ATTN, pallas interpret/backward route, lookup
    scatter impl, helpers disable, fuse unroll) lint clean with ZERO
    G004 suppressions — the declarations in config.py carry them."""
    r = lint_live([os.path.join(REPO, "deeplearning4j_tpu")],
                  rule_ids={"G004"})
    assert r.findings == [], [f.format() for f in r.findings]
    for rel in ("models/transformer.py", "ops/pallas_kernels.py",
                "nlp/lookup.py", "nn/helpers.py",
                "models/_device_state.py"):
        with open(os.path.join(REPO, "deeplearning4j_tpu", rel),
                  encoding="utf-8") as fh:
            assert "disable=G004" not in fh.read(), \
                f"{rel} still carries a retired G004 suppression"


def test_g004_scan_bodies_are_traced():
    r = check("""
        import jax

        def body(carry, x):
            print(carry)
            return carry, x

        def run(xs):
            return jax.lax.scan(body, 0, xs)
    """)
    assert ids(r) == ["G004"]


# ---------------------------------------------------------------------------
# G005 swallow-all-except
# ---------------------------------------------------------------------------
G005_BAD = """
    def f():
        try:
            g()
        except:
            cleanup()

    def h():
        try:
            g()
        except Exception:
            pass
"""

G005_GOOD = """
    def f():
        try:
            g()
        except ValueError:
            pass                       # narrow: fine

    def h(errbox):
        try:
            g()
        except Exception as e:
            errbox.append(e)           # recorded, not swallowed

    def reraiser():
        try:
            g()
        except:
            raise                      # bare but transparent
"""


def test_g005_fires_on_bare_and_silent_broad():
    r = check(G005_BAD)
    assert [f.rule_id for f in r.findings] == ["G005"] * 2


def test_g005_allows_narrow_recorded_and_reraising():
    assert check(G005_GOOD).findings == []


# ---------------------------------------------------------------------------
# G006 lock-discipline
# ---------------------------------------------------------------------------
G006_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.items = []

        def put(self, x):
            with self._lock:
                self.items = self.items + [x]

        def clear(self):
            self.items = []            # racing every locked writer
"""

G006_GOOD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.items = []            # construction: single-threaded

        def put(self, x):
            with self._lock:
                self.items = self.items + [x]

        def clear(self):
            with self._lock:
                self.items = []
"""


def test_g006_fires_on_unlocked_write():
    r = check(G006_BAD)
    assert ids(r) == ["G006"]
    assert "items" in r.findings[0].message


def test_g006_consistent_locking_passes():
    assert check(G006_GOOD).findings == []


# ---------------------------------------------------------------------------
# suppression semantics
# ---------------------------------------------------------------------------
def test_suppression_with_justification_works():
    r = check("""
        class Net:
            def fit_batch(self, x):
                s = self._jit_train[0](x)
                return s.item()  # graftlint: disable=G001 -- epoch-end sync is the documented contract
    """)
    assert r.findings == [] and len(r.suppressed) == 1


def test_suppression_on_preceding_comment_line():
    r = check("""
        class Net:
            def fit_batch(self, x):
                s = self._jit_train[0](x)
                # graftlint: disable=G001 -- epoch-end sync by design
                return s.item()
    """)
    assert r.findings == [] and len(r.suppressed) == 1


def test_suppression_without_justification_is_g000():
    r = check("""
        class Net:
            def fit_batch(self, x):
                s = self._jit_train[0](x)
                return s.item()  # graftlint: disable=G001
    """)
    assert ids(r) == ["G000", "G001"]   # both the lint AND the lazy disable


def test_file_wide_suppression():
    r = check("""
        # graftlint: disable-file=G005 -- probe module: every failure is survivable
        def f():
            try:
                g()
            except Exception:
                pass
    """)
    assert r.findings == [] and len(r.suppressed) == 1


def test_stacked_suppression_comments_cover_the_statement():
    """Two disable comments stacked above one statement must BOTH land on
    the code line, not on each other."""
    r = check("""
        import os

        class Net:
            def fit_batch(self, x):
                # graftlint: disable=G001 -- epoch-end sync by design
                # graftlint: disable=G003 -- legacy knob, migration tracked
                return float(os.environ["DL4J_TPU_X"])
    """)
    assert r.findings == [], [f.format() for f in r.findings]
    assert len(r.suppressed) == 2


def test_rule_filter_also_scopes_g000():
    src = textwrap.dedent("""
        def f():
            try:
                g()
            except Exception:
                pass  # graftlint: disable=G005
    """)
    # unfiltered: the lazy disable is itself a finding
    assert ids(lint_source(src)) == ["G000", "G005"]
    # scoping to one unrelated rule must not drag G000 in
    assert lint_source(src, rule_ids={"G006"}).findings == []
    assert ids(lint_source(src, rule_ids={"G000"})) == ["G000"]


def test_suppression_only_silences_named_rule():
    r = check("""
        class Net:
            def fit_batch(self, x):
                s = self._jit_train[0](x)
                return s.item()  # graftlint: disable=G002 -- wrong id
    """)
    # the G001 still fires AND the wrong-id disable is dead weight (G011)
    assert ids(r) == ["G001", "G011"]


# ---------------------------------------------------------------------------
# G011 unused-suppression
# ---------------------------------------------------------------------------
def test_g011_fires_on_stale_disable_and_stays_quiet_on_used():
    r = lint_file(os.path.join(FIXDIR, "g011_bad.py"))
    assert [f.rule_id for f in r.findings] == ["G011", "G011"]
    assert "delete the disable comment" in r.findings[0].message
    r = lint_file(os.path.join(FIXDIR, "g011_good.py"))
    assert r.findings == [] and len(r.suppressed) == 1


def test_g011_flags_only_the_dead_id_of_a_multi_id_disable():
    r = check("""
        import os

        class Net:
            def fit_batch(self, x):
                # graftlint: disable=G001,G003 -- only the env read is real here
                return os.environ["DL4J_TPU_X"]
    """)
    assert ids(r) == ["G011"]
    assert "G001" in r.findings[0].message


def test_g011_skipped_under_rule_filters():
    src = "x = 1   # graftlint: disable=G001 -- stale\n"
    assert ids(lint_source(src)) == ["G011"]
    assert lint_source(src, rule_ids={"G001"}).findings == []


# ---------------------------------------------------------------------------
# interprocedural analysis: the cross-module fixtures
# ---------------------------------------------------------------------------
FIXDIR = os.path.join(REPO, "tests", "fixtures", "graftlint")


def test_cross_module_host_sync_needs_the_package_graph():
    """The acceptance case: a fit_batch -> imported helper -> float(score)
    chain is invisible to PR 2's module-local graph (both files lint
    clean alone) and caught by the whole-package analysis."""
    pkg = os.path.join(FIXDIR, "xsync_bad")
    for name in ("trainer.py", "metrics.py"):
        alone = lint_file(os.path.join(pkg, name))
        assert alone.findings == [], (name, [f.format() for f in
                                             alone.findings])
    r = lint_paths([pkg])
    assert ids(r) == ["G001"], [f.format() for f in r.findings]
    assert r.findings[0].path.endswith("metrics.py")
    assert "log_score" in r.findings[0].message


def test_cross_module_chained_construct_and_call_resolves():
    """Cls(...).m(...) — name_chain truncates at the inner Call, so the
    receiver's constructor must be resolved explicitly."""
    r = lint_sources({
        "pkg/a.py": ("class Helper:\n"
                     "    def read_score(self, s):\n"
                     "        return float(s)\n"),
        "pkg/b.py": ("import jax\n"
                     "from pkg.a import Helper\n\n"
                     "@jax.jit\n"
                     "def train_step(x):\n"
                     "    return Helper().read_score(x)\n"),
    })
    assert any(f.rule_id == "G001" and "read_score" in f.message
               for f in r.findings), [f.format() for f in r.findings]


def test_cross_module_good_package_stays_quiet():
    r = lint_paths([os.path.join(FIXDIR, "xsync_good")])
    assert r.findings == [], [f.format() for f in r.findings]


def test_obs_recording_helpers_are_carved_out_of_g001():
    """ISSUE 6 satellite: fit_batch -> deeplearning4j_tpu/obs/ recording
    helper. The hot closure reaches the helper's float()/clock reads, but
    obs modules are exempt from G001/G004 on the documented host-scalar
    contract — no false-positive spray at group-boundary instrumentation."""
    r = lint_paths([os.path.join(FIXDIR, "xobs_good")])
    assert r.findings == [], [f.format() for f in r.findings]


def test_same_shaped_helper_outside_obs_still_fires_g001():
    """Control twin: the identical helper NOT under obs/ keeps its G001 —
    the carve-out is the obs path contract, not a helper amnesty."""
    r = lint_paths([os.path.join(FIXDIR, "xobs_bad")])
    assert ids(r) == ["G001"], [f.format() for f in r.findings]
    assert r.findings[0].path.endswith("helpers.py")
    assert "record_scalar" in r.findings[0].message


def test_live_obs_module_is_reachable_but_quiet():
    """Seeded on the live tree: metrics.py's record() does float(v) and
    IS called from both models' hot paths; the package lint must stay
    quiet there while still linting obs for every other rule."""
    r = lint_live([os.path.join(REPO, "deeplearning4j_tpu", "obs"),
                   os.path.join(REPO, "deeplearning4j_tpu", "models")],
                  rule_ids=["G001", "G004"])
    obs_findings = [f for f in r.findings if "/obs/" in f.path]
    assert obs_findings == [], [f.format() for f in obs_findings]


def test_cross_module_undonated_carry_is_g002():
    """jax.jit(imported_step): the jit site and the carry-threading step
    live in different files; the finding lands at the CALLER's jit site."""
    pkg = os.path.join(FIXDIR, "xdonate_bad")
    for name in ("steps.py", "build.py"):
        assert lint_file(os.path.join(pkg, name)).findings == []
    r = lint_paths([pkg])
    assert ids(r) == ["G002"]
    assert r.findings[0].path.endswith("build.py")
    assert "train_step" in r.findings[0].message


# ---------------------------------------------------------------------------
# G007 sharding-consistency
# ---------------------------------------------------------------------------
def test_g007_fires_on_unknown_axis_and_allows_known():
    r = lint_file(os.path.join(FIXDIR, "g007_bad.py"))
    assert ids(r) == ["G007"]
    assert "'modle'" in r.findings[0].message
    assert lint_file(os.path.join(FIXDIR, "g007_good.py")).findings == []


def test_g007_mesh_builder_axes_resolve_interprocedurally():
    """Axis names passed at the call site of an imported mesh-builder
    helper (and the helper's own default) are in scope; anything else is
    a finding."""
    r = lint_paths([os.path.join(FIXDIR, "g007_pkg")])
    assert ids(r) == ["G007"]
    assert "'tensor'" in r.findings[0].message
    assert "data" in r.findings[0].message and "model" in r.findings[0].message


def test_g007_skips_modules_with_open_axis_sets():
    # the mesh's axis names are not constants: nothing can be checked
    r = check("""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def make(devices, names):
            mesh = Mesh(devices, tuple(names))
            return NamedSharding(mesh, P("anything"))
    """)
    assert r.findings == []


# ---------------------------------------------------------------------------
# G008 use-after-donate
# ---------------------------------------------------------------------------
def test_g008_fires_on_loop_and_straight_line_use_after_donate():
    r = lint_file(os.path.join(FIXDIR, "g008_bad.py"))
    assert [f.rule_id for f in r.findings] == ["G008", "G008"]
    msgs = " ".join(f.message for f in r.findings)
    assert "loop" in msgs and "read after" in msgs


def test_g008_rebind_patterns_pass():
    assert lint_file(os.path.join(FIXDIR, "g008_good.py")).findings == []


def test_g008_decorated_step_and_attr_cache():
    r = check("""
        import functools, jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def train_step(params, x):
            return params

        def run(params, x):
            out = train_step(params, x)
            return params        # read after donate -> G008
    """)
    assert "G008" in ids(r)
    r = check("""
        import jax

        class Net:
            def _build(self):
                def train_step(params, x):
                    return params
                self._jit_train = jax.jit(train_step, donate_argnums=(0,))

            def fit_batch(self, x):
                self.params = self._jit_train(self.params, x)
                return self.params     # rebound: safe
    """)
    assert "G008" not in ids(r)


# ---------------------------------------------------------------------------
# G009 dtype-discipline
# ---------------------------------------------------------------------------
def test_g009_fires_in_traced_code_only():
    r = lint_file(os.path.join(FIXDIR, "g009_bad.py"))
    assert [f.rule_id for f in r.findings] == ["G009", "G009"]
    assert lint_file(os.path.join(FIXDIR, "g009_good.py")).findings == []


def test_g009_dtype_kwarg_string():
    r = check("""
        import jax, jax.numpy as jnp

        def step(w):
            return jnp.zeros((2, 2), dtype="float64")

        train = jax.jit(step)
    """)
    assert ids(r) == ["G009"]


# ---------------------------------------------------------------------------
# G010 thread-affinity
# ---------------------------------------------------------------------------
def test_g010_fires_on_worker_thread_jax_and_allows_consumer():
    r = lint_file(os.path.join(FIXDIR, "g010_bad.py"))
    assert ids(r) == ["G010"]
    assert "device_put" in r.findings[0].message
    assert lint_file(os.path.join(FIXDIR, "g010_good.py")).findings == []


def _package_sources():
    from tools.graftlint import iter_python_files
    pkg = os.path.join(REPO, "deeplearning4j_tpu")
    out = {}
    for p in iter_python_files([pkg]):
        with open(p, encoding="utf-8") as fh:
            out[p] = fh.read()
    return out


def test_g008_guards_the_real_fused_carry():
    """Seeded regression on the LIVE tree: a second donating dispatch in
    fit_fused whose result is discarded, followed by a read of the
    donated carry — the exact bug class the fused loop's donated carry
    makes easy to write. The donation is resolved interprocedurally
    (self._jit_train[sig] = self._build_fused_train_step() ->
    `return jax.jit(fused, donate_argnums=...)`)."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    mln = os.path.join(REPO, "deeplearning4j_tpu", "models",
                       "multi_layer_network.py")
    anchor = "        k = stacked.n_steps"
    assert anchor in sources[mln]
    sources[mln] = sources[mln].replace(
        anchor,
        "        self._jit_train[sig](\n"
        "            self.params_list, self.states_list,\n"
        "            self.updater_states, self._rng,\n"
        "            self._device_iteration(), xs, ys, ews)\n"
        "        _leak = self.params_list\n" + anchor, 1)
    r = lint_sources(sources)
    assert any(f.rule_id == "G008" and f.path == mln
               and "params_list" in f.message for f in r.findings), \
        [f.format() for f in r.findings]


def test_g010_guards_the_real_worker_thread():
    """Seeded regression on the LIVE tree: a device_put sneaking into the
    prefetch worker's host-stack helper (the round-5 hang class) is
    caught through the Thread(target=self._worker) closure."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    ai = os.path.join(REPO, "deeplearning4j_tpu", "datasets",
                      "async_iterator.py")
    anchor = "        first = group[0][0]"
    assert anchor in sources[ai]
    sources[ai] = sources[ai].replace(
        anchor, "        first = jax.device_put(group[0][0])", 1)
    r = lint_sources(sources)
    assert any(f.rule_id == "G010" and f.path == ai
               and "device_put" in f.message for f in r.findings), \
        [f.format() for f in r.findings]


def test_g007_guards_the_real_parallel_meshes():
    """Seeded regression on the LIVE tree: a typo'd axis in
    tensor_parallel's constant specs is caught against the mesh-builder
    vocabulary resolved through the package graph."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    tp = os.path.join(REPO, "deeplearning4j_tpu", "parallel",
                      "tensor_parallel.py")
    assert 'P(None, "model")' in sources[tp]
    sources[tp] = sources[tp].replace('P(None, "model")',
                                      'P(None, "modle")', 1)
    r = lint_sources(sources)
    g7 = [f for f in r.findings if f.rule_id == "G007"]
    assert len(g7) == 1 and g7[0].path == tp and "modle" in g7[0].message, \
        [f.format() for f in r.findings]


def test_g010_real_prefetcher_worker_is_clean():
    """The live AsyncDataSetIterator honors its own contract: linting the
    datasets package (whose _worker is a thread target) raises no G010."""
    r = lint_live([os.path.join(REPO, "deeplearning4j_tpu", "datasets")],
                  rule_ids={"G010"})
    assert r.findings == [], [f.format() for f in r.findings]


# ---------------------------------------------------------------------------
# the findings ratchet
# ---------------------------------------------------------------------------
def test_ratchet_compare_directions():
    from tools.graftlint import ratchet_compare
    base = {"findings": {}, "suppressed": {"G001": 3, "G005": 2}}
    worse = {"findings": {"G009": 1}, "suppressed": {"G001": 4, "G005": 2}}
    reg, imp = ratchet_compare(worse, base)
    assert len(reg) == 2 and imp == []
    better = {"findings": {}, "suppressed": {"G001": 2, "G005": 2}}
    reg, imp = ratchet_compare(better, base)
    assert reg == [] and len(imp) == 1


def test_ratchet_cli_blocks_growth(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    baseline = tmp_path / "baseline.json"
    p = _cli([str(clean), "--update-baseline", "--baseline", str(baseline)])
    assert p.returncode == 0 and baseline.exists()
    assert _cli([str(clean), "--ratchet", "--baseline",
                 str(baseline)]).returncode == 0
    # a new suppression (no new finding!) must still trip the ratchet
    supp = tmp_path / "supp.py"
    supp.write_text("class N:\n"
                    "    def fit_batch(self, x):\n"
                    "        s = self._jit_train[0](x)\n"
                    "        return s.item()  "
                    "# graftlint: disable=G001 -- new\n")
    p = _cli([str(clean), str(supp), "--ratchet", "--baseline",
              str(baseline)])
    assert p.returncode == 1
    assert "ratchet" in p.stderr


def test_update_baseline_succeeds_with_findings_present(tmp_path):
    """Re-baselining a reviewed nonzero floor is the flag's purpose: the
    write must succeed (rc 0) even while findings exist."""
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nX = os.environ.get('DL4J_TPU_X')\n")
    baseline = tmp_path / "baseline.json"
    p = _cli([str(bad), "--update-baseline", "--baseline", str(baseline)])
    assert p.returncode == 0, p.stderr
    assert json.loads(baseline.read_text())["findings"] == {"G003": 1}
    # and the ratchet then accepts that floor but not one more
    assert _cli([str(bad), "--ratchet", "--baseline",
                 str(baseline)]).returncode == 1   # findings still fail
    assert "ratchet" not in _cli([str(bad), "--ratchet", "--baseline",
                                  str(baseline)]).stderr


def test_ratchet_cli_missing_baseline_fails():
    p = _cli(["tests/fixtures/graftlint/g011_good.py", "--ratchet",
              "--baseline", "/nonexistent/baseline.json"])
    assert p.returncode == 1
    assert "lint-baseline" in p.stderr


def test_committed_baseline_matches_the_tree():
    """make lint's gate: the committed baseline has zero findings and the
    live tree's per-rule counts do not exceed it."""
    from tools.graftlint import (counts_by_rule, load_baseline,
                                 ratchet_compare)
    baseline = load_baseline()
    assert baseline is not None, "tools/graftlint/baseline.json missing"
    assert baseline.get("findings", {}) == {}
    r = lint_live([os.path.join(REPO, "deeplearning4j_tpu"),
                   os.path.join(REPO, "tools"),
                   os.path.join(REPO, "chip_smoke.py"),
                   os.path.join(REPO, "examples")])
    regressions, _ = ratchet_compare(counts_by_rule(r), baseline)
    assert regressions == [], regressions


# ---------------------------------------------------------------------------
# walker
# ---------------------------------------------------------------------------
def test_walker_skips_pycache(tmp_path):
    pkg = tmp_path / "pkg"
    (pkg / "__pycache__").mkdir(parents=True)
    (pkg / "ok.py").write_text("x = 1\n")
    bad = 'import os\nX = os.environ.get("DL4J_TPU_X")\n'
    (pkg / "__pycache__" / "stray.py").write_text(bad)
    (pkg / "__pycache__" / "stray.cpython-310.pyc").write_bytes(b"\x00\x01")
    r = lint_paths([str(pkg)])
    assert r.findings == [] and r.errors == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def _cli(args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.graftlint"] + args,
                          capture_output=True, text=True, cwd=cwd)


def test_cli_list_rules():
    p = _cli(["--list-rules"])
    assert p.returncode == 0
    for rule in RULES:
        assert rule.id in p.stdout


def test_cli_exit_codes_and_json(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nX = os.environ.get('DL4J_TPU_X')\n")
    p = _cli([str(bad)])
    assert p.returncode == 1
    assert "G003" in p.stdout and "bad.py:2" in p.stdout
    p = _cli([str(bad), "--json"])
    findings = json.loads(p.stdout)
    assert findings[0]["rule_id"] == "G003" and findings[0]["line"] == 2

    good = tmp_path / "good.py"
    good.write_text("x = 1\n")
    assert _cli([str(good)]).returncode == 0


# ---------------------------------------------------------------------------
# the tier-1 gate: the package itself is clean, and fast
# ---------------------------------------------------------------------------
def test_package_gate_zero_unsuppressed_findings():
    """The whole-package gate (same scope as `make lint`): zero findings
    across deeplearning4j_tpu + tools + chip_smoke.py + examples,
    interprocedural graph AND the shared dataflow fixpoint included,
    within the tier-1 budget on the 2-core box. One lint pass builds the
    parsed-AST/symbol-table/dataflow caches once and shares them across
    all rules — that sharing is what the 60s budget asserts."""
    t0 = time.monotonic()
    r = lint_paths([os.path.join(REPO, "deeplearning4j_tpu"),
                    os.path.join(REPO, "tools"),
                     os.path.join(REPO, "chip_smoke.py"),
                    os.path.join(REPO, "examples")])
    elapsed = time.monotonic() - t0
    assert r.errors == []
    assert r.findings == [], "\n".join(f.format() for f in r.findings)
    # suppressions must all carry justifications (G000 would have fired)
    # and must all still be live (G011 would have fired on dead ones);
    # the pass must stay cheap enough for tier-1
    assert elapsed < 60, f"lint took {elapsed:.1f}s"


def test_graftlint_itself_is_clean():
    r = lint_live([os.path.join(REPO, "tools", "graftlint")])
    assert r.findings == [], "\n".join(f.format() for f in r.findings)


# ---------------------------------------------------------------------------
# the knob registry and its generated documentation
# ---------------------------------------------------------------------------
def test_every_dl4j_env_read_in_package_is_registered():
    """Grep-level belt to G003's AST suspenders: every DL4J_TPU_* name
    that appears anywhere in the package source is a declared knob."""
    import re
    from deeplearning4j_tpu.config import KNOBS
    pkg = os.path.join(REPO, "deeplearning4j_tpu")
    seen = set()
    for root, dirs, files in os.walk(pkg):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(root, name), encoding="utf-8") as fh:
                seen |= set(re.findall(r"DL4J_TPU_[A-Z0-9_]+", fh.read()))
    unregistered = sorted(seen - set(KNOBS))
    assert not unregistered, f"undeclared knobs: {unregistered}"


def test_knob_table_doc_is_in_sync():
    from deeplearning4j_tpu.config import knob_table_md
    doc = os.path.join(REPO, "docs", "CONFIG.md")
    with open(doc, encoding="utf-8") as fh:
        content = fh.read()
    assert knob_table_md() in content, (
        "docs/CONFIG.md is stale — regenerate with "
        "`python -m deeplearning4j_tpu.config > docs/CONFIG.md` (make knobs)")


def test_env_helpers_contracts(monkeypatch):
    import warnings
    from deeplearning4j_tpu.config import env_flag, env_int, env_str
    monkeypatch.delenv("DL4J_TPU_FUSE_STEPS", raising=False)
    assert env_int("DL4J_TPU_FUSE_STEPS") == 8
    monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "3")
    assert env_int("DL4J_TPU_FUSE_STEPS") == 3
    monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "-2")
    assert env_int("DL4J_TPU_FUSE_STEPS", minimum=1) == 1
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", "banana")
        assert env_int("DL4J_TPU_FUSE_STEPS") == 8   # warn-and-fall-back
        assert any("banana" in str(x.message) for x in w)
    monkeypatch.setenv("DL4J_TPU_ALLOW_DOWNLOAD", "1")
    assert env_flag("DL4J_TPU_ALLOW_DOWNLOAD") is True
    monkeypatch.setenv("DL4J_TPU_ALLOW_DOWNLOAD", "0")
    assert env_flag("DL4J_TPU_ALLOW_DOWNLOAD") is False
    monkeypatch.delenv("DL4J_TPU_DP_SHARD_UPDATER", raising=False)
    assert env_flag("DL4J_TPU_DP_SHARD_UPDATER") is True   # default-on knob
    # set-but-empty (wrapper scripts, k8s env entries) == unset, so a
    # default-on knob must NOT silently flip off
    monkeypatch.setenv("DL4J_TPU_DP_SHARD_UPDATER", "")
    assert env_flag("DL4J_TPU_DP_SHARD_UPDATER") is True
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "scan")
    assert env_str("DL4J_TPU_LM_ATTN") == "scan"
    import pytest
    with pytest.raises(KeyError):
        env_int("DL4J_TPU_NOT_A_KNOB")


def test_env_float_contract(monkeypatch):
    import warnings
    import pytest
    from deeplearning4j_tpu.config import env_float
    monkeypatch.delenv("DL4J_TPU_COLLECTIVE_TIMEOUT", raising=False)
    assert env_float("DL4J_TPU_COLLECTIVE_TIMEOUT") == 300.0
    monkeypatch.setenv("DL4J_TPU_COLLECTIVE_TIMEOUT", "2.5")
    assert env_float("DL4J_TPU_COLLECTIVE_TIMEOUT") == 2.5
    monkeypatch.setenv("DL4J_TPU_COLLECTIVE_TIMEOUT", "-1")
    assert env_float("DL4J_TPU_COLLECTIVE_TIMEOUT", minimum=0.001) == 0.001
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        monkeypatch.setenv("DL4J_TPU_COLLECTIVE_TIMEOUT", "soon")
        assert env_float("DL4J_TPU_COLLECTIVE_TIMEOUT") == 300.0
        assert any("soon" in str(x.message) for x in w)
    with pytest.raises(KeyError):
        env_float("DL4J_TPU_NOT_A_KNOB")


# ---------------------------------------------------------------------------
# G012 unbounded-blocking-call
# ---------------------------------------------------------------------------
G012DIR = os.path.join(FIXDIR, "g012")


def test_g012_fires_on_each_unbounded_form():
    r = lint_file(os.path.join(G012DIR, "parallel", "bad.py"))
    assert set(ids(r)) == {"G012"} and len(r.findings) == 7, \
        [f.format() for f in r.findings]
    msgs = " ".join(f.message for f in r.findings)
    assert "'.wait()'" in msgs and "'.get()'" in msgs
    assert "create_connection" in msgs and "'.recv()'" in msgs


def test_g012_quiet_on_bounded_forms_and_dict_get():
    r = lint_file(os.path.join(G012DIR, "parallel", "good.py"))
    assert r.findings == [], [f.format() for f in r.findings]


def test_g012_scoped_to_threaded_dirs():
    """The same bad code outside parallel/datasets/streaming is out of
    the rule's scope (blocking main-thread CLI code is not a liveness
    hazard class this rule owns)."""
    r = lint_file(os.path.join(G012DIR, "offscope", "bad_elsewhere.py"))
    assert r.findings == [], [f.format() for f in r.findings]


def test_g012_real_threaded_modules_are_clean():
    """The live coordinator/prefetcher/broker — and, since the scope
    extension, the UI server/storage and obs layer — honor the deadline
    model: every remaining blocking-by-design site carries a justified
    suppression."""
    r = lint_live([os.path.join(REPO, "deeplearning4j_tpu", "parallel"),
                   os.path.join(REPO, "deeplearning4j_tpu", "datasets"),
                   os.path.join(REPO, "deeplearning4j_tpu", "streaming"),
                   os.path.join(REPO, "deeplearning4j_tpu", "ui"),
                   os.path.join(REPO, "deeplearning4j_tpu", "obs")],
                  rule_ids={"G012"})
    assert r.findings == [], [f.format() for f in r.findings]


def test_g012_scope_extends_to_ui_and_obs():
    """The satellite scope extension: the same unbounded wait that fires
    under parallel/ now fires under ui/ and obs/ too (server threads and
    the metrics/trace layer block on peers just the same)."""
    src = "def f(ev):\n    ev.wait()\n"
    for scoped in ("pkg/ui/mod.py", "pkg/obs/mod.py", "pkg/parallel/m.py"):
        r = lint_source(src, scoped, rule_ids={"G012"})
        assert [f.rule_id for f in r.findings] == ["G012"], scoped
    r = lint_source(src, "pkg/models/mod.py", rule_ids={"G012"})
    assert r.findings == []


def test_serving_scope_fixture_pair():
    """ISSUE 14 satellite: the serving/ scope extension, proven on the
    dedicated fixture pair — the bad server fires G001 (the serving
    dispatch loop is a hot-closure root), G012 (unbounded queue pull),
    G015 (unlocked cross-thread counter), G021 (request-keyed device
    cache, no eviction), and — since the v5 resource pack — G023 (the
    batch loop has no stop flag: an unstoppable serving thread IS a
    serving defect); the disciplined good twin is clean."""
    d = os.path.join(FIXDIR, "serving")
    bad = lint_file(os.path.join(d, "bad.py"))
    assert ids(bad) == ["G001", "G012", "G015", "G021", "G023"], \
        [f.format() for f in bad.findings]
    good = lint_file(os.path.join(d, "good.py"))
    assert good.findings == [], [f.format() for f in good.findings]


def test_serving_ingress_fixture_pair():
    """ISSUE 20 satellite: the resilience-tier discipline on the ingress
    fixture pair — the bad front door fires G012 (a stream pump blocking
    unbounded on its chunk queue: a dead producer wedges the handler
    thread) and G015 (the drain path flips the readiness flag with no
    lock while the listener loop reads it); the disciplined good twin —
    bounded pull, flag under the lock — is clean."""
    d = os.path.join(FIXDIR, "serving")
    bad = lint_file(os.path.join(d, "ingress_bad.py"))
    assert ids(bad) == ["G012", "G015"], [f.format() for f in bad.findings]
    good = lint_file(os.path.join(d, "ingress_good.py"))
    assert good.findings == [], [f.format() for f in good.findings]


def test_g012_scope_extends_to_serving():
    src = "def f(ev):\n    ev.wait()\n"
    r = lint_source(src, "pkg/serving/mod.py", rule_ids={"G012"})
    assert [f.rule_id for f in r.findings] == ["G012"]


def test_serving_hot_seeds_blessed_builders_and_loops():
    """The inference hot closure now roots on the serving dispatch loops
    (by name) and on every _gen/_decode/_admit blessed-builder or
    _jit_gen/_jit_decode cache user — a stray per-chunk sync in any of
    them is a finding, exactly like fit_batch."""
    for src in (
        # name-seeded dispatch loop
        """
        class S:
            def _decode_loop(self):
                loss = self._step(None)
                return float(loss)
        """,
        # blessed-builder user
        """
        class S:
            def tick(self, x):
                sig = self._decode_signature(4, 8)
                loss = self._step(x)
                return float(loss)
        """,
        # compiled-sampler cache user
        """
        class S:
            def tick(self, x, sig):
                out = self._jit_gen[sig](x)
                return out.item()
        """,
    ):
        r = check(src)
        assert "G001" in ids(r), (src, [f.format() for f in r.findings])


def test_paging_scope_fixture_pair():
    """ISSUE 16 satellite: the paged-decode rung discipline, proven on
    its fixture pair — the bad scheduler keys a raw shape-derived rung
    into the decode jit cache beside the blessed builder (G017: one
    compile per novel prompt length) and grows a prompt-keyed
    prefix-page cache with no eviction (G021); the good twin routes the
    rung through ``_decode_signature`` and LRU-bounds the pages."""
    d = os.path.join(FIXDIR, "paging")
    bad = lint_file(os.path.join(d, "bad.py"))
    assert ids(bad) == ["G017", "G021"], \
        [f.format() for f in bad.findings]
    good = lint_file(os.path.join(d, "good.py"))
    assert good.findings == [], [f.format() for f in good.findings]


def test_prefill_hot_seeds():
    """The ISSUE 16 rung builders root the hot closure exactly like the
    decode ones: ``_prefill_signature``/``_prefill_fn``/``_decode_fns``
    users and the prefill pump loop are G001 roots."""
    for src in (
        """
        class S:
            def tick(self, x):
                sig = self._prefill_signature(4, 16)
                loss = self._step(x)
                return float(loss)
        """,
        """
        class S:
            def tick(self, x):
                pf = self._prefill_fn(4, 16)
                loss = pf(x)
                return float(loss)
        """,
        """
        class S:
            def tick(self, x):
                admit, step = self._decode_fns(4, 8, 64)
                loss = step(x)
                return float(loss)
        """,
        """
        class S:
            def _pump_prefill(self):
                loss = self._step(None)
                return float(loss)
        """,
    ):
        r = check(src)
        assert "G001" in ids(r), (src, [f.format() for f in r.findings])


def test_live_serving_modules_clean_under_concurrency_scope():
    """The real serving/ package holds the full scoped rule set (G001
    suppressions at the documented completion seams only, bounded waits,
    locked shared state, no unbounded device caches)."""
    r = lint_live([os.path.join(REPO, "deeplearning4j_tpu", "serving")])
    assert r.findings == [], [f.format() for f in r.findings]


def test_g012_guards_the_real_coordinator_wait():
    """Seeded regression on the LIVE tree: reverting the coordinator's
    deadline-bounded round wait to a bare Event.wait() is caught."""
    from tools.graftlint import lint_sources
    coord = os.path.join(REPO, "deeplearning4j_tpu", "parallel",
                         "coordinator.py")
    with open(coord, encoding="utf-8") as fh:
        src = fh.read()
    anchor = "if not e.complete.wait(self.timeout):"
    assert anchor in src
    src = src.replace(anchor, "if not e.complete.wait():", 1)
    r = lint_sources({coord: src}, rule_ids={"G012"})
    assert any(f.rule_id == "G012" and "'.wait()'" in f.message
               for f in r.findings), [f.format() for f in r.findings]


def test_g012_guards_the_real_prefetch_consumer():
    """Seeded regression on the LIVE tree: reverting the prefetch
    consumer's bounded get to a bare queue.get() is caught."""
    from tools.graftlint import lint_sources
    ai = os.path.join(REPO, "deeplearning4j_tpu", "datasets",
                      "async_iterator.py")
    with open(ai, encoding="utf-8") as fh:
        src = fh.read()
    anchor = "return got(q.get(timeout=_LIVENESS_POLL_S))"
    assert anchor in src
    src = src.replace(anchor, "return got(q.get())", 1)
    r = lint_sources({ai: src}, rule_ids={"G012"})
    assert any(f.rule_id == "G012" and "'.get()'" in f.message
               for f in r.findings), [f.format() for f in r.findings]


# ---------------------------------------------------------------------------
# G013 non-atomic-checkpoint-write
# ---------------------------------------------------------------------------
G013DIR = os.path.join(FIXDIR, "g013")


def test_g013_fires_on_each_bare_write_form():
    r = lint_file(os.path.join(G013DIR, "utils", "bad.py"))
    assert set(ids(r)) == {"G013"} and len(r.findings) == 6, \
        [f.format() for f in r.findings]
    msgs = " ".join(f.message for f in r.findings)
    assert "open(" in msgs and "ZipFile(" in msgs
    assert "np.savez" in msgs and "np.save " in msgs


def test_g013_quiet_on_reads_buffers_and_atomic_commits():
    r = lint_file(os.path.join(G013DIR, "utils", "good.py"))
    assert r.findings == [], [f.format() for f in r.findings]


def test_g013_scoped_to_persistence_dirs():
    """The same writes outside utils/ / earlystopping/ (bench dumps, tool
    output) are not checkpoints and stay out of the rule's scope."""
    r = lint_file(os.path.join(G013DIR, "offscope", "bad_elsewhere.py"))
    assert r.findings == [], [f.format() for f in r.findings]


def test_g013_exempts_the_atomic_helper_itself():
    """utils/atomic_io.py is the ONE module allowed to open files for
    writing — it is where the tmp+fsync+rename protocol lives."""
    r = lint_file(os.path.join(REPO, "deeplearning4j_tpu", "utils",
                               "atomic_io.py"), rule_ids={"G013"})
    assert r.findings == [], [f.format() for f in r.findings]


def test_g013_real_persistence_modules_are_clean():
    """The live serializers commit exclusively through atomic_io."""
    r = lint_live([os.path.join(REPO, "deeplearning4j_tpu", "utils"),
                   os.path.join(REPO, "deeplearning4j_tpu",
                                "earlystopping")],
                  rule_ids={"G013"})
    assert r.findings == [], [f.format() for f in r.findings]


def test_g013_guards_the_real_model_serializer():
    """Seeded regression on the LIVE tree: reverting write_model's atomic
    commit to a ZipFile write-in-place is caught."""
    from tools.graftlint import lint_sources
    ms = os.path.join(REPO, "deeplearning4j_tpu", "utils",
                      "model_serializer.py")
    with open(ms, encoding="utf-8") as fh:
        src = fh.read()
    anchor = "return atomic_io.write_zip_atomic(path, entries)"
    assert anchor in src
    src = src.replace(
        anchor,
        'import zipfile as _zf\n'
        '    with _zf.ZipFile(path, "w") as z:\n'
        '        [z.writestr(n, d) for n, d in entries.items()]', 1)
    r = lint_sources({ms: src}, rule_ids={"G013"})
    assert any(f.rule_id == "G013" and "ZipFile" in f.message
               for f in r.findings), [f.format() for f in r.findings]


def test_g013_guards_the_real_orbax_config_write():
    """Seeded regression on the LIVE tree: reverting the orbax adapter's
    config write to a bare open(path, "w") is caught."""
    from tools.graftlint import lint_sources
    ob = os.path.join(REPO, "deeplearning4j_tpu", "utils", "orbax_io.py")
    with open(ob, encoding="utf-8") as fh:
        src = fh.read()
    anchor = "atomic_io.write_file(os.path.join(tmp, _CONFIG_NAME), cj)"
    assert anchor in src
    src = src.replace(
        anchor,
        'open(os.path.join(tmp, _CONFIG_NAME), "w").write(cj)', 1)
    r = lint_sources({ob: src}, rule_ids={"G013"})
    assert any(f.rule_id == "G013" and "open(" in f.message
               for f in r.findings), [f.format() for f in r.findings]


# ---------------------------------------------------------------------------
# G006 explicit acquire/release (satellite fix: bare acquire pairs used to
# be invisible, silently exempting whole classes)
# ---------------------------------------------------------------------------
G006_ACQUIRE_BAD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.items = []

        def put(self, x):
            self._lock.acquire()
            try:
                self.items = self.items + [x]
            finally:
                self._lock.release()

        def clear(self):
            self.items = []            # unguarded vs the acquire() writers
"""

G006_ACQUIRE_GOOD = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.items = []

        def put(self, x):
            self._lock.acquire()
            try:
                self.items = self.items + [x]
            finally:
                self._lock.release()

        def clear(self):
            self._lock.acquire()
            self.items = []
            self._lock.release()
"""


def test_g006_sees_explicit_acquire_release_pairs():
    r = check(G006_ACQUIRE_BAD)
    assert ids(r) == ["G006"], [f.format() for f in r.findings]
    assert "items" in r.findings[0].message
    assert check(G006_ACQUIRE_GOOD).findings == []


def test_g006_condition_via_acquire_counts_as_lock_scope():
    """A Condition guarded through acquire()/release() (no 'lock' in the
    name) is a lock protocol: the acquire/release PAIR makes it a scope."""
    r = check("""
        import threading

        class CondBox:
            def __init__(self):
                self._cv = threading.Condition()
                self.ready = False

            def arm(self):
                self._cv.acquire()
                try:
                    self.ready = True
                finally:
                    self._cv.release()

            def disarm(self):
                self.ready = False     # races the acquire()-guarded writer
    """)
    assert ids(r) == ["G006"]
    assert "ready" in r.findings[0].message


def test_g006_write_after_release_is_unguarded():
    r = check("""
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0

            def locked_then_not(self):
                self._lock.acquire()
                self.n = 1
                self._lock.release()
                self.n = 2             # after release: unguarded
    """)
    assert ids(r) == ["G006"]


# ---------------------------------------------------------------------------
# G014 lock-order-cycle
# ---------------------------------------------------------------------------
G014DIR = os.path.join(FIXDIR, "g014")


def test_g014_fires_on_abba_and_stays_quiet_on_ordered():
    r = lint_file(os.path.join(G014DIR, "bad.py"))
    assert [f.rule_id for f in r.findings] == ["G014", "G014"], \
        [f.format() for f in r.findings]
    msgs = " ".join(f.message for f in r.findings)
    assert "lock-order cycle" in msgs and "deadlock" in msgs
    assert "_feed_lock" in msgs and "_state_lock" in msgs
    assert lint_file(os.path.join(G014DIR, "good.py")).findings == []


def test_g014_cross_module_inversion_needs_the_package_graph():
    """Each half is cycle-free alone (one edge each); the whole-package
    graph closes the cycle through the caller-holds-while-callee-acquires
    edges in both directions."""
    pkg = os.path.join(G014DIR, "g014_pkg")
    for name in ("a.py", "b.py"):
        alone = lint_file(os.path.join(pkg, name))
        assert alone.findings == [], (name, [f.format() for f in
                                             alone.findings])
    r = lint_paths([pkg])
    assert ids(r) == ["G014"], [f.format() for f in r.findings]
    assert {os.path.basename(f.path) for f in r.findings} == \
        {"a.py", "b.py"}


def test_g014_guards_the_live_tree_against_a_seeded_inversion():
    """Seeded regression on the LIVE tree: a class with an ABBA pair
    appended to the coordinator module is caught by the package lint."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    coord = os.path.join(REPO, "deeplearning4j_tpu", "parallel",
                         "coordinator.py")
    sources[coord] += textwrap.dedent("""

        class _SeededInversion:
            def __init__(self):
                self._a_lock = threading.Lock()
                self._b_lock = threading.Lock()

            def fwd(self):
                with self._a_lock:
                    with self._b_lock:
                        pass

            def rev(self):
                with self._b_lock:
                    with self._a_lock:
                        pass
    """)
    r = lint_sources(sources)
    g14 = [f for f in r.findings if f.rule_id == "G014" and f.path == coord]
    assert len(g14) == 2, [f.format() for f in r.findings]


def test_g014_caller_held_helper_contract_is_seen():
    """The _fail_entry pattern: a private helper whose EVERY call site
    holds lock A is analyzed as holding A, so its acquisition of B makes
    an A->B edge — and an inversion through it is caught."""
    r = check("""
        import threading

        class Registry:
            def __init__(self):
                self._reg_lock = threading.Lock()
                self._io_lock = threading.Lock()

            def record(self):
                with self._reg_lock:
                    self._flush()      # helper runs WITH reg held

            def _flush(self):
                with self._io_lock:
                    pass

            def drain(self):
                with self._io_lock:
                    with self._reg_lock:   # the opposite order
                        pass
    """)
    assert "G014" in ids(r), [f.format() for f in r.findings]


# ---------------------------------------------------------------------------
# G015 unlocked-cross-thread-write
# ---------------------------------------------------------------------------
G015DIR = os.path.join(FIXDIR, "g015")


def test_g015_fires_on_unlocked_cross_thread_pair():
    r = lint_paths([os.path.join(G015DIR, "datasets", "bad.py")])
    assert ids(r) == ["G015"], [f.format() for f in r.findings]
    msg = r.findings[0].message
    assert "Feeder.pulled" in msg and "_worker" in msg
    assert "Thread(" in msg and "main" in msg


def test_g015_common_lock_silences():
    r = lint_paths([os.path.join(G015DIR, "datasets", "good.py")])
    assert r.findings == [], [f.format() for f in r.findings]


def test_g015_scoped_to_threaded_dirs():
    """The identical class outside the threaded scope dirs (model replica
    state is per-thread by construction) is out of scope."""
    with open(os.path.join(G015DIR, "datasets", "bad.py"),
              encoding="utf-8") as fh:
        src = fh.read()
    r = lint_sources({"pkg/models/feeder.py": src})
    assert r.findings == [], [f.format() for f in r.findings]


def test_g015_threadsafe_attrs_and_init_writes_exempt():
    r = lint_sources({"pkg/datasets/m.py": textwrap.dedent("""
        import queue
        import threading

        class Pump:
            def __init__(self):
                self.q = queue.Queue()     # thread-safe channel: exempt
                self._stop = threading.Event()
                self.batch = 8             # construction write: exempt

            def start(self):
                self._thread = threading.Thread(target=self._worker,
                                                daemon=True)
                self._thread.start()

            def stop(self):
                self._stop.set()
                self._thread.join()

            def _worker(self):
                while not self._stop.is_set():
                    self.q.put(self.batch)   # queue op + config read only
    """)})
    assert r.findings == [], [f.format() for f in r.findings]


def test_g015_container_mutation_counts_as_write():
    """self.items.append(...) mutates shared state just like assignment —
    the handler-thread reader with no common lock is a finding."""
    r = lint_sources({"pkg/streaming/m.py": textwrap.dedent("""
        import threading

        class Log:
            def __init__(self):
                self._lock = threading.Lock()
                self._stop = threading.Event()
                self.items = []

            def start(self):
                self._thread = threading.Thread(target=self._worker,
                                                daemon=True)
                self._thread.start()

            def stop(self):
                self._stop.set()
                self._thread.join()

            def _worker(self):
                while not self._stop.is_set():
                    self.items.append(1)

            def snapshot(self):
                return list(self.items)
    """)})
    assert ids(r) == ["G015"], [f.format() for f in r.findings]


def test_g015_guards_the_real_coordinator_entry_map():
    """Seeded regression on the LIVE tree: stripping the lock from the
    coordinator's _entry() leaves handler-thread writes of _entries
    racing the (locked) main-thread accesses — caught through the
    handler-class thread root."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    coord = os.path.join(REPO, "deeplearning4j_tpu", "parallel",
                         "coordinator.py")
    anchor = ("    def _entry(self, tag):\n"
              "        with self._lock:\n"
              "            e = self._entries.get(tag)\n"
              "            if e is None:\n"
              "                e = _Entry()\n"
              "                self._entries[tag] = e\n"
              "            return e\n")
    assert anchor in sources[coord]
    sources[coord] = sources[coord].replace(anchor, (
        "    def _entry(self, tag):\n"
        "        e = self._entries.get(tag)\n"
        "        if e is None:\n"
        "            e = _Entry()\n"
        "            self._entries[tag] = e\n"
        "        return e\n"), 1)
    r = lint_sources(sources)
    assert any(f.rule_id == "G015" and f.path == coord
               and "_entries" in f.message for f in r.findings), \
        [f.format() for f in r.findings if f.rule_id == "G015"]


# ---------------------------------------------------------------------------
# SARIF output (satellite: CI PR-annotation surface)
# ---------------------------------------------------------------------------
def test_sarif_document_shape(tmp_path):
    from tools.graftlint import to_sarif
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nX = os.environ.get('DL4J_TPU_X')\n")
    doc = to_sarif(lint_paths([str(bad)]))
    assert doc["version"] == "2.1.0"
    assert "sarif-schema-2.1.0" in doc["$schema"]
    (run,) = doc["runs"]
    driver = run["tool"]["driver"]
    assert driver["name"] == "graftlint"
    rule_ids = {r["id"] for r in driver["rules"]}
    # catalogue + concurrency pack + the core-reported rules
    for rid in ("G001", "G014", "G015", "G000", "G011"):
        assert rid in rule_ids
    (res,) = run["results"]
    assert res["ruleId"] == "G003" and res["level"] == "error"
    assert driver["rules"][res["ruleIndex"]]["id"] == "G003"
    (loc,) = res["locations"]
    region = loc["physicalLocation"]["region"]
    assert region["startLine"] == 2 and region["startColumn"] >= 1
    assert loc["physicalLocation"]["artifactLocation"]["uri"].endswith(
        "bad.py")


def test_sarif_cli_round_trips_and_omits_suppressed(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import os\n"
        "X = os.environ.get('DL4J_TPU_X')\n"
        "Y = os.environ.get('DL4J_TPU_Y')  "
        "# graftlint: disable=G003 -- covered knob\n")
    p = _cli([str(bad), "--sarif"])
    assert p.returncode == 1
    doc = json.loads(p.stdout)
    results = doc["runs"][0]["results"]
    # the suppressed finding is absent: a justified disable is a reviewed
    # decision, not an annotation to re-litigate
    assert [r["ruleId"] for r in results] == ["G003"]
    assert results[0]["locations"][0]["physicalLocation"]["region"][
        "startLine"] == 2

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    p = _cli([str(clean), "--sarif"])
    assert p.returncode == 0
    assert json.loads(p.stdout)["runs"][0]["results"] == []


# ---------------------------------------------------------------------------
# --changed (make lint-fast: the pre-commit lane)
# ---------------------------------------------------------------------------
def _git(tmp, *args):
    return subprocess.run(["git", "-C", str(tmp)] + list(args),
                          capture_output=True, text=True)


@pytest.fixture
def git_repo(tmp_path):
    if _git(tmp_path, "init", "-q").returncode != 0:
        pytest.skip("git unavailable")
    _git(tmp_path, "config", "user.email", "t@t")
    _git(tmp_path, "config", "user.name", "t")
    (tmp_path / "pkg").mkdir()
    (tmp_path / "pkg" / "mod.py").write_text("x = 1\n")
    (tmp_path / "pkg" / "other.py").write_text("y = 1\n")
    _git(tmp_path, "add", "-A")
    assert _git(tmp_path, "commit", "-q", "-m", "seed").returncode == 0
    return tmp_path


def _cli_in(cwd, args):
    env = dict(os.environ, PYTHONPATH=REPO)
    return subprocess.run([sys.executable, "-m", "tools.graftlint"] + args,
                          capture_output=True, text=True, cwd=str(cwd),
                          env=env)


def test_changed_lints_only_dirty_files(git_repo):
    p = _cli_in(git_repo, ["pkg", "--changed"])
    assert p.returncode == 0, p.stderr
    assert "no changed .py files" in p.stderr
    # dirty ONE file with a violation: the fast lane sees it
    (git_repo / "pkg" / "mod.py").write_text(
        "import os\nX = os.environ.get('DL4J_TPU_X')\n")
    p = _cli_in(git_repo, ["pkg", "--changed"])
    assert p.returncode == 1
    assert "G003" in p.stdout and "mod.py" in p.stdout
    assert "1 changed file(s)" in p.stderr
    assert "make lint" in p.stderr        # the interprocedural pointer
    assert "G014" in p.stderr and "G015" in p.stderr


def test_changed_scopes_to_the_lint_paths(git_repo):
    """A dirty file OUTSIDE the lint scope (tests/, scripts) is not the
    fast lane's business — same scope as make lint."""
    (git_repo / "elsewhere.py").write_text(
        "import os\nX = os.environ.get('DL4J_TPU_X')\n")
    p = _cli_in(git_repo, ["pkg", "--changed"])
    assert p.returncode == 0, p.stdout + p.stderr
    assert "no changed .py files" in p.stderr


def test_changed_skips_unused_suppression_rule(git_repo):
    """A suppression whose rule needs the whole-package graph must not be
    reported dead by a file-scoped fast-lane run."""
    (git_repo / "pkg" / "mod.py").write_text(
        "def report(score):\n"
        "    return float(score)  "
        "# graftlint: disable=G001 -- hot only via models/, not visible "
        "file-scoped\n")
    p = _cli_in(git_repo, ["pkg", "--changed"])
    assert p.returncode == 0, p.stdout + p.stderr
    assert "G011" not in p.stdout


def test_changed_rejects_ratchet_combination(git_repo):
    """The ratchet accounts for the FULL scope; a partial-scope run with
    ratchet semantics would lie in both directions."""
    p = _cli_in(git_repo, ["pkg", "--changed", "--ratchet"])
    assert p.returncode == 2
    assert "FULL scope" in p.stderr


def test_cli_lists_concurrency_rules():
    p = _cli(["--list-rules"])
    assert p.returncode == 0
    assert "G014" in p.stdout and "G015" in p.stdout
    assert "lock-order cycle" in p.stdout


def test_changed_works_from_a_subdirectory(git_repo):
    """git emits repo-root-relative paths; the fast lane must see the
    same dirty files no matter which directory the hook runs from."""
    (git_repo / "pkg" / "mod.py").write_text(
        "import os\nX = os.environ.get('DL4J_TPU_X')\n")
    p = _cli_in(git_repo / "pkg", [str(git_repo / "pkg"), "--changed"])
    assert p.returncode == 1, p.stdout + p.stderr
    assert "G003" in p.stdout and "mod.py" in p.stdout


def test_g015_least_guarded_write_wins_regardless_of_order():
    """A locked write AFTER an unlocked write of the same attr (same fn)
    must not shadow it — the unlocked one is the finding either way."""
    body = """
        import threading

        class Feeder:
            def __init__(self):
                self._lock = threading.Lock()
                self.buf = None

            def start(self):
                self._thread = threading.Thread(target=self._worker,
                                                daemon=True)
                self._thread.start()

            def _worker(self):
                while True:
                    {first}
                    {second}

            def snapshot(self):
                with self._lock:
                    return self.buf
    """
    unlocked = "self.buf = None"
    locked = ("with self._lock:\n"
              "                        self.buf = object()")
    for first, second in ((unlocked, locked), (locked, unlocked)):
        r = lint_sources({"pkg/datasets/m.py": textwrap.dedent(
            body.format(first=first, second=second))})
        # G006 also (correctly) flags the with/without inconsistency; the
        # regression under test is that G015 fires in BOTH orderings
        assert "G015" in ids(r), (first[:20], [f.format()
                                               for f in r.findings])


def test_g006_nested_def_inside_acquire_span_is_not_double_counted():
    """One write, inside a nested def that lexically sits between
    acquire() and release(): the nested def does not inherit the span
    (it may run on any thread), and there is no second write to conflict
    with — no finding."""
    r = check("""
        import threading

        class Box:
            def __init__(self):
                self._lock = threading.Lock()
                self.x = 0

            def schedule(self):
                self._lock.acquire()
                def cb():
                    self.x = 1
                self._lock.release()
                return cb
    """)
    assert r.findings == [], [f.format() for f in r.findings]


def test_changed_resolves_relative_scope_from_a_subdirectory(git_repo):
    """The Makefile's relative LINT_PATHS must mean the same files no
    matter which directory the hook runs from: scope paths that don't
    exist cwd-relative resolve against the git toplevel."""
    (git_repo / "pkg" / "mod.py").write_text(
        "import os\nX = os.environ.get('DL4J_TPU_X')\n")
    p = _cli_in(git_repo / "pkg", ["pkg", "--changed"])
    assert p.returncode == 1, p.stdout + p.stderr
    assert "G003" in p.stdout and "mod.py" in p.stdout


# ---------------------------------------------------------------------------
# graftlint v3: the flow-sensitive dataflow pack (G016/G017/G018)
# ---------------------------------------------------------------------------
G016_BAD_FLOW = """
    class Net:
        def fit_batch(self, x):
            sig = self._train_signature(x)
            loss = self._jit_train[sig](x)
            self.scores.append(loss)
            if self.scores[-1] > self.threshold:   # implicit sync
                self.lr *= 0.5
            return loss

        def reset(self):
            self.scores.clear()    # bounded: keeps v4's G021 out of
                                   # this G016-focused fixture
"""

G016_BAD_FORMAT = """
    class Net:
        def fit_batch(self, x):
            sig = self._train_signature(x)
            loss = self._jit_train[sig](x)
            msg = f"step loss={loss}"              # __format__ syncs
            z = float(loss * x.shape[0])           # G001-exempt arg shape
            return msg, z
"""

G016_GOOD = """
    import numpy as np

    class Net:
        def fit_batch(self, x):
            sig = self._train_signature(x)
            loss = self._jit_train[sig](x)
            self.score_ = loss                     # device, lazy sync
            n = int(x.shape[0])                    # host metadata
            if x is None:                          # identity: no sync
                return None
            if n > 8:                              # host int: fine
                self._last_batch_size = n
            return loss

    def report(scores):
        return [float(s) for s in scores]          # cold path: not hot
"""


def test_g016_flow_carried_truth_test_fires_with_flow_path():
    """The motivating miss class: no syncing CALL anywhere — the device
    loss flows through a list into an `if`. The finding names the whole
    flow."""
    r = check(G016_BAD_FLOW)
    assert ids(r) == ["G016"], [f.format() for f in r.findings]
    msg = r.findings[0].message
    assert "truth test" in msg
    assert "_jit_train[...] dispatch" in msg        # flow origin
    assert "'loss'" in msg and "self.scores" in msg  # flow steps


def test_g016_format_and_flow_carried_float_fire():
    r = check(G016_BAD_FORMAT)
    assert ids(r) == ["G016"] and len(r.findings) == 2, \
        [f.format() for f in r.findings]
    msgs = " ".join(f.message for f in r.findings)
    assert "formatting" in msgs
    assert "LOOKS" in msgs        # the G001-heuristic-exempt float()


def test_g016_shape_reads_identity_checks_and_cold_paths_pass():
    assert check(G016_GOOD).findings == [], \
        [f.format() for f in check(G016_GOOD).findings]


def test_g016_numpy_coercion_of_flowed_device_value_fires():
    r = check("""
        import numpy as np

        class Net:
            def fit_batch(self, x):
                sig = self._train_signature(x)
                loss = self._jit_train[sig](x)
                return np.mean(loss)        # host materialization
    """)
    assert ids(r) == ["G016"]
    assert "np.mean" in r.findings[0].message


def test_g016_cross_module_flow_needs_the_package_graph():
    """The device kind crosses the file boundary through the callee's
    SUMMARY: per-file lint sees an unknown call and stays silent; the
    package lint knows the helper returns a device value."""
    helper = textwrap.dedent("""
        import jax.numpy as jnp

        def device_norm(grads):
            return jnp.sqrt(sum(jnp.vdot(g, g) for g in grads))
    """)
    hot = textwrap.dedent("""
        from pkg.helper import device_norm

        class Net:
            def fit_batch(self, x):
                sig = self._train_signature(x)
                loss = self._jit_train[sig](x)
                gn = device_norm(self._last_gradients)
                if gn > 100.0:                  # flow-carried sync
                    self.lr *= 0.5
                return loss
    """)
    sources = {"pkg/helper.py": helper, "pkg/net.py": hot}
    from tools.graftlint import lint_sources as ls
    alone = ls({"pkg/net.py": hot})
    assert [f for f in alone.findings if f.rule_id == "G016"] == [], \
        [f.format() for f in alone.findings]
    r = ls(sources)
    g16 = [f for f in r.findings if f.rule_id == "G016"]
    assert len(g16) == 1 and g16[0].path == "pkg/net.py", \
        [f.format() for f in r.findings]
    assert "device_norm" in g16[0].message


def test_g017_shape_branch_and_range_in_traced_fn_fire():
    r = check("""
        import jax

        def step(w, x):
            B, T = x.shape
            if B > 64:                      # retrace per batch size
                w = w + 1
            for i in range(T):              # unrolls per seq length
                w = w * 2
            return w

        train = jax.jit(step)
    """)
    assert ids(r) == ["G017"] and len(r.findings) == 2, \
        [f.format() for f in r.findings]
    msgs = " ".join(f.message for f in r.findings)
    assert "branch" in msgs and "range()" in msgs
    assert ".shape" in msgs and "'B'" in msgs


def test_g017_rank_checks_and_raise_guards_are_exempt():
    """Branching on RANK (.ndim, len()) is idiomatic rank-normalization,
    stable per model; a raise-only guard validates without forking the
    traced program. Neither retraces per batch shape."""
    r = check("""
        import jax

        def step(w, x):
            if x.ndim == 3:                 # rank: stable per model
                w = w * 2
            if x.shape[0] % 8:
                raise ValueError("pad the batch")   # validation only
            assert x.shape[1] > 0           # ditto
            for i in range(x.ndim):
                w = w + i
            return w

        train = jax.jit(step)
    """)
    assert r.findings == [], [f.format() for f in r.findings]


def test_g017_raw_shape_cache_key_fires_blessed_signature_passes():
    bad = check("""
        class Net:
            def fit_batch(self, x):
                key = (x.shape, str(x.dtype))
                if key not in self._jit_train:
                    self._jit_train[key] = self._build(x)
                return self._jit_train[key](x)
    """)
    # the same defect at both depths: G017 (syntactic raw-key-beside-
    # blessed-path) and its v6 flow deepening G025 (unblessed jit
    # callsite) — see docs/STATIC_ANALYSIS.md, the compile-signature layer
    assert set(ids(bad)) == {"G017", "G025"}, \
        [f.format() for f in bad.findings]
    g017 = [f for f in bad.findings if f.rule_id == "G017"]
    assert "_train_signature" in g017[0].message
    good = check("""
        class Net:
            def fit_batch(self, x, guard):
                sig = self._train_signature(x) + (guard,)
                if sig not in self._jit_train:
                    self._jit_train[sig] = self._build(x)
                return self._jit_train[sig](x)
    """)
    assert good.findings == [], [f.format() for f in good.findings]


def test_g017_shape_flowing_into_static_argnums_fires():
    r = check("""
        import jax

        def run(f, x):
            n = x.shape[0]
            step = jax.jit(f, static_argnums=n)   # one program per shape
            return step(x)
    """)
    g17 = [f for f in r.findings if f.rule_id == "G017"]
    assert len(g17) == 1, [f.format() for f in r.findings]
    assert "static_argnums" in g17[0].message


def test_g018_flowed_axis_rank_and_arity_checks():
    r = check("""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def colspec(ax):
            return P(None, ax)

        def biasspec(ax):
            return P(ax, None)

        def build(devices):
            mesh = Mesh(devices, ("data", "model"))
            sh = NamedSharding(mesh, colspec("modle"))      # typo'd axis
            b = jnp.zeros((8,))
            b = jax.device_put(b, NamedSharding(mesh, biasspec("model")))
            return sh, b

        def step(params, x, y):
            return params, x

        def wrap(mesh):
            from jax import shard_map
            return shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data")),     # 2 != 3 args
                             out_specs=(P(), P()))
    """)
    assert ids(r) == ["G018"] and len(r.findings) == 3, \
        [f.format() for f in r.findings]
    msgs = " ".join(f.message for f in r.findings)
    assert "'modle'" in msgs and "data" in msgs and "model" in msgs
    assert "rank-2" in msgs and "rank-1" in msgs
    assert "in_specs has 2 entries" in msgs and "takes 3" in msgs


def test_g018_correct_specs_through_helpers_stay_quiet():
    r = check("""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def colspec(ax):
            return P(None, ax)

        def build(devices):
            mesh = Mesh(devices, ("data", "model"))
            sh = NamedSharding(mesh, colspec("model"))
            b = jnp.zeros((8,))
            b = jax.device_put(b, NamedSharding(mesh, P("model")))
            return sh, b

        def step(params, x, y):
            return params, x

        def wrap(mesh):
            from jax import shard_map
            return shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data"), P("data")),
                             out_specs=(P(), P()))
    """)
    assert r.findings == [], [f.format() for f in r.findings]


def test_g018_spec_helper_resolves_across_modules():
    """The wrong axis is only visible when the helper's spec summary
    crosses the file boundary — lint_file on the use-site file alone
    cannot see it."""
    helper = textwrap.dedent("""
        from jax.sharding import PartitionSpec as P

        def rowspec(ax):
            return P(ax, None)
    """)
    use = textwrap.dedent("""
        from jax.sharding import Mesh, NamedSharding
        from pkg.specs import rowspec

        def build(devices):
            mesh = Mesh(devices, ("data", "model"))
            return NamedSharding(mesh, rowspec("modle"))
    """)
    from tools.graftlint import lint_sources as ls
    alone = ls({"pkg/use.py": use})
    assert [f for f in alone.findings if f.rule_id == "G018"] == [], \
        [f.format() for f in alone.findings]
    r = ls({"pkg/specs.py": helper, "pkg/use.py": use})
    g18 = [f for f in r.findings if f.rule_id == "G018"]
    assert len(g18) == 1 and g18[0].path == "pkg/use.py", \
        [f.format() for f in r.findings]
    assert "'modle'" in g18[0].message


# ---- seeded live-tree regressions (lint_paths catches, lint_file misses)


def test_g016_guards_the_real_hot_path_against_flowed_sync():
    """Seeded regression on the LIVE tree: a flow-carried truth test on
    the device all-finite predicate planted in fit_batch. The device
    kind comes from step_all_finite's summary (models/_device_state.py)
    — invisible to per-file lint, caught by the package pass."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    mln = os.path.join(REPO, "deeplearning4j_tpu", "models",
                       "multi_layer_network.py")
    anchor = ("            if guard:\n"
              "                self._nanguard_record(skipped)")
    assert anchor in sources[mln]
    seeded = ("            healthy = step_all_finite(score, grads)\n"
              "            if healthy:\n"
              "                self._streak = self._streak + 1\n" + anchor)
    mln_src = sources[mln].replace(anchor, seeded, 1)
    alone = lint_sources({mln: mln_src})
    assert not any(f.rule_id == "G016" and f.line and "healthy"
                   in f.message for f in alone.findings), \
        "per-file lint should NOT resolve the cross-module summary"
    sources[mln] = mln_src
    r = lint_sources(sources)
    g16 = [f for f in r.findings if f.rule_id == "G016"
           and f.path == mln and "step_all_finite" in f.message]
    assert g16, [f.format() for f in r.findings
                 if f.rule_id == "G016"]


def test_g017_guards_the_real_traced_helper_against_shape_branch():
    """Seeded regression on the LIVE tree: a batch.shape[0]-keyed branch
    planted in the LSTM helper's scan builder. helpers.py alone does not
    know `scan` is traced (it is reached from the recurrent layer's
    traced forward in another file) — only the package closure flags
    it."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    hp = os.path.join(REPO, "deeplearning4j_tpu", "nn", "helpers.py")
    anchor = "        b, t, _ = x.shape"
    assert anchor in sources[hp]
    seeded = anchor + ("\n        if b > 64:\n"
                       "            zx_block = 2 * n_out\n")
    hp_src = sources[hp].replace(anchor, seeded, 1)
    alone = lint_sources({hp: hp_src})
    assert [f for f in alone.findings if f.rule_id == "G017"] == [], \
        [f.format() for f in alone.findings]
    sources[hp] = hp_src
    r = lint_sources(sources)
    g17 = [f for f in r.findings if f.rule_id == "G017"
           and f.path == hp and "'b'" in f.message]
    assert g17, [f.format() for f in r.findings
                 if f.rule_id == "G017"]


def test_g017_tbptt_window_loop_fixture_pair():
    """ISSUE 10 contract: a HOST ``range(n_windows)`` window loop with
    sized shapes inside a traced step builder fires G017; the blessed
    scan-of-scans twin — window plan derived host-side beside the
    blessed ``_fused_signature``, inner ``lax.scan`` over the reshaped
    time axis — lints clean."""
    r = lint_file(os.path.join(FIXDIR, "g017_tbptt_bad.py"))
    g17 = [f for f in r.findings if f.rule_id == "G017"]
    assert len(g17) == 1, [f.format() for f in r.findings]
    assert "range()" in g17[0].message
    good = lint_file(os.path.join(FIXDIR, "g017_tbptt_good.py"))
    assert good.findings == [], [f.format() for f in good.findings]


def test_traced_closure_follows_step_builder_alias():
    """Linter fix regression (ISSUE 10): a scan callee selected through a
    simple alias — ``step_body = body if plan is None else tbptt_body`` —
    must put BOTH candidates in the traced closure. Before the
    ``fn_aliases`` hop, the select-a-step-builder idiom silently dropped
    every scan body from traced/hot analysis (no G017/G016/G004/G009
    coverage inside the fused step)."""
    r = check("""
        import jax

        def build(plan):
            def body(carry, x):
                return carry + x.sum(), None

            def tbptt_body(carry, x):
                for w in range(x.shape[1] // 10):   # G017 when traced
                    carry = carry * 2
                return carry, None

            step_body = body if plan is None else tbptt_body

            def fused(carry, xs):
                out, _ = jax.lax.scan(step_body, carry, xs)
                return out

            return jax.jit(fused, donate_argnums=0)
    """)
    g17 = [f for f in r.findings if f.rule_id == "G017"]
    assert len(g17) == 1, [f.format() for f in r.findings]
    assert "range()" in g17[0].message


def test_g017_guards_the_real_fused_builder_against_window_loop():
    """Seeded regression on the LIVE tree: the pre-ISSUE-10 host window
    loop (``range`` over the sized windows-per-example count) planted
    back inside ``_build_fused_train_step``'s traced tBPTT body must
    still fire G017 — the lint keeps the scan-of-scans discipline from
    regressing to per-shape retraces."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    mln = os.path.join(REPO, "deeplearning4j_tpu", "models",
                       "multi_layer_network.py")
    anchor = ("                slice_y = y.ndim == 3   "
              "# per-timestep labels window-slice")
    assert anchor in sources[mln]
    seeded = anchor + (
        "\n                n_windows = x.shape[1] // seg\n"
        "                for w in range(n_windows):\n"
        "                    iteration = iteration + 0\n")
    sources[mln] = sources[mln].replace(anchor, seeded, 1)
    r = lint_sources(sources)
    g17 = [f for f in r.findings if f.rule_id == "G017"
           and f.path == mln and "range()" in f.message]
    assert g17, [f.format() for f in r.findings
                 if f.rule_id == "G017"]


def test_g018_guards_the_real_tensor_parallel_spec_rank():
    """Seeded regression on the LIVE tree: a wrong-rank P() threaded
    through a parallel_wrapper helper into tensor_parallel's bias
    placement — rank 2 spec on the rank-1 b1. The spec summary crosses
    the module boundary; per-file lint cannot see it."""
    from tools.graftlint import lint_sources
    sources = _package_sources()
    pw = os.path.join(REPO, "deeplearning4j_tpu", "parallel",
                      "parallel_wrapper.py")
    tp = os.path.join(REPO, "deeplearning4j_tpu", "parallel",
                      "tensor_parallel.py")
    sources[pw] += textwrap.dedent("""

        from jax.sharding import PartitionSpec as P

        def _seeded_bias_spec(ax):
            return P(ax, None)
    """)
    anchor = ("        self.params = place_tree(self.mesh, host, "
              "self.param_specs())")
    assert anchor in sources[tp]
    seeded = (
        "        from deeplearning4j_tpu.parallel.parallel_wrapper "
        "import _seeded_bias_spec\n"
        "        b1 = jnp.zeros((hidden,))\n"
        "        b1 = jax.device_put(b1, NamedSharding(\n"
        "            mesh, _seeded_bias_spec(\"model\")))\n" + anchor)
    tp_src = sources[tp].replace(anchor, seeded, 1)
    alone = lint_sources({tp: tp_src})
    assert [f for f in alone.findings if f.rule_id == "G018"] == [], \
        [f.format() for f in alone.findings]
    sources[tp] = tp_src
    r = lint_sources(sources)
    g18 = [f for f in r.findings if f.rule_id == "G018"
           and f.path == tp and "rank-2" in f.message]
    assert g18, [f.format() for f in r.findings
                 if f.rule_id == "G018"]


def test_dataflow_fixpoint_is_shared_across_rules(monkeypatch):
    """ISSUE 8 satellite: ONE dataflow fixpoint per lint run — the three
    rule packs (and every file) read the same cached facts, the same
    budget contract as the parsed-AST/symbol pass."""
    import tools.graftlint.dataflow as dfmod
    built = []
    orig = dfmod._Dataflow

    class Counting(orig):
        def __init__(self, pkg):
            built.append(1)
            orig.__init__(self, pkg)

    monkeypatch.setattr(dfmod, "_Dataflow", Counting)
    r = lint_sources({
        "pkg/a.py": "import jax.numpy as jnp\n\n"
                    "def f(x):\n    return jnp.sum(x)\n",
        "pkg/b.py": "from pkg.a import f\n\n"
                    "class Net:\n"
                    "    def fit_batch(self, x):\n"
                    "        s = self._jit_train[0](x)\n"
                    "        return s\n",
    })
    assert built == [1], f"dataflow built {len(built)} times"


# ---- lint-ci: ratchet + SARIF artifact in one run -------------------------


def test_sarif_out_composes_with_ratchet(tmp_path):
    """make lint-ci's contract: one invocation gates under the ratchet
    AND writes the SARIF artifact."""
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nX = os.environ.get('DL4J_TPU_X')\n")
    baseline = tmp_path / "baseline.json"
    sarif = tmp_path / "lint.sarif"
    _cli([str(bad), "--update-baseline", "--baseline", str(baseline)])
    p = _cli([str(bad), "--ratchet", "--baseline", str(baseline),
              "--sarif-out", str(sarif)])
    assert p.returncode == 1          # findings still fail the gate
    assert "ratchet" not in p.stderr  # ... but not as a ratchet breach
    assert "SARIF log written" in p.stderr
    doc = json.loads(sarif.read_text())
    assert doc["version"] == "2.1.0"
    assert [res["ruleId"] for res in doc["runs"][0]["results"]] == ["G003"]


def test_sarif_round_trips_through_changed_lane_from_subdir(git_repo):
    """ISSUE 8 satellite: the --changed fast lane, run from a
    SUBDIRECTORY, writes a SARIF artifact whose locations resolve back
    to the dirty file — the artifact a pre-commit hook can upload."""
    (git_repo / "pkg" / "mod.py").write_text(
        "import os\nX = os.environ.get('DL4J_TPU_X')\n")
    p = _cli_in(git_repo / "pkg",
                ["pkg", "--changed", "--sarif-out", "lint.sarif"])
    assert p.returncode == 1, p.stdout + p.stderr
    sarif = git_repo / "pkg" / "lint.sarif"
    assert sarif.exists()
    doc = json.loads(sarif.read_text())
    (res,) = doc["runs"][0]["results"]
    assert res["ruleId"] == "G003"
    uri = res["locations"][0]["physicalLocation"]["artifactLocation"]["uri"]
    region = res["locations"][0]["physicalLocation"]["region"]
    # round trip: the recorded location points at the real dirty file,
    # and the flagged line is the env read — resolvable from anywhere
    assert os.path.isabs(uri) and os.path.exists(uri)
    assert os.path.samefile(uri, str(git_repo / "pkg" / "mod.py"))
    with open(uri, encoding="utf-8") as fh:
        line = fh.read().splitlines()[region["startLine"] - 1]
    assert "DL4J_TPU_X" in line


def test_examples_directory_is_lint_clean():
    """ISSUE 8 satellite: examples/ joined the lint scope (make lint) —
    linted TOGETHER with the package so the cross-module closures span
    the example entry points too."""
    r = lint_live([os.path.join(REPO, "examples")])
    assert r.findings == [], [f.format() for f in r.findings]


def test_g016_while_condition_sees_loop_carried_taint():
    """Review regression: taint acquired INSIDE a while body must reach
    the loop's own truth test — `while not done:` with `done = loss` is
    the convergence-loop sync the pack exists for."""
    r = check("""
        class Net:
            def fit_batch(self, x):
                sig = self._train_signature(x)
                done = False
                while not done:                 # re-tested per iteration
                    loss = self._jit_train[sig](x)
                    done = loss
                return loss
    """)
    g16 = [f for f in r.findings if f.rule_id == "G016"]
    assert len(g16) == 1, [f.format() for f in r.findings]
    assert "truth test" in g16[0].message and "'done'" in g16[0].message


def test_changed_with_no_dirty_files_writes_empty_sarif(git_repo):
    """Review regression: a CI annotation step uploads whatever sits at
    the artifact path — a clean --changed run must overwrite a STALE
    lint.sarif with an empty run, not leave the previous findings
    behind."""
    stale = git_repo / "lint.sarif"
    stale.write_text(json.dumps({"runs": [{"results": [{"ruleId":
                                                        "G003"}]}]}))
    p = _cli_in(git_repo, ["pkg", "--changed", "--sarif-out",
                           "lint.sarif"])
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.loads(stale.read_text())
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["results"] == []


def test_summary_transform_beats_argument_kind():
    """Review regression: a helper that TRANSFORMS its argument to host
    metadata (`return x.shape[0]`) keeps its transform kind at every
    call site — a device argument does not turn the result into a
    device value (G016 false positive), and in traced code the
    helper-routed shape still steers G017 (false negative twin)."""
    helper = """
        def batch_size(x):
            return x.shape[0]
    """
    r = check(helper + """
        class Net:
            def fit_batch(self, x):
                sig = self._train_signature(x)
                loss = self._jit_train[sig](x)
                n = batch_size(loss)
                if n > 8:                  # host shape metadata: fine
                    self.big = True
                return loss
    """)
    assert [f for f in r.findings if f.rule_id == "G016"] == [], \
        [f.format() for f in r.findings]
    r = check(helper + """
        import jax

        def step(w, x):
            if batch_size(x) > 64:         # helper-routed shape branch
                w = w + 1
            return w

        train = jax.jit(step)
    """)
    g17 = [f for f in r.findings if f.rule_id == "G017"]
    assert len(g17) == 1, [f.format() for f in r.findings]
    assert "batch_size" in g17[0].message


def test_g004_keyword_form_registry_read_is_recognized():
    """Review regression: env_str(name="...") is the same read as
    env_str("...") — the trace-time allowance (and the fast lane's
    never-false-positive presumption) must see the keyword form too."""
    pkg = _g004_pkg(trace_time=True)
    pkg["pkg/deeplearning4j_tpu/models/transformer.py"] = \
        pkg["pkg/deeplearning4j_tpu/models/transformer.py"].replace(
            'env_str("DL4J_TPU_LM_ATTN")', 'env_str(name="DL4J_TPU_LM_ATTN")')
    r = lint_sources(pkg)
    assert [f for f in r.findings if f.rule_id == "G004"] == [], \
        [f.format() for f in r.findings]
    # file-scoped (no registry in set): keyword form is presumed too
    r = check(_G004_READER.replace('env_str("DL4J_TPU_LM_ATTN")',
                                   'env_str(name="DL4J_TPU_LM_ATTN")'))
    assert [f for f in r.findings if f.rule_id == "G004"] == [], \
        [f.format() for f in r.findings]


def test_g007_and_g018_share_one_spec_ctor_vocabulary():
    """Review regression: a module's own unrelated helper named P() must
    not be treated as a PartitionSpec constructor by the dataflow layer
    when G007 would not — the two layers share spec_ctor_names()."""
    r = check("""
        from jax.sharding import Mesh, NamedSharding

        def P(rows, cols):
            return rows * cols              # NOT a PartitionSpec

        def build(devices):
            mesh = Mesh(devices, ("data",))
            n = P("modle", None)            # no spec payload, no G018
            return mesh, n
    """)
    assert [f for f in r.findings if f.rule_id in ("G007", "G018")] == \
        [], [f.format() for f in r.findings]


def test_g018_arity_accepts_defaulted_params():
    """Review regression: a wrapped step with defaulted params accepts
    any arity in [required, total] — `step(params, x, y=None)` wrapped
    with 2 in_specs is a valid shard_map, not a finding."""
    r = check("""
        from jax.sharding import Mesh, PartitionSpec as P

        def step(params, x, y=None):
            return params, x

        def wrap(mesh):
            from jax import shard_map
            return shard_map(step, mesh=mesh,
                             in_specs=(P(), P("data")),
                             out_specs=(P(), P()))

        def build(devices):
            return Mesh(devices, ("data",))
    """)
    assert [f for f in r.findings if f.rule_id == "G018"] == [], \
        [f.format() for f in r.findings]


def test_declare_rejects_positional_trace_time():
    """Review regression: trace_time is keyword-only — G004 collects
    the declarations by scanning for the keyword, so a positional True
    would be invisible to the linter; _declare must refuse it."""
    import pytest as _pytest
    from deeplearning4j_tpu import config as _cfg
    with _pytest.raises(TypeError):
        _cfg._declare("DL4J_TPU_TEST_POSITIONAL", "str", "x", "doc", True)
    assert "DL4J_TPU_TEST_POSITIONAL" not in _cfg.KNOBS


def test_changed_pointer_discloses_g004():
    """The fast lane's miss disclosure covers G004: the trace-time
    allowance needs the registry module, which a file-scoped run may
    not include."""
    from tools.graftlint.__main__ import INTERPROCEDURAL_RULES
    assert "G004" in INTERPROCEDURAL_RULES


def test_g016_walrus_binding_is_seen():
    """Review regression: the walrus spelling of a device truth test
    binds AND syncs — the linter's verdict must not flip on a pure
    syntax change from the two-line form."""
    r = check("""
        class Net:
            def fit_batch(self, x):
                sig = self._train_signature(x)
                if (loss := self._jit_train[sig](x)) > 0:
                    self.lr *= 0.5
                msg = f"last={loss}"
                return loss
    """)
    g16 = [f for f in r.findings if f.rule_id == "G016"]
    assert len(g16) == 2, [f.format() for f in r.findings]
    msgs = " ".join(f.message for f in g16)
    assert "truth test" in msgs and "formatting" in msgs


def test_g016_match_arm_bodies_are_interpreted():
    """Review regression: match-statement arms are compound bodies like
    any If/While — a device sync inside a case body must not vanish."""
    r = check("""
        class Net:
            def fit_batch(self, x, mode):
                sig = self._train_signature(x)
                loss = self._jit_train[sig](x)
                match mode:
                    case "strict":
                        if loss > 0:
                            self.lr *= 0.5
                    case _:
                        pass
                return loss
    """)
    g16 = [f for f in r.findings if f.rule_id == "G016"]
    assert len(g16) == 1, [f.format() for f in r.findings]
    assert "truth test" in g16[0].message


def test_summary_kwonly_param_taint_maps_to_keyword():
    """Review regression: a keyword-only parameter's summary index must
    resolve to the keyword argument, never to a positional at the same
    index — `f(x, y, b=loss)` taints through b, not y."""
    r = check("""
        def pick(a, *rest, b):
            return b

        class Net:
            def fit_batch(self, x):
                sig = self._train_signature(x)
                loss = self._jit_train[sig](x)
                chosen = pick(1, 2, b=loss)
                if chosen > 0:                 # device via b=
                    self.lr *= 0.5
                safe = pick(1, 2, b=3)
                if safe > 0:                   # host via b=: fine
                    self.big = True
                return loss
    """)
    g16 = [f for f in r.findings if f.rule_id == "G016"]
    assert len(g16) == 1, [f.format() for f in r.findings]
    assert "'chosen'" in g16[0].message


def test_summary_keeps_param_link_through_accessor_helpers():
    """Review regression: subscript/attribute access inside a helper
    must not sever the param→return taint link — `def first(out):
    return out[0]` passes its caller's device kind through."""
    r = check("""
        def first(out):
            return out[0]

        def view(x):
            return x.T

        class Net:
            def fit_batch(self, x):
                sig = self._train_signature(x)
                loss = self._jit_train[sig](x)
                if first(loss) > 0:            # device via out[0]
                    self.lr *= 0.5
                msg = f"{view(loss)}"          # device via x.T
                return loss
    """)
    g16 = [f for f in r.findings if f.rule_id == "G016"]
    assert len(g16) == 2, [f.format() for f in r.findings]


def test_passthrough_helper_keeps_the_sized_bit():
    """Review regression: an identity-style helper passes an
    already-sized shape through — the traced branch on it must still
    fire G017."""
    r = check("""
        import jax

        def passthru(n):
            return n

        def step(w, x):
            b = x.shape[0]
            if passthru(b) > 64:
                w = w + 1
            return w

        train = jax.jit(step)
    """)
    g17 = [f for f in r.findings if f.rule_id == "G017"]
    assert len(g17) == 1, [f.format() for f in r.findings]


def test_raw_cache_key_reported_once_per_defect():
    """Review regression: the same raw key variable hits the check at
    its store and its load — one defect, one finding (one suppression,
    one ratchet count)."""
    r = check("""
        class Net:
            def fit_batch(self, x):
                key = (x.shape, str(x.dtype))
                if key not in self._jit_train:
                    self._jit_train[key] = self._build(x)
                return self._jit_train[key](x)
    """)
    g17 = [f for f in r.findings if f.rule_id == "G017"]
    assert len(g17) == 1, [f.format() for f in r.findings]


def test_g016_comprehension_filter_is_a_truth_test():
    """Review regression: a device value as a comprehension `if` filter
    syncs per evaluation, same as the statement form."""
    r = check("""
        class Net:
            def fit_batch(self, x, vals):
                sig = self._train_signature(x)
                loss = self._jit_train[sig](x)
                kept = [v for v in vals if loss > 0]
                return loss
    """)
    g16 = [f for f in r.findings if f.rule_id == "G016"]
    assert len(g16) == 1, [f.format() for f in r.findings]
    assert "truth test" in g16[0].message


def test_changed_with_no_dirty_files_emits_empty_sarif_stdout(git_repo):
    """Review regression: the stdout --sarif form of a clean --changed
    run must print a valid empty SARIF log, not zero bytes — a
    redirect-to-artifact CI step parses whatever this run printed."""
    p = _cli_in(git_repo, ["pkg", "--changed", "--sarif"])
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.loads(p.stdout)
    assert doc["version"] == "2.1.0"
    assert doc["runs"][0]["results"] == []


def test_g017_size_branch_in_traced_fn_fires():
    """Review regression: `.size` is a PRODUCT of dimension sizes —
    branching on it in a traced function retraces per shape exactly
    like shape[0] (only .ndim/len() are stable rank metadata)."""
    r = check("""
        import jax

        def step(w, x):
            if x.size > 1024:
                w = w + 1
            return w

        train = jax.jit(step)
    """)
    g17 = [f for f in r.findings if f.rule_id == "G017"]
    assert len(g17) == 1, [f.format() for f in r.findings]
    assert ".size" in g17[0].message


def test_g016_formatting_a_container_of_device_values_fires():
    """Review regression: formatting a host container reprs every
    element — a list of device scores syncs them all, unlike a truth
    test (`if scores:` stays a host len check)."""
    r = check("""
        class Net:
            def fit_batch(self, x):
                sig = self._train_signature(x)
                loss = self._jit_train[sig](x)
                scores = [loss]
                if scores:                       # host len check: fine
                    print(scores)                # reprs the device value
                return loss
    """)
    g16 = [f for f in r.findings if f.rule_id == "G016"]
    assert len(g16) == 1, [f.format() for f in r.findings]
    assert "formatting" in g16[0].message


def test_changed_with_no_dirty_files_emits_empty_json(git_repo):
    """Review regression: --json parity with the SARIF surfaces — a
    clean --changed run prints a valid empty JSON array, not zero
    bytes (a `| jq` consumer fails on empty input)."""
    p = _cli_in(git_repo, ["pkg", "--changed", "--json"])
    assert p.returncode == 0, p.stdout + p.stderr
    assert json.loads(p.stdout) == []
