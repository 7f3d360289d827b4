"""graftlint v4 memlint: the symbolic shape algebra, the layer-formula
mirror, the per-program footprint report (pinned to ``jax.live_arrays()``
within ±20% after REAL fits), the --mem-report CLI, the G019/G020/G021
rule pack, the inference-path hot roots, the cross-method ``self.*``
dataflow, and the one-shape-pass-per-run budget contract.

The pure-linter tests import nothing from jax (same discipline as
test_graftlint); only the footprint-accuracy class builds real models.
"""

import gc
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ZOO = os.path.join(REPO, "deeplearning4j_tpu", "models", "zoo.py")
sys.path.insert(0, REPO) if REPO not in sys.path else None

from tools.graftlint import (lint_file, lint_paths, lint_source,  # noqa: E402
                             lint_sources)
from tools.graftlint.shapes import (extract_models_from_source,  # noqa: E402
                                    infer_shapes, mem_budget, mem_report,
                                    mem_report_md, model_footprint,
                                    model_mem_report, shape_bytes)

FIXDIR = os.path.join(REPO, "tests", "fixtures", "graftlint")


def ids(result):
    return sorted({f.rule_id for f in result.findings})


def check(src, path="mod.py"):
    return lint_source(textwrap.dedent(src), path)


def _infer(src):
    import ast
    from tools.graftlint.rules import ModuleAnalysis
    tree = ast.parse(textwrap.dedent(src))
    analysis = ModuleAnalysis(tree)
    fn = analysis.functions[0]
    return infer_shapes(fn, analysis)


# ---------------------------------------------------------------------------
# the shape algebra
# ---------------------------------------------------------------------------
class TestShapeAlgebra:
    def test_zeros_literal_and_dtype(self):
        got = _infer("""
            import jax.numpy as jnp
            def f():
                a = jnp.zeros((128, 784))
                b = jnp.zeros((4, 8), dtype=jnp.bfloat16)
                c = jnp.ones(16)
        """)
        assert got["a"] == ((128, 784), None)
        assert got["b"] == ((4, 8), "bfloat16")
        assert got["c"] == ((16,), None)

    def test_reshape_swapaxes_transpose(self):
        got = _infer("""
            import jax.numpy as jnp
            def f():
                a = jnp.zeros((8, 128, 20, 77))
                b = a.reshape((8, 128, 4, 5, 77))
                c = a.swapaxes(1, 2)
                d = jnp.zeros((3, 4)).transpose()
        """)
        assert got["b"][0] == (8, 128, 4, 5, 77)
        assert got["c"][0] == (8, 20, 128, 77)
        assert got["d"][0] == (4, 3)

    def test_concatenate_and_stack(self):
        got = _infer("""
            import jax.numpy as jnp
            def f():
                a = jnp.zeros((4, 10))
                b = jnp.zeros((2, 10))
                c = jnp.concatenate([a, b], axis=0)
                d = jnp.stack([a, a, a])
        """)
        assert got["c"][0] == (6, 10)
        assert got["d"][0] == (3, 4, 10)

    def test_matmul_contraction(self):
        got = _infer("""
            import jax.numpy as jnp
            def f():
                x = jnp.zeros((128, 784))
                w = jnp.zeros((784, 300))
                h = x @ w
        """)
        assert got["h"][0] == (128, 300)

    def test_scan_carry_shape_survives(self):
        got = _infer("""
            import jax
            import jax.numpy as jnp
            def f(body):
                carry = jnp.zeros((32, 200))
                out = jax.lax.scan(body, carry, None)
        """)
        assert got["out"][0] == (32, 200)

    def test_astype_changes_dtype_not_shape(self):
        got = _infer("""
            import jax.numpy as jnp
            def f():
                a = jnp.zeros((4, 4))
                b = a.astype("bfloat16")
        """)
        assert got["b"] == ((4, 4), "bfloat16")

    def test_symbolic_dims_from_shape_unpack(self):
        # B, T = x.shape of an UNKNOWN x: later uses of B/T as dims keep
        # their own names — the report's named unknowns
        got = _infer("""
            import jax.numpy as jnp
            def f(x):
                B, T = x.shape
                pad = jnp.zeros((B, T, 77))
        """)
        assert got["pad"][0] == ("B", "T", 77)

    def test_const_dims_flow_through_enclosing_scope(self):
        got = _infer("""
            def outer():
                V, H = 64, 128
                def f():
                    import jax.numpy as jnp
                    w = jnp.zeros((V, 4 * H))
        """)
        # outer() is functions[0]; its nested f is walked separately
        import ast
        from tools.graftlint.rules import ModuleAnalysis
        tree = ast.parse(textwrap.dedent("""
            def outer():
                V, H = 64, 128
                def f():
                    import jax.numpy as jnp
                    w = jnp.zeros((V, 4 * H))
        """))
        analysis = ModuleAnalysis(tree)
        inner = [fn for fn in analysis.functions if fn.name == "f"][0]
        got = infer_shapes(inner, analysis)
        assert got["w"][0] == (64, 512)

    def test_reshape_minus_one_is_unknown_not_negative(self):
        """A reshape(-1) placeholder dim must make the bytes UNKNOWN —
        a negative byte count would silently defeat every rule's size
        threshold (a 256 MiB buffer reading as -4 KiB)."""
        assert shape_bytes((1024, -1)) is None
        got = _infer("""
            import jax.numpy as jnp
            def f():
                big = jnp.zeros((1024, 1024, 64))
                flat = big.reshape(1024, -1)
        """)
        shape, dtype = got["flat"]
        assert shape_bytes(shape, dtype) is None

    def test_shape_bytes_with_symbol_bindings(self):
        assert shape_bytes((128, 784)) == 128 * 784 * 4
        assert shape_bytes((4, 8), "bfloat16") == 64
        assert shape_bytes(("B", 10)) is None
        assert shape_bytes(("B", 10), None, {"B": 32}) == 32 * 10 * 4

    def test_mem_budget_env(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_MEM_BUDGET", raising=False)
        assert mem_budget() == 16 * 1024 ** 3
        monkeypatch.setenv("DL4J_TPU_MEM_BUDGET", "1048576")
        assert mem_budget() == 1048576
        monkeypatch.setenv("DL4J_TPU_MEM_BUDGET", "banana")
        assert mem_budget() == 16 * 1024 ** 3   # garbage: documented default


# ---------------------------------------------------------------------------
# model extraction: builder chains to ModelSpecs
# ---------------------------------------------------------------------------
MLN_SRC = """
    def small_mln():
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        return (NeuralNetConfiguration.Builder()
                .seed(7).learning_rate(0.1).updater("adam").list()
                .layer(DenseLayer(n_in=32, n_out=64, activation="relu"))
                .layer(OutputLayer(n_in=64, n_out=10, activation="softmax",
                                   loss="mcxent"))
                .build())
"""

CG_SRC = """
    def small_cg():
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
        return (NeuralNetConfiguration.Builder()
                .seed(7).learning_rate(0.1).updater("adam")
                .graph_builder()
                .add_inputs("in")
                .add_layer("d", DenseLayer(n_in=32, n_out=64,
                                           activation="relu"), "in")
                .add_layer("out", OutputLayer(n_in=64, n_out=10,
                                              activation="softmax",
                                              loss="mcxent"), "d")
                .set_outputs("out")
                .build())
"""


class TestExtraction:
    def test_mln_chain(self):
        specs, unresolved = extract_models_from_source(
            textwrap.dedent(MLN_SRC), "m.py")
        assert unresolved == []
        (s,) = specs
        # 32*64+64 + 64*10+10 = 2762
        assert (s.name, s.kind, s.n_params(), s.updater,
                s.updater_slots()) == ("small_mln", "mln", 2762, "adam", 2)

    def test_cg_fluent_chain(self):
        specs, unresolved = extract_models_from_source(
            textwrap.dedent(CG_SRC), "g.py")
        assert unresolved == []
        (s,) = specs
        assert (s.kind, s.n_params(), s.updater_slots()) == ("cg", 2762, 2)

    def test_zoo_lenet_formula_mirror(self):
        """The conv/pool arithmetic mirror, pinned against the real zoo
        builder constants: 431,080 params is LeNet-MNIST's documented
        count (20*1*5*5+20 + 50*20*5*5+50 + 500*800+500 + 10*500+10)."""
        zoo = os.path.join(REPO, "deeplearning4j_tpu", "models", "zoo.py")
        with open(zoo, encoding="utf-8") as fh:
            specs, _ = extract_models_from_source(fh.read(), zoo)
        by_name = {s.name: s for s in specs}
        assert by_name["lenet_mnist"].n_params() == 431080
        assert by_name["mlp_mnist"].n_params() == 795010

    def test_consts_override(self):
        zoo = os.path.join(REPO, "deeplearning4j_tpu", "models", "zoo.py")
        with open(zoo, encoding="utf-8") as fh:
            src = fh.read()
        specs, _ = extract_models_from_source(
            src, zoo, consts={"vocab_size": 32, "hidden": 64})
        cr = {s.name: s for s in specs}["char_rnn"]
        # GravesLSTM(32->64) + GravesLSTM(64->64) + RnnOut(64->32):
        # (32*256+64*256+256+192) + (64*256+64*256+256+192) + (64*32+32)
        assert cr.n_params() == (32 * 256 + 64 * 256 + 256 + 192) + \
            (64 * 256 + 64 * 256 + 256 + 192) + (64 * 32 + 32)

    def test_statement_style_builder_reported_unresolved(self):
        src = """
            def looped():
                from deeplearning4j_tpu import NeuralNetConfiguration
                from deeplearning4j_tpu.nn.layers import DenseLayer
                b = NeuralNetConfiguration.Builder().list()
                for i in range(3):
                    b = b.layer(DenseLayer(n_in=4, n_out=4))
                return b.build()
        """
        specs, unresolved = extract_models_from_source(
            textwrap.dedent(src), "m.py")
        assert specs == []
        assert unresolved and unresolved[0]["model"] == "looped"

    def test_cg_control_flow_reported_unresolved(self):
        zoo = os.path.join(REPO, "deeplearning4j_tpu", "models", "zoo.py")
        with open(zoo, encoding="utf-8") as fh:
            _, unresolved = extract_models_from_source(fh.read(), zoo)
        names = {u["model"] for u in unresolved}
        # resnet50/googlenet build topology in loops: the absence is
        # REPORTED, never a silent "fits"
        assert "resnet50" in names and "googlenet" in names

    def test_keyword_or_odd_arity_input_type_degrades(self):
        """A keyword-spelled or wrong-arity InputType call must degrade
        to an unresolved entry, never crash the report (the extractor's
        'never guessed, never silent' contract)."""
        src = """
            def kw_input():
                from deeplearning4j_tpu import NeuralNetConfiguration
                from deeplearning4j_tpu.nn.conf.inputs import InputType
                from deeplearning4j_tpu.nn.layers import ConvolutionLayer
                return (NeuralNetConfiguration.Builder().list()
                        .layer(ConvolutionLayer(n_out=8, kernel_size=3))
                        .set_input_type(InputType.convolutional(28, 28))
                        .build())
        """
        specs, unresolved = extract_models_from_source(
            textwrap.dedent(src), "m.py")
        assert specs == []
        assert unresolved and unresolved[0]["model"] == "kw_input"

    def test_short_add_vertex_degrades(self):
        src = """
            def short_vertex():
                from deeplearning4j_tpu import NeuralNetConfiguration
                from deeplearning4j_tpu.nn.layers import DenseLayer
                return (NeuralNetConfiguration.Builder().graph_builder()
                        .add_inputs("in")
                        .add_layer("d", DenseLayer(n_in=4, n_out=4), "in")
                        .add_vertex("v")
                        .build())
        """
        specs, unresolved = extract_models_from_source(
            textwrap.dedent(src), "m.py")
        assert specs == []
        assert unresolved and unresolved[0]["model"] == "short_vertex"

    def test_transformer_config(self):
        src = """
            def lm():
                from deeplearning4j_tpu.models.transformer import (
                    TransformerConfig, TransformerLM)
                return TransformerLM(TransformerConfig(
                    vocab_size=2048, max_len=128, d_model=128, n_heads=4,
                    n_layers=2, d_ff=512))
        """
        specs, unresolved = extract_models_from_source(
            textwrap.dedent(src), "m.py")
        assert unresolved == []
        (s,) = specs
        assert s.kind == "transformer_lm"
        assert s.n_params() > 2048 * 128   # embeddings alone


# ---------------------------------------------------------------------------
# the footprint report
# ---------------------------------------------------------------------------
class TestFootprint:
    def _spec(self, src=MLN_SRC):
        specs, _ = extract_models_from_source(textwrap.dedent(src), "m.py")
        return specs[0]

    def test_train_row_counts_each_tree_once(self):
        rows = model_footprint(self._spec(), batch=16, steps=4)
        train = rows[0]["bytes"]
        # donated buffers counted ONCE: total is exactly the sum of the
        # component trees, no fresh-output double count
        assert train["total"] == (train["params"] + train["grads"] +
                                  train["updater"] + train["inputs"])
        assert train["params"] == 2762 * 4
        assert train["updater"] == 2 * 2762 * 4          # adam m+v

    def test_fused_row_scales_inputs_by_k(self):
        rows = model_footprint(self._spec(), batch=16, steps=4)
        train, fused = rows[0]["bytes"], rows[1]["bytes"]
        # [K,B,...] stacked features/labels + the [K,B] ew plane
        assert fused["inputs"] == 4 * train["inputs"] + 4 * 16 * 4
        assert fused["params"] == train["params"]

    def test_output_row_has_no_grads_or_updater(self):
        rows = model_footprint(self._spec(), batch=16, steps=4)
        out = [r for r in rows if r["program"].startswith("output")][0]
        assert out["bytes"]["grads"] == 0 and out["bytes"]["updater"] == 0

    def test_transformer_kv_bytes(self):
        src = """
            def lm():
                from deeplearning4j_tpu.models.transformer import (
                    TransformerConfig)
                return TransformerConfig(vocab_size=2048, max_len=128,
                                         d_model=128, n_heads=4, n_layers=2)
        """
        rows = model_footprint(self._spec(src), batch=8, seq=128)
        decode = [r for r in rows if r["program"].startswith("decode")][0]
        # 2 (k+v) * L * B * kv_heads * total * head_dim * 4B
        assert decode["bytes"]["kv_cache"] == 2 * 2 * 8 * 4 * 128 * 32 * 4

    def test_optax_updater_slots(self):
        src = MLN_SRC.replace('.updater("adam")', '.updater("optax:adamw")')
        rows = model_footprint(self._spec(src), batch=16, steps=4)
        # the optax adapter's adamw carries m+v like built-in adam
        assert rows[0]["bytes"]["updater"] == 2 * 2762 * 4

    def test_unknown_updater_makes_total_unknown(self):
        """An updater rule outside the slot table must make the TOTAL
        unknown — a concrete number silently omitting the moment trees
        would read as 'fits'."""
        src = MLN_SRC.replace('.updater("adam")', '.updater("optax:muon")')
        rows = model_footprint(self._spec(src), batch=16, steps=4)
        train, fused = rows[0]["bytes"], rows[1]["bytes"]
        assert train["updater"] is None and train["total"] is None
        assert fused["total"] is None
        assert not rows[0]["over_budget"]
        assert rows[0]["total_human"] == "?"

    def test_over_budget_flag(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_MEM_BUDGET", "10000")
        rows = model_footprint(self._spec(), batch=16, steps=4)
        assert all(r["over_budget"] for r in rows)

    def test_lower_bound_total_never_asserts_fits(self):
        """An RNN model with no static T leaves the inputs component
        unresolved: the total is a lower bound, so over_budget must be
        None (unknown) — never a hard False — and the markdown carries
        a >= marker."""
        zoo = os.path.join(REPO, "deeplearning4j_tpu", "models", "zoo.py")
        with open(zoo, encoding="utf-8") as fh:
            specs, _ = extract_models_from_source(fh.read(), zoo)
        cr = {s.name: s for s in specs}["char_rnn"]
        rows = model_footprint(cr, batch=8, steps=2)      # no seq
        train = rows[0]
        assert train["bytes"]["inputs"] is None
        assert train["bytes"]["total"] is not None        # lower bound
        assert train["over_budget"] is None
        md = mem_report_md({"assumptions": {
            "batch": 8, "steps": 2, "seq": None,
            "param_dtype": "float32", "budget_bytes": 1 << 34},
            "models": rows, "unresolved": []})
        assert "≥ " + train["total_human"] in md

    def test_mem_report_carries_unresolved(self):
        report = mem_report(sources={
            "a.py": textwrap.dedent(MLN_SRC),
            "b.py": textwrap.dedent("""
                def looped():
                    from deeplearning4j_tpu import NeuralNetConfiguration
                    from deeplearning4j_tpu.nn.layers import DenseLayer
                    b = NeuralNetConfiguration.Builder().list()
                    for i in range(3):
                        b = b.layer(DenseLayer(n_in=4, n_out=4))
                    return b.build()
            """)})
        assert {r["model"] for r in report["models"]} == {"small_mln"}
        assert report["unresolved"][0]["model"] == "looped"
        md = mem_report_md(report)
        assert "| small_mln | train[B=128]" in md
        assert "unresolved" in md and "looped" in md

    def test_model_mem_report_unknown_name(self):
        got = model_mem_report(ZOO, "nonesuch", batch=8, steps=4)
        assert got["rows"] == [] and "nonesuch" in got["unresolved"]


# ---------------------------------------------------------------------------
# the --mem-report CLI surface
# ---------------------------------------------------------------------------
def _cli(args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "tools.graftlint"] + args,
                          capture_output=True, text=True, cwd=cwd,
                          timeout=300)


class TestMemReportCli:
    def test_markdown_table(self, tmp_path):
        f = tmp_path / "m.py"
        f.write_text(textwrap.dedent(MLN_SRC))
        p = _cli([str(f), "--mem-report"])
        assert p.returncode == 0, p.stderr
        assert "| small_mln | train[B=128]" in p.stdout
        assert "Static HBM footprint" in p.stdout

    def test_json_payload(self, tmp_path):
        f = tmp_path / "m.py"
        f.write_text(textwrap.dedent(MLN_SRC))
        p = _cli([str(f), "--mem-report", "--json", "--mem-batch", "16",
                  "--mem-steps", "4"])
        got = json.loads(p.stdout)
        assert got["assumptions"]["batch"] == 16
        row = got["models"][0]
        assert row["n_params"] == 2762
        assert row["bytes"]["total"] > 0

    def test_does_not_compose_with_lint_modes(self, tmp_path):
        f = tmp_path / "m.py"
        f.write_text("x = 1\n")
        for extra in (["--ratchet"], ["--changed"], ["--update-baseline"]):
            p = _cli([str(f), "--mem-report"] + extra)
            assert p.returncode == 2, (extra, p.stderr)


# ---------------------------------------------------------------------------
# G019 donation-miss
# ---------------------------------------------------------------------------
class TestG019:
    def test_fixture_pair(self):
        bad = lint_file(os.path.join(FIXDIR, "g019_bad.py"))
        assert ids(bad) == ["G019"], [f.format() for f in bad.findings]
        assert "256.0 MiB" in bad.findings[0].message
        good = lint_file(os.path.join(FIXDIR, "g019_good.py"))
        assert good.findings == [], [f.format() for f in good.findings]

    def test_state_named_carry_fires_unsized(self):
        r = check("""
            import jax

            def _body(p, x):
                return p

            step = jax.jit(_body)

            def run(params, xs):
                for x in xs:
                    params = step(params, x)
                return params
        """)
        assert ids(r) == ["G019"]
        assert "statically unsized model state" in r.findings[0].message

    def test_small_buffer_is_noise_exempt(self):
        r = check("""
            import jax
            import jax.numpy as jnp

            norm = jax.jit(lambda t: t / 2)

            def run(xs):
                acc = jnp.zeros((16, 16))
                for x in xs:
                    acc = norm(acc)
                return acc
        """)
        assert r.findings == [], [f.format() for f in r.findings]

    def test_aliased_buffer_stays_quiet(self):
        """An alias keeps the old buffer ALIVE past the rebind —
        following the finding's advice (add donate_argnums) would make
        `buf + snapshot` a donated-buffer runtime error, so the rule
        must stay quiet."""
        r = check("""
            import jax
            import jax.numpy as jnp

            refresh = jax.jit(lambda t: t * 2)

            def serve_loop(xs):
                buf = jnp.zeros((1024, 1024, 64))
                snapshot = buf
                for x in xs:
                    buf = refresh(buf)
                return buf + snapshot
        """)
        assert r.findings == [], [f.format() for f in r.findings]

    def test_ambiguous_key_never_guesses(self):
        # self._jit holds BOTH donating and non-donating programs: the
        # key is dropped, no finding either way
        r = check("""
            import jax

            class Net:
                def _arm(self, which):
                    if which:
                        self._prog = jax.jit(lambda p: p,
                                             donate_argnums=(0,))
                    else:
                        self._prog = jax.jit(lambda p: p)

                def run(self, params, xs):
                    for x in xs:
                        params = self._prog(params, x)
                    return params
        """)
        assert "G019" not in ids(r), [f.format() for f in r.findings]

    def test_factory_resolved_donation(self):
        # the jit hides behind a builder: `self._refresh =
        # self._build()` where _build returns a DONATING jit — quiet
        r = check("""
            import jax

            class Net:
                def _build(self):
                    return jax.jit(lambda p: p, donate_argnums=(0,))

                def arm(self):
                    self._refresh = self._build()

                def run(self, params, xs):
                    for x in xs:
                        params = self._refresh(params)
                    return params
        """)
        assert "G019" not in ids(r), [f.format() for f in r.findings]

    def test_live_tree_seeded_refresh_without_donation(self):
        """Seeded on the LIVE tree: a params-refresh dispatch through a
        donation-less jit planted in MultiLayerNetwork — the exact HBM
        double-residency G019 exists to catch."""
        mln = os.path.join(REPO, "deeplearning4j_tpu", "models",
                           "multi_layer_network.py")
        with open(mln, encoding="utf-8") as fh:
            src = fh.read()
        anchor = "    def output(self, x, train=False, fmask=None):"
        assert anchor in src
        seeded = ("    def _seeded_refresh(self):\n"
                  "        refresh = jax.jit(lambda t: t)\n"
                  "        params = self.params_list\n"
                  "        params = refresh(params)\n"
                  "        return params\n\n" + anchor)
        r = lint_sources({mln: src.replace(anchor, seeded, 1)})
        g19 = [f for f in r.findings if f.rule_id == "G019"
               and "params" in f.message]
        assert g19, [f.format() for f in r.findings]


# ---------------------------------------------------------------------------
# G020 replicated-state-budget (the static ZeRO-2/3 ratchet)
# ---------------------------------------------------------------------------
class TestG020:
    def test_over_budget_dp_fixture_vs_zero1_twin(self, monkeypatch):
        """The acceptance pair: replicated updater state over the budget
        under a DP mesh fires; the ZeRO-1-sharded twin is quiet."""
        monkeypatch.setenv("DL4J_TPU_MEM_BUDGET", str(1 << 20))
        bad = lint_file(os.path.join(FIXDIR, "g020_bad.py"))
        assert ids(bad) == ["G020"], [f.format() for f in bad.findings]
        assert "exceeds the 1.0 MiB budget" in bad.findings[0].message
        good = lint_file(os.path.join(FIXDIR, "g020_good.py"))
        assert good.findings == [], [f.format() for f in good.findings]

    def test_under_budget_is_quiet(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_MEM_BUDGET", str(1 << 30))
        r = lint_file(os.path.join(FIXDIR, "g020_bad.py"))
        assert r.findings == [], [f.format() for f in r.findings]

    def test_state_named_tree_fires_without_size(self):
        r = check("""
            import jax
            import numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P

            def place(mesh, net):
                rep = NamedSharding(mesh, P())
                put = lambda t: jax.device_put(np.asarray(t), rep)
                net.updater_states = jax.tree.map(put, net.updater_states)
        """)
        assert "G020" in ids(r), [f.format() for f in r.findings]
        g20 = [f for f in r.findings if f.rule_id == "G020"][0]
        assert "statically-unbounded model state" in g20.message

    def test_live_tree_seeded_unsharded_updater(self):
        """Seeded on the LIVE tree: bypassing the sharding core with a
        hand-rolled replicated putter over ParallelWrapper's updater
        state — the exact regression G020 guards now that the ZeRO
        placements live in sharding_core and the five pre-ZeRO-2/3
        suppressions are gone."""
        pw = os.path.join(REPO, "deeplearning4j_tpu", "parallel",
                          "parallel_wrapper.py")
        with open(pw, encoding="utf-8") as fh:
            src = fh.read()
        anchor = ("        net.updater_states = "
                  "self.core.place_updater(net.updater_states)")
        assert anchor in src
        seeded = (
            "        from jax.sharding import NamedSharding, "
            "PartitionSpec as P\n"
            "        rep = NamedSharding(self.mesh, P())\n"
            "        put = lambda t: jax.device_put(np.asarray(t), rep)\n"
            "        net.updater_states = jax.tree.map("
            "put, net.updater_states)")
        r = lint_sources({pw: src.replace(anchor, seeded, 1)})
        g20 = [f for f in r.findings if f.rule_id == "G020"
               and "updater_states" in f.message]
        assert g20, [f.format() for f in r.findings]

    def test_live_tree_sharded_path_is_quiet(self):
        """The ZeRO-2/3 acceptance ratchet: with placement unified in
        sharding_core, the live parallel/ + models/ tree holds ZERO G020
        findings AND zero G020 suppressions — the five pre-ZeRO-2/3
        suppressions (parallel_wrapper x2, sp_transformer,
        models/transformer x2) are gone for good, and a new hand-rolled
        replicated state placement fails this gate."""
        paths = [os.path.join(REPO, "deeplearning4j_tpu", "parallel"),
                 os.path.join(REPO, "deeplearning4j_tpu", "models")]
        r = lint_paths(paths, rule_ids=["G020"])
        assert [f.format() for f in r.findings] == []
        assert sum(1 for s in r.suppressed if s.rule_id == "G020") == 0, \
            [s.format() for s in r.suppressed]


# ---------------------------------------------------------------------------
# G021 unbounded-device-cache (serving-tier groundwork)
# ---------------------------------------------------------------------------
class TestG021:
    def test_fixture_pair(self):
        bad = lint_file(os.path.join(FIXDIR, "g021_bad.py"))
        assert ids(bad) == ["G021"], [f.format() for f in bad.findings]
        msgs = "\n".join(f.message for f in bad.findings)
        assert "_req_cache" in msgs and "PER CALL" in msgs
        good = lint_file(os.path.join(FIXDIR, "g021_good.py"))
        assert good.findings == [], [f.format() for f in good.findings]

    def test_param_keyed_store_fires(self):
        r = check("""
            import jax.numpy as jnp

            class Server:
                def serve(self, n_new):
                    self._cache[n_new] = jnp.zeros((128, 1024))
                    return self._cache[n_new]
        """)
        assert "G021" in ids(r)

    def test_hot_list_growth_fires(self):
        r = check("""
            class Net:
                def fit_batch(self, x):
                    out = self._jit_train[("sig",)](x)
                    self._history.append(out)
                    return out
        """)
        g21 = [f for f in r.findings if f.rule_id == "G021"]
        assert g21 and "_history" in g21[0].message

    def test_clear_anywhere_in_class_bounds_growth(self):
        r = check("""
            class Net:
                def fit_batch(self, x):
                    out = self._jit_train[("sig",)](x)
                    self._history.append(out)
                    return out

                def reset(self):
                    self._history.clear()
        """)
        assert "G021" not in ids(r), [f.format() for f in r.findings]

    def test_reset_by_reassignment_bounds_growth(self):
        """`self._cache = {}` in a non-__init__ method evicts everything
        — the common reset idiom must count as bounding, or every class
        with a reset() gets a finding it can only falsely suppress."""
        r = check("""
            import jax.numpy as jnp

            class Server:
                def serve(self, n_new):
                    self._cache[n_new] = jnp.zeros((128, 1024))
                    return self._cache[n_new]

                def reset(self):
                    self._cache = {}
        """)
        assert "G021" not in ids(r), [f.format() for f in r.findings]

    def test_init_time_store_is_exempt(self):
        r = check("""
            import jax.numpy as jnp

            class Net:
                def __init__(self, shapes):
                    for s in shapes:
                        self._slots[s] = jnp.zeros(s)
        """)
        assert "G021" not in ids(r), [f.format() for f in r.findings]

    def test_live_tree_seeded_shape_keyed_output_cache(self):
        """Seeded on the LIVE tree: a raw-shape-keyed device-output
        cache planted in MultiLayerNetwork.output — every novel request
        shape would pin its activations forever."""
        mln = os.path.join(REPO, "deeplearning4j_tpu", "models",
                           "multi_layer_network.py")
        with open(mln, encoding="utf-8") as fh:
            src = fh.read()
        anchor = ("        # graftlint: disable=G001 -- output()'s "
                  "contract IS the eval seam")
        assert anchor in src
        seeded = ("        self._seen_outputs[(\"out\", x.shape)] = "
                  "self._jit_output[sig](self.params_list, "
                  "self.states_list, x, fmask)\n" + anchor)
        r = lint_sources({mln: src.replace(anchor, seeded, 1)})
        g21 = [f for f in r.findings if f.rule_id == "G021"
               and "_seen_outputs" in f.message]
        assert g21, [f.format() for f in r.findings
                     if f.rule_id == "G021"]


# ---------------------------------------------------------------------------
# inference-path hot roots (satellite: the serving tier inherits the
# sync-free discipline before it exists)
# ---------------------------------------------------------------------------
class TestInferenceHotRoots:
    def test_output_is_a_hot_root(self):
        r = check("""
            class Net:
                def output(self, x):
                    sig = self._output_signature(x)
                    out = self._jit_output[sig](x)
                    return out.item()
        """)
        assert "G001" in ids(r), [f.format() for f in r.findings]

    def test_output_signature_user_is_a_hot_root(self):
        r = check("""
            class Net:
                def predict_scores(self, x):
                    sig = self._output_signature(x)
                    out = self._dispatch(sig, x)
                    return float(out)
        """)
        assert "G001" in ids(r), [f.format() for f in r.findings]

    def test_generate_scalar_default_params_are_host_seams(self):
        # float(temperature)/int(top_k) parse config scalars, not device
        # values: the inference API's argument-validation idiom stays
        # quiet while real syncs (item()) still fire
        r = check("""
            class LM:
                def generate(self, prompt, n_new, *, temperature=1.0,
                             top_k=None):
                    t = float(temperature)
                    k = top_k and int(top_k)
                    out = self._jit_output[(n_new, t, k)](prompt)
                    return out
        """)
        assert r.findings == [], [f.format() for f in r.findings]

    def test_cold_helper_stays_cold(self):
        r = check("""
            class Net:
                def summarize(self, scores):
                    return float(scores)   # not reachable from any root
        """)
        assert r.findings == [], [f.format() for f in r.findings]


# ---------------------------------------------------------------------------
# cross-method self.* flows (satellite: the v3 table's false negative)
# ---------------------------------------------------------------------------
class TestCrossMethodSelfAttr:
    def test_device_attr_written_in_sibling_fires_g016(self):
        r = check("""
            class Net:
                def fit_batch(self, x):
                    loss = self._jit_train[("sig",)](x)
                    self._last_loss = loss
                    return loss

                def fit_fused(self, xs):
                    if self._last_loss > 2.0:     # device truth test
                        return None
                    return self._jit_train[("sig",)](xs)
        """)
        g16 = [f for f in r.findings if f.rule_id == "G016"]
        assert g16, [f.format() for f in r.findings]
        assert "sibling method" in g16[0].message

    def test_host_attr_stays_quiet(self):
        r = check("""
            class Net:
                def fit_batch(self, x):
                    self._step = self._step + 1
                    out = self._jit_train[("sig",)](x)
                    if self._step > 10:
                        return out
                    return out
        """)
        assert r.findings == [], [f.format() for f in r.findings]

    def test_live_tree_seeded_cross_method_flow(self):
        """Seeded on the LIVE tree, the lint_paths-vs-lint_file pair:
        the device all-finite predicate written to ``self._last_finite``
        in fit_batch and truth-tested in output(). Per-file lint cannot
        know step_all_finite returns a device value (its summary lives
        in models/_device_state.py) — only the package pass carries the
        taint into the sibling method."""
        mln = os.path.join(REPO, "deeplearning4j_tpu", "models",
                           "multi_layer_network.py")
        with open(mln, encoding="utf-8") as fh:
            src = fh.read()
        w_anchor = ("            if guard:\n"
                    "                self._nanguard_record(skipped)")
        r_anchor = "        sig = self._output_signature(x, fmask)"
        assert w_anchor in src and r_anchor in src
        seeded = src.replace(
            w_anchor,
            "            self._last_finite = step_all_finite(score, grads)\n"
            + w_anchor, 1)
        seeded = seeded.replace(
            r_anchor,
            r_anchor + "\n        if self._last_finite:\n"
                       "            fmask = fmask", 1)
        alone = lint_sources({mln: seeded})
        assert not any(f.rule_id == "G016" and "_last_finite" in f.message
                       for f in alone.findings), \
            "per-file lint should NOT resolve the cross-module summary"
        sources = {mln: seeded}
        from tools.graftlint import iter_python_files
        pkg = os.path.join(REPO, "deeplearning4j_tpu")
        for p in iter_python_files([pkg]):
            if p not in sources:
                with open(p, encoding="utf-8") as fh:
                    sources[p] = fh.read()
        r = lint_sources(sources)
        g16 = [f for f in r.findings if f.rule_id == "G016"
               and "_last_finite" in f.message]
        assert g16, [f.format() for f in r.findings
                     if f.rule_id == "G016"]
        assert "sibling method" in g16[0].message

    def test_mesh_axis_sizes_are_host_metadata(self):
        # mesh.shape[axis] is the mesh's FIXED layout, not an array
        # shape: range() over it in traced code is one program per mesh,
        # not per batch — the carve-out the cross-method flow needs to
        # stay false-positive-free on pp_transformer
        r = check("""
            import jax

            class PP:
                def __init__(self, mesh, axis):
                    self.S = mesh.shape[axis]

                @staticmethod
                def _traced(self, x):
                    pass

                def build(self):
                    @jax.jit
                    def step(x):
                        for i in range(self.S):
                            x = x + i
                        return x
                    return step
        """)
        assert "G017" not in ids(r), [f.format() for f in r.findings]


# ---------------------------------------------------------------------------
# the budget contract: ONE shape pass per lint run
# ---------------------------------------------------------------------------
def test_shape_pass_is_built_once(monkeypatch):
    import tools.graftlint.shapes as shmod
    built = []
    orig = shmod._ShapeFacts

    class Counting(orig):
        def __init__(self, pkg):
            built.append(1)
            orig.__init__(self, pkg)

    monkeypatch.setattr(shmod, "_ShapeFacts", Counting)
    lint_sources({
        "pkg/a.py": "import jax\n\nstep = jax.jit(lambda p: p)\n\n"
                    "def run(params, xs):\n"
                    "    for x in xs:\n"
                    "        params = step(params, x)\n"
                    "    return params\n",
        "pkg/b.py": "import jax\nimport jax.numpy as jnp\n"
                    "from jax.sharding import NamedSharding, "
                    "PartitionSpec as P\n\n"
                    "def place(mesh, net):\n"
                    "    rep = NamedSharding(mesh, P())\n"
                    "    m = jnp.zeros((8, 8))\n"
                    "    m = jax.device_put(m, rep)\n"
                    "    return m\n",
    })
    assert built == [1], f"shape facts built {len(built)} times"


# ---------------------------------------------------------------------------
# footprint accuracy: the static mirror vs jax.live_arrays() after REAL
# fits (MLN + CG, fused and unfused) — the ±20% acceptance bar
# ---------------------------------------------------------------------------
class TestFootprintAccuracy:
    def _measure(self, build, fit_steps, fuse, monkeypatch):
        import numpy as np
        import jax
        from deeplearning4j_tpu.datasets.dataset import (
            DataSet, ListDataSetIterator)
        monkeypatch.setenv("DL4J_TPU_FUSE_STEPS", str(fuse))
        monkeypatch.delenv("DL4J_TPU_FUSE_AUTOTUNE", raising=False)
        rng = np.random.default_rng(0)

        def it():
            return ListDataSetIterator([DataSet(
                rng.normal(size=(16, 32)).astype(np.float32),
                np.eye(10, dtype=np.float32)[rng.integers(0, 10, 16)])
                for _ in range(fit_steps)])

        gc.collect()
        before = {id(a) for a in jax.live_arrays()}
        net = build()
        net.fit(it())
        float(net.score_)
        gc.collect()
        live = sum(a.nbytes for a in jax.live_arrays()
                   if id(a) not in before)
        del net
        gc.collect()
        return live

    @pytest.mark.parametrize("fuse", [1, 4], ids=["unfused", "fused"])
    @pytest.mark.parametrize("kind", ["mln", "cg"])
    def test_static_state_within_20pct_of_live_arrays(self, kind, fuse,
                                                      monkeypatch):
        src = MLN_SRC if kind == "mln" else CG_SRC
        specs, _ = extract_models_from_source(textwrap.dedent(src), "m.py")
        row = model_footprint(specs[0], batch=16, steps=4)[0]["bytes"]
        # what stays LIVE after fit() returns: params + updater slots +
        # the retained last gradients — the state trees; batch inputs
        # are transient
        static = row["params"] + row["grads"] + row["updater"]

        ns = {}
        exec(textwrap.dedent(src), ns)
        if kind == "mln":
            from deeplearning4j_tpu.models.multi_layer_network import (
                MultiLayerNetwork)
            build = lambda: MultiLayerNetwork(ns["small_mln"]()).init()
        else:
            from deeplearning4j_tpu.models.computation_graph import (
                ComputationGraph)
            build = lambda: ComputationGraph(ns["small_cg"]()).init()
        live = self._measure(build, 8, fuse, monkeypatch)
        assert 0.8 * static <= live <= 1.2 * static, (
            f"{kind} fuse={fuse}: static {static} vs live {live}")

    def test_n_params_mirror_is_exact(self):
        import jax
        import numpy as np
        from deeplearning4j_tpu.models.multi_layer_network import (
            MultiLayerNetwork)
        specs, _ = extract_models_from_source(
            textwrap.dedent(MLN_SRC), "m.py")
        ns = {}
        exec(textwrap.dedent(MLN_SRC), ns)
        net = MultiLayerNetwork(ns["small_mln"]()).init()
        runtime = sum(int(np.prod(p.shape)) for tree in net.params_list
                      for p in jax.tree.leaves(tree))
        assert specs[0].n_params() == runtime == 2762


# ---------------------------------------------------------------------------
# the zoo's builders through the standalone entry
# ---------------------------------------------------------------------------
class TestZooReport:
    def test_zoo_rows_and_unresolved(self):
        got = model_mem_report(ZOO, "lenet_mnist", batch=128, steps=8)
        assert got["unresolved"] is None
        programs = [r["program"] for r in got["rows"]]
        assert "train[B=128]" in programs and any(
            p.startswith("fused[") for p in programs)
        # a control-flow builder carries its reason, never a silent miss
        got = model_mem_report(ZOO, "resnet50", batch=32, steps=8)
        assert got["rows"] == [] and "control flow" in got["unresolved"]

    def test_consts_override_the_builders_defaults(self):
        got = model_mem_report(
            ZOO, "char_rnn", batch=8, steps=8, seq=200,
            consts={"vocab_size": 32, "hidden": 64, "tbptt_length": 25})
        assert got["unresolved"] is None
        train = got["rows"][0]
        assert train["n_params"] == 60320
        assert train["bytes"]["inputs"] == 2 * 8 * 200 * 32 * 4
