"""The ``ouro`` family's benchmark files: the work functions against counts made
by hand, the rehearsal twin through the harness (sound, broken three ways, and
the control one precision down), and the four readers the cell adds on a
reduction counted by hand and on a program that has nothing for them to
read."""

import importlib
import json
import os

import jax
import pytest

os.environ.setdefault("DL4J_TPU_PALLAS_INTERPRET", "1")

from benchmark import check, run, scope_reduce  # noqa: E402
from benchmark.work import ouro as work  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(__file__))
SEED = 3_200_000_011   # past 2**31, as the driver's are
TWIN, CELL = "ouro-tiny-train", "ouro-2.6b-train-t4096"
READERS = ("loop_attn_roofline", "sandwich_norm_ms", "exit_gate_ms",
           "exit_mass_min")


def _config():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


# --- required work, by hand ----------------------------------------------------

def test_the_cut_is_depth_alone_and_the_widths_are_the_catalogs():
    c = _config()
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers_published"] == 48
    assert 4 <= c["num_hidden_layers"] <= 9
    assert len(c["layer_types"]) == 48 and set(c["layer_types"]) \
        == {"full_attention"}
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
            c["vocab_size"], c["total_ut_steps"], c["rope_theta"],
            c["rms_norm_eps"], c["tie_word_embeddings"]) == (
        2048, 16, 16, 128, 5632, 49152, 4, 1000000, 1e-6, False)
    assert work.applications(c) == 4 * c["num_hidden_layers"]
    for said in ("exit_entropy_beta", "layer", "loop", "exits", "inference",
                 "initializer_range", "optimizer"):
        assert said in c["assumed"]


def test_attention_work_by_hand():
    c = _config()
    # query i meets keys 0..i: 4096 * 4097 / 2
    assert work.attention_pairs(4096) == 8_390_656
    one = work.attention_application_work(c, 2, 4096)
    # QK^T, PV forward; dV, dP, dQ, dK backward: 6 products of 2 x 128 a pair
    assert one["flops"] == 6 * 2 * 128 * 8_390_656 * 2 * 16
    like = 2 * 16 * 4096 * 128 * 2        # bfloat16; no grouping: K, V as Q
    assert one["bytes"] == 12 * like
    every = work.attention_work(c, 2, 4096)
    assert every == {k: work.applications(c) * v for k, v in one.items()}


def test_step_flops_by_hand():
    c = _config()
    L, tokens, d = c["num_hidden_layers"], 8192, 2048
    proj = 2 * tokens * d * 4 * 2048                  # q, k, v, o: 16 x 128
    attn = 2 * 2 * 128 * 8_390_656 * 2 * 16
    ffn = 3 * 2 * tokens * d * 5632
    head = 2 * tokens * d * 49152
    fwd = 4 * L * (proj + attn + ffn) + 4 * head
    assert work.train_step_flops(c, 2, 4096) == 3 * fwd
    # 119.5 MFLOP a token and application forward, 14 % of it attention at
    # 4096; an exit's head 201.3: with so few layers the four exits are
    # over-weighted (3.4 % of the published 48 layers' forward)
    assert 119e6 < (proj + attn + ffn) / tokens < 120e6
    assert 0.135 < attn / (proj + attn + ffn) < 0.145
    assert head / tokens == 2 * d * 49152 == 201_326_592
    assert 0.033 < 4 * head / (4 * 48 * (proj + attn + ffn) + 4 * head) < 0.035


# --- the rehearsal twin through the harness ------------------------------------

def _measure():
    cell = run.Cell(TWIN, rehearse=True)
    return run.measure(cell, SEED, 0.3, False, jax.local_devices()[:1])


def _state_unchanged(monkeypatch):
    from deeplearning4j_tpu.models import transformer as t
    real = t._adamw_apply
    monkeypatch.setattr(t, "_adamw_apply", lambda c, p, *a, **k:
                        (p, real(c, p, *a, **k)[1]))


def _half_batch(monkeypatch):
    from deeplearning4j_tpu.models.transformer import TransformerLM
    real = TransformerLM._loss
    half = lambda a: a[:max(1, a.shape[0] // 2)]
    monkeypatch.setattr(
        TransformerLM, "_loss", lambda self, params, tokens, targets, mask,
        *rest: real(self, params, half(tokens), half(targets), None, *rest))


def _one_run_fewer(monkeypatch):
    """The timed path runs the stack one time fewer, so that the last exit is
    left out and the one before it takes what is left."""
    from benchmark.drivers import ouro_train
    real = ouro_train.program_config

    def fewer(config, seq_len, seed):
        return real(dict(config, total_ut_steps=config["total_ut_steps"] - 1),
                    seq_len, seed)

    monkeypatch.setattr(ouro_train, "program_config", fewer)


def test_twin_sound_run_is_correct_and_reports_the_cells_metrics():
    out = _measure()
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_step_ms", "setup_s"}
    untraced = out["run"]["per_layer_untraced"]
    # the counters' ratio needs no trace: three exits near 1/2, 1/4, 1/4
    assert 15.0 < untraced["exit_mass_min"] < 33.4
    assert untraced["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [_half_batch, _state_unchanged,
                                   _one_run_fewer])
def test_twin_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _measure()
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_twin_control_one_precision_down_is_not_correct(seed, precision="fp8"):
    """The fp8 reference in the program's place."""
    c = run.Cell(TWIN, rehearse=True)
    driver = importlib.import_module(f"benchmark.drivers.{c.traffic['driver']}")
    job = driver.Job(c.config, c.traffic, seed, run.Spans())
    batches = job.check_batches()
    job.free()
    correct, compared = check.verify_training(
        c.config, seed, None, batches, c.limits, precision=precision)
    assert correct is False, compared


def test_the_planted_fault_of_a_run_fewer_is_caught_by_the_twins_limits():
    """The reference with ``total_ut_steps - 1`` runs in the program's place
    (the fault as the chip's calibration plants it)."""
    c = run.Cell(TWIN, rehearse=True)
    driver = importlib.import_module(f"benchmark.drivers.{c.traffic['driver']}")
    job = driver.Job(c.config, c.traffic, SEED, run.Spans())
    batches = job.check_batches()
    job.free()
    correct, compared = check.verify_training(
        c.config, SEED, None, batches, c.limits, fault="one_run_fewer")
    assert correct is False, compared


# --- the readers ---------------------------------------------------------------

KERNEL = '%x = bf16[4] custom-call(%q), custom_call_target="tpu_custom_call"'
REDUCED = {"chips": 1, "spans": {}, "idle_gaps_by_span": {}, "instructions": {
    # one application's forward, dQ and dK/dV kernels; under remat the
    # backward's sit under checkpoint/
    KERNEL + " a": {"stack": "jit(step)/jvp(block.attn)/pallas_call",
                    "count": 5, "mean_s": 0.004},
    KERNEL + " b": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                             "block.attn/pallas_call",
                    "count": 5, "mean_s": 0.006},
    KERNEL + " c": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                             "block.attn/pallas_call",
                    "count": 5, "mean_s": 0.010},
    # not a kernel: the layout around them is not the kernels' time
    "%copy.1": {"stack": "jit(step)/jvp(block.attn)/transpose",
                "count": 5, "mean_s": 0.5},
    "%fusion.2": {"stack": "jit(step)/jvp(block.attn_norm)/mul",
                  "count": 5, "mean_s": 0.003},
    "%fusion.3": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                           "rematted_computation/block.mlp_norm/rsqrt",
                  "count": 5, "mean_s": 0.004},
    "%fusion.4": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                           "block.mlp_norm/mul",
                  "count": 5, "mean_s": 0.002},
    "%fusion.5": {"stack": "jit(step)/jvp(jvp())/checkpoint/exit_gate/"
                           "log_sigmoid",
                  "count": 5, "mean_s": 0.0005},
    "%fusion.6": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                           "exit_gate/mul",
                  "count": 5, "mean_s": 0.0015},
    # the pre-norms and the products are other readers'
    "%fusion.7": {"stack": "jit(step)/jvp(block.ln1)/mul",
                  "count": 5, "mean_s": 0.7},
    "%fusion.8": {"stack": "jit(step)/jvp(block.mlp)/dot_general",
                  "count": 5, "mean_s": 0.9},
}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def _ctx(**changes):
    ctx = {"trace": {"path": "a trace"}, "peaks": PEAKS,
           "counters": {"exit.tokens": 8000,
                        "exit.mass": [4000, 2000, 1100, 900]},
           "work": {"loop_attn": {"flops": 5e9, "bytes": 1e6}}}      # 5 ms
    ctx.update(changes)
    return ctx


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


@pytest.mark.parametrize("metric,want", [
    # the accepted reader of ``attn`` outside the kernels: ``attn_norm`` is
    # another token and not its
    ("attn_layout_ms", 500.0),
    ("loop_attn_roofline", 100.0 * 5 / 20),   # kernels alone: 4 + 6 + 10
    ("sandwich_norm_ms", 9.0),                # both post-norms, fwd and bwd
    ("exit_gate_ms", 2.0),
    ("exit_mass_min", 100.0 * 900 / 8000)])
def test_reader_on_a_reduction_counted_by_hand(metric, want, monkeypatch):
    monkeypatch.setattr(scope_reduce, "of", lambda ctx: REDUCED)
    assert _read(metric, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_nothing_on_the_parents_program(metric, monkeypatch):
    """A program without the scopes, the counters and this family's work (the
    other cells' ``ctx``, traced or not): None, never 0, never a raise."""
    gpt2_only = {"chips": 1, "spans": {}, "idle_gaps_by_span": {},
                 "instructions": {
                     "%fusion.1": {"stack": "jit(step)/jvp(block.mlp)/dot",
                                   "count": 3, "mean_s": 0.1}}}
    bare = _ctx(counters={}, work={"step_flops": 1.0})
    for reduced in (gpt2_only, None):
        monkeypatch.setattr(scope_reduce, "of", lambda ctx, r=reduced: r)
        assert _read(metric, bare) is None
        assert _read(metric, dict(bare, counters=None, peaks=None)) is None
    # the Laguna cell's counters are not this family's
    moe = _ctx(counters={"moe.local_rows": 10, "moe.rows_computed": 20})
    assert _read("exit_mass_min", moe) is None


def test_the_cells_entries_and_files_agree():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = run.Cell(CELL, rehearse=False)
    assert cell.chips == 1 and cell.traffic["driver"] == "ouro_train"
    assert (cell.traffic["rows"], cell.traffic["seq_len"],
            cell.traffic["pool"]) == (2, 4096, 16)
    names = {m["name"] for m in cell.per_layer}
    assert set(READERS) <= names
    assert {"train_mfu", "optimizer_ms", "logits_loss_ms", "layernorm_ms",
            "unscoped_share", "h2d_ms", "step_call_ms", "device_idle_share",
            "compiles_in_window", "host_dispatch_ms", "attn_layout_ms"} <= names
    # other families' work counts and scopes: not read here
    assert not {"flash_fwd_roofline", "flash_bwd_roofline",
                "attn_full_roofline", "moe_row_waste"} & names
    entry = [c for c in bench["configs"] if c["name"] == "ouro-2.6b"][0]
    assert entry["reduced"] == cell.config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cell.config["source"]
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "train_step_ms"
    assert set(cell.limits) <= {"loss1_gap", "loss2_gap", "loss3_gap",
                                "grad_gap", "grad_median_gap", "delta_gap"}
