"""The ``smallthinker`` family's benchmark files: the work functions against
counts made by hand, the rehearsal twin through the harness (sound, broken, and
the control one precision down), and the two readers the cell adds on a
synthetic ``ctx`` and on one that has nothing for them to read."""

import importlib
import json
import os

import jax
import pytest

os.environ.setdefault("DL4J_TPU_PALLAS_INTERPRET", "1")

from benchmark import check, run  # noqa: E402
from benchmark.work import smallthinker as work  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(__file__))
SEED = 3_400_000_011   # past 2**31, as the driver's are
TWIN = "smallthinker-tiny-train"
CELL = "smallthinker-21b-train-t16384"
NEW_READERS = ("moe_peak_load", "attn_window_edge_share")
LISTED = ("h2d_ms", "step_call_ms", "optimizer_ms", "logits_loss_ms",
          "layernorm_ms", "unscoped_share", "attn_window_roofline",
          "attn_full_roofline", "moe_experts_roofline", "moe_dispatch_ms",
          "moe_row_waste", "attn_mixed_layout_ms")


def _config():
    with open(os.path.join(BENCH, "configs", "smallthinker-21b-a3b.json")) as f:
        return json.load(f)


# --- required work, by hand ----------------------------------------------------

def test_attention_pairs_by_hand():
    c = _config()
    # full: query i meets keys 0..i
    assert work.attention_pairs(c, False, 16384) == 16384 * 16385 // 2 \
        == 134_225_920
    # window 4096: 4096 * 4097 / 2 in the first 4096 rows, then 4096 a row
    assert work.attention_pairs(c, True, 16384) \
        == 8_390_656 + 12288 * 4096 == 58_722_304
    # 44 % of a full layer's pairs at 16384, 75 % at 8192
    assert 0.43 < 58_722_304 / 134_225_920 < 0.44
    assert 0.74 < work.attention_pairs(c, True, 8192) \
        / work.attention_pairs(c, False, 8192) < 0.76
    # a sequence no longer than the window is plain causal attention
    assert work.attention_pairs(c, True, 4096) == 4096 * 4097 // 2


def test_attention_work_by_hand():
    c = _config()
    got = work.attention_work(c, 1, 16384, True)          # three layers
    # QK^T, PV forward; dV, dP, dQ, dK backward: 6 products of 2 x 128 a pair
    assert got["flops"] == 3 * 6 * 2 * 128 * 58_722_304 * 28
    q_like = 28 * 16384 * 128 * 2       # bfloat16
    kv_like = 4 * 16384 * 128 * 2
    assert got["bytes"] == 3 * (6 * q_like + 6 * kv_like)
    full = work.attention_work(c, 1, 16384, False)        # one layer
    assert full["flops"] == 6 * 2 * 128 * 134_225_920 * 28
    assert full["bytes"] == 6 * q_like + 6 * kv_like


def test_experts_work_is_the_balanced_load_by_hand():
    c = _config()
    # 16384 tokens x 6 assignments x 16 / 64 held: 1.5 a token, 1536 an expert
    assert work.balanced_rows(c, 16384) == 24576 == 16 * 1536
    got = work.experts_work(c, 1, 16384)
    # four layers, three products of 2 x 2560 x 768 a row, x 3
    assert got["flops"] == 4 * 3 * 3 * 2 * 24576 * 2560 * 768
    weights = 3 * 16 * 2560 * 768 * 2
    assert got["bytes"] == 4 * (3 * weights + 4 * 24576 * 2560 * 2)


def test_step_flops_by_hand():
    c = _config()
    tokens, d = 16384, 2560
    proj = 2 * tokens * d * (2 * 28 * 128 + 2 * 4 * 128)
    ffn = 2 * tokens * d * 64 + 3 * 2 * 24576 * d * 768
    full = proj + 2 * 2 * 128 * 134_225_920 * 28 + ffn
    window = proj + 2 * 2 * 128 * 58_722_304 * 28 + ffn
    head = 2 * tokens * d * 37984
    assert work.train_step_flops(c, 1, 16384) \
        == 3 * (full + 3 * window + head)
    step = work.train_step_flops(c, 1, 16384)
    assert 34.6e12 < step < 34.8e12          # 176 ms at the v5e's peak
    assert 0.27 < 3 * head / step < 0.28     # the head, over-weighted by the cut
    assert 0.09 < work.experts_work(c, 1, 16384)["flops"] / step < 0.11


# --- the rehearsal twin through the harness ------------------------------------

def _measure(seed=SEED):
    cell = run.Cell(TWIN, rehearse=True)
    return run.measure(cell, seed, 0.3, False, jax.local_devices()[:1])


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


def _router_from_ln2(monkeypatch):
    from benchmark.drivers import smallthinker_train as driver
    real = driver.program_config
    monkeypatch.setattr(driver, "program_config", lambda *a, **k:
                        real(*a, router_input="ffn", **k))


def test_twin_sound_run_is_correct_and_reports_the_cells_metrics():
    out = _measure()
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_step_ms", "setup_s"}
    untraced = out["run"]["per_layer_untraced"]
    # the counters' ratios need no trace: the twin's buffer is every
    # assignment, a quarter of which meet one of the 2 of 8 experts held
    assert 3.0 < untraced["moe_row_waste"] < 6.0
    # two held experts: 1.0 at an even load, 2.0 with all on one
    assert 1.0 <= untraced["moe_peak_load"] < 1.6
    # T 64 in blocks of 16 under a window of 32: 9 live pairs a row, 6 edge
    assert untraced["attn_window_edge_share"] == pytest.approx(100 * 6 / 9)
    assert untraced["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [_half_batch, _state_unchanged,
                                   _router_from_ln2])
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


# --- the readers ---------------------------------------------------------------

def _ctx(**changes):
    ctx = {"trace": None, "peaks": None, "work": {"step_flops": 1.0},
           "counters": {"moe.local_rows": 1200, "moe.rows_computed": 2400,
                        "moe.rows_over_buffer": 0,
                        "moe.peak_group_rows": 450,      # 4 layers x steps
                        "moe.even_group_rows": 1200 / 4,
                        "flash.window_steps_live": 3 * 252 * 28 * 3,
                        "flash.window_steps_edge": 3 * 56 * 28 * 3}}
    ctx.update(changes)
    return ctx


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


@pytest.mark.parametrize("metric,want", [
    ("moe_peak_load", 1.5),                      # 450 against 300 at an even load
    ("attn_window_edge_share", 100.0 * 56 / 252)])
def test_reader_on_a_synthetic_ctx(metric, want):
    assert _read(metric, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW_READERS)
def test_reader_finds_nothing_on_the_parents_program(metric):
    """A program without the counter and the gauges (the parent's Laguna cell
    hands on three counters; the GPT-2 cells none): None, never 0, never a
    raise."""
    parents = {"moe.local_rows": 1200, "moe.rows_computed": 2400,
               "moe.rows_over_buffer": 0}
    for counters in (parents, {}, None):
        assert _read(metric, _ctx(counters=counters)) is None


def test_the_accepted_laguna_driver_hands_on_the_new_counter():
    """``laguna_train.Job.window`` differences whatever ``moe_counters()``
    returns: the fourth counter and ``moe.even_group_rows`` (linear in a
    counter) reach ``moe_peak_load`` with no edit of the driver."""
    cell = run.Cell("laguna-tiny-train", rehearse=True)
    out = run.measure(cell, SEED, 0.3, False, jax.local_devices()[:1])
    assert 1.0 <= out["run"]["per_layer_untraced"]["moe_peak_load"] < 3.0


def test_the_cells_entries_and_files_agree():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = run.Cell(CELL, rehearse=False)
    assert cell.chips == 1 and cell.traffic["driver"] == "smallthinker_train"
    assert (cell.traffic["rows"], cell.traffic["pool"]) == (1, 16)
    assert cell.traffic["seq_len"] in (16384, 12288, 8192)
    names = {m["name"] for m in cell.per_layer}
    assert set(LISTED) | set(NEW_READERS) <= names
    assert {"train_mfu", "device_idle_share", "compiles_in_window",
            "host_dispatch_ms"} <= names
    # other families' work counts and scopes: not read here
    assert not {"flash_fwd_roofline", "flash_bwd_roofline", "attn_layout_ms",
                "loop_attn_roofline", "exit_mass_min"} & names
    entry = [c for c in bench["configs"]
             if c["name"] == "smallthinker-21b-a3b"][0]
    assert entry["reduced"] == cell.config["reduced"] \
        == ["num_hidden_layers", "experts_held", "vocab_size"]
    assert entry["source"] == cell.config["source"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED:
        assert by_name[name]["workloads"][-1] == CELL
    assert by_name["moe_peak_load"]["workloads"] \
        == ["laguna-xs2-train-t8192", CELL]
    assert by_name["attn_window_edge_share"]["workloads"] == [CELL]
    assert set(cell.limits) <= {"loss1_gap", "loss2_gap", "loss3_gap",
                                "grad_gap", "grad_median_gap", "delta_gap",
                                "delta_median_gap"}
