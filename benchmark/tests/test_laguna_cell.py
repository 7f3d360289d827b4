"""The ``laguna`` family's benchmark files: the work functions against counts
made by hand, the rehearsal twin through the harness (sound, broken, and the
control one precision down), and the five readers the cell adds on a reduction
counted by hand and on a program that has nothing for them to read."""

import importlib
import json
import os

import jax
import pytest

os.environ.setdefault("DL4J_TPU_PALLAS_INTERPRET", "1")

from benchmark import check, run, scope_reduce  # noqa: E402
from benchmark.work import laguna as work  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(__file__))
SEED = 2_800_000_011   # past 2**31, as the driver's are
TWIN = "laguna-tiny-train"
READERS = ("attn_window_roofline", "attn_full_roofline",
           "moe_experts_roofline", "moe_dispatch_ms", "moe_row_waste",
           "attn_mixed_layout_ms")


def _config():
    with open(os.path.join(BENCH, "configs", "laguna-xs2.json")) as f:
        return json.load(f)


# --- required work, by hand ----------------------------------------------------

def test_the_cut_is_one_dense_layer_and_one_whole_period():
    c = _config()
    kinds = work.layer_kinds(c)
    assert kinds == [("full_attention", 48, "dense"),
                     ("sliding_attention", 64, "sparse"),
                     ("sliding_attention", 64, "sparse"),
                     ("sliding_attention", 64, "sparse"),
                     ("full_attention", 48, "sparse")]
    assert work.held(c) == (0, 32)
    assert c["num_experts"] == 256 and c["num_experts_per_tok"] == 8


def test_attention_pairs_by_hand():
    c = _config()
    # full: query i meets keys 0..i: 8192 * 8193 / 2
    assert work.attention_pairs(c, ("full_attention", 48, "dense"), 8192) \
        == 33_558_528
    # window 512: 512 * 513 / 2 in the first 512 rows, then 512 a row
    assert work.attention_pairs(c, ("sliding_attention", 64, "sparse"), 8192) \
        == 131_328 + 7680 * 512 == 4_063_488
    # a sequence no longer than the window is plain causal attention
    assert work.attention_pairs(c, ("sliding_attention", 64, "sparse"), 256) \
        == 256 * 257 // 2


def test_attention_layer_work_by_hand():
    c = _config()
    got = work.attention_layer_work(c, ("sliding_attention", 64, "sparse"),
                                    2, 8192)
    # QK^T, PV forward; dV, dP, dQ, dK backward: 6 products of 2 x 128 a pair
    assert got["flops"] == 6 * 2 * 128 * 4_063_488 * 2 * 64
    q_like = 2 * 64 * 8192 * 128 * 2       # bfloat16
    kv_like = 2 * 8 * 8192 * 128 * 2
    assert got["bytes"] == 6 * q_like + 6 * kv_like
    both = work.attention_work(c, 2, 8192, "full_attention")
    one = work.attention_layer_work(c, ("full_attention", 48, "dense"), 2, 8192)
    assert both == {k: 2 * v for k, v in one.items()}   # layers 0 and 4
    assert work.attention_work(c, 2, 8192, "linear_attention") is None


def test_experts_work_is_the_balanced_load_by_hand():
    c = _config()
    # 16384 tokens x 8 assignments x 32 / 256 held: one a token
    assert work.balanced_rows(c, 16384) == 16384
    got = work.experts_work(c, 2, 8192)
    # four sparse layers, three products of 2 x 2048 x 512 a row, x 3
    assert got["flops"] == 4 * 3 * 3 * 2 * 16384 * 2048 * 512
    weights = 3 * 32 * 2048 * 512 * 2
    assert got["bytes"] == 4 * (3 * weights + 4 * 16384 * 2048 * 2)


def test_step_flops_by_hand():
    c = _config()
    tokens, d = 16384, 2048
    full = 2 * tokens * d * (2 * 48 * 128 + 2 * 8 * 128) + 2 * tokens * d * 48 \
        + 2 * 2 * 128 * 33_558_528 * 2 * 48
    window = 2 * tokens * d * (2 * 64 * 128 + 2 * 8 * 128) \
        + 2 * tokens * d * 64 + 2 * 2 * 128 * 4_063_488 * 2 * 64
    dense = 3 * 2 * tokens * d * 8192
    sparse = 2 * tokens * d * 256 + 3 * 2 * tokens * d * 512 \
        + 3 * 2 * 16384 * d * 512
    fwd = (full + dense) + 3 * (window + sparse) + (full + sparse) \
        + 2 * tokens * d * 12544
    assert work.train_step_flops(c, 2, 8192) == 3 * fwd
    # 802 MFLOP a token forward, 25 of them the four expert layers' routed
    # products (a token meets one held expert on average): a window/full
    # attention cell first
    assert 800e6 < fwd / tokens < 805e6
    routed = 4 * 3 * 2 * 16384 * d * 512
    assert 0.03 < routed / fwd < 0.035


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


def test_twin_sound_run_is_correct_and_reports_the_cells_metrics():
    out = _measure()
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_step_ms", "setup_s"}
    untraced = out["run"]["per_layer_untraced"]
    # the counter's ratio needs no trace: rows computed for each row that met
    # a held expert (the twin's buffer is every assignment, half of which
    # meet one of the 8 of 16 experts held)
    assert 1.5 < untraced["moe_row_waste"] < 3.0
    assert untraced["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [_half_batch, _state_unchanged])
def test_twin_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _measure()
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_twin_control_one_precision_down_is_not_correct(seed, precision="fp8"):
    """The fp8 reference in the program's place. (int8 reads within 1.1 times
    the program at the twin's size and is held on the chip only: PERF.md.)"""
    c = run.Cell(TWIN, rehearse=True)
    driver = importlib.import_module(f"benchmark.drivers.{c.traffic['driver']}")
    job = driver.Job(c.config, c.traffic, seed, run.Spans())
    batches = job.check_batches()
    job.free()
    correct, compared = check.verify_training(
        c.config, seed, None, batches, c.limits, precision=precision)
    assert correct is False, compared


# --- the readers ---------------------------------------------------------------

KERNEL = '%x = bf16[4] custom-call(%q), custom_call_target="tpu_custom_call"'
REDUCED = {"chips": 1, "spans": {}, "idle_gaps_by_span": {}, "instructions": {
    # window layers: forward, the forward again under remat, dQ, dK/dV
    KERNEL + " a": {"stack": "jit(step)/jvp(block.attn_window)/pallas_call",
                    "count": 12, "mean_s": 0.004},
    KERNEL + " b": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                             "rematted_computation/block.attn_window/"
                             "pallas_call",
                    "count": 12, "mean_s": 0.002},
    KERNEL + " d": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                             "block.attn_window/pallas_call",
                    "count": 12, "mean_s": 0.004},
    # not a kernel: the layout around them is not the kernels' time
    "%copy.1": {"stack": "jit(step)/jvp(block.attn_window)/transpose",
                "count": 12, "mean_s": 0.5},
    KERNEL + " c": {"stack": "jit(step)/jvp(block.attn_full)/pallas_call",
                    "count": 8, "mean_s": 0.020},
    "%fusion.11": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                            "rematted_computation/block.attn_full/add",
                   "count": 8, "mean_s": 0.25},
    "%fusion.7": {"stack": "jit(step)/jvp(block.experts)/ragged_dot",
                  "count": 16, "mean_s": 0.003},
    # XLA's own grouped-product kernel carries no name stack
    "%ragged-dot-gmm.3 = custom-call()": {"stack": "", "count": 16,
                                          "mean_s": 0.005},
    "%fusion.8": {"stack": "jit(step)/jvp(block.router)/top_k",
                  "count": 16, "mean_s": 0.001},
    "%fusion.9": {"stack": "jit(step)/transpose(jvp(jvp()))/checkpoint/"
                           "block.moe_dispatch/scatter-add",
                  "count": 16, "mean_s": 0.002},
    "%fusion.10": {"stack": "jit(step)/jvp(block.mlp)/dot_general",
                   "count": 4, "mean_s": 0.7},
}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def _ctx(**changes):
    ctx = {"trace": {"path": "a trace"}, "peaks": PEAKS,
           "counters": {"moe.local_rows": 1000, "moe.rows_computed": 2500,
                        "moe.rows_over_buffer": 0},
           "work": {"attn_window": {"flops": 2e9, "bytes": 1e6},    # 2 ms
                    "attn_full": {"flops": 1e9, "bytes": 4e6},      # 4 ms
                    "experts": {"flops": 2e9, "bytes": 1e6}}}       # 2 ms
    ctx.update(changes)
    return ctx


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


@pytest.mark.parametrize("metric,want", [
    ("attn_window_roofline", 100.0 * 2 / 10),   # kernels alone: 4 + 2 + 4
    ("attn_full_roofline", 100.0 * 4 / 20),     # bound by its bytes
    ("moe_experts_roofline", 100.0 * 2 / 8),    # the scope and %ragged-dot
    ("moe_dispatch_ms", 3.0),                   # router + dispatch
    ("moe_row_waste", 2.5),
    ("attn_mixed_layout_ms", 750.0)])           # both scopes, no kernel
def test_reader_on_a_reduction_counted_by_hand(metric, want, monkeypatch):
    monkeypatch.setattr(scope_reduce, "of", lambda ctx: REDUCED)
    assert _read(metric, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_nothing_on_the_parents_program(metric, monkeypatch):
    """A program without the scopes, the counters and this family's work (the
    GPT-2 cells' ``ctx``, traced or not): None, never 0, never a raise."""
    gpt2_only = {"chips": 1, "spans": {}, "idle_gaps_by_span": {},
                 "instructions": {
                     "%fusion.1": {"stack": "jit(step)/jvp(block.mlp)/dot",
                                   "count": 3, "mean_s": 0.1}}}
    bare = _ctx(counters={}, work={"step_flops": 1.0})
    for reduced in (gpt2_only, None):
        monkeypatch.setattr(scope_reduce, "of", lambda ctx, r=reduced: r)
        assert _read(metric, bare) is None
        assert _read(metric, dict(bare, counters=None, peaks=None)) is None


def test_the_cells_entries_and_files_agree():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = run.Cell("laguna-xs2-train-t8192", rehearse=False)
    assert cell.chips == 1 and cell.traffic["driver"] == "laguna_train"
    assert (cell.traffic["rows"], cell.traffic["seq_len"],
            cell.traffic["pool"]) == (2, 8192, 16)
    names = {m["name"] for m in cell.per_layer}
    assert set(READERS) <= names
    assert {"train_mfu", "optimizer_ms", "logits_loss_ms", "device_idle_share",
            "compiles_in_window", "host_dispatch_ms"} <= names
    # GPT-2's work count and name match: not read here
    assert not {"flash_fwd_roofline", "flash_bwd_roofline",
                "attn_layout_ms"} & names
    entry = [c for c in bench["configs"] if c["name"] == "laguna-xs2"][0]
    assert entry["reduced"] == cell.config["reduced"] \
        == ["num_hidden_layers", "experts_held", "vocab_size"]
    for m in bench["per_layer"]:
        if m["name"] in READERS:
            assert m["workloads"] == ["laguna-xs2-train-t8192"]
