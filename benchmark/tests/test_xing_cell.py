"""The ``xing`` family's benchmark files: the work functions against counts
made by hand, the cell's entries and files, the rehearsal twin through the
harness (sound, broken, and the control one precision down), and the two
readers the cell adds on a synthetic ``ctx`` and on one that has nothing for
them to read."""

import importlib
import json
import os

import jax
import pytest

os.environ.setdefault("DL4J_TPU_PALLAS_INTERPRET", "1")

from benchmark import check, run  # noqa: E402
from benchmark.work import xing as work  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(__file__))
SEED = 4_200_000_011   # past 2**31, as the driver's are
TWIN = "xing-tiny-train"
CELL = "xing4-train-t4096"
NEW_READERS = ("hc_ms", "hc_roofline")
LISTED = ("h2d_ms", "step_call_ms", "optimizer_ms", "logits_loss_ms",
          "layernorm_ms", "unscoped_share", "setup_trace_lower_s",
          "setup_compile_s", "setup_cold_compiles", "step_build_s",
          "moe_experts_roofline", "moe_dispatch_ms", "moe_row_waste",
          "moe_peak_load", "attn_latent_roofline", "latent_proj_ms",
          "mtp_ms", "moe_router_peak_load", "attn_latent_layout_ms")


def _config():
    with open(os.path.join(BENCH, "configs", "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


# --- required work, by hand ----------------------------------------------------

def test_attention_work_by_hand():
    c = _config()
    got = work.attention_work(c, 1, 4096)          # 5 layers + the module
    pairs = 4096 * 4097 // 2
    # QK^T (192) and PV (128) forward; dP, dQ, dK (192 / 128) and dV backward
    assert got["flops"] == 6 * 3 * 2 * (192 + 128) * pairs * 4
    qk, vo = 4 * 4096 * 192 * 2, 4 * 4096 * 128 * 2
    assert got["bytes"] == 6 * (6 * qk + 6 * vo)


def test_hc_work_by_hand():
    """A sublayer and token: the product of 4 x 3584 with 24 columns, the mix
    in (4), the streams' mix (16) and the add (4) a channel; 14, 14 and 13
    stream-widths of bytes forward, backward of hc_post, backward of
    hc_pre."""
    c = _config()
    got = work.hc_work(c, 1, 4096)
    tokens = 4096 * 2 * 6                          # two sublayers, six layers
    fwd = 2 * 14336 * 24 + 2 * 3584 * 24
    assert got["flops"] == 3 * fwd * tokens
    assert got["bytes"] == (14 + 14 + 13) * 3584 * 2 * tokens


def test_step_flops_by_hand():
    """787 MFLOP a token forward: the heads 30 %, the dense MLP 25 %, the
    latent projections 16 %, the held experts 7 %, the maps 1.3 %."""
    c = _config()
    tokens, d = 4096, 3584
    proj = 2 * tokens * (d * 768 + 768 * 768 + d * 576 + 512 * 1024 + 512 * d)
    core = 2 * (192 + 128) * (4096 * 4097 // 2) * 4
    dense = 3 * 2 * tokens * d * 9216
    routed = 2 * tokens * d * 64 + 3 * 2 * tokens * d * 1024 \
        + 3 * 2 * 2048 * d * 1024
    join = 2 * tokens * 2 * d * d
    heads = 2 * 2 * tokens * d * 16384
    fwd = 6 * (proj + core) + dense + 5 * routed + join + heads
    step = work.train_step_flops(c, 1, 4096)
    hc = work.hc_work(c, 1, 4096)["flops"]
    assert step == 3 * fwd + hc
    assert 786e6 < step / 3 / tokens < 788e6
    assert 0.29 < 3 * heads / step < 0.31
    assert 0.25 < 3 * dense / step < 0.26
    assert 0.012 < hc / step < 0.014


# --- the cell's entries and files ----------------------------------------------

def test_the_cells_entries_and_files_agree():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = run.Cell(CELL, rehearse=False)
    assert cell.chips == 1 and cell.traffic["driver"] == "xing_train"
    assert (cell.traffic["rows"], cell.traffic["seq_len"],
            cell.traffic["pool"]) == (1, 4096, 16)
    names = {m["name"] for m in cell.per_layer}
    assert set(LISTED) | set(NEW_READERS) <= names
    assert {"train_mfu", "device_idle_share", "compiles_in_window",
            "host_dispatch_ms"} <= names
    assert not {"flash_fwd_roofline", "flash_bwd_roofline", "attn_layout_ms",
                "attn_mixed_layout_ms", "attn_window_roofline",
                "attn_full_roofline", "loop_attn_roofline",
                "exit_mass_min"} & names
    entry = [c for c in bench["configs"] if c["name"] == "xing4.0-29b-a4b"][0]
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_hidden_layers", "num_attention_heads", "experts_held",
        "vocab_size"]
    assert entry["source"] == cell.config["source"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED:   # appended: later cells may follow it
        assert CELL in by_name[name]["workloads"]
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_step_ms"
    assert (by_name["hc_ms"]["layer"], by_name["hc_roofline"]["layer"]) \
        == ("model step", "kernels")
    assert set(cell.limits) <= {"loss1_gap", "loss2_gap", "loss3_gap",
                                "grad_gap", "grad_median_gap", "delta_gap",
                                "delta_median_gap"}


def test_the_configuration_holds_the_catalog_rows_numbers():
    """The catalog row's numbers under its own keys, the four cut keys (and
    only they) in ``reduced`` with the published value beside them."""
    c = _config()
    kept = {"hidden_size": 3584, "intermediate_size": 9216,
            "kv_lora_rank": 512, "q_lora_rank": 768, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "moe_intermediate_size": 1024, "n_routed_experts": 64,
            "n_shared_experts": 1, "num_experts_per_tok": 4,
            "first_k_dense_replace": 2, "hc_mult": 4,
            "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
            "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
            "rms_norm_eps": 1e-06, "rope_theta": 10000,
            "routed_scaling_factor": 2, "num_key_value_heads": 32,
            "max_position_embeddings": 262144,
            "num_nextn_predict_layers": 1}
    assert {k: c[k] for k in kept} == kept
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert (c["num_hidden_layers"], c["num_attention_heads"],
            c["vocab_size"], c["experts_held"]) == (5, 4, 16384, [0, 8])
    assert (c["num_hidden_layers_published"],
            c["num_attention_heads_published"],
            c["vocab_size_published"]) == (40, 32, 131072)
    for key in ("hc_init", "hc_init_note", "hc_norm", "hc_streams",
                "mtp_block", "rope", "bias_update_speed", "mtp_loss_weight"):
        assert key in c["assumed"]


# --- the rehearsal twin through the harness ------------------------------------

def _measure(seed=SEED):
    cell = run.Cell(TWIN, rehearse=True)
    return run.measure(cell, seed, 0.3, False, jax.local_devices()[:1])


def _state_unchanged(monkeypatch):
    from deeplearning4j_tpu.models import transformer as t
    real = t._adamw_apply
    monkeypatch.setattr(t, "_adamw_apply", lambda c, p, *a, **k:
                        (p, real(c, p, *a, **k)[1]))


def _rows_alone(monkeypatch):
    """H_res by a row normalisation, no Sinkhorn, in the program."""
    from deeplearning4j_tpu.models import transformer as t
    monkeypatch.setattr(t, "_sinkhorn", lambda m, iters, eps:
                        m / m.sum(-1, keepdims=True))


def _post_sigmoid(monkeypatch):
    """H_post at sigmoid, not 2 sigmoid, in the program."""
    from deeplearning4j_tpu.models import transformer as t
    real = t._hc_post
    monkeypatch.setattr(t, "_hc_post", lambda x, y, post, res:
                        real(x, y, post / 2, res))


def test_twin_sound_run_is_correct_and_reports_the_cells_metrics():
    out = _measure()
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"train_step_ms", "setup_s"}
    untraced = out["run"]["per_layer_untraced"]
    assert 1.0 <= untraced["moe_peak_load"] < 2.0
    assert 1.0 < untraced["moe_router_peak_load"] < 16.0
    assert untraced["compiles_in_window"] == 0
    for name in NEW_READERS:      # device readers: nothing without a trace
        assert name not in untraced


@pytest.mark.parametrize("fault", [_state_unchanged, _rows_alone,
                                   _post_sigmoid])
def test_twin_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _measure()
    assert out["correct"] is False, out["compared"]


@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_twin_control_one_precision_down_is_not_correct(seed):
    """The fp8 reference in the program's place."""
    c = run.Cell(TWIN, rehearse=True)
    driver = importlib.import_module(f"benchmark.drivers.{c.traffic['driver']}")
    job = driver.Job(c.config, c.traffic, seed, run.Spans())
    batches = job.check_batches()
    job.free()
    correct, compared = check.verify_training(
        c.config, seed, None, batches, c.limits, precision="fp8")
    assert correct is False, compared


# --- the readers ---------------------------------------------------------------

def _ctx(**changes):
    ctx = {"trace": None, "peaks": None, "work": {"step_flops": 1.0},
           "counters": {}}
    ctx.update(changes)
    return ctx


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


@pytest.mark.parametrize("metric", NEW_READERS)
def test_reader_finds_nothing_on_the_parents_program(metric, monkeypatch):
    """A trace without the scopes (the parent's program), or no trace: None,
    never 0, never a raise."""
    from benchmark import scope_reduce
    assert _read(metric, _ctx()) is None
    reduced = {"chips": 1, "instructions": {"%fusion.1": {
        "stack": "jit(step)/jvp(block.ln1)/mul", "mean_s": 0.002}}}
    monkeypatch.setattr(scope_reduce, "of", lambda ctx: reduced)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 8e11}
    assert _read(metric, _ctx(peaks=peaks)) is None


def test_the_device_readers_read_their_scopes(monkeypatch):
    """On a reduction that holds the new scopes: their milliseconds, and the
    maps' required bytes over them; nothing else counts."""
    from benchmark import scope_reduce
    reduced = {"chips": 1, "instructions": {
        "%fusion.1": {"stack": "jit(step)/jvp(block.hc_pre)/dot_general",
                      "mean_s": 0.002},
        "%fusion.2": {"stack": "jit(step)/transpose(jvp(block.hc_post))/mul",
                      "mean_s": 0.003},
        "%fusion.3": {"stack": "jit(step)/jvp(block.ln1)/mul",
                      "mean_s": 0.004}}}
    monkeypatch.setattr(scope_reduce, "of", lambda ctx: reduced)
    ctx = _ctx(work={"hc": {"flops": 1.0, "bytes": 8e11 * 0.0025}},
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 8e11})
    assert _read("hc_ms", ctx) == pytest.approx(5.0)
    assert _read("hc_roofline", ctx) == pytest.approx(50.0)
