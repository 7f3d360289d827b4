"""Xing4.0-29B-A4B's layers on the normal path (manifold-constrained
hyper-connections around every sublayer, latent attention whose q / k (12 + 4
rope) and v (8) have widths of their own under YaRN's softmax scale, a dense
first layer, a 16-way top-4 sigmoid router with a selection bias, a shared
expert and the multi-token-prediction module) against the plain reference of
the ``xing`` family (``benchmark/references/xing.py``) on seeded weights, at
the rehearsal twin's sizes: d 64, 4 streams, 4 heads, ranks 24 / 16, 16
experts top-4 of which 2 are held, T 64, three layers and the module.
"""

import copy
import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import xing_train as driver
from benchmark.references import glm
from benchmark.references import xing as ref
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models.expert_layer import expert_ffn
from deeplearning4j_tpu.models.transformer import (
    HyperConnections, Latent, LayerSpec, TransformerConfig, TransformerLM,
    _latent_qkv, _latent_scale, _rope_pairs, _sinkhorn)
from deeplearning4j_tpu.parallel.sequence_parallel import dense_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, SEED = 64, 2, 42


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def tiny_config(**changes):
    """The rehearsal twin's configuration file, computed in float32 with
    dense attention unless told otherwise."""
    config = load("rehearsal", "configs", "xing-tiny.json")
    config["assumed"].update(compute_dtype="float32", block_size=None)
    for key, value in changes.items():
        if key in config["assumed"]:
            config["assumed"][key] = value
        else:
            config[key] = value
    return config


def program(config, weights):
    lm = TransformerLM(driver.program_config(config, SEQ, SEED))
    # fresh buffers: fit_batch donates the parameters it is given
    lm.params = jax.jit(driver._to_program)(weights)
    lm._init_opt_state()
    return lm


def batches(config, n=3):
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, config["vocab_size"], (ROWS, SEQ + 1),
                         dtype=np.int32) for _ in range(n)]


def worst_leaf(got, want):
    """Largest relative error of any leaf, ``|got - want| / |want|`` in the
    L2 norm, and the leaf; ``|want|`` no less than the median leaf's, as
    ``benchmark/check.py`` floors it. Some maps have (all but) no gradient:
    the first sublayer of the stack and of the module reads n equal copies,
    whose RMSNorm'd mix depends on H_pre and H_res only through the norm's
    eps, and the last sublayer before the streams' sum has column sums of 1,
    so the sum does not depend on its H_res; their round-off is no gap."""
    assert set(got) == set(want)
    norm = lambda a: float(jnp.linalg.norm(a.ravel()))
    floor = float(np.median([norm(a) for a in want.values()]))
    gaps = {k: norm(got[k] - want[k]) / max(norm(want[k]), floor)
            for k in want}
    at = max(gaps, key=gaps.get)
    return gaps[at], at


# The program in float32 and the reference compute the same sums in another
# order (the norm over n·C applied after the maps' product, the mixes as
# float32 sums, the latent heads reshaped and joined, grouped products over
# sorted rows): float32 round-off. Read here: the loss 5e-8 apart, the worst
# gradient leaf 2e-6 in the L2 norm; with bfloat16 in float32's place the
# worst leaf reads 0.06 and each planted fault 0.05 or more: the limits sit
# between. Over three AdamW steps the matrices' change reads 3e-4 (Adam's
# m / sqrt(v) turns a leaf's round-off near zero into a share of a step); a
# gain's, an alpha's or a b's reads higher, held apart as in the glm test.
LOSS_TOL, GRAD_TOL, STEP_TOL, GAIN_STEP_TOL = 2e-6, 2e-5, 3e-3, 0.1


@pytest.fixture(scope="module")
def seeded():
    config = tiny_config()
    return config, ref.init_weights(config, SEED), batches(config)


def loss_and_grads(lm, tokens):
    tokens = jnp.asarray(tokens)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm._loss(p, tokens[:, :-1], tokens[:, 1:], None)))(lm.params)
    return float(loss), dict(driver.leaves(grads))


def reference_loss_and_grads(config, weights, tokens, fault=None):
    (loss, _), grads = jax.jit(jax.value_and_grad(lambda p: ref.loss_fn(
        config, "float32", p, ref.init_bias(config), jnp.asarray(tokens),
        fault), has_aux=True))(weights)
    return float(loss), ref.flat_leaves(grads)


@pytest.fixture(scope="module")
def sound(seeded):
    config, weights, (tokens, *_) = seeded
    return reference_loss_and_grads(config, weights, tokens)


def test_the_cells_configuration_is_the_published_one_cut_four_ways():
    real = load("configs", "xing4.0-29b-a4b.json")
    conf = driver.program_config(real, 4096, 1)
    assert (conf.d_model, conf.n_heads, conf.hd, conf.kv_group, conf.d_ff) \
        == (3584, 4, 128, 1, 9216)
    mscale = 0.1 * math.log(64) + 1.0
    assert conf.latent == Latent(q_rank=768, kv_rank=512, d_nope=128,
                                 d_rope=64, d_v=128,
                                 scale=pytest.approx(mscale ** 2))
    assert _latent_scale(conf) == pytest.approx(2.00474 / math.sqrt(192),
                                                rel=1e-5)
    assert conf.hyper == HyperConnections(streams=4, sinkhorn_iters=20,
                                          eps=1e-6, clamp=30.0)
    assert [s.ffn for s in conf.layers] == ["dense"] + ["experts"] * 4
    assert conf.mtp.ffn == "experts" and conf.mtp_weight == 0.3
    rope = conf.layer_spec(0).rope
    assert rope == conf.mtp_spec.rope
    assert (rope.base, rope.yarn_factor, rope.yarn_original_len,
            rope.yarn_beta_fast, rope.yarn_beta_slow, rope.attention_factor) \
        == (1e4, 64.0, 4096, 32.0, 1.0, 1.0)
    ex = conf.experts
    assert (ex.n_experts, ex.top_k, ex.d_expert, ex.held, ex.d_shared,
            ex.scale, ex.scoring, ex.gate, ex.bias_rate) \
        == (64, 4, 1024, (0, 8), 1024, 2.0, "sigmoid", "silu", 1e-3)
    assert ex.rows(4096) == 16384       # every assignment
    assert conf.biased_layers == ("b1", "b2", "b3", "b4", "mtp")
    assert (conf.rope_layout, conf.norm_eps, conf.remat, conf.block_size) \
        == ("interleaved", 1e-6, True, 512)


def test_the_configuration_file_holds_789610628_parameters():
    real = load("configs", "xing4.0-29b-a4b.json")
    assert ref.num_params(real) == 789_610_628
    C, n = 3584, 4
    mla = C * 768 + 768 * 4 * 192 + C * 576 + 512 * 4 * 256 + 4 * 128 * C \
        + 768 + 512
    hc = 2 * (n * C * (n + n + n * n) + 3 + n * (n + 2))
    assert (mla, hc) == (7_767_296, 688_182)
    dense = mla + hc + 3 * C * 9216 + 2 * C
    routed = mla + hc + C * 64 + 64 + 3 * C * 1024 + 2 * C \
        + 8 * 3 * C * 1024
    mtp = 3 * C + 2 * C * C + routed
    assert (dense, routed, mtp) == (107_553_078, 107_782_518, 133_483_382)
    assert dense + 4 * routed + mtp + 2 * 16384 * C + C == 789_610_628
    # the program's weights and its five routers' selection biases
    lm = TransformerLM(driver.program_config(real, 4096, 1))
    shapes, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
    lm.params = lm.opt_state = None
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        + sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
            opt["expert_bias"])) == 789_610_628
    # the published widths, unchanged; the cut beside the published counts
    cat = {"hidden_size": 3584, "q_lora_rank": 768, "kv_lora_rank": 512,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
           "v_head_dim": 128, "intermediate_size": 9216,
           "moe_intermediate_size": 1024, "n_routed_experts": 64,
           "num_experts_per_tok": 4, "routed_scaling_factor": 2,
           "hc_mult": 4, "hc_sinkhorn_iters": 20, "first_k_dense_replace": 2}
    assert {k: real[k] for k in cat} == cat
    assert (real["num_hidden_layers_published"],
            real["num_attention_heads_published"],
            real["vocab_size_published"]) == (40, 32, 131072)
    assert real["layers_held"] == [0, 2, 3, 4, 5]


@pytest.mark.parametrize("compute,ok", [("float32", True),
                                        ("bfloat16", False)])
def test_loss_and_every_gradient_leaf_match_the_reference(seeded, sound,
                                                          compute, ok):
    config, weights, (tokens, *_) = seeded
    want_loss, want = sound
    got_loss, got = loss_and_grads(
        program(tiny_config(compute_dtype=compute), weights), tokens)
    grad_gap, at = worst_leaf(got, want)
    if ok:
        assert abs(got_loss - want_loss) / want_loss <= LOSS_TOL
        assert grad_gap <= GRAD_TOL, at
    else:   # the tolerances are tight enough to tell the precision
        assert grad_gap > 10 * GRAD_TOL


@pytest.mark.parametrize("fault", ref.ARCHITECTURE_FAULTS)
def test_the_comparison_fails_under_each_architecture_fault(seeded, sound,
                                                            fault):
    """Each fault the reference can plant (what ``calibrate.py`` reads on the
    chip) moves some gradient leaf far outside the sound comparison's
    tolerance, judged by the norm of the DIFFERENCE; the one-stream fault's
    maps of streams 1-3 get no gradient at all."""
    config, weights, (tokens, *_) = seeded
    _, want = sound
    _, got = reference_loss_and_grads(config, weights, tokens, fault)
    assert worst_leaf(got, want)[0] > 100 * GRAD_TOL


def test_three_adamw_steps_and_the_bias_updates_match_the_reference(seeded):
    """Three steps of ``fit_batch`` against the reference's AdamW, each
    router's bias to the last bit, and the hyper-connections' counters: one
    step each, an error under 1e-4 after 20 rounds of Sinkhorn."""
    config, weights, three = seeded
    params, bias = weights, ref.init_bias(config)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def step(p, b, m, v, t, tokens):
        out = ref.train_step(config, "float32", None, p, b, m, v, t, tokens)
        return out[:4]
    for i, tokens in enumerate(three):
        params, bias, m, v = step(params, bias, m, v, float(i + 1),
                                  jnp.asarray(tokens))
    lm = program(config, weights)
    start = jax.device_get(dict(driver.leaves(lm.params)))
    for tokens in three:
        lm.fit_batch(tokens)
    got = {k: a - start[k] for k, a in driver.leaves(lm.params)}
    first = ref.flat_leaves(weights)
    want = {k: a - first[k] for k, a in ref.flat_leaves(params).items()}
    # leaves without a gradient (``worst_leaf``) move by Adam's normalised
    # round-off alone, as check.py's delta_gap leaves them out
    _, grads = reference_loss_and_grads(config, weights, three[0])
    norms = {k: float(jnp.linalg.norm(g.ravel())) for k, g in grads.items()}
    moved = {k for k, g in norms.items()
             if g >= 1e-3 * float(np.median(list(norms.values())))}
    got, want = ({k: d[k] for k in moved} for d in (got, want))
    matrices = {k for k, a in want.items() if a.ndim >= 2}
    gap, at = worst_leaf({k: got[k] for k in matrices},
                         {k: want[k] for k in matrices})
    assert gap <= STEP_TOL, at
    gap, at = worst_leaf(got, want)
    assert gap <= GAIN_STEP_TOL, at
    for k, b in bias.items():
        np.testing.assert_array_equal(lm.opt_state["expert_bias"][k], b)
    counters = lm.hc_counters()
    assert counters["hc.steps"] == 3
    assert 0 <= counters["hc.sinkhorn_err"] <= 3 * 100
    assert lm.moe_counters()["moe.rows_over_buffer"] == 0


@pytest.mark.parametrize("spread,after_20", [(0.024, 3e-6), (2.4, 0.05)])
def test_sinkhorn_makes_every_row_and_column_sum_to_one(spread, after_20):
    """The program's and the reference's Sinkhorn on exp of logits of the
    maps' spread at Zhu et al.'s gating factor (0.024) and at the cell's
    (2.4), 256 maps: the two agree; the columns, normalised last, sum to 1,
    and the rows within what 20 rounds leave at that spread (the worst of
    256 maps at 2.4 reads 0.028: what ``hc.sinkhorn_err`` counts), and within
    1e-4 after 300."""
    z = spread * jax.random.normal(jax.random.PRNGKey(3), (256, 4, 4))
    m = jnp.exp(jnp.clip(z, -30.0, 30.0))
    got = _sinkhorn(m, 20, 1e-6)
    np.testing.assert_allclose(got, ref.sinkhorn(m, 20, 1e-6), rtol=1e-5,
                               atol=1e-7)
    assert float(jnp.abs(got.sum(-2) - 1).max()) < 1e-5
    assert float(jnp.abs(got.sum(-1) - 1).max()) < after_20
    assert float(jnp.abs(_sinkhorn(m, 300, 1e-6).sum(-1) - 1).max()) < 1e-4
    assert float(got.min()) >= 0.0


def test_yarn_turns_and_scales_as_the_reference_reads_rope_scaling():
    """The program's cos / sin of the 64 rope dims at the cell's YaRN
    settings against the reference's float64 tables, unscaled (mscale /
    mscale_all_dim = 1); the softmax scale is 192^-1/2 x 2.0048."""
    real = load("configs", "xing4.0-29b-a4b.json")
    conf = driver.program_config(real, 4096, 1)
    cos, sin = _rope_pairs(conf.layer_spec(0).rope, 64, None, 4096)
    want_cos, want_sin = ref.rope_tables(real, 4096)
    np.testing.assert_allclose(cos, want_cos, atol=2e-4)
    np.testing.assert_allclose(sin, want_sin, atol=2e-4)
    assert ref.softmax_scale(real) == pytest.approx(
        (0.1 * math.log(64) + 1) ** 2 / math.sqrt(192))
    assert ref.softmax_scale(real, "no_mscale") == 192 ** -0.5


def _share(config, lp, heads, experts):
    """One chip's share of a layer: heads ``heads`` (their columns of W_uq,
    W_ukv, their rows of W_o) and experts ``experts``."""
    cut = copy.deepcopy(config)
    H = config["num_attention_heads"]
    h0, h1 = heads
    cut["num_attention_heads"] = h1 - h0
    cut["experts_held"] = list(experts)
    q, kv = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
             config["qk_nope_head_dim"] + config["v_head_dim"])
    lp = dict(lp)
    lp["q_b_proj"] = lp["q_b_proj"].reshape(-1, H, q)[:, h0:h1].reshape(
        lp["q_b_proj"].shape[0], -1)
    lp["kv_b_proj"] = lp["kv_b_proj"].reshape(-1, H, kv)[:, h0:h1].reshape(
        lp["kv_b_proj"].shape[0], -1)
    dv = config["v_head_dim"]
    lp["o_proj"] = lp["o_proj"][h0 * dv:h1 * dv]
    e0, e1 = experts
    for name in ("experts_gate", "experts_up", "experts_down"):
        lp[name] = lp[name][e0:e1]
    return cut, lp


def _attention(who, config, h, lp):
    """The attention sublayer's output after W_o, [B, T, d]."""
    if who == "reference":
        return jax.vmap(lambda hr: glm.matmul(
            ref.latent_attention(config, "float32", None, hr, lp),
            lp["o_proj"], "float32"))(h)
    conf = driver.program_config(config, SEQ, SEED)
    bp = driver._layer(lp)
    q, k, v = _latent_qkv(conf, bp, h, conf.layer_spec(1), None)
    o = dense_attention(q, k, v, causal=True, scale=_latent_scale(conf))
    B, H, T, dv = o.shape
    return o.transpose(0, 2, 1, 3).reshape(B, T, H * dv) @ bp["proj"]


def _ffn(who, config, u, lp, bias):
    if who == "program":
        ex = driver.program_config(config, SEQ, SEED).experts
        ep = {k: lp[v] for k, v in driver.LEAVES.items() if v in lp}
        out, stats = expert_ffn(ex, dict(ep, router_bias=bias), u)
        assert int(stats["rows_over_buffer"]) == 0
        return out
    flat = u.reshape(-1, u.shape[-1])
    w, _ = glm.routing(config, "float32", None, flat, lp["router"], bias)
    return (glm.swiglu("float32", flat, lp["shared_gate"], lp["shared_up"],
                       lp["shared_down"])
            + glm.experts(config, "float32", flat, w, lp)).reshape(u.shape)


@pytest.mark.parametrize("who", ["program", "reference"])
def test_the_eight_shares_add_up_to_the_uncut_layer(who):
    """Eight chips, each with 2 of 16 heads and 2 of 16 experts: their
    attention outputs after W_o add up to the whole layer's (W_dq, W_dkv
    and the latent norms, which every chip holds whole, computed alike), and
    their routed parts, with the shared expert counted once, to the whole
    expert layer's."""
    whole = tiny_config(num_attention_heads=16, experts_held=[0, 16])
    weights = ref.init_weights(whole, SEED)
    lp = weights["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (ROWS, SEQ, 64))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(6), (16,))
    want_attn = _attention(who, whole, h, lp)
    want_ffn = _ffn(who, whole, h, lp, bias)
    shared = glm.swiglu("float32", h, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
    attn, ffn = 0.0, shared
    for s in range(8):
        cut, cut_lp = _share(whole, lp, (2 * s, 2 * s + 2), (2 * s, 2 * s + 2))
        attn = attn + _attention(who, cut, h, cut_lp)
        ffn = ffn + _ffn(who, cut, h, cut_lp, bias) - shared
    np.testing.assert_allclose(attn, want_attn, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(ffn, want_ffn, rtol=2e-5, atol=2e-5)


def test_the_kernel_route_trains_the_same_model(seeded, monkeypatch):
    """Flash kernels at q / k 16 against v 8 under YaRN's scale and the
    streams' passes as kernels (interpret mode; the twin widened to one
    lane tile, 128, where they run), remat and the bfloat16 the twin
    states, against the float32 reference within bfloat16's reach; every
    sublayer of the stack and the module took the kernels."""
    _, _, (tokens, *_) = seeded
    config = tiny_config(hidden_size=128)
    weights = ref.init_weights(config, SEED)
    want = float(ref.loss_fn(config, "float32", weights, ref.init_bias(config),
                             jnp.asarray(tokens))[0])
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    obs.reset_metrics()
    lm = program(tiny_config(hidden_size=128, compute_dtype="bfloat16",
                             block_size=16), weights)
    got = float(lm.fit_batch(tokens))
    assert abs(got - want) / want < 2e-3   # bfloat16: 8 mantissa bits
    assert lm.moe_counters()["moe.rows_over_buffer"] == 0
    assert obs.metrics.value("hc.kernel_sublayers") > 0
    assert obs.metrics.value("hc.xla_sublayers") == 0


def sublayers_by_route(conf, seq=32):
    """``(hc.kernel_sublayers, hc.xla_sublayers)`` once the training step
    of ``conf`` is traced (interpret mode, shapes only)."""
    lm = TransformerLM(conf)
    params, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
    lm.params = lm.opt_state = None
    tokens = jax.ShapeDtypeStruct((ROWS, seq), jnp.int32)
    obs.reset_metrics()
    lm._build_step().trace(
        params, opt, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), tokens, tokens, None)
    read = (obs.metrics.value("hc.kernel_sublayers"),
            obs.metrics.value("hc.xla_sublayers"))
    obs.reset_metrics()
    return read


def test_a_width_of_no_whole_lane_tiles_takes_xla_passes(monkeypatch):
    """The twin as it stands (d 64) takes XLA's passes where the kernels
    could run: all eight sublayers of its three layers and the module;
    at 128 the kernels take all eight."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    for width, want in ((64, (0, 8)), (128, (8, 0))):
        conf = driver.program_config(tiny_config(hidden_size=width), SEQ,
                                     SEED)
        assert sublayers_by_route(conf) == want, width


def test_a_model_without_hyper_connections_counts_no_sublayer(monkeypatch):
    """Without ``hyper`` neither pass is traced: both gauges stay at 0 (the
    other models' steps lower to their former text:
    tests/test_ouro.py::LOWERED_BEFORE)."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    conf = TransformerConfig(vocab_size=64, max_len=32, d_model=128,
                             n_heads=2, n_layers=2, d_ff=64)
    assert sublayers_by_route(conf) == (0, 0)


# --- refusals by name ----------------------------------------------------------

HYPER = dict(vocab_size=64, max_len=16, d_model=32, n_heads=2,
             hyper=HyperConnections())


def test_trainers_of_one_block_refuse_hyper_connections_by_name():
    with pytest.raises(NotImplementedError, match="hyper"):
        TransformerConfig(**HYPER).one_block("the tensor-parallel trainer")


def test_serving_refuses_hyper_connections_by_name():
    lm = TransformerLM(TransformerConfig(**HYPER)).init()
    with pytest.raises(NotImplementedError, match="hyper"):
        lm.generate(np.zeros((1, 4), np.int32), 2)


@pytest.mark.parametrize("changes", [dict(loops=2), dict(post_norm=True),
                                     dict(exit_gate=True)])
def test_hyper_connections_on_a_looped_stack_are_refused_by_name(changes):
    with pytest.raises(NotImplementedError, match="hyper"):
        TransformerConfig(**HYPER, **changes)


def test_a_plain_model_with_hyper_connections_trains_and_counts():
    """Hyper-connections without experts or latent attention: the streams,
    the maps and the counters on the GPT-2 block."""
    lm = TransformerLM(TransformerConfig(**HYPER)).init()
    tokens = np.random.default_rng(0).integers(0, 64, (2, 17), np.int32)
    first = float(lm.fit_batch(tokens))
    assert math.isfinite(first) and lm.num_params() > 0
    assert lm.hc_counters()["hc.steps"] == 1
    assert lm.moe_counters() == {}


@pytest.mark.parametrize("latent", [
    Latent(8, 8, 12, 4, 8),       # v narrower than q / k: taken
    Latent(8, 8, 4, 4, 16)])      # v wider than q / k: refused
def test_v_may_be_narrower_than_q_and_k_and_never_wider(latent):
    make = lambda: TransformerConfig(
        **dict(HYPER, hyper=None), pos_embed="rope", head_dim=latent.d_v,
        latent=latent)
    if latent.d_v <= latent.d_nope + latent.d_rope:
        assert dataclasses.replace(make()).latent == latent
        return
    with pytest.raises(ValueError, match="latent"):
        make()


def test_the_mtp_module_layer_has_maps_of_its_own(seeded):
    config, weights, _ = seeded
    lm = program(config, weights)
    block = lm.params["mtp"]["block"]
    assert block["hc1"].shape == (4 * 64, 24) and block["hc2_b"].shape == (24,)
    assert {"hc1", "hc1_alpha", "hc1_b", "hc2", "hc2_alpha", "hc2_b"} \
        <= set(block)
    assert lm.conf.mtp == LayerSpec(ffn="experts", rope=lm.conf.mtp.rope)
