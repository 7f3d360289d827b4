"""The per-layer vocabulary of ``TransformerConfig`` (window and full
attention layers with their own head counts and rotary embeddings, RMSNorm,
SwiGLU, the attention gate, an untied head) and the expert layer that holds
its share of the experts, against the plain reference of the ``laguna`` family
(``benchmark/references/laguna.py``) on seeded weights, at the rehearsal
twin's sizes: d 64, heads of 16, 4 / 6 query heads by layer type over 2
key/value heads, window 8, T 32, 16 experts top-2, five layers in the cut's
pattern (full + dense, then window, window, window, full with experts).
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

from benchmark.drivers import laguna_train as driver
from benchmark.references import laguna as ref
from deeplearning4j_tpu.models import expert_layer
from deeplearning4j_tpu.models.expert_layer import Experts, expert_ffn
from deeplearning4j_tpu.models.transformer import (Rope, TransformerConfig,
                                                   TransformerLM, _apply_rope,
                                                   _rope_cos_sin)
from deeplearning4j_tpu.parallel.sequence_parallel import dense_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, SEED = 32, 2, 11


def tiny_config(**changes):
    """The rehearsal twin's configuration file, every expert's row kept
    (``expert_row_buffer`` 8 = ``num_experts`` / held: the layer is exact for
    every routing), computed in float32 with dense attention unless told
    otherwise."""
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                           "laguna-tiny.json")) as f:
        config = json.load(f)
    config["assumed"].update(compute_dtype="float32", block_size=None,
                             expert_row_buffer=8.0)
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


def program_leaves(tree):
    """A program tree under the reference's names, the fused ``qkv`` split:
    ``{"b1.q_proj": array}``."""
    return dict(driver.leaves(tree))


def reference_leaves(tree):
    flat = {k: v for k, v in tree.items() if k != "layers"}
    for i, lp in enumerate(tree["layers"]):
        flat.update({f"b{i}.{k}": v for k, v in lp.items()})
    return flat


def worst_leaf(got, want):
    """Largest relative error of any leaf, ``|got - want| / |want|`` in the
    L2 norm, and the leaf."""
    assert set(got) == set(want)
    gaps = {k: float(jnp.linalg.norm((got[k] - want[k]).ravel())
                     / jnp.linalg.norm(want[k].ravel())) for k in want}
    at = max(gaps, key=gaps.get)
    return gaps[at], at


# The program in float32 and the reference compute the same sums in another
# order (fused qkv, grouped products over sorted rows, a scatter-add back):
# float32 round-off. Read here: the loss 2.3e-7 apart, the worst gradient leaf
# 6e-7 in the L2 norm, the worst leaf's change over three AdamW steps 1.2e-4
# (Adam divides by the gradient's own size, so an entry whose gradient is
# round-off moves by the learning rate in either direction). With bfloat16 in
# float32's place the same three read 3.5e-5, 0.19 and 0.31: the limits sit
# between, ten times off each side, and the second case of each test holds
# that bfloat16 is refused.
LOSS_TOL, GRAD_TOL, STEP_TOL = 2e-6, 1e-5, 1e-3


@pytest.fixture(scope="module")
def seeded():
    config = tiny_config()
    return config, ref.init_weights(config, SEED), batches(config)


def loss_and_grads(config, weights, tokens):
    lm = program(config, weights)
    tokens = jnp.asarray(tokens)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm._loss(p, tokens[:, :-1], tokens[:, 1:], None)))(lm.params)
    return float(loss), program_leaves(grads)


@pytest.mark.parametrize("compute,sound", [("float32", True),
                                           ("bfloat16", False)])
def test_loss_and_every_gradient_leaf_match_the_reference(seeded, compute,
                                                          sound):
    config, weights, (tokens, *_) = seeded
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(config, "float32", p, jnp.asarray(tokens))))(
            weights)
    got_loss, got = loss_and_grads(tiny_config(compute_dtype=compute),
                                   weights, tokens)
    loss_gap = abs(got_loss - float(want_loss)) / float(want_loss)
    grad_gap, at = worst_leaf(got, reference_leaves(want))
    if sound:
        assert loss_gap <= LOSS_TOL
        assert grad_gap <= GRAD_TOL, at
    else:   # the tolerances are tight enough to tell the precision
        assert grad_gap > 10 * GRAD_TOL


@pytest.mark.parametrize("compute,sound", [("float32", True),
                                           ("bfloat16", False)])
def test_three_adamw_steps_match_the_reference(seeded, compute, sound):
    config, weights, three = seeded
    opt = config["assumed"]["optimizer"]
    params = weights
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(lambda p, m, v, t, tokens: ref.adamw(
        opt, p, jax.grad(lambda q: ref.loss_fn(config, "float32", q, tokens))(
            p), m, v, t))
    for i, tokens in enumerate(three):
        params, m, v = step(params, m, v, float(i + 1), jnp.asarray(tokens))
    lm = program(tiny_config(compute_dtype=compute), weights)
    start = jax.device_get(program_leaves(lm.params))
    for tokens in three:
        lm.fit_batch(tokens)
    got = {k: a - start[k] for k, a in program_leaves(lm.params).items()}
    first = reference_leaves(weights)
    want = {k: a - first[k] for k, a in reference_leaves(params).items()}
    gap, at = worst_leaf(got, want)
    if sound:
        assert gap <= STEP_TOL, at
        assert lm.moe_counters()["moe.rows_over_buffer"] == 0
    else:
        assert gap > 10 * STEP_TOL


def test_the_kernel_route_trains_the_same_model(seeded):
    """Flash kernels (interpret mode), remat and the bfloat16 the rehearsal
    twin states, against the float32 reference within bfloat16's reach."""
    config, weights, (tokens, *_) = seeded
    want = float(ref.loss_fn(config, "float32", weights, jnp.asarray(tokens)))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
        mp.setenv("DL4J_TPU_LM_ATTN", "pallas")
        lm = program(tiny_config(compute_dtype="bfloat16", block_size=16),
                     weights)
        got = float(lm.fit_batch(tokens))
    assert abs(got - want) / want < 2e-3   # bfloat16: 8 mantissa bits


# --- the expert layer --------------------------------------------------------

def layer_inputs(config, seed=3):
    d = config["hidden_size"]
    kind = ("full_attention", 4, "sparse")
    shapes = ref.layer_shapes(config, kind)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes) + 1)
    lp = {name: 0.2 * jax.random.normal(k, shape)
          for k, (name, shape) in zip(keys, sorted(shapes.items()))}
    h = jax.random.normal(keys[-1], (ROWS, SEQ, d))
    return h, lp


def program_experts(config, lp, **changes):
    first, count = ref.held(config)
    ex = Experts(n_experts=config["num_experts"],
                 top_k=config["num_experts_per_tok"],
                 d_expert=config["moe_intermediate_size"],
                 held=(first, count),
                 scale=config["moe_routed_scaling_factor"],
                 d_shared=config["shared_expert_intermediate_size"],
                 row_buffer=config["num_experts"] / count)
    ex = dataclasses.replace(ex, **changes)
    ep = {"router": lp["router"], "W_gate": lp["experts_gate"],
          "W_up": lp["experts_up"], "W_down": lp["experts_down"],
          "sh_gate": lp["shared_gate"], "sh_up": lp["shared_up"],
          "sh_down": lp["shared_down"]}
    return ex, ep


def reference_layer(config, h, lp):
    return jnp.stack([ref.experts(config, "float32", row, lp) for row in h])


def share_of(config, lp, first, count):
    """The configuration and the weights of the chip that holds the experts
    [first, first + count)."""
    cut = copy.deepcopy(config)
    cut["experts_held"] = [first, first + count]
    lp = dict(lp)
    for name in ("experts_gate", "experts_up", "experts_down"):
        lp[name] = lp[name][first:first + count]
    return cut, lp


@pytest.mark.parametrize("who", ["program", "reference"])
def test_the_shares_add_up_to_the_uncut_layer(who):
    """Four chips with four of the sixteen experts each: the routed parts of
    all four, plus the shared expert once, are the whole layer."""
    whole = tiny_config(experts_held=[0, 16])
    h, lp = layer_inputs(whole)
    want = reference_layer(whole, h, lp)
    shared = jnp.stack([ref.swiglu("float32", row, lp["shared_gate"],
                                   lp["shared_up"], lp["shared_down"])
                        for row in h])
    total = shared
    for first in range(0, 16, 4):
        cut, cut_lp = share_of(whole, lp, first, 4)
        if who == "program":
            ex, ep = program_experts(cut, cut_lp, d_shared=0)
            routed, stats = expert_ffn(ex, ep, h)
            assert int(stats["rows_over_buffer"]) == 0
        else:
            routed = reference_layer(cut, h, cut_lp) - shared
        total = total + routed
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_one_expert_taking_most_rows_is_still_exact_with_room_for_all():
    """A router that sends every token to held expert 2 first: with the
    buffer at every assignment the layer equals the reference."""
    config = tiny_config()
    h, lp = layer_inputs(config)
    # positive inputs and a large positive column: expert 2 wins every token
    h = jnp.abs(h)
    lp["router"] = lp["router"].at[:, 2].set(1.0)
    ex, ep = program_experts(config, lp)
    got, stats = expert_ffn(ex, ep, h)
    chosen = expert_layer.route(ex, h.reshape(-1, h.shape[-1]),
                                ep["router"])[1]
    assert int((chosen == 2).sum()) == ROWS * SEQ
    assert int(stats["rows_over_buffer"]) == 0
    assert int(stats["local_rows"]) >= ROWS * SEQ
    np.testing.assert_allclose(got, reference_layer(config, h, lp),
                               rtol=1e-5, atol=1e-5)


def test_rows_past_the_buffer_are_counted_never_silent():
    """The same routing into a buffer of the balanced load: what does not
    fit is left out and ``rows_over_buffer`` says how many. The groups then
    fill the buffer, and ``rows_computed``, the rows the products visit, is
    all of it."""
    config = tiny_config()
    h, lp = layer_inputs(config)
    h = jnp.abs(h)
    lp["router"] = lp["router"].at[:, 2].set(1.0)
    ex, ep = program_experts(config, lp, row_buffer=1.0)
    rows = ex.rows(ROWS * SEQ)
    assert rows == ROWS * SEQ * 2 * 8 // 16
    _, stats = expert_ffn(ex, ep, h)
    assert int(stats["rows_computed"]) == rows
    assert int(stats["local_rows"]) > rows
    assert int(stats["rows_over_buffer"]) == int(stats["local_rows"]) - rows


@pytest.mark.parametrize("products", ["pallas", "ragged_dot"])
def test_rows_computed_is_the_rows_the_products_visit(products, monkeypatch):
    """With room for every assignment the buffer's tail lies in no group:
    ``rows_computed`` is the groups' sum where ``ragged_dot`` runs the
    products, and the walk's steps times the row tile where the Pallas
    product does: at most a tile a held expert over the assignments."""
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    if products == "pallas":
        monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    config = tiny_config()
    h, lp = layer_inputs(config)
    ex, ep = program_experts(config, lp)
    tokens, count = ROWS * SEQ, ex.held_range[1]
    rows = ex.rows(tokens)
    assert rows == tokens * ex.top_k
    obs.metrics.gauge("moe.row_tile").set(-1)
    w, chosen = expert_layer.route(ex, h.reshape(tokens, -1), ep["router"])
    routing = expert_layer.Routing(w, *expert_layer.dispatch(ex, chosen,
                                                             tokens))
    stats = {k: int(v) for k, v in routing.stats.items()}
    local = stats["local_rows"]
    assert 0 < local < rows and int(routing.group_sizes.sum()) == local
    assert int(routing.valid.sum()) == local
    if products == "ragged_dot":
        assert routing.tiles is None
        assert stats["rows_computed"] == local
        assert obs.metrics.value("moe.row_tile") == -1
    else:
        tile = pk.grouped_row_tile(tokens * ex.top_k // ex.n_experts)
        assert obs.metrics.value("moe.row_tile") == tile == 8
        sizes = np.asarray(routing.group_sizes)
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        visited = sum(1 if hi == lo else (hi - 1) // tile - lo // tile + 1
                      for lo, hi in zip(bounds[:-1], bounds[1:]))
        assert stats["rows_computed"] == int(routing.tiles[-1]) * tile \
            == visited * tile
        assert 1.0 <= stats["rows_computed"] / local \
            <= 1 + count * tile / local
        assert stats["rows_computed"] < rows
    got, _ = expert_ffn(ex, ep, h, routing)
    np.testing.assert_allclose(got, reference_layer(config, h, lp),
                               rtol=1e-5, atol=1e-5)


def test_the_pallas_products_leave_no_trace_of_the_rows_in_no_group(
        monkeypatch):
    """The layer and every gradient of it (tokens, router, the three expert
    weights) under the Pallas product, whose rows outside every group are
    never written (NaN under the interpreter), against ``ragged_dot`` over
    the same groups, whose rows there are zeros on the CPU."""
    config = tiny_config()
    h, lp = layer_inputs(config)
    ex, ep = program_experts(config, lp)

    def loss(ep, h):
        y, _ = expert_ffn(ex, ep, h)
        return jnp.square(y).sum()

    want = jax.value_and_grad(loss, (0, 1))(ep, h)
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    got = jax.value_and_grad(loss, (0, 1))(ep, h)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


def _equations(eqn):
    """An equation and those of every jaxpr under it."""
    yield eqn
    for value in eqn.params.values():
        for item in (value if isinstance(value, (list, tuple)) else [value]):
            item = getattr(item, "jaxpr", item)
            for sub in getattr(item, "eqns", ()):
                yield from _equations(sub)


@pytest.mark.parametrize("dtype,row_buffer", [
    ("float32", 8.0), ("bfloat16", 8.0), ("float32", 2.0), ("float32", 0.5)])
def test_the_rows_move_the_same_along_the_walk_and_over_the_buffer(
        dtype, row_buffer, monkeypatch):
    """The layer at the rehearsal configuration (top 2 of 16, 2 held, a
    shared expert, weights scaled by 2.5), loss and every gradient: the
    Pallas path, whose rows move one DMA each over the row tiles the walk
    visits (``gather_rows``, ``combine_rows``: no gather, no scatter-add and
    no select over the buffer is left in its jaxpr), against the
    ``ragged_dot`` path's XLA gather, float32 select and scatter-add; with a
    buffer of every assignment, of twice the balanced load, and of half of
    it (assignments left out, on both paths the same ones)."""
    config = tiny_config()
    h, lp = layer_inputs(config)
    ex, ep = program_experts(config, lp, row_buffer=row_buffer)
    ep, h = jax.tree.map(lambda a: a.astype(dtype), (ep, h))

    def loss(ep, h):
        y, stats = expert_ffn(ex, ep, h)
        return jnp.square(y.astype(jnp.float32)).sum(), stats

    (want, stats), want_grads = jax.value_and_grad(loss, (0, 1), True)(ep, h)
    assert (int(stats["rows_over_buffer"]) > 0) == (row_buffer < 1)
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    (got, _), got_grads = jax.value_and_grad(loss, (0, 1), True)(ep, h)
    tol = 1e-4 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * np.abs(w).max())
    jaxpr = jax.make_jaxpr(jax.grad(lambda ep, h: loss(ep, h)[0], (0, 1)))(
        ep, h)
    found = [sub for eqn in jaxpr.eqns for sub in _equations(eqn)]
    # the gather and the combine, each behind the pass that lays its source
    # out as words, around the three products; backward two, six, two (the
    # combine's is the gather with the rows' weights and dots, the gather's
    # the combine with unit weights)
    assert sum(e.primitive.name == "pallas_call" for e in found) == 7 + 10
    # and XLA moves no row: what it still gathers and scatters (the chosen
    # logits, the rows' weights and numbers) is narrower than one
    d = h.shape[-1]
    assert not [e for e in found
                if e.primitive.name in ("gather", "scatter", "scatter-add")
                and e.outvars[0].aval.shape[-1:] == (d,)]


def test_the_drivers_window_fails_when_a_row_was_left_out():
    """``moe.rows_over_buffer`` > 0 fails every step of the window."""
    from benchmark import run
    config = tiny_config(compute_dtype="bfloat16", expert_row_buffer=0.25)
    traffic = {"driver": "laguna_train", "rows": ROWS, "seq_len": SEQ,
               "pool": 2}
    job = driver.Job(config, traffic, SEED, run.Spans())
    job.first_steps()
    out = job.window(0.2)
    assert job.counters()["moe.rows_over_buffer"] > 0
    assert out["failed"] == out["steps"] > 0


@pytest.mark.parametrize("top_k,scale", [(1, 1.0), (2, 2.5), (8, 2.0)])
def test_routing_weights(top_k, scale):
    """Sigmoid scores, the ``top_k`` largest, normalised to sum 1, scaled."""
    ex = Experts(n_experts=8, top_k=top_k, d_expert=4, scale=scale)
    h = jax.random.normal(jax.random.PRNGKey(0), (5, 6))
    router = jax.random.normal(jax.random.PRNGKey(1), (6, 8))
    w, chosen = expert_layer.route(ex, h, router)
    scores = 1 / (1 + np.exp(-np.asarray(h @ router, np.float64)))
    order = np.argsort(-scores, -1)[:, :top_k]
    np.testing.assert_array_equal(chosen, order)
    want = np.take_along_axis(scores, order, -1)
    np.testing.assert_allclose(w, scale * want / want.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_experts_refuses_what_it_cannot_hold():
    with pytest.raises(ValueError, match="held"):
        Experts(n_experts=8, top_k=2, d_expert=4, held=(6, 4))
    with pytest.raises(ValueError, match="top_k"):
        Experts(n_experts=8, top_k=9, d_expert=4)
    with pytest.raises(ValueError, match="row_buffer"):
        Experts(n_experts=8, top_k=2, d_expert=4, row_buffer=0.0)


# --- rotary embeddings against the formulas ----------------------------------

def yarn_by_the_paper(base, rot, factor, original, beta_fast, beta_slow):
    """Peng et al. 2023, section 3.2, written per dim: ``r = original /
    wavelength`` turns over the original context; the dim keeps its frequency
    where r > beta_fast, is interpolated where r < beta_slow. The ramp runs
    over the integer dims that bound the two (``find_correction_range``)."""
    def dim_of(turns):
        return rot * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), rot - 1)
    i = np.arange(rot // 2)
    freq = base ** (-2.0 * i / rot)
    keep = 1 - np.clip((i - low) / max(high - low, 0.001), 0, 1)
    return freq / factor * (1 - keep) + freq * keep


ROPES = {
    "plain": (Rope(base=10000.0), "interleaved"),
    "half": (Rope(base=10000.0), "half"),
    "partial_half": (Rope(base=500000.0, share=0.5), "half"),
    "yarn_partial_half": (Rope(base=500000.0, share=0.5, yarn_factor=64.0,
                               yarn_original_len=4096, yarn_beta_fast=64.0,
                               yarn_beta_slow=1.0,
                               attention_factor=1.4158883083359672), "half"),
    "yarn_own_factor": (Rope(base=10000.0, yarn_factor=4.0,
                             yarn_original_len=16, yarn_beta_fast=4.0),
                        "interleaved"),
}


@pytest.mark.parametrize("name", sorted(ROPES))
def test_rope_against_the_formulas(name):
    rope, layout = ROPES[name]
    hd, T = 128, 40
    rot = int(hd * rope.share)
    freq = rope.base ** (-2.0 * np.arange(rot // 2) / rot)
    scale = 1.0
    if rope.yarn_factor is not None:
        freq = yarn_by_the_paper(rope.base, rot, rope.yarn_factor,
                                 rope.yarn_original_len, rope.yarn_beta_fast,
                                 rope.yarn_beta_slow)
        scale = rope.attention_factor \
            or 0.1 * math.log(rope.yarn_factor) + 1.0
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (3, T, hd)),
                   np.float64)
    ang = np.arange(T)[:, None] * freq
    cos, sin = np.cos(ang) * scale, np.sin(ang) * scale
    want = x.copy()
    pairs = [(2 * i, 2 * i + 1) for i in range(rot // 2)] \
        if layout == "interleaved" \
        else [(i, i + rot // 2) for i in range(rot // 2)]
    for i, (a, b) in enumerate(pairs):
        want[..., a] = x[..., a] * cos[:, i] - x[..., b] * sin[:, i]
        want[..., b] = x[..., a] * sin[:, i] + x[..., b] * cos[:, i]
    got_cos, got_sin = _rope_cos_sin(rope, hd, jnp.arange(T))
    got = _apply_rope(jnp.asarray(x, jnp.float32), got_cos, got_sin, layout,
                      rot if rot < hd else None)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    if rot < hd:   # the dims past the rotated share pass as they are
        np.testing.assert_array_equal(got[..., rot:],
                                      x[..., rot:].astype(np.float32))
    if name == "yarn_partial_half":
        # the published attention_factor is YaRN's own 0.1 ln(64) + 1
        assert abs(scale - (0.1 * math.log(64) + 1)) < 1e-12
        # dims that turn more than 64 times in 4096 positions keep theirs;
        # the slowest are divided by the factor
        assert freq[0] == 1.0 and abs(freq[-1] * 64 - 500000.0 ** (
            -2.0 * (rot // 2 - 1) / rot)) < 1e-12


# --- the flash kernels at a head of 16, in groups, under a window ------------

# T 32 is a row of 4 blocks; the cell's rows are 16 blocks in groups of 8
# (window layers) and 6 (full layers): T 128, where the grid walks 31 and
# 136 of a row's 256 block pairs
@pytest.mark.parametrize("window,kv_group,T", [
    (8, 3, 32), (None, 3, 32), (8, 2, 32), (None, 2, 32), (20, 3, 32),
    (8, 8, 128), (None, 6, 128), (12, 6, 64)])
def test_flash_kernels_in_groups_under_a_window_match_dense(window, kv_group,
                                                            T):
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
    kv, hd, block = 2, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (2, kv * kv_group, T, hd))
    k = jax.random.normal(keys[1], (2, kv, T, hd))
    v = jax.random.normal(keys[2], (2, kv, T, hd))
    g = jax.random.normal(keys[3], q.shape)

    def dense(q, k, v):
        return dense_attention(q, jnp.repeat(k, kv_group, 1),
                               jnp.repeat(v, kv_group, 1), causal=True,
                               window=window)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, window=window)

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
        got, got_vjp = jax.vjp(flash, q, k, v)
        got_grads = got_vjp(g)
    want, want_vjp = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for a, b in zip(got_grads, want_vjp(g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# --- the configuration's vocabulary ------------------------------------------

def test_the_gpt2_setting_is_the_parents_step_bit_for_bit():
    """The defaults of the new fields are the GPT-2 block: the first three
    losses of a seeded model are, to the bit, what the tree before the
    per-layer list gave (read there on this CPU; the lowered step's text is
    the same too, CHANGES.md PR 28)."""
    before = {
        (): ["0x1.0aab680000000p+2", "0x1.097e160000000p+2",
             "0x1.0856fe0000000p+2"],
        (("pos_embed", "rope"), ("n_kv_heads", 1), ("window", 8)):
            ["0x1.0aca800000000p+2", "0x1.0987420000000p+2",
             "0x1.084c160000000p+2"]}
    tokens = np.random.default_rng(5).integers(0, 64, (4, 33)).astype(np.int32)
    for changes, want in before.items():
        lm = TransformerLM(TransformerConfig(
            vocab_size=64, max_len=32, d_model=32, n_heads=2, n_layers=2,
            d_ff=64, seed=3, **dict(changes))).init()
        assert [float(lm.fit_batch(tokens)).hex() for _ in range(3)] == want


def mixed(**changes):
    from deeplearning4j_tpu.models.transformer import LayerSpec
    base = dict(vocab_size=64, max_len=32, d_model=32, n_heads=2, n_layers=2,
                d_ff=64, head_dim=8, n_kv_heads=1, pos_embed="rope",
                layers=(LayerSpec(), LayerSpec(window=4, n_heads=4)))
    base.update(changes)
    return TransformerConfig(**base)


@pytest.mark.parametrize("changes,message", [
    (dict(layers=(None,)), "layers listed"),
    (dict(n_kv_heads=3), "not divisible"),
    (dict(norm="batchnorm"), "norm"),
    (dict(ffn="relu"), "ffn"),
    (dict(rope_layout="thirds"), "rope_layout"),
    (dict(head_dim=7), "even"),
])
def test_a_configuration_that_cannot_be_built_is_refused_by_name(changes,
                                                                 message):
    with pytest.raises(ValueError, match=message):
        mixed(**changes)


def test_a_layer_with_experts_needs_the_experts_setting():
    from deeplearning4j_tpu.models.transformer import LayerSpec
    with pytest.raises(ValueError, match="experts"):
        mixed(layers=(LayerSpec(), LayerSpec(ffn="experts")))


@pytest.mark.parametrize("call", ["generate", "beam_search", "continuous"])
def test_serving_refuses_the_new_settings_by_name(call):
    """``generate``, ``beam_search`` and ``ContinuousLM`` call the one block and
    serve what it takes, but experts: a row buffer and counters beside the
    optimizer's state have no meaning for one decoded token, so they are
    refused by name, never run."""
    from deeplearning4j_tpu.models.transformer import LayerSpec
    lm = TransformerLM(mixed(
        experts=Experts(n_experts=4, top_k=2, d_expert=16),
        layers=(LayerSpec(), LayerSpec(window=4, ffn="experts")))).init()
    prompt = np.zeros((1, 4), np.int32)
    with pytest.raises(NotImplementedError, match="with experts"):
        if call == "generate":
            lm.generate(prompt, 2)
        elif call == "beam_search":
            lm.beam_search(prompt, 2, beams=2)
        else:
            from deeplearning4j_tpu.serving.decode import ContinuousLM
            ContinuousLM(lm, slots=2)


# --- the served programs against the training block ---------------------------
# ``output()`` is ``_block_apply`` with its own attention and no cache. Every
# setting the served programs take from the same block, one a case; the GPT-2
# block and a LayerNorm epsilon far from the default (the served copies of the
# block once normalised with 1e-5 whatever the configuration said) beside them.
def tiny(**changes):
    return TransformerConfig(vocab_size=64, max_len=32, d_model=32, n_heads=2,
                             n_layers=2, d_ff=64, **changes)


def half_partial_rope():
    from deeplearning4j_tpu.models.transformer import LayerSpec
    rope = Rope(base=100.0, share=0.5)
    return tiny(pos_embed="rope", rope_layout="half",
                layers=(LayerSpec(rope=rope), LayerSpec(rope=rope)))


SERVED = {
    "gpt2": tiny,
    "gpt2_rope_gqa_window": lambda: tiny(pos_embed="rope", n_kv_heads=1,
                                         window=4),
    "norm_eps": lambda: tiny(norm_eps=1e-3),
    "rmsnorm": lambda: tiny(norm="rmsnorm"),
    "no_bias": lambda: tiny(bias=False, ffn="swiglu"),
    "swiglu": lambda: tiny(ffn="swiglu"),
    "untied_head": lambda: tiny(tie_embeddings=False),
    "head_dim": lambda: tiny(head_dim=8),
    "rope_half_partial": half_partial_rope,
    "attn_gate": lambda: tiny(attn_gate=True),
    "mixed_layers": lambda: mixed(rope_base=100.0),
    # a looped stack: three runs over the same two layers, one KV cache entry
    # a run and layer, the last exit read (tests/test_ouro.py holds it
    # against the family's reference)
    "looped": lambda: tiny(loops=3, post_norm=True, exit_gate=True,
                           norm="rmsnorm", bias=False, ffn="swiglu",
                           tie_embeddings=False, pos_embed="rope",
                           rope_layout="half"),
}
PROMPT, NEW = 5, 7


def served_model(name):
    """A tiny float32 model of the named setting. The blocks' leaves are redrawn
    at a size where each block moves the logits (at ``init()``'s 0.02 a tied
    head answers the token it was given whatever the blocks do); the embeddings
    keep ``init()``'s size, whose variance is of the order of ``norm_eps``
    1e-3, so the first norm shows which epsilon it was given."""
    lm = TransformerLM(SERVED[name]()).init()
    blocks = {k: v for k, v in lm.params.items() if k.startswith("b")}
    leaves, tree = jax.tree.flatten(blocks)
    keys = jax.random.split(jax.random.PRNGKey(SEED), len(leaves))
    lm.params.update(tree.unflatten([
        a + jax.random.normal(k, a.shape) * (0.2 if a.ndim > 1 else 0.1)
        for a, k in zip(leaves, keys)]))
    return lm


@pytest.mark.parametrize("call", ["generate", "continuous"])
@pytest.mark.parametrize("name", sorted(SERVED))
def test_every_greedy_token_is_the_training_blocks_argmax(name, call):
    """Every token the served programs pick greedily is the argmax of
    ``output()`` at the same prefix (one call over the whole row: attention is
    causal, so position t reads exactly the prefix). A seed whose two best
    logits lie within 1e-5 anywhere is to be replaced, not tolerated."""
    lm = served_model(name)
    prompts = np.random.default_rng(SEED).integers(0, 64, (2, PROMPT),
                                                   dtype=np.int32)
    if call == "generate":
        rows = lm.generate(prompts, NEW, temperature=0.0)
    else:
        from deeplearning4j_tpu.serving.decode import ContinuousLM
        served = ContinuousLM(lm, slots=2, chunk=3)
        try:
            rows = np.stack([f.result(120) for f in
                             [served.submit(p, NEW) for p in prompts]])
        finally:
            served.stop()
    assert np.array_equal(rows[:, :PROMPT], prompts)
    logits = lm.output(rows[:, :-1])[:, PROMPT - 1:]
    best = np.sort(logits, axis=-1)
    assert (best[..., -1] - best[..., -2]).min() > 1e-5, "replace the seed"
    assert np.array_equal(rows[:, PROMPT:], logits.argmax(-1))


def test_a_custom_attend_receives_the_grouped_heads():
    """``_block_apply`` hands ``attend`` K and V on ``kv_heads`` heads (and q
    on the layer's own count); a caller that wants MHA repeats them itself."""
    from deeplearning4j_tpu.models.transformer import _block_apply
    lm = TransformerLM(mixed()).init()
    c, seen = lm.conf, []

    def attend(q, k, v):
        seen.append((q.shape, k.shape, v.shape))
        return jnp.zeros_like(q)

    x = jnp.zeros((3, 6, c.d_model))
    for i in range(c.n_layers):
        _block_apply(c, lm.params[f"b{i}"], x, c.layer_spec(i), attend=attend)
    assert seen == [((3, 2, 6, 8), (3, 1, 6, 8), (3, 1, 6, 8)),
                    ((3, 4, 6, 8), (3, 1, 6, 8), (3, 1, 6, 8))]


def test_num_params_of_the_cut_is_the_issues_count():
    """691.6 M parameters at the published widths (shapes only)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs2.json")) as f:
        config = json.load(f)
    lm = TransformerLM(driver.program_config(config, 8192, 0))
    shapes = jax.eval_shape(lambda: lm.init().params)
    lm.params = lm.opt_state = None
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert n == 691_623_936


@pytest.mark.parametrize("trainer", ["pp", "sp", "tp", "moe"])
def test_trainers_of_one_block_refuse_a_per_layer_list_by_name(trainer):
    """The PP, SP, TP and Switch-MoE / EP trainers build one block program for
    every layer: a per-layer list or experts are refused by name, never
    trained as layer 0 throughout."""
    from jax.sharding import Mesh
    mesh = lambda axis: Mesh(np.array(jax.devices()[:2]), (axis,))
    with pytest.raises(NotImplementedError, match="per-layer list"):
        if trainer == "pp":
            from deeplearning4j_tpu.parallel.pp_transformer import \
                PPTransformerLM
            PPTransformerLM(mesh("pipe"), mixed(), n_micro=2)
        elif trainer == "sp":
            from deeplearning4j_tpu.parallel.sp_transformer import \
                SPTransformerLM
            SPTransformerLM(mesh("seq"), mixed())
        elif trainer == "tp":
            from deeplearning4j_tpu.parallel.tp_transformer import \
                TPTransformerLM
            TPTransformerLM(mesh("model"), mixed())
        else:   # the configuration MoETransformerLM and EPTransformerLM take
            from deeplearning4j_tpu.models.moe_transformer import \
                MoETransformerConfig
            from deeplearning4j_tpu.models.transformer import LayerSpec
            MoETransformerConfig(vocab_size=64, n_layers=2,
                                 layers=(LayerSpec(), LayerSpec(window=4)))


def test_a_row_left_out_before_the_window_fails_the_window_too():
    """The counters run from the seed's weights: a row over the buffer in a
    checked step or the warm-up fails every step of a window that itself left
    none out."""
    from benchmark import run
    config = tiny_config(compute_dtype="bfloat16")
    traffic = {"driver": "laguna_train", "rows": ROWS, "seq_len": SEQ,
               "pool": 2}
    job = driver.Job(config, traffic, SEED, run.Spans())
    job.first_steps()
    moe = job.lm.opt_state["moe"]
    assert job.lm.moe_counters()["moe.rows_over_buffer"] == 0
    moe["rows_over_buffer"] = moe["rows_over_buffer"].at[1].add(3)
    out = job.window(0.2)
    assert job.counters()["moe.rows_over_buffer"] == 0
    assert out["failed"] == out["steps"] > 0
