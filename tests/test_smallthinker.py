"""SmallThinker's layer on the normal path (a router that reads the block's
normed input ahead of attention, softmax over the chosen logits, ReGLU experts
in every layer and no dense MLP, full-causal layers that turn nothing beside
window layers with rope, groups of 7 query heads a key/value head) against the
plain reference of the ``smallthinker`` family
(``benchmark/references/smallthinker.py``) on seeded weights, at the rehearsal
twin's sizes: d 64, 14 / 2 heads of 16, 8 experts top-3 of which 2 are held,
window 32, T 64, one period of four layers.
"""

import copy
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import smallthinker_train as driver
from benchmark.references import smallthinker as ref
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models import expert_layer
from deeplearning4j_tpu.models.expert_layer import Experts, expert_ffn
from deeplearning4j_tpu.models.transformer import (Rope, TransformerLM,
                                                   _block_apply)
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.parallel.sequence_parallel import dense_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, SEED = 64, 2, 34


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def tiny_config(**changes):
    """The rehearsal twin's configuration file, every expert's row kept
    (``expert_row_buffer`` 4 = experts / held: the layer is exact for every
    routing), computed in float32 with dense attention unless told
    otherwise."""
    config = load("rehearsal", "configs", "smallthinker-tiny.json")
    config["assumed"].update(compute_dtype="float32", block_size=None,
                             expert_row_buffer=4.0)
    for key, value in changes.items():
        if key in config["assumed"]:
            config["assumed"][key] = value
        else:
            config[key] = value
    return config


# the three faults this architecture can hide, planted in the PROGRAM's
# configuration (the reference plants its own by name)
def _router_from_ln2(conf):
    return dataclasses.replace(conf, experts=dataclasses.replace(
        conf.experts, router_input="ffn"))


def _rope_on_full(conf):
    return dataclasses.replace(conf, layers=tuple(
        dataclasses.replace(s, rope=Rope(base=conf.rope_base))
        for s in conf.layers))


def _window_dropped(conf):
    return dataclasses.replace(conf, layers=tuple(
        dataclasses.replace(s, window=None) for s in conf.layers))


FAULTS = {"router_from_ln2": _router_from_ln2, "rope_on_full": _rope_on_full,
          "window_dropped": _window_dropped}


def program(config, weights, fault=None):
    conf = driver.program_config(config, SEQ, SEED)
    if fault is not None:
        conf = FAULTS[fault](conf)
    lm = TransformerLM(conf)
    # fresh buffers: fit_batch donates the parameters it is given
    lm.params = jax.jit(driver._to_program)(weights)
    lm._init_opt_state()
    return lm


def batches(config, n=3):
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, config["vocab_size"], (ROWS, SEQ + 1),
                         dtype=np.int32) for _ in range(n)]


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
# order (fused qkv, grouped products over sorted rows, a scatter-add back,
# softmax over six logits where the reference takes it over all eight and
# renormalises): float32 round-off. Read here: the loss 1e-7 apart, the
# logits 2e-6 of their norm, the worst gradient leaf 1.5e-6 in the L2 norm,
# the worst leaf's change over three AdamW steps 3e-4. With bfloat16 in
# float32's place the gradient reads 0.07 and each planted fault 0.3 or more:
# the limits sit between.
LOSS_TOL, LOGIT_TOL, GRAD_TOL, STEP_TOL = 2e-6, 2e-5, 2e-5, 3e-3


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
    loss, grads = jax.jit(jax.value_and_grad(lambda p: ref.loss_fn(
        config, "float32", p, jnp.asarray(tokens), fault)))(weights)
    return float(loss), reference_leaves(grads)


@pytest.fixture(scope="module")
def sound(seeded):
    config, weights, (tokens, *_) = seeded
    return reference_loss_and_grads(config, weights, tokens)


def test_the_period_is_one_full_nope_layer_and_three_rope_window_layers():
    real = load("configs", "smallthinker-21b-a3b.json")
    assert ref.layer_kinds(real) == ref.layer_kinds(tiny_config()) \
        == [(False, False), (True, True), (True, True), (True, True)]
    conf = driver.program_config(real, 16384, 1)
    assert [(s.window, s.rope.rotated(conf.hd)) for s in conf.layers] \
        == [(None, 0), (4096, 128), (4096, 128), (4096, 128)]
    assert conf.kv_group == 7 and conf.hd * conf.n_heads == 3584
    ex = conf.experts
    assert (ex.n_experts, ex.top_k, ex.d_expert, ex.held, ex.d_shared,
            ex.scale) == (64, 6, 768, (0, 16), 0, 1.0)
    assert (ex.scoring, ex.gate, ex.router_input) \
        == ("softmax", "relu", "block")
    assert ex.rows(16384) == 98304         # every assignment: exact for any routing


def test_logits_match_the_reference(seeded):
    config, weights, (tokens, *_) = seeded
    lm = program(config, weights)
    got = lm.output(tokens[:, :-1])
    want = ref.logits(config, "float32", weights, jnp.asarray(tokens[:, :-1]))
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) \
        <= LOGIT_TOL


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


@pytest.mark.parametrize("who", ["program", "reference"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_fails_under_each_architecture_fault(seeded, sound,
                                                            fault, who):
    """The router fed from ``ln2``'s output, rope turned on the full layer,
    the window layers run full: planted in the program's configuration, or
    by name in the reference (what ``calibrate.py`` reads on the chip), each
    is far outside the tolerances of the sound comparison."""
    config, weights, (tokens, *_) = seeded
    want_loss, want = sound
    if who == "program":
        got_loss, got = loss_and_grads(program(config, weights, fault),
                                       tokens)
    else:
        got_loss, got = reference_loss_and_grads(config, weights, tokens,
                                                 fault)
    # read here: the worst gradient leaf 1.2, 0.61, 0.98 of its norm; at the
    # seed's weights the LOSS hardly feels its routing or its positions
    # (1.6e-5, 2.1e-5, 5.4e-4 of itself), which is why a cell holds gradients
    assert worst_leaf(got, want)[0] > 1000 * GRAD_TOL
    assert abs(got_loss - want_loss) / want_loss > 5 * LOSS_TOL


def test_a_planted_fault_in_both_agrees_again(seeded):
    """The reference's ``router_from_ln2`` IS the program's ``router_input =
    "ffn"``: the two faults are the same model."""
    config, weights, (tokens, *_) = seeded
    _, want = reference_loss_and_grads(config, weights, tokens,
                                       "router_from_ln2")
    _, got = loss_and_grads(program(config, weights, "router_from_ln2"),
                            tokens)
    assert worst_leaf(got, want)[0] <= GRAD_TOL


def test_three_adamw_steps_match_the_reference(seeded):
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
    lm = program(config, weights)
    start = jax.device_get(dict(driver.leaves(lm.params)))
    for tokens in three:
        lm.fit_batch(tokens)
    got = {k: a - start[k] for k, a in driver.leaves(lm.params)}
    first = reference_leaves(weights)
    want = {k: a - first[k] for k, a in reference_leaves(params).items()}
    gap, at = worst_leaf(got, want)
    assert gap <= STEP_TOL, at
    assert lm.moe_counters()["moe.rows_over_buffer"] == 0


def test_the_kernel_route_trains_the_same_model(seeded, monkeypatch):
    """Flash kernels (interpret mode; the window is two blocks, 7 query heads
    a key/value head), remat and the bfloat16 the twin states, against the
    float32 reference within bfloat16's reach."""
    config, weights, (tokens, *_) = seeded
    want = float(ref.loss_fn(config, "float32", weights, jnp.asarray(tokens)))
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    lm = program(tiny_config(compute_dtype="bfloat16", block_size=16), weights)
    obs.metrics.gauge("moe.row_tile").set(-1)
    got = float(lm.fit_batch(tokens))
    assert abs(got - want) / want < 2e-3   # bfloat16: 8 mantissa bits
    # the grouped products are Pallas calls on this route: the gauge holds
    # their row tile since the step was traced, and the counters the rows
    # their walk visited, at most a tile a held expert and layer over the
    # assignments
    ex = lm.conf.experts
    tile = pk.grouped_row_tile(ROWS * SEQ * ex.top_k // ex.n_experts)
    assert obs.metrics.value("moe.row_tile") == tile
    counts = lm.moe_counters()
    assert counts["moe.rows_over_buffer"] == 0
    assert counts["moe.local_rows"] < counts["moe.rows_computed"] \
        <= counts["moe.local_rows"] + 4 * ex.held_range[1] * tile
    assert counts["moe.rows_computed"] < 4 * ex.rows(ROWS * SEQ)


def _primitives(eqn):
    """The primitives of an equation and of every jaxpr under it."""
    yield eqn.primitive.name
    for value in eqn.params.values():
        for item in (value if isinstance(value, (list, tuple)) else [value]):
            item = getattr(item, "jaxpr", item)
            for sub in getattr(item, "eqns", ()):
                yield from _primitives(sub)


def test_the_grouped_products_are_pallas_calls_under_the_experts_scope(
        seeded, monkeypatch):
    """``moe_experts_roofline`` finds the products by the scope in their name
    stack: a block's three products are Pallas calls, each under
    ``block.experts``, and no ``ragged_dot`` is left beside them; without
    the interpreter flag (off the TPU) it is the other way round."""
    def products(conf):
        lm = TransformerLM(conf).init()
        jaxpr = jax.make_jaxpr(lambda bp, x: _block_apply(
            conf, bp, x, conf.layer_spec(1))[0])(
                lm.params["b1"], jnp.zeros((ROWS, SEQ, conf.d_model)))
        found = {"pallas_call": [], "ragged_dot_general": []}
        for eqn in jaxpr.eqns:
            for prim in _primitives(eqn):
                if prim in found:
                    found[prim].append(str(eqn.source_info.name_stack))
        return found

    conf = driver.program_config(seeded[0], SEQ, SEED)
    plain = products(conf)
    assert not plain["pallas_call"] and len(plain["ragged_dot_general"]) == 3
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    own = products(conf)
    # ... and the rows move in four more (the gather and the combine, each
    # behind the pass that lays its source out as words), under the
    # dispatch's scope: moe_dispatch_ms times them and the roofline's reader
    # does not
    stacks = own["pallas_call"]
    assert not own["ragged_dot_general"] and len(stacks) == 7
    assert ["block.experts" in stack for stack in stacks] \
        == [False, False, True, True, True, False, False]
    assert sum("block.moe_dispatch" in stack for stack in stacks) == 4


def test_no_dense_ffn_leaf_and_the_served_path_refuses_by_name(seeded):
    config, weights, (tokens, *_) = seeded
    lm = TransformerLM(driver.program_config(config, SEQ, SEED)).init()
    for i in range(4):
        assert sorted(lm.params[f"b{i}"]) == sorted(driver.LEAVES)
    assert lm.params["b0"]["qkv"].shape == (64, (14 + 2 + 2) * 16)
    with pytest.raises(NotImplementedError, match="experts"):
        lm.generate(tokens[:, :4], 2)


# --- the block: the decision ahead of attention, no position on a full layer ---

def _block_eqns(config, layer):
    conf = driver.program_config(config, SEQ, SEED)
    lm = TransformerLM(conf).init()
    jaxpr = jax.make_jaxpr(lambda bp, x: _block_apply(
        conf, bp, x, conf.layer_spec(layer))[0])(
            lm.params[f"b{layer}"], jnp.zeros((ROWS, SEQ, conf.d_model)))
    return [(str(e.source_info.name_stack),
             e.params.get("name", e.primitive.name)) for e in jaxpr.eqns]


def test_the_router_and_the_sort_open_before_attention(seeded):
    eqns = _block_eqns(seeded[0], 1)
    first = lambda scope: next(i for i, (stack, _) in enumerate(eqns)
                               if scope in stack)
    last = lambda scope: max(i for i, (stack, _) in enumerate(eqns)
                             if scope in stack)
    assert first("block.ln1") < first("block.router") \
        < first("block.moe_dispatch") < first("block.qkv") \
        < first("block.attn_window") < first("block.ln2") \
        < first("block.experts") < last("block.moe_dispatch")
    assert last("block.router") < first("block.qkv")
    # the sort is part of the decision, the gather of its application
    sort = next(i for i, (_, prim) in enumerate(eqns) if prim == "argsort")
    assert first("block.router") < sort < first("block.qkv")


def test_a_layer_that_rotates_no_dim_builds_no_cos_or_sin(seeded):
    turned = {prim for _, prim in _block_eqns(seeded[0], 1)}
    still = {prim for _, prim in _block_eqns(seeded[0], 0)}
    assert {"cos", "sin"} <= turned
    assert not {"cos", "sin"} & still   # no position at all


# --- the expert layer ----------------------------------------------------------

def layer_inputs(config, seed=3):
    shapes = ref.layer_shapes(config)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes) + 2)
    lp = {name: 0.2 * jax.random.normal(k, shape)
          for k, (name, shape) in zip(keys, sorted(shapes.items()))}
    d = config["hidden_size"]
    # what the experts read, and what the router reads: two tensors
    return (jax.random.normal(keys[-1], (ROWS, SEQ, d)),
            jax.random.normal(keys[-2], (ROWS, SEQ, d)), lp)


def program_layer(config, u, r, lp):
    first, count = ref.held(config)
    E = config["moe_num_primary_experts"]
    ex = Experts(n_experts=E, top_k=config["moe_num_active_primary_experts"],
                 d_expert=config["moe_ffn_hidden_size"], held=(first, count),
                 row_buffer=E / count, scoring="softmax", gate="relu",
                 router_input="block")
    ep = {"W_gate": lp["experts_gate"], "W_up": lp["experts_up"],
          "W_down": lp["experts_down"]}
    routing = expert_layer.decide(ex, lp["router"], r.reshape(-1, r.shape[-1]))
    return expert_ffn(ex, ep, u, routing)


def reference_layer(config, u, r, lp):
    return jnp.stack([ref.experts(config, "float32", ur, ref.routing(
        config, "float32", rr, lp["router"]), lp) for ur, rr in zip(u, r)])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_rows_move_the_same_along_the_walk_and_over_the_buffer(
        dtype, monkeypatch):
    """The layer at the rehearsal configuration (top 3 of 8 by softmax, 2
    held ReGLU experts, the router reading another tensor than the experts,
    a buffer of every assignment), loss and every gradient (the experts'
    input, the router's input, the router and the three expert weights): the
    Pallas path, whose rows move one DMA each over the row tiles the walk
    visits, against the ``ragged_dot`` path's XLA gather, float32 select and
    scatter-add over the whole buffer."""
    config = tiny_config()
    u, r, lp = jax.tree.map(lambda a: a.astype(dtype), layer_inputs(config))

    def loss(u, r, lp):
        y, stats = program_layer(config, u, r, lp)
        return jnp.square(y.astype(jnp.float32)).sum(), stats

    grad = jax.value_and_grad(loss, (0, 1, 2), True)
    (want, stats), want_grads = grad(u, r, lp)
    assert int(stats["rows_over_buffer"]) == 0
    assert 0 < int(stats["local_rows"]) < ROWS * SEQ * 3
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    (got, _), got_grads = grad(u, r, lp)
    tol = 1e-4 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, rtol=tol,
                                   atol=tol * np.abs(w).max())


def share_of(config, lp, first, count):
    cut = copy.deepcopy(config)
    cut["experts_held"] = [first, first + count]
    lp = dict(lp)
    for name in ("experts_gate", "experts_up", "experts_down"):
        lp[name] = lp[name][first:first + count]
    return cut, lp


@pytest.mark.parametrize("who", ["program", "reference"])
def test_the_four_shares_add_up_to_the_uncut_layer(who):
    """Four chips with two of the eight experts each: their parts are the
    whole layer (no shared expert: nothing is counted twice)."""
    whole = tiny_config(experts_held=[0, 8])
    u, r, lp = layer_inputs(whole)
    want = reference_layer(whole, u, r, lp)
    total = 0.0
    for first in range(0, 8, 2):
        cut, cut_lp = share_of(whole, lp, first, 2)
        if who == "program":
            part, stats = program_layer(cut, u, r, cut_lp)
            assert int(stats["rows_over_buffer"]) == 0
        else:
            part = reference_layer(cut, u, r, cut_lp)
        total = total + part
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)


def test_the_whole_layer_is_the_dense_loop_for_top6_softmax_regl_u():
    """``held = (0, n)``: top-6 of 8, softmax over the six chosen logits,
    ReGLU, against a loop over the experts written here in numpy."""
    config = tiny_config(experts_held=[0, 8],
                         moe_num_active_primary_experts=6)
    u, r, lp = layer_inputs(config)
    got, _ = program_layer(config, u, r, lp)
    f64 = lambda a: np.asarray(a, np.float64)
    uu, rr = f64(u).reshape(-1, 64), f64(r).reshape(-1, 64)
    logits = rr @ f64(lp["router"])
    want = np.zeros_like(uu)
    for t in range(len(uu)):
        six = np.argsort(-logits[t])[:6]
        w = np.exp(logits[t, six] - logits[t, six].max())
        w /= w.sum()
        for e, we in zip(six, w):
            act = np.maximum(uu[t] @ f64(lp["experts_gate"][e]), 0) \
                * (uu[t] @ f64(lp["experts_up"][e]))
            want[t] += we * (act @ f64(lp["experts_down"][e]))
    np.testing.assert_allclose(got.reshape(-1, 64), want, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("top_k", [1, 3, 6])
def test_softmax_routing_weights(top_k):
    """Softmax over the chosen logits = softmax over all, the ``top_k``
    largest, renormalised to sum 1."""
    ex = Experts(n_experts=8, top_k=top_k, d_expert=4, scoring="softmax")
    h = jax.random.normal(jax.random.PRNGKey(0), (5, 6))
    router = jax.random.normal(jax.random.PRNGKey(1), (6, 8))
    w, chosen = expert_layer.route(ex, h, router)
    logits = np.asarray(h @ router, np.float64)
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    order = np.argsort(-p, -1)[:, :top_k]
    np.testing.assert_array_equal(chosen, order)
    want = np.take_along_axis(p, order, -1)
    np.testing.assert_allclose(w, want / want.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_experts_refuses_a_setting_it_does_not_know():
    for field in ("scoring", "gate", "router_input"):
        with pytest.raises(ValueError, match=field):
            Experts(n_experts=8, top_k=2, d_expert=4, **{field: "other"})


def test_peak_group_rows_against_a_hand_count(seeded):
    """A layer's fullest held expert's assignments, from the chosen experts
    counted by hand; and the same through a step's counters, where the four
    layers' peaks add."""
    config = tiny_config()
    u, r, lp = layer_inputs(config)
    _, stats = program_layer(config, u, r, lp)
    ex = Experts(n_experts=8, top_k=3, d_expert=32, scoring="softmax")
    chosen = np.asarray(expert_layer.route(
        ex, r.reshape(-1, 64), lp["router"])[1])
    by_hand = [int((chosen == e).sum()) for e in (0, 1)]     # held: 0 and 1
    assert int(stats["peak_group_rows"]) == max(by_hand)
    assert int(stats["local_rows"]) == sum(by_hand)
    # a buffer of half the balanced load does not hide the peak
    small = Experts(n_experts=8, top_k=3, d_expert=32, held=(0, 2),
                    row_buffer=0.5, scoring="softmax")
    assert int(expert_layer.Routing(None, *expert_layer.dispatch(
        small, jnp.asarray(chosen), len(chosen))).stats["peak_group_rows"]) \
        == max(by_hand)

    config, weights, (tokens, *_) = seeded
    lm = program(config, weights)
    lm.fit_batch(tokens)
    got = lm.moe_counters()
    assert got["moe.local_rows"] / 2 <= got["moe.peak_group_rows"] \
        <= got["moe.local_rows"]
    assert got["moe.even_group_rows"] == got["moe.local_rows"] / 2
    assert obs.metrics.value("moe.peak_group_rows") \
        == got["moe.peak_group_rows"]


def test_the_configuration_file_holds_656529920_parameters():
    real = load("configs", "smallthinker-21b-a3b.json")
    assert ref.num_params(real) == 656_529_920
    layer = 2560 * (3584 + 512 + 512) + 3584 * 2560 + 2 * 2560 + 2560 * 64 \
        + 16 * 3 * 2560 * 768
    assert layer == 115_512_320
    assert 4 * layer + 2 * 37984 * 2560 + 2560 == 656_529_920
    lm = TransformerLM(driver.program_config(real, 16384, 1))
    shapes = jax.eval_shape(lambda: lm.init().params)
    lm.params = lm.opt_state = None
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == 656_529_920
    # the published widths, unchanged
    assert (real["hidden_size"], real["num_attention_heads"],
            real["num_key_value_heads"], real["head_dim"],
            real["moe_ffn_hidden_size"], real["moe_num_primary_experts"],
            real["moe_num_active_primary_experts"],
            real["sliding_window_size"], real["rope_theta"],
            real["rms_norm_eps"]) \
        == (2560, 28, 4, 128, 768, 64, 6, 4096, 1500000, 1e-6)
    assert len(real["rope_layout"]) == len(real["sliding_window_layout"]) == 52


# --- the flash kernels under a window of several blocks ------------------------

def _edge(walk, block, window):
    return int(np.count_nonzero(~pk._block_full(walk.q, walk.k, block, block,
                                                window)))


@pytest.mark.parametrize("inner", ["k", "q"])
def test_the_cells_window_walk_is_252_steps_of_which_56_edge(inner):
    walk = pk.flash_walk(True, 4096, 512, 512, 16384, inner)
    assert (walk.steps, walk.live, _edge(walk, 512, 4096)) == (252, 252, 56)
    # Laguna's: a window of one block, every live block an edge block
    walk = pk.flash_walk(True, 512, 512, 512, 8192, inner)
    assert (walk.steps, _edge(walk, 512, 512)) == (31, 31)


def test_window_gauges_count_where_a_windowed_call_is_traced(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    x = jnp.zeros((2, 64, 8), jnp.float32)

    def loss(q, window):
        return pk.flash_attention(q, q, q, causal=True, block_q=16,
                                  block_k=16, window=window).sum()

    read = lambda: (obs.metrics.value("flash.window_steps_live"),
                    obs.metrics.value("flash.window_steps_edge"))
    obs.reset_metrics()
    jax.make_jaxpr(lambda q: loss(q, None))(x)     # no window: not counted
    assert read() == (0, 0)
    # window 32 = two blocks, T 64: 9 live pairs a row, 6 of them edge;
    # forward + dQ + dK/dV, two rows of n
    jax.make_jaxpr(jax.grad(lambda q: loss(q, 32)))(x)
    assert read() == (3 * 2 * 9, 3 * 2 * 6)
    obs.reset_metrics()


def test_a_window_of_several_blocks_in_groups_of_7_agrees_with_dense(
        monkeypatch):
    """Forward and backward, ``kv_group`` 7 (``_flash_bwd`` sums a K/V head's
    seven query heads), a window of three blocks: rows hold ``full`` and
    ``edge`` blocks."""
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    B, H, KV, T, hd, block, window = 1, 14, 2, 128, 16, 16, 48
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(kq, (B, H, T, hd))
    k = jax.random.normal(kk, (B, KV, T, hd))
    v = jax.random.normal(kv, (B, KV, T, hd))
    g = jax.random.normal(kg, (B, H, T, hd))
    walk = pk.flash_walk(True, window, block, block, T, "k")
    assert 0 < _edge(walk, block, window) < walk.steps

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, block_q=block,
                                  block_k=block, window=window)

    def dense(q, k, v):
        rep = lambda a: jnp.repeat(a, H // KV, axis=1)
        return dense_attention(q, rep(k), rep(v), causal=True, window=window)

    got, pull = jax.vjp(flash, q, k, v)
    want, pull_dense = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for a, b in zip(pull(g), pull_dense(g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
