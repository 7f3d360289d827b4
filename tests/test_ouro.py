"""The looped stack of ``TransformerConfig`` (``loops`` runs of the layers over
the same weights, a norm on every sublayer's output, an exit gate and a loss
over all the exits) against the plain reference of the ``ouro`` family
(``benchmark/references/ouro.py``) on seeded weights, at the rehearsal twin's
sizes: d 64, 4 heads of 16, 2 layers run 3 times, vocabulary 128, T 32.
"""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import ouro_train as driver
from benchmark.references import ouro as ref
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM, _cast_params,
                                                   _embed, _exit_terms, _head,
                                                   _stack_runs)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, ROWS, SEED = 32, 2, 11


def tiny_config(**changes):
    """The rehearsal twin's configuration file, computed in float32 with dense
    attention and no remat unless told otherwise."""
    with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                           "ouro-tiny.json")) as f:
        config = json.load(f)
    config["assumed"].update(compute_dtype="float32", block_size=None,
                             remat=False)
    for key, value in changes.items():
        if key in config["assumed"]:
            config["assumed"][key] = value
        else:
            config[key] = value
    return config


def seeded_weights(config):
    """The seed's weights with the gains and the gate moved off 1 and 0: a
    gain of one or a gate at its initial balance would hide a leaf that is
    read from the wrong place."""
    weights = ref.init_weights(config, SEED)
    leaves, tree = jax.tree.flatten(weights)
    keys = jax.random.split(jax.random.PRNGKey(SEED), len(leaves))
    return tree.unflatten([
        a + (0.1 if a.ndim == 1 else 0.05) * jax.random.normal(k, a.shape)
        for a, k in zip(leaves, keys)])


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


@pytest.fixture(scope="module")
def seeded():
    config = tiny_config()
    return config, seeded_weights(config), batches(config)


@pytest.fixture(scope="module")
def wanted(seeded):
    """The reference's loss, gradients, exits' logits and exit distribution."""
    config, weights, (tokens, *_) = seeded
    tokens = jnp.asarray(tokens)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(config, "float32", p, tokens)))(weights)
    logits, p = jax.jit(lambda w: ref.exits(config, "float32", w,
                                            tokens[:, :-1]))(weights)
    return float(loss), reference_leaves(grads), logits, p


# The program in float32 and the reference compute the same sums in another
# order (fused qkv, whole batch against a row at a time, log p carried as a
# sum of log-sigmoids against a product of probabilities; on the flash route
# the online softmax): float32 round-off. Read here over the four routes: the
# loss equal to the bit, the worst gradient leaf 1.0e-6 to 1.4e-6 in the L2
# norm (the gate's), an exit's logits 1.3e-6 at most against logits of size
# 1.7, the exit distribution 1.8e-7. With bfloat16 in float32's place they
# read 1.3e-4, 3.0e-2, 3.9e-2 and 3.1e-3 at least: each limit sits between,
# ten times off either side (the loss's 26 times under bfloat16's reading),
# and the second case holds that bfloat16 is refused by every one of them.
LOSS_TOL, GRAD_TOL, LOGIT_TOL, P_TOL = 5e-6, 5e-5, 2e-5, 2e-6


def program_readings(config, weights, tokens):
    """The program's loss, gradient leaves, every exit's logits and the exit
    distribution it weighted the exits by."""
    lm = program(config, weights)
    c = lm.conf
    tokens = jnp.asarray(tokens)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: lm._loss(p, inputs, targets, None)))(lm.params)

    def exits(params):
        logits, survived = [], [jnp.zeros(targets.shape, jnp.float32)]

        def after_run(run, cast, h):
            logits.append(_head(c, cast, h))
            survived.append(_exit_terms(
                c, run == c.loops - 1, cast, h, targets,
                jnp.ones(targets.shape), survived[-1])[1])

        lm._states(params, inputs, after_run=after_run)
        # p(t) = surv(t) - surv(t+1), and the last exit takes what is left
        surv = jnp.exp(jnp.stack(survived[:c.loops]))
        return jnp.stack(logits), jnp.concatenate(
            [surv[:-1] - surv[1:], surv[-1:]])

    logits, p = jax.jit(exits)(lm.params)
    return float(loss), dict(driver.leaves(grads)), logits, p


ROUTES = {"dense": {}, "dense_remat": dict(remat=True),
          "flash": dict(block_size=16), "flash_remat": dict(block_size=16,
                                                            remat=True)}


@pytest.mark.parametrize("compute,sound", [("float32", True),
                                           ("bfloat16", False)])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_loss_exits_and_every_gradient_leaf_match_the_reference(
        seeded, wanted, route, compute, sound, monkeypatch):
    config, weights, (tokens, *_) = seeded
    want_loss, want_grads, want_logits, want_p = wanted
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    loss, grads, logits, p = program_readings(
        tiny_config(compute_dtype=compute, **ROUTES[route]), weights, tokens)
    gaps = {"loss": abs(loss - want_loss) / want_loss,
            "grad": worst_leaf(grads, want_grads)[0],
            "logits": float(jnp.abs(logits - want_logits).max()),
            "p": float(jnp.abs(p - want_p).max())}
    limits = {"loss": LOSS_TOL, "grad": GRAD_TOL, "logits": LOGIT_TOL,
              "p": P_TOL}
    assert logits.shape == (3, ROWS, SEQ, 128) and p.shape == (3, ROWS, SEQ)
    if sound:
        assert all(gaps[k] <= limits[k] for k in limits), gaps
    else:   # the tolerances are tight enough to tell the precision
        assert all(gaps[k] > 5 * limits[k] for k in limits), gaps


def test_three_adamw_steps_match_the_reference(seeded):
    """``fit_batch`` against the reference's own AdamW over three batches: each
    loss, and every leaf's change. Adam divides by the gradient's own size, so
    an entry whose gradient is round-off moves by the learning rate in either
    direction: read here, the worst leaf's change 1.1e-4 apart (bfloat16:
    0.15), the losses 2.0e-7 (bfloat16: 2.7e-4)."""
    config, weights, three = seeded
    opt = config["assumed"]["optimizer"]
    params = weights
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = jax.jit(lambda p, m, v, t, tokens: (ref.loss_fn(
        config, "float32", p, tokens),) + ref.adamw(
        opt, p, jax.grad(lambda q: ref.loss_fn(config, "float32", q, tokens))(
            p), m, v, t))
    want_losses = []
    for i, tokens in enumerate(three):
        loss, params, m, v = step(params, m, v, float(i + 1),
                                  jnp.asarray(tokens))
        want_losses.append(float(loss))
    lm = program(config, weights)
    start = jax.device_get(dict(driver.leaves(lm.params)))
    losses = [float(lm.fit_batch(tokens)) for tokens in three]
    np.testing.assert_allclose(losses, want_losses, rtol=5e-6)
    got = {k: a - start[k] for k, a in driver.leaves(lm.params)}
    want = {k: a - b for (k, a), b in zip(
        reference_leaves(params).items(), reference_leaves(weights).values())}
    gap, at = worst_leaf(got, want)
    assert gap <= 2e-3, at


# --- the tie to the plain block ------------------------------------------------

def test_the_loop_is_the_plain_walk_chained_and_shared_gradients_add_up(seeded):
    """R runs of L shared layers are R plain walks (``loops`` 1: the L blocks,
    the final norm) chained, each over a copy of its own of the weights, run
    t + 1 starting from run t's normed state: the same last-exit logits, and
    the shared leaves' gradient is the sum of the copies'. The logits read
    equal to the bit; a gradient leaf 6e-8 apart in the L2 norm (the three
    copies' gradients are added in another order than the one cotangent the
    looped walk accumulates), held to 1e-6."""
    config, weights, (tokens, *_) = seeded
    lm = program(config, weights)
    c = lm.conf
    plain = dataclasses.replace(c, loops=1)
    tokens = jnp.asarray(tokens)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    apply = lambda k, bp, x: lm._block(plain.layer_spec(k), bp, x)

    def last_logits(copies):
        x = _embed(plain, copies[0], inputs)
        for copy in copies:
            x = _stack_runs(plain, _cast_params(plain, copy), x, apply)
        return _head(plain, copies[-1], x)

    def nll(logits):
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, targets[..., None], -1).mean()

    copies = [lm.params] * c.loops
    untied = jax.jit(last_logits)(copies)
    looped = jax.jit(lm._logits)(lm.params, inputs)
    np.testing.assert_array_equal(looped, untied)
    assert float(jnp.abs(looped).max()) > 1.0

    per_copy = jax.jit(jax.grad(lambda cs: nll(last_logits(cs))))(copies)
    shared = jax.jit(jax.grad(lambda p: nll(lm._logits(p, inputs))))(lm.params)
    summed = jax.tree.map(lambda *g: sum(g), *per_copy)
    gap, at = worst_leaf(dict(driver.leaves(shared)),
                         dict(driver.leaves(summed)))
    assert gap <= 1e-6, at
    # and the last-exit loss is what eval_loss reads
    assert lm.eval_loss(tokens) == pytest.approx(float(nll(looped)), rel=1e-6)


# --- the exits' loss on fixed numbers ------------------------------------------

def test_exit_distribution_sums_to_one_and_the_loss_is_the_hand_computed_one():
    """Three exits of one row of two tokens over a vocabulary of three, d 2:
    ``sum_t p(t) = 1`` a token, and the summands of ``_exit_terms`` add up to
    ``sum_i [sum_t p_i(t) L_i^t - beta H(p_i)]`` written out in numpy."""
    c = TransformerConfig(vocab_size=3, d_model=2, n_heads=1, n_layers=1,
                          tie_embeddings=False, loops=3, exit_gate=True,
                          exit_entropy=0.1)
    head = np.array([[1.0, -0.5, 0.25], [0.5, 2.0, -1.0]], np.float32)
    gate_w, gate_b = np.array([[0.7], [-1.3]], np.float32), np.float32(0.2)
    hs = np.array([[[[0.3, -1.2], [1.5, 0.4]]],
                   [[[-0.8, 0.9], [0.1, 0.1]]],
                   [[[2.0, 1.0], [-0.4, -2.2]]]], np.float32)  # [R, 1, 2, d]
    targets = np.array([[2, 0]])
    ep = {"head": jnp.asarray(head), "exit_gate": jnp.asarray(gate_w),
          "exit_gate_b": jnp.asarray([gate_b])}
    total, masses = 0.0, []
    log_surv, ones = jnp.zeros((1, 2)), jnp.ones((1, 2))
    for t in range(3):
        term, log_surv, mass = _exit_terms(c, t == 2, ep, jnp.asarray(hs[t]),
                                           jnp.asarray(targets), ones,
                                           log_surv)
        total += float(term)
        masses.append(float(mass))
    # by hand
    logits = hs @ head                                         # [R, 1, 2, V]
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    nll = -np.take_along_axis(logp, targets[None, ..., None], -1)[..., 0]
    lam = 1.0 / (1.0 + np.exp(-((hs @ gate_w)[..., 0] + gate_b)))
    p = np.stack([lam[0], lam[1] * (1 - lam[0]),
                  (1 - lam[0]) * (1 - lam[1])])
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-7)
    want = ((p * nll).sum(0) + 0.1 * (p * np.log(p)).sum(0)).sum()
    assert total == pytest.approx(float(want), rel=2e-6)
    np.testing.assert_allclose(masses, p.sum((1, 2)), rtol=2e-6)
    assert sum(masses) == pytest.approx(2.0, rel=1e-6)


# --- the served programs --------------------------------------------------------

def test_token_by_token_through_the_caches_gives_the_references_logits(seeded):
    """The one-token decode step over its ``loops x n_layers`` caches, fed a
    row token by token, against the reference's full forward pass (the last
    exit): logits, not sampled tokens. Float32 both sides, another order of
    sums (a cache row against a causal matrix): read 9.5e-7 of logits of size
    1.7, held to ``LOGIT_TOL``."""
    config, weights, (tokens, *_) = seeded
    lm = program(config, weights)
    c = lm.conf
    inputs = jnp.asarray(tokens)[:, :-1]
    step = jax.jit(lm._make_token_step(SEQ))
    caches = lambda: [jnp.zeros((ROWS, c.kv_heads, SEQ, c.hd))
                      for _ in range(c.applications)]
    kcs, vcs, got = caches(), caches(), []
    assert len(kcs) == 3 * 2
    for t in range(SEQ):
        logits, kcs, vcs = step(lm.params, inputs[:, t], t, kcs, vcs)
        got.append(logits)
    want = ref.exits(config, "float32", weights, inputs)[0][-1]
    np.testing.assert_allclose(jnp.stack(got, axis=1), want, atol=LOGIT_TOL)


def test_prefill_then_decode_gives_the_references_logits(seeded):
    """Chunked prefill of a prompt into the continuous-batching slot pool, two
    windows of 8, then the decode step's token program over the pool's
    ``loops x n_layers`` caches for the tokens that follow, against the
    reference's full forward pass on the logits."""
    config, weights, (tokens, *_) = seeded
    lm = program(config, weights)
    c = lm.conf
    row = jnp.asarray(tokens)[0, :-1]
    S, W, P = 2, 8, 16
    state = lm._init_decode_state(S)
    assert len(state["k"]) == c.applications == 6
    prefill = lm._prefill_fn(S, W)
    zeros = jnp.zeros((c.applications, c.kv_heads, W, c.hd))
    for start in range(0, P, W):
        state, pk, _ = prefill(lm.params, state, 1, row[start:start + W],
                               start, W, start + W == P, False, zeros, zeros)
        assert pk.shape == (c.applications, c.kv_heads, W, c.hd)
    step = jax.jit(lm._make_token_step(SEQ, vector_pos=True))
    kcs, vcs = state["k"], state["v"]
    live = jnp.array([False, True])
    got = []
    for t in range(P, SEQ):
        pos = jnp.array([0, t], jnp.int32)
        logits, kcs, vcs = step(lm.params, jnp.stack([row[0], row[t]]), pos,
                                kcs, vcs, write=live)
        got.append(logits[1])
    want = ref.exits(config, "float32", weights, row[None])[0][-1][0]
    np.testing.assert_allclose(jnp.stack(got), want[P:], atol=LOGIT_TOL)


@pytest.mark.parametrize("call", ["generate", "beam_search"])
def test_generate_reads_the_references_last_exit(seeded, call):
    """``generate`` (greedy) and ``beam_search`` (one beam) run all the runs a
    token and pick the argmax of the reference's last exit at every prefix.
    A seed whose two best logits lie within 1e-4 anywhere is to be replaced."""
    config, weights, (tokens, *_) = seeded
    lm = program(config, weights)
    prompt = tokens[:, :5]
    rows = (lm.generate(prompt, 6, temperature=0.0) if call == "generate"
            else lm.beam_search(prompt, 6, beams=1))
    logits = ref.exits(config, "float32", weights,
                       jnp.asarray(rows[:, :-1]))[0][-1][:, 4:]
    best = np.sort(np.asarray(logits), axis=-1)
    assert (best[..., -1] - best[..., -2]).min() > 1e-4, "replace the seed"
    assert np.array_equal(rows[:, 5:], np.asarray(logits).argmax(-1))


# --- the configuration, the parameters, the counters -----------------------------

def test_num_params_of_the_cut_is_the_files_count():
    """The published widths at the depth held (shapes only): 51,388,416 a
    layer (12,582,912 + 4,194,304 of q, k, v, o, 34,603,008 of SwiGLU, four
    gains) and 201,330,689 beside the layers (embedding, head, final gain,
    the gate's 2048 + 1)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        config = json.load(f)
    L = config["num_hidden_layers"]
    lm = TransformerLM(driver.program_config(config, 4096, 0))
    shapes = jax.eval_shape(lambda: lm.init().params)
    lm.params = lm.opt_state = None
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert n == 51_388_416 * L + 201_330_689 == config["parameters"] \
        == ref.num_params(config)
    assert (lm.conf.loops, lm.conf.applications) == (4, 4 * L)
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers_published"] == 48


@pytest.mark.parametrize("changes", [dict(loops=2), dict(post_norm=True),
                                     dict(exit_gate=True)])
@pytest.mark.parametrize("trainer", ["pp", "sp", "tp", "moe"])
def test_trainers_of_one_block_refuse_a_looped_stack_by_name(trainer, changes):
    """The PP, SP, TP and Switch-MoE / EP trainers build one block program and
    walk the layers once: a looped stack is refused by name, never trained as
    one run without its exits."""
    from jax.sharding import Mesh
    conf = lambda: TransformerConfig(vocab_size=64, max_len=32, d_model=32,
                                     n_heads=2, n_layers=2, d_ff=64, **changes)
    mesh = lambda axis: Mesh(np.array(jax.devices()[:2]), (axis,))
    with pytest.raises(NotImplementedError, match="looped stack"):
        if trainer == "pp":
            from deeplearning4j_tpu.parallel.pp_transformer import \
                PPTransformerLM
            PPTransformerLM(mesh("pipe"), conf(), n_micro=2)
        elif trainer == "sp":
            from deeplearning4j_tpu.parallel.sp_transformer import \
                SPTransformerLM
            SPTransformerLM(mesh("seq"), conf())
        elif trainer == "tp":
            from deeplearning4j_tpu.parallel.tp_transformer import \
                TPTransformerLM
            TPTransformerLM(mesh("model"), conf())
        else:   # the configuration MoETransformerLM and EPTransformerLM take
            from deeplearning4j_tpu.models.moe_transformer import \
                MoETransformerConfig
            MoETransformerConfig(vocab_size=64, n_layers=2, **changes)


def test_loops_below_one_are_refused():
    with pytest.raises(ValueError, match="loops"):
        TransformerConfig(vocab_size=64, loops=0)


def test_exit_counters_carry_every_exits_mass(seeded):
    """``exit.mass`` sums, step by step, each exit's share of the tokens (a
    step's sum rounded to whole tokens), ``exit.tokens`` the tokens: beside the
    optimizer's state, fetched outside any step, set as gauges; the gauge
    ``lm.block_applications`` is set where the step is traced."""
    config, weights, three = seeded
    lm = program(config, weights)
    assert lm.exit_counters() == {"exit.mass": [0, 0, 0], "exit.tokens": 0}
    for tokens in three[:2]:
        lm.fit_batch(tokens)
    got = lm.exit_counters()
    assert got["exit.tokens"] == 2 * ROWS * SEQ
    assert abs(sum(got["exit.mass"]) - got["exit.tokens"]) <= 3
    assert min(got["exit.mass"]) > 0.05 * got["exit.tokens"]
    assert obs.metrics.value("exit.tokens") == got["exit.tokens"]
    assert [obs.metrics.value(f"exit.mass.{t}") for t in (1, 2, 3)] \
        == got["exit.mass"]
    assert obs.metrics.value("lm.block_applications") == 6
    assert TransformerLM(TransformerConfig(vocab_size=64)).init() \
        .exit_counters() == {}


def test_save_and_load_round_trip_the_gate_and_the_counters(seeded, tmp_path):
    from deeplearning4j_tpu.utils import model_serializer
    config, weights, three = seeded
    lm = program(config, weights)
    lm.fit_batch(three[0])
    path = str(tmp_path / "looped.zip")
    model_serializer.write_model(lm, path)
    back = model_serializer.restore_model(path)
    assert back.conf.loops == 3 and back.conf.exit_gate \
        and back.conf.post_norm and back.conf.exit_entropy == 0.1
    for name in ("exit_gate", "exit_gate_b"):
        np.testing.assert_array_equal(back.params[name], lm.params[name])
    np.testing.assert_array_equal(back.params["b1"]["mlp_norm_g"],
                                  lm.params["b1"]["mlp_norm_g"])
    assert back.exit_counters() == lm.exit_counters()
    np.testing.assert_array_equal(back.output(three[1][:, :-1]),
                                  lm.output(three[1][:, :-1]))
    assert float(back.fit_batch(three[1])) == float(lm.fit_batch(three[1]))


# --- the defaults leave every other model's step as it was -----------------------

# sha256 of the lowered training step's text, the numbers MLIR's symbol table
# hangs on private function names left out, read on the tree BEFORE the looped
# stack (PR 31's commit, this CPU): the GPT-2 block dense, on the flash route
# in bfloat16, and with remat / rope / GQA / window / z-loss / smoothing /
# dropout, and the Laguna cell's rehearsal twin. A PR that means to change
# one of these steps reads the new text and replaces its hash: PR 33 did for
# the two on the flash route (lse and delta one float32 a row at the
# kernels' boundary); the two off it stand as PR 31 left them. PR 34 renewed
# Laguna's once, for the expert layers' fourth counter (a diff and a max over
# a layer's group sizes, one more int32 pair beside the optimizer's state:
# CHANGES.md shows the lowered text's diff), and added SmallThinker's twin:
# the early router, the ReLU gate, the softmax scoring and a layer without
# position are all choices at trace time, and what they lower to is held too.
# PR 35 renewed both twins once: the group sizes sum to the rows that hold an
# assignment (no ``scatter`` of the tail into the last group), the walk's
# table and the select by ``valid`` after the weighting are new, and under
# the interpreter flag the three grouped products of a sparse layer are
# interpreted Pallas calls (``pallas_kernels.grouped_matmul``) where
# ``ragged_dot``'s masked batched products were; the three GPT-2 steps stand.
# PR 37 renewed both twins once more: ``dispatch`` sorts a second time for
# each assignment's row (``Routing.pos``), and under the interpreter flag a
# sparse layer's rows move in interpreted Pallas calls
# (``pallas_kernels.gather_rows`` / ``combine_rows`` and the pass that lays
# their sources out as words) where XLA's gather, the two selects by
# ``valid`` and the float32 scatter-add were; the three GPT-2 steps stand.
LOWERED_BEFORE = {
    "gpt2": "f19a640cb05302ba6b1a96247726095553a0daa69a62f3ba0adb18d9229d8743",
    "gpt2_flash_bf16":
        "3aff487a9faed63983d356db3d834591bcd013d21cb428b0790eae0b5be26ced",
    "gpt2_remat_rope":
        "8e7c5ef5fab6e2288fcbbc65e11159aa928b9b4301d789f30c51f345a9493539",
    "laguna_tiny":
        "b2f11698d2e441e16d08c1e1af0bd65424e3d78f59f17a1566e94c20bbd1926f",
    "smallthinker_tiny":
        "48483549c7c0ec6618763e954a916134d00e71191a33cfec8a5f4f842356c362",
}


@pytest.mark.parametrize("name", sorted(LOWERED_BEFORE))
def test_the_defaults_leave_the_gpt2_and_laguna_steps_as_they_were(
        name, monkeypatch):
    """``loops`` 1, no ``post_norm``, no ``exit_gate``: the step lowers to the
    text it lowered to before the fields were there. Renewed since, each
    once and on purpose (the comment above ``LOWERED_BEFORE``): the two flash
    steps by PR 33, ``laguna_tiny`` by PR 34 and PR 35, ``smallthinker_tiny``
    by PR 35 (the expert layer's groups, masks and product), both by PR 37
    (the expert layer's rows move in Pallas calls)."""
    import hashlib
    import re
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    gpt2 = dict(vocab_size=512, max_len=64, d_model=64, n_heads=4, n_layers=2,
                d_ff=256)
    if name in ("laguna_tiny", "smallthinker_tiny"):
        from benchmark.drivers import laguna_train, smallthinker_train
        driver, twin, seq = {
            "laguna_tiny": (laguna_train, "laguna-tiny.json", 32),
            "smallthinker_tiny": (smallthinker_train,
                                  "smallthinker-tiny.json", 64)}[name]
        with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                               twin)) as f:
            conf, rows = driver.program_config(json.load(f), seq, 7), 2
    else:
        conf, rows, seq = TransformerConfig(**gpt2, **{
            "gpt2": {},
            "gpt2_flash_bf16": dict(block_size=16, compute_dtype="bfloat16"),
            "gpt2_remat_rope": dict(remat=True, pos_embed="rope",
                                    n_kv_heads=2, window=8, z_loss=1e-4,
                                    label_smoothing=0.1, dropout=0.1),
        }[name]), 2, 64
    lm = TransformerLM(conf)
    params, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
    lm.params = lm.opt_state = None
    tokens = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
    text = lm._build_step().lower(
        params, opt, jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), tokens, tokens, None).as_text()
    text = re.sub(r"(@\w+?)_\d+\b", r"\1", text)
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED_BEFORE[name]
