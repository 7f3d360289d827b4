"""``head_loss``: the training head and its loss with the gradient formed in
the forward, a chunk of rows at a time (``models/transformer.py``).

Its value and its gradients in the states, the head's leaf and the per-token
weights are held against plain autodiff of ``_head_nll`` (the head and loss
as the served path and ``eval_loss`` compute them), over one chunk and three
with an uneven last one, a tied and an untied head, label smoothing with the
z-loss, masks with zeros, per-token weights (the exits' form) and two heads'
rows in one call (the multi-token-prediction module's form). Then the
training loss of each benchmark twin against the same loss with every head
through plain autodiff, and the chunk rule at the cells' shapes. All float32.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models import transformer
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM, _head_nll,
                                                   _head_spans, head_loss)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
D, V, B, T = 16, 37, 2, 10

# float32 both sides, the same sums in another order (one reduction over the
# vocabulary against log_softmax's, W's gradient summed over chunks): round-off
RTOL, ATOL = 2e-5, 2e-6


def plain_head_loss(c, ep, hs, targets, ws, scopes=None):
    """The parent's formulation: each head's ``_head_nll`` weighted and summed,
    differentiated by autodiff."""
    return sum((w * _head_nll(c, ep, h, t)).sum()
               for h, t, w in zip(hs, targets, ws))


def chunk_budget(chunks):
    """A ``HEAD_CHUNK_BYTES`` that cuts a head's [B, T] rows into ``chunks``
    chunks (``_head_spans``): 3 of 4, 4 and 2 positions."""
    return B * T * V * 4 // chunks + (chunks > 1)


@pytest.mark.parametrize("form", ["mask_zeros", "per_token", "two_heads"])
@pytest.mark.parametrize("extras", ["plain", "smoothing_z_loss"])
@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("chunks", [1, 3])
def test_value_and_gradients_match_autodiff_of_the_plain_head(
        chunks, tied, extras, form, monkeypatch):
    monkeypatch.setattr(transformer, "HEAD_CHUNK_BYTES",
                        chunk_budget(chunks))
    c = TransformerConfig(
        vocab_size=V, d_model=D, n_heads=2, n_layers=1, tie_embeddings=tied,
        **(dict(label_smoothing=0.1, z_loss=1e-2)
           if extras == "smoothing_z_loss" else {}))
    k = jax.random.split(jax.random.PRNGKey(chunks + 2 * tied), 8)
    leaf = jax.random.normal(k[0], (V, D) if tied else (D, V))
    ep = {"wte" if tied else "head": leaf}
    heads = 2 if form == "two_heads" else 1
    hs = [jax.random.normal(k[1 + i], (B, T, D)) for i in range(heads)]
    targets = [jax.random.randint(k[3 + i], (B, T), 0, V)
               for i in range(heads)]
    if form == "mask_zeros":
        m = (jax.random.uniform(k[5], (B, T)) > 0.3).astype(jnp.float32)
        ws = [m / m.sum()]
    elif form == "per_token":
        ws = [jax.random.uniform(k[5], (B, T))]
    else:   # the main head's mean, and the module's under its own weight
        later = jnp.ones((B, T)).at[:, -1].set(0.0)
        ws = [jnp.full((B, T), 1.0 / (B * T)), 0.3 * later / later.sum()]
    scopes = ("logits_loss", "mtp.logits_loss")[:heads]

    def grads(fn):
        return jax.value_and_grad(
            lambda leaf, hs, ws: fn(c, {next(iter(ep)): leaf}, hs, targets,
                                    ws, scopes), argnums=(0, 1, 2))(
            leaf, hs, ws)

    want = grads(plain_head_loss)
    got = grads(head_loss)
    assert obs.metrics.value("head.chunks") == chunks * heads
    assert obs.metrics.value("head.fused_rows") == B * T * heads
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=RTOL,
                                   atol=ATOL)
    # called without a gradient it runs the chunks' forward alone
    assert float(head_loss(c, ep, hs, targets, ws, scopes)) \
        == pytest.approx(float(want[0]), rel=RTOL)


def test_three_chunks_have_an_uneven_last_one(monkeypatch):
    monkeypatch.setattr(transformer, "HEAD_CHUNK_BYTES", chunk_budget(3))
    assert _head_spans((B, T), V) == [(0, 4), (4, 8), (8, 10)]


# the cells' heads: [B, T] rows of a head, the vocabulary, chunks a head at
# the budget of 2 GiB of float32 logits a chunk
CELLS = {"gpt2m-train-t1024": ((8, 1024), 50257, 1),
         "gpt2m-train-t256": ((32, 256), 50257, 1),
         "laguna-xs2-train-t8192": ((2, 8192), 12544, 1),
         "ouro-2.6b-train-t4096": ((2, 4096), 49152, 1),   # each of 4 exits
         "smallthinker-21b-train-t16384": ((1, 16384), 37984, 2),
         "glm47-flash-train-t8192": ((1, 8192), 19360, 1),  # each of 2 heads
         "xing4-train-t4096": ((1, 4096), 16384, 1)}        # each of 2 heads


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_chunk_rule_at_the_cells_shapes(cell):
    """Few chunks, each within the budget, covering every position once; a
    head whose whole float32 logits fit is one chunk."""
    (rows, seq), vocab, chunks = CELLS[cell]
    spans = _head_spans((rows, seq), vocab)
    assert len(spans) == chunks
    assert [a for a, _ in spans] == [0] + [b for _, b in spans[:-1]]
    assert spans[-1][1] == seq
    assert all(rows * (b - a) * vocab * 4 <= transformer.HEAD_CHUNK_BYTES
               for a, b in spans)


# --- the benchmark twins' training losses --------------------------------------

def twin(name, seq):
    """The twin's configuration in float32."""
    if name == "gpt2":
        # the GPT-2 step with remat, rope, GQA, a window, z-loss and smoothing
        conf = TransformerConfig(vocab_size=512, max_len=64, d_model=64,
                                 n_heads=4, n_layers=2, d_ff=256, remat=True,
                                 pos_embed="rope", n_kv_heads=2, window=8,
                                 z_loss=1e-4, label_smoothing=0.1)
    else:
        from benchmark.drivers import (glm_train, ouro_train,
                                       smallthinker_train, xing_train)
        driver = {"smallthinker": smallthinker_train, "glm": glm_train,
                  "ouro": ouro_train, "xing": xing_train}[name]
        with open(os.path.join(ROOT, "benchmark", "rehearsal", "configs",
                               f"{name}-tiny.json")) as f:
            conf = driver.program_config(json.load(f), seq, 7)
    return dataclasses.replace(conf, compute_dtype=None)


# twin: (sequence, heads or exits a step)
TWINS = {"gpt2": (64, 1), "smallthinker": (64, 1), "glm": (32, 2),
         "ouro": (32, None), "xing": (32, 2)}


@pytest.mark.parametrize("name", sorted(TWINS))
def test_the_twins_training_loss_and_gradients_are_the_parents(
        name, monkeypatch):
    """``_loss`` (the step's loss: last exit, the exits' expectation, or the
    two heads) against the same loss with every head differentiated by
    autodiff, on a mask with zeros; the gauges read the rows and chunks of
    the traced loss, and each head chunk runs three vocabulary-wide products
    (the logits, the rows' gradient, the leaf's), none rematerialised."""
    seq, heads = TWINS[name]
    lm = TransformerLM(twin(name, seq)).init()
    c = lm.conf
    heads = heads or c.loops
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, c.vocab_size, (2, seq + 1)),
                         jnp.int32)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    mask = jnp.asarray(rng.random((2, seq)) > 0.25)

    def loss(p):
        return lm._loss(p, inputs, targets, mask)

    got = jax.jit(jax.value_and_grad(loss))(lm.params)
    assert obs.metrics.value("head.fused_rows") == 2 * seq * heads
    assert obs.metrics.value("head.chunks") == heads
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(lm.params)
    wide = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "dot_general"
            and "logits_loss" in str(e.source_info.name_stack)]
    assert len(wide) == 3 * heads
    monkeypatch.setattr(transformer, "head_loss", plain_head_loss)
    want = jax.jit(jax.value_and_grad(loss))(lm.params)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=RTOL)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4,
                                   atol=1e-4 * scale + 1e-9,
                                   err_msg=jax.tree_util.keystr(path))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        yield e
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_eval_loss_keeps_the_plain_head():
    """A call with no gradient does no gradient work: ``eval_loss`` is the
    last exit's plain head and loss, with no ``head_loss`` inside, and reads
    what the training loss reads."""
    lm = TransformerLM(twin("gpt2", 64)).init()
    tokens = jnp.asarray(np.random.default_rng(5).integers(0, 512, (2, 65)),
                         jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p: lm._last_exit_loss(
        p, tokens[:, :-1], tokens[:, 1:], None))(lm.params)
    assert not any("custom_vjp" in e.primitive.name
                   for e in _eqns(jaxpr.jaxpr))
    assert lm.eval_loss(tokens) == pytest.approx(
        float(lm._loss(lm.params, tokens[:, :-1], tokens[:, 1:], None)),
        rel=RTOL)
