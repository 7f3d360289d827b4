"""The names a profiler trace shows for device ops: ``jax.named_scope`` on the
LM step, on the layer zoo's layers and on the updater (PERF.md section 3).

A scope is metadata of the compiled program only, so these tests read the
lowered text (``.as_text(debug_info=True)``: the name stack of every op is its
``loc``), on the CPU, at tiny widths. What the chip's compiler makes of the
names (the flash kernels' instruction names, which the benchmark's accepted
readers match) is held by tests/test_aot_compile.py.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import scope_reduce
from deeplearning4j_tpu import NeuralNetConfiguration
from deeplearning4j_tpu.datasets.dataset import MultiDataSet
from deeplearning4j_tpu.models import transformer
from deeplearning4j_tpu.models.computation_graph import ComputationGraph
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer


def name_stacks(lowered_text):
    return set(re.findall(r'loc\("(jit\([^"]+)"', lowered_text))


@pytest.fixture(scope="module")
def lm_stacks():
    """The name stacks of a tiny LM step on the flash route, every optional
    part of the step switched on."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
        mp.setenv("DL4J_TPU_LM_ATTN", "pallas")
        lm = transformer.TransformerLM(transformer.TransformerConfig(
            vocab_size=64, max_len=32, d_model=32, n_heads=2, n_layers=3,
            d_ff=64, block_size=16, compute_dtype="bfloat16",
            grad_clip_norm=1.0, ema_decay=0.99)).init()
        tokens = jnp.zeros((2, 32), jnp.int32)
        lowered = lm._build_step().lower(
            lm.params, lm.opt_state, jnp.int32(0), jax.random.PRNGKey(0),
            tokens, tokens, None)
        return name_stacks(lowered.as_text(debug_info=True))


@pytest.fixture(scope="module")
def mixed_stacks():
    """The name stacks of a tiny step with a per-layer list: a full and a
    window layer, the gate, experts and a shared expert, under remat."""
    from deeplearning4j_tpu.models.transformer import Experts, LayerSpec
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
        mp.setenv("DL4J_TPU_LM_ATTN", "pallas")
        lm = transformer.TransformerLM(transformer.TransformerConfig(
            vocab_size=64, max_len=32, d_model=32, n_heads=2, n_kv_heads=1,
            head_dim=8, n_layers=2, d_ff=64, block_size=16, pos_embed="rope",
            norm="rmsnorm", bias=False, ffn="swiglu", tie_embeddings=False,
            attn_gate=True, remat=True, compute_dtype="bfloat16",
            layers=(LayerSpec(), LayerSpec(window=8, n_heads=4,
                                           ffn="experts")),
            experts=Experts(n_experts=4, top_k=2, d_expert=16, held=(0, 2),
                            d_shared=16))).init()
        tokens = jnp.zeros((2, 32), jnp.int32)
        lowered = lm._build_step().lower(
            lm.params, lm.opt_state, jnp.int32(0), jax.random.PRNGKey(0),
            tokens, tokens, None)
        return name_stacks(lowered.as_text(debug_info=True))


@pytest.fixture(scope="module")
def looped_stacks():
    """The name stacks of a tiny looped step: two layers run three times,
    sandwich norms, the exit gate and the loss over the exits, under remat."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
        mp.setenv("DL4J_TPU_LM_ATTN", "pallas")
        lm = transformer.TransformerLM(transformer.TransformerConfig(
            vocab_size=64, max_len=32, d_model=32, n_heads=2, n_layers=2,
            d_ff=64, block_size=16, pos_embed="rope", norm="rmsnorm",
            bias=False, ffn="swiglu", tie_embeddings=False, loops=3,
            post_norm=True, exit_gate=True, exit_entropy=0.1, remat=True,
            compute_dtype="bfloat16")).init()
        tokens = jnp.zeros((2, 32), jnp.int32)
        lowered = lm._build_step().lower(
            lm.params, lm.opt_state, jnp.int32(0), jax.random.PRNGKey(0),
            tokens, tokens, None)
        return name_stacks(lowered.as_text(debug_info=True))


# what a model with a per-layer list enters in ``attn``'s and ``mlp``'s place
PER_LAYER_SCOPES = ("attn_full", "attn_window", "attn_gate", "router",
                    "moe_dispatch", "experts", "shared_expert")
# what a looped model enters besides (PR 32)
LOOPED_SCOPES = ("attn_norm", "mlp_norm", "exit_gate")


def test_the_benchmark_reads_the_programs_vocabulary():
    """``scope_reduce.LM_SCOPES`` is the benchmark's accepted copy (its
    ``unscoped_share`` finds ``block`` in every layer's scope either way);
    what PR 28 and PR 32 added comes after it."""
    assert transformer.SCOPES == scope_reduce.LM_SCOPES + PER_LAYER_SCOPES \
        + LOOPED_SCOPES


@pytest.mark.parametrize("scope", transformer.SCOPES)
def test_lm_step_enters_every_scope_of_the_vocabulary(lm_stacks, mixed_stacks,
                                                      looped_stacks, scope):
    stacks = (mixed_stacks if scope in PER_LAYER_SCOPES else
              looped_stacks if scope in LOOPED_SCOPES else lm_stacks)
    assert any(scope in scope_reduce.tokens(s) for s in stacks)


def test_looped_scopes_are_one_element_each_forward_and_backward(
        looped_stacks):
    """The post-norms and the exit gate are entered beside ``block.proj`` and
    ``logits_loss``, never inside them (ONE scope element an op), forward and
    backward; every application's flash kernels stay under ``block.attn``."""
    for scope in ("block.attn_norm", "block.mlp_norm", "exit_gate"):
        mine = [s for s in looped_stacks
                if scope.split(".")[-1] in scope_reduce.tokens(s)]
        assert any("transpose" in scope_reduce.tokens(s) for s in mine), scope
        assert any("transpose" not in scope_reduce.tokens(s) for s in mine)
        others = set(transformer.SCOPES) - {"block", scope.split(".")[-1]}
        for s in mine:
            assert not others & scope_reduce.tokens(s), s
    kernels = {s for s in looped_stacks if s.endswith("/pallas_call")}
    assert kernels and all("attn" in scope_reduce.tokens(s) for s in kernels)


def test_per_layer_scopes_name_the_kernels_of_their_layer_type(mixed_stacks):
    """The flash kernels of a window layer sit under ``block.attn_window``,
    forward and (rematerialised) backward, those of a full layer under
    ``block.attn_full``; no layer with a per-layer list enters ``attn``; the
    expert layer's grouped products sit under ``block.experts`` and the
    kernels that move its rows under ``block.moe_dispatch``."""
    calls = {s for s in mixed_stacks if s.endswith("/pallas_call")}
    grouped = {s for s in calls if "experts" in scope_reduce.tokens(s)}
    moved = {s for s in calls if "moe_dispatch" in scope_reduce.tokens(s)}
    kernels = calls - grouped - moved
    assert kernels
    for s in kernels:
        assert len({"attn_full", "attn_window"} & scope_reduce.tokens(s)) == 1
    for scope in ("attn_full", "attn_window"):
        mine = [s for s in kernels if scope in scope_reduce.tokens(s)]
        assert any("transpose" in scope_reduce.tokens(s) for s in mine)
        assert any("transpose" not in scope_reduce.tokens(s) for s in mine)
    assert not any("attn" in scope_reduce.tokens(s) for s in mixed_stacks)
    # the experts' grouped products, forward and backward: Pallas calls on
    # this route since PR 35, found by ``moe_experts_roofline`` through the
    # scope in their name stack; no ``ragged_dot`` is left beside them
    assert any("transpose" in scope_reduce.tokens(s) for s in grouped)
    assert any("transpose" not in scope_reduce.tokens(s) for s in grouped)
    assert not any(s.endswith("ragged_dot_general") for s in mixed_stacks)
    # ... and since PR 37 the gather and the combine around them, forward and
    # backward, which ``moe_dispatch_ms`` times and the roofline does not
    assert any("transpose" in scope_reduce.tokens(s) for s in moved)
    assert any("transpose" not in scope_reduce.tokens(s) for s in moved)
    assert not grouped & moved


def test_lm_scopes_carry_no_layer_index(lm_stacks):
    """``block``, never ``b2``: the trace's reduction sums the unrolled
    layers' copies of one op into one row by its instruction's name."""
    every = set().union(*(scope_reduce.tokens(s) for s in lm_stacks))
    assert not [t for t in every if re.fullmatch(r"(b|block|layer)_?\d+", t)]


def test_lm_scopes_are_no_primitive_or_transform():
    """The readers split a name stack into tokens; a scope named like a
    primitive would match every op of that primitive."""
    taken = {getattr(jax.lax, n).name for n in dir(jax.lax)
             if n.endswith("_p")}
    taken |= {"jit", "pjit", "jvp", "transpose", "vmap", "checkpoint",
              "custom_jvp", "custom_vjp", "shard_map", "pallas_call"}
    assert not taken & set(transformer.SCOPES)


def test_lm_backward_keeps_the_scopes_and_nothing_sits_outside_the_transform(
        lm_stacks):
    """Every scope of the differentiated function comes INSIDE ``jvp(`` /
    ``transpose(jvp(``, as one element, right after ``jit(step)``: a scope
    around ``value_and_grad``, or ``attn`` nested in ``block``, would move the
    flash kernels' instruction names off ``%jvp`` / ``%transpose``."""
    inside = "embed|block\\.(ln1|qkv|attn|proj|ln2|mlp)|final_ln|logits_loss"
    for s in lm_stacks:
        if {"jvp", "transpose"} & scope_reduce.tokens(s):
            assert re.match(
                rf"jit\(step\)/(transpose\()?jvp\(({inside})?\)\)?/", s), s
    kernels = {s for s in lm_stacks if s.endswith("/pallas_call")}
    assert kernels == {"jit(step)/jvp(block.attn)/pallas_call",
                       "jit(step)/transpose(jvp(block.attn))/pallas_call"}
    for scope in ("ln1", "mlp", "logits_loss", "embed"):
        assert any("transpose" in scope_reduce.tokens(s)
                   and scope in scope_reduce.tokens(s) for s in lm_stacks)
    outside = {s for s in lm_stacks
               if {"grad_clip", "optimizer"} & scope_reduce.tokens(s)}
    assert outside and not any("jvp" in scope_reduce.tokens(s)
                               for s in outside)


def lowered_train_step(net, fit_one):
    """The lowered text of the train step ``fit_one`` dispatches: the step is
    built by a first call, then lowered on the arguments of a second."""
    fit_one()
    (sig, step), = net._jit_train.items()
    texts = []

    def spy(*args):
        texts.append(step.lower(*args).as_text(debug_info=True))
        return step(*args)

    net._jit_train[sig] = spy
    fit_one()
    net._jit_train[sig] = step
    return texts[0]


def test_mln_step_names_its_layers_by_class_and_the_updater():
    conf = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
            .list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(DenseLayer(n_in=8, n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = np.zeros((8, 4), np.float32)
    y = np.eye(3, dtype=np.float32)[np.zeros(8, int)]
    stacks = name_stacks(lowered_train_step(net, lambda: net.fit_batch(x, y)))
    every = set().union(*(scope_reduce.tokens(s) for s in stacks))
    assert {"DenseLayer", "OutputLayer", "updater"} <= every
    assert any(re.search(r"transpose\(jvp\(DenseLayer\)\)", s) for s in stacks)
    # two dense layers, one name: no index
    assert not [t for t in every if re.search(r"Layer_?\d", t)]


def test_cg_step_names_layers_and_vertices_by_class_and_the_updater():
    conf = (NeuralNetConfiguration.Builder().seed(1).learning_rate(0.1)
            .graph_builder()
            .add_inputs("in")
            .add_layer("a", DenseLayer(n_in=4, n_out=8, activation="relu"),
                       "in")
            .add_layer("b", DenseLayer(n_in=4, n_out=8, activation="relu"),
                       "in")
            .add_vertex("sum", ElementWiseVertex("add"), "a", "b")
            .add_layer("out", OutputLayer(n_in=8, n_out=3,
                                          activation="softmax",
                                          loss="mcxent"), "sum")
            .set_outputs("out")
            .build())
    net = ComputationGraph(conf).init()
    mds = MultiDataSet([np.zeros((8, 4), np.float32)],
                       [np.eye(3, dtype=np.float32)[np.zeros(8, int)]])
    stacks = name_stacks(lowered_train_step(net, lambda: net.fit_batch(mds)))
    every = set().union(*(scope_reduce.tokens(s) for s in stacks))
    assert {"DenseLayer", "ElementWiseVertex", "OutputLayer",
            "updater"} <= every
    assert not {"a", "b", "sum", "out"} & every   # no vertex names
