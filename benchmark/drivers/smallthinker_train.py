"""Driver ``smallthinker_train``: a ``smallthinker``-family decoder (every
layer routed ReGLU experts, of which this chip holds its share, under a router
that reads the block's normed input ahead of attention; full-causal layers
without position beside window layers with rope) trained through
``TransformerLM.fit_batch`` on host ``int32`` token batches, one chip.
``lm_train``'s job with another family's configuration: the closed loop, the
call and the spans ARE ``lm_train.Job``'s, the window with the expert layers'
counters around it ``laguna_train.Job``'s.

Traffic parameters (``benchmark/traffic/<mix>.json``): ``rows`` sequences of
``seq_len`` tokens per step (the batch is ``rows`` x ``seq_len + 1``: inputs and
shifted targets), ``pool`` distinct batches drawn from the seed and cycled, ids
uniform over the ``vocab_size`` rows held.

The weights are the benchmark's own (``references/smallthinker.init_weights``,
one jitted call from the seed), re-laid into the program's tree; the program's
``init()`` is never called. The object that takes the first steps is the object
the window drives. The expert layers' counters are read before and after the
window, never inside it; an assignment left out of the row buffer
(``moe.rows_over_buffer``) in any step since the seed's weights (the three
checked steps, the warm-up, the window) fails every step of the window. The
gauges of the flash kernels' window walks (``flash.window_steps_live``,
``flash.window_steps_edge``), set where the step was traced, ride with the
counters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check
from benchmark.drivers import laguna_train, lm_train
from benchmark.references import smallthinker as ref
from benchmark.work import smallthinker as work
from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models.transformer import (Experts, LayerSpec, Rope,
                                                   TransformerConfig,
                                                   TransformerLM)

CHECKED_STEPS = lm_train.CHECKED_STEPS

# the program's leaf -> the reference's leaf (or the leaves fused in it)
LEAVES = {"ln1_g": "input_norm", "qkv": ("q_proj", "k_proj", "v_proj"),
          "proj": "o_proj", "ln2_g": "post_norm", "router": "router",
          "W_gate": "experts_gate", "W_up": "experts_up",
          "W_down": "experts_down"}
TOP = {"wte": "embed", "head": "head", "lnf_g": "norm_f"}
WINDOW_GAUGES = ("flash.window_steps_live", "flash.window_steps_edge")


def program_config(config, seq_len, seed, **experts):
    """The configuration file as a ``TransformerConfig``: a program without
    the early router, the ReLU gate or the softmax scoring fails here, on the
    first unknown field of ``Experts``, before a weight is made. ``experts``
    overrides fields of ``Experts`` (the tests' planted faults)."""
    a = config["assumed"]
    o = a["optimizer"]
    turned = Rope(base=float(config["rope_theta"]))
    layers = tuple(LayerSpec(
        window=config["sliding_window_size"] if windowed else None,
        rope=turned if rope else Rope(share=0.0),   # 0 dims: no position
        ffn="experts") for windowed, rope in ref.layer_kinds(config))
    return TransformerConfig(
        vocab_size=config["vocab_size"], max_len=seq_len,
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_layers=len(layers), pos_embed="rope",
        rope_base=float(config["rope_theta"]), rope_layout="half",
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], bias=False,
        tie_embeddings=config["tie_word_embeddings"], layers=layers,
        experts=Experts(**{**dict(
            n_experts=config["moe_num_primary_experts"],
            top_k=config["moe_num_active_primary_experts"],
            d_expert=config["moe_ffn_hidden_size"], held=ref.held(config),
            row_buffer=a["expert_row_buffer"], scoring="softmax",
            gate="relu", router_input="block"), **experts}),
        compute_dtype=a["compute_dtype"], block_size=a["block_size"],
        remat=a["remat"], learning_rate=o["learning_rate"], beta1=o["beta1"],
        beta2=o["beta2"], eps=o["eps"], weight_decay=o["weight_decay"],
        seed=seed % (2 ** 31 - 1))


def _to_program(tree):
    """The reference's tree as ``TransformerLM``'s ``params``."""
    out = {mine: tree[theirs] for mine, theirs in TOP.items()}
    for i, lp in enumerate(tree["layers"]):
        out[f"b{i}"] = {
            mine: (jnp.concatenate([lp[t] for t in theirs], axis=1)
                   if isinstance(theirs, tuple) else lp[theirs])
            for mine, theirs in LEAVES.items()}
    return out


def leaves(tree):
    """``(name, array)`` of every leaf of a program tree under the reference's
    leaf names (``b1.q_proj``), the fused ``qkv`` read as its q, k and v
    columns."""
    for k, bp in tree.items():
        if k in TOP:
            yield TOP[k], bp
            continue
        for mine, a in bp.items():
            theirs = LEAVES[mine]
            if isinstance(theirs, tuple):
                kv = (a.shape[1] - bp["proj"].shape[0]) // 2
                parts = jnp.split(a, [a.shape[1] - 2 * kv, a.shape[1] - kv],
                                  axis=1)
                for name, part in zip(theirs, parts):
                    yield f"{k}.{name}", part
            else:
                yield f"{k}.{theirs}", a


def _norms(tree, scale=1.0):
    """Per-leaf L2 norms of a program tree under the reference's names."""
    return {name: scale * jnp.sqrt(jnp.sum(jnp.square(a)))
            for name, a in leaves(tree)}


verify = check.verify_training


class Job(laguna_train.Job):
    def __init__(self, config, traffic, seed, spans):
        self.config, self.traffic, self.seed, self.spans = (
            config, traffic, seed, spans)
        self.rows, self.seq = traffic["rows"], traffic["seq_len"]
        if self.seq > config["max_position_embeddings"]:
            raise ValueError("seq_len exceeds max_position_embeddings")
        self.lm = TransformerLM(program_config(config, self.seq, seed))
        self.lm.params = jax.jit(_to_program)(ref.init_weights(config, seed))
        self.lm._init_opt_state()
        rng = np.random.default_rng([seed, 2])
        self.pool = [rng.integers(0, config["vocab_size"],
                                  (self.rows, self.seq + 1), dtype=np.int32)
                     for _ in range(traffic["pool"])]
        self.window_counters = {}

    def first_steps(self):
        """Steps 1..3 through the window's own call; what the comparison reads
        of them, fetched once the three are dispatched."""
        b1 = self.config["assumed"]["optimizer"]["beta1"]
        grad_of_m = jax.jit(lambda m: _norms(m, 1.0 / (1.0 - b1)))
        losses, grad_norm = [], None
        for i in range(CHECKED_STEPS):
            losses.append(self._call(i))
            if i == 0:   # Adam's first moment after one step is (1 - b1) g
                grad_norm = grad_of_m(self.lm.opt_state["m"])
        # ONE program: the seed's weights are remade inside it (the reference's
        # jitted maker, inlined), each leaf beside its subtraction, so they are
        # never held whole a second time beside the step's temporaries
        change = jax.jit(lambda p: _norms(jax.tree.map(
            lambda a, b: a - b, p,
            _to_program(ref.init_weights(self.config, self.seed)))))
        delta = change(self.lm.params)
        self.steps_done = CHECKED_STEPS
        get = lambda d: {k: float(v) for k, v in jax.device_get(d).items()}
        return {"loss": [float(x) for x in losses],
                "grad_norm": get(grad_norm), "delta_norm": get(delta)}

    def work(self):
        c, rows, seq = self.config, self.rows, self.seq
        return {"step_flops": work.train_step_flops(c, rows, seq),
                "attn_window": work.attention_work(c, rows, seq, True),
                "attn_full": work.attention_work(c, rows, seq, False),
                "experts": work.experts_work(c, rows, seq),
                "tokens_per_step": rows * seq}

    def counters(self):
        """The expert layers' counts over the window's steps, and the window
        walks' gauges as the traced step left them."""
        return {**self.window_counters,
                **{name: obs.metrics.value(name) for name in WINDOW_GAUGES}}
