"""Driver ``ouro_train``: an ``ouro``-family decoder (the whole stack of layers
run ``total_ut_steps`` times over the same weights, a norm on every sublayer's
output, an exit with a learned gate after every run and a loss over all the
exits) trained through ``TransformerLM.fit_batch`` on host ``int32`` token
batches, one chip. ``lm_train``'s job with another family's configuration:
the closed loop, the call and the spans ARE ``lm_train.Job``'s.

Traffic parameters (``benchmark/traffic/<mix>.json``): ``rows`` sequences of
``seq_len`` tokens per step (the batch is ``rows`` x ``seq_len + 1``: inputs and
shifted targets), ``pool`` distinct batches drawn from the seed and cycled, ids
uniform over the vocabulary.

The weights are the benchmark's own (``references/ouro.init_weights``, one
jitted call from the seed), re-laid into the program's tree; the program's
``init()`` is never called. The object that takes the first steps is the object
the window drives. The exits' counters are read before and after the window,
never inside it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check
from benchmark.drivers import lm_train
from benchmark.references import ouro as ref
from benchmark.work import ouro as work
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)

CHECKED_STEPS = lm_train.CHECKED_STEPS

# the program's leaf -> the reference's leaf (or the leaves fused in it)
LEAVES = {"ln1_g": "input_norm", "qkv": ("q_proj", "k_proj", "v_proj"),
          "proj": "o_proj", "attn_norm_g": "attn_out_norm",
          "ln2_g": "pre_mlp_norm", "fc_gate": "gate_proj", "fc": "up_proj",
          "out": "down_proj", "mlp_norm_g": "mlp_out_norm"}
TOP = {"wte": "embed", "head": "head", "lnf_g": "norm_f",
       "exit_gate": "gate_w", "exit_gate_b": "gate_b"}


def program_config(config, seq_len, seed):
    """The configuration file as a ``TransformerConfig``: a program without
    the looped stack fails here, on the first unknown field, before a weight
    is made."""
    a = config["assumed"]
    o = a["optimizer"]
    return TransformerConfig(
        vocab_size=config["vocab_size"], max_len=seq_len,
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_layers=config["num_hidden_layers"],
        d_ff=config["intermediate_size"], pos_embed="rope",
        rope_base=float(config["rope_theta"]), rope_layout="half",
        norm="rmsnorm", norm_eps=config["rms_norm_eps"], bias=False,
        ffn="swiglu", tie_embeddings=config["tie_word_embeddings"],
        loops=config["total_ut_steps"], post_norm=True, exit_gate=True,
        exit_entropy=a["exit_entropy_beta"],
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


class Job(lm_train.Job):
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
        # never held whole a second time: 0.09 GB of temporaries where a
        # second tree is 2.2 GB that the step's 8.6 GB leave no room for
        change = jax.jit(lambda p: _norms(jax.tree.map(
            lambda a, b: a - b, p,
            _to_program(ref.init_weights(self.config, self.seed)))))
        delta = change(self.lm.params)
        self.steps_done = CHECKED_STEPS
        get = lambda d: {k: float(v) for k, v in jax.device_get(d).items()}
        return {"loss": [float(x) for x in losses],
                "grad_norm": get(grad_norm), "delta_norm": get(delta)}

    def window(self, seconds):
        """``lm_train``'s window; the exits' counters are read before its
        clock starts and after it stopped (each read is a sync)."""
        before = self.lm.exit_counters()
        out = super().window(seconds)
        after = self.lm.exit_counters()
        self.window_counters = {
            "exit.tokens": after["exit.tokens"] - before["exit.tokens"],
            "exit.mass": [a - b for a, b in zip(after["exit.mass"],
                                                before["exit.mass"])]}
        return out

    def work(self):
        c, rows, seq = self.config, self.rows, self.seq
        return {"step_flops": work.train_step_flops(c, rows, seq),
                "loop_attn": work.attention_work(c, rows, seq),
                "tokens_per_step": rows * seq}

    def counters(self):
        """The exits' counts over the window's steps."""
        return dict(self.window_counters)
