"""Driver ``laguna_train``: a ``laguna``-family decoder (window and full
attention layers with their own head counts, a routed expert layer that holds
its share of the experts) trained through ``TransformerLM.fit_batch`` on host
``int32`` token batches, one chip. ``lm_train``'s job with another family's
configuration: the closed loop, the call and the spans ARE ``lm_train.Job``'s.

Traffic parameters (``benchmark/traffic/<mix>.json``): ``rows`` sequences of
``seq_len`` tokens per step (the batch is ``rows`` x ``seq_len + 1``: inputs and
shifted targets), ``pool`` distinct batches drawn from the seed and cycled, ids
uniform over the ``vocab_size`` rows held.

The weights are the benchmark's own (``references/laguna.init_weights``, one
jitted call from the seed), re-laid into the program's tree; the program's
``init()`` is never called. The object that takes the first steps is the object
the window drives. The expert layers' counters are read before and after the
window, never inside it; an assignment left out of the row buffer
(``moe.rows_over_buffer``) in any step since the seed's weights (the three
checked steps, the warm-up, the window) fails every step of the window.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check
from benchmark.drivers import lm_train
from benchmark.references import laguna as ref
from benchmark.work import laguna as work
# a program without the per-layer list cannot run this family: fail here, at
# the import, before a weight is made
from deeplearning4j_tpu.models.transformer import (Experts, LayerSpec, Rope,
                                                   TransformerConfig,
                                                   TransformerLM)

CHECKED_STEPS = lm_train.CHECKED_STEPS

# the program's leaf -> the reference's leaf (or the leaves fused in it)
LEAVES = {"ln1_g": "input_norm", "qkv": ("q_proj", "k_proj", "v_proj"),
          "attn_gate": "g_proj", "proj": "o_proj", "ln2_g": "post_norm",
          "fc_gate": "gate_proj", "fc": "up_proj", "out": "down_proj",
          "router": "router", "W_gate": "experts_gate", "W_up": "experts_up",
          "W_down": "experts_down", "sh_gate": "shared_gate",
          "sh_up": "shared_up", "sh_down": "shared_down"}
TOP = {"wte": "embed", "head": "head", "lnf_g": "norm_f"}


def program_config(config, seq_len, seed):
    """The configuration file as a ``TransformerConfig``."""
    a = config["assumed"]
    o = a["optimizer"]

    def rope(layer_type):
        r = config["rope_parameters"][layer_type]
        yarn = r["rope_type"] == "yarn"
        return Rope(base=float(r["rope_theta"]),
                    share=float(r.get("partial_rotary_factor", 1.0)),
                    yarn_factor=float(r["factor"]) if yarn else None,
                    yarn_original_len=r.get(
                        "original_max_position_embeddings", 4096),
                    yarn_beta_fast=float(r.get("beta_fast", 32)),
                    yarn_beta_slow=float(r.get("beta_slow", 1)),
                    attention_factor=r.get("attention_factor"))

    kinds = ref.layer_kinds(config)
    layers = tuple(LayerSpec(
        window=config["sliding_window"] if t == "sliding_attention" else None,
        n_heads=heads, rope=rope(t),
        ffn="experts" if mlp == "sparse" else "dense")
        for t, heads, mlp in kinds)
    return TransformerConfig(
        vocab_size=config["vocab_size"], max_len=seq_len,
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_layers=len(kinds),
        d_ff=config["intermediate_size"], pos_embed="rope",
        rope_layout="half", norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        bias=config["attention_bias"], ffn="swiglu",
        tie_embeddings=config["tie_word_embeddings"],
        attn_gate=config["gating"], layers=layers,
        experts=Experts(
            n_experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"],
            held=ref.held(config),
            scale=config["moe_routed_scaling_factor"],
            d_shared=config["shared_expert_intermediate_size"],
            row_buffer=a["expert_row_buffer"]),
        compute_dtype=a["compute_dtype"], block_size=a["block_size"],
        remat=a["remat"], learning_rate=o["learning_rate"], beta1=o["beta1"],
        beta2=o["beta2"], eps=o["eps"], weight_decay=o["weight_decay"],
        seed=seed % (2 ** 31 - 1))


def _to_program(tree):
    """The reference's tree as ``TransformerLM``'s ``params``."""
    out = {mine: tree[theirs] for mine, theirs in TOP.items()}
    for i, lp in enumerate(tree["layers"]):
        bp = out[f"b{i}"] = {}
        for mine, theirs in LEAVES.items():
            if isinstance(theirs, tuple):
                bp[mine] = jnp.concatenate([lp[t] for t in theirs], axis=1)
            elif theirs in lp:
                bp[mine] = lp[theirs]
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
        # one program: the seed's weights are remade leaf by leaf beside the
        # subtraction, not held whole a second time
        change = jax.jit(lambda p, start: _norms(jax.tree.map(
            lambda a, b: a - b, p, _to_program(start))))
        delta = change(self.lm.params,
                       ref.init_weights(self.config, self.seed))
        self.steps_done = CHECKED_STEPS
        get = lambda d: {k: float(v) for k, v in jax.device_get(d).items()}
        return {"loss": [float(x) for x in losses],
                "grad_norm": get(grad_norm), "delta_norm": get(delta)}

    def window(self, seconds):
        """``lm_train``'s window; the expert layers' counters are read before
        its clock starts and after it stopped (each read is a sync)."""
        before = self.lm.moe_counters()
        out = super().window(seconds)
        after = self.lm.moe_counters()
        self.window_counters = {k: after[k] - before[k] for k in after}
        # the counters run from the seed's weights: a row left out in a
        # checked step or in the warm-up fails the window as one in it does
        if after["moe.rows_over_buffer"]:
            out["failed"] = out["steps"]
        return out

    def work(self):
        c, rows, seq = self.config, self.rows, self.seq
        return {"step_flops": work.train_step_flops(c, rows, seq),
                "attn_window": work.attention_work(c, rows, seq,
                                                   "sliding_attention"),
                "attn_full": work.attention_work(c, rows, seq,
                                                 "full_attention"),
                "experts": work.experts_work(c, rows, seq),
                "tokens_per_step": rows * seq}

    def counters(self):
        """The expert layers' counts over the window's steps."""
        return dict(self.window_counters)
