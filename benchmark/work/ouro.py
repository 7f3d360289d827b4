"""Required work of an ``ouro`` training step, from shapes alone: the same count
whatever implements the layer. 2 FLOP per multiply-add; elementwise work
(norms, SiLU, softmax, rope, the exit gate's sigmoid and its ``hidden -> 1``
product) is not counted; recomputation is never counted.

The stack of ``num_hidden_layers`` layers is APPLIED ``total_ut_steps`` times a
step over the same weights: every application's products and attention are
required work, and so is every run's exit (the vocabulary-wide head), since
the training loss reads all of them. Attention is counted at what the causal
mask requires: query i meets keys 0..i, T(T+1)/2 pairs a head and row.
"""

from __future__ import annotations

TRAIN_MULTIPLIER = 3   # forward + backward (dX and dW: twice the forward)


def applications(config):
    """Layer applications a forward pass makes."""
    return config["total_ut_steps"] * config["num_hidden_layers"]


def attention_pairs(seq):
    """(query, key) pairs a head and row of one causal layer application."""
    return seq * (seq + 1) // 2


def attention_application_work(config, rows, seq, itemsize=2):
    """``{"flops", "bytes"}`` of one application's attention, forward +
    backward: QK^T and PV forward, dV, dP, dQ, dK backward, 2 FLOP x head_dim
    a pair each (the scores recomputed inside a flash backward are not
    required work). Bytes: Q, K, V read and O written once forward; Q, K, V,
    O, dO read and dQ, dK, dV written once backward, the key/value heads at
    their own count."""
    H, KV, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    q_like = rows * H * seq * hd * itemsize
    kv_like = rows * KV * seq * hd * itemsize
    return {"flops": 6 * 2 * hd * attention_pairs(seq) * rows * H,
            "bytes": (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)}


def attention_work(config, rows, seq):
    """Summed over the step's ``total_ut_steps`` x ``num_hidden_layers``
    applications."""
    one = attention_application_work(config, rows, seq)
    return {k: applications(config) * v for k, v in one.items()}


def application_fwd_flops(config, rows, seq):
    """One layer application's forward products and attention."""
    d, hd = config["hidden_size"], config["head_dim"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    tokens = rows * seq
    proj = 2 * tokens * d * (2 * H * hd + 2 * KV * hd)    # q, k, v, o
    attn = attention_application_work(config, rows, seq)["flops"] // 3
    ffn = 3 * 2 * tokens * d * config["intermediate_size"]
    return proj + attn + ffn


def exit_fwd_flops(config, rows, seq):
    """One exit's head: the vocabulary-wide product."""
    return 2 * rows * seq * config["hidden_size"] * config["vocab_size"]


def train_step_flops(config, rows, seq):
    """Required FLOPs of one training step on ``rows`` sequences of ``seq``
    tokens: (every application + every run's exit) x 3."""
    fwd = applications(config) * application_fwd_flops(config, rows, seq) \
        + config["total_ut_steps"] * exit_fwd_flops(config, rows, seq)
    return TRAIN_MULTIPLIER * fwd
