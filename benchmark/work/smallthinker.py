"""Required work of a ``smallthinker`` training step on one chip's share, from
shapes alone: the same count whatever implements the layer. 2 FLOP per
multiply-add; elementwise work (norms, ReLU, softmax, rope) is not counted;
recomputation is never counted.

Attention is counted at what the mask REQUIRES: in a full layer query i meets
keys 0..i, T(T+1)/2 pairs; in a window layer of width W the last W of them,
W(W+1)/2 + (T-W)W pairs. The experts are counted at the balanced load: of the
tokens x ``moe_num_active_primary_experts`` assignments, the share ``held /
moe_num_primary_experts`` meets an expert held here, whatever buffer the
program computes over. The output head is the slice held.
"""

from __future__ import annotations

from benchmark.references.smallthinker import held, layer_kinds

TRAIN_MULTIPLIER = 3   # forward + backward (dX and dW: twice the forward)


def attention_pairs(config, windowed, seq):
    """(query, key) pairs a head and row of one layer."""
    w = config["sliding_window_size"]
    if windowed and w < seq:
        return w * (w + 1) // 2 + (seq - w) * w
    return seq * (seq + 1) // 2


def attention_layer_work(config, windowed, rows, seq, itemsize=2):
    """``{"flops", "bytes"}`` of one layer's attention, forward + backward:
    QK^T and PV forward, dV, dP, dQ, dK backward, 2 FLOP x head_dim a pair
    each (the scores recomputed inside a flash backward are not required
    work). Bytes: Q, K, V read and O written once forward; Q, K, V, O, dO read
    and dQ, dK, dV written once backward, the key/value heads at their own
    count."""
    H, KV, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    pairs = attention_pairs(config, windowed, seq)
    q_like = rows * H * seq * hd * itemsize
    kv_like = rows * KV * seq * hd * itemsize
    return {"flops": 6 * 2 * hd * pairs * rows * H,
            "bytes": (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)}


def attention_work(config, rows, seq, windowed):
    """Summed over the window layers (``windowed``) or the full ones; None
    where there is none."""
    n = sum(kind[0] == windowed for kind in layer_kinds(config))
    if not n:
        return None
    one = attention_layer_work(config, windowed, rows, seq)
    return {k: n * v for k, v in one.items()}


def balanced_rows(config, tokens):
    """Assignments that meet a held expert when the router is balanced."""
    return tokens * config["moe_num_active_primary_experts"] \
        * held(config)[1] // config["moe_num_primary_experts"]


def experts_work(config, rows, seq, itemsize=2):
    """The held experts' three products over all layers, forward + backward,
    at the balanced load. Bytes: the rows in and out and each held expert's
    three matrices, read forward and backward and their gradients written
    (float32 masters aside)."""
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    L = config["num_hidden_layers"]
    n = balanced_rows(config, rows * seq)
    weights = 3 * held(config)[1] * d * f * itemsize
    return {"flops": L * TRAIN_MULTIPLIER * 3 * 2 * n * d * f,
            "bytes": L * (3 * weights + 4 * n * d * itemsize)}


def layer_fwd_flops(config, windowed, rows, seq):
    """One layer's forward products and attention."""
    d, hd = config["hidden_size"], config["head_dim"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    tokens = rows * seq
    proj = 2 * tokens * d * (2 * H * hd + 2 * KV * hd)    # q, k, v, o
    attn = attention_layer_work(config, windowed, rows, seq)["flops"] // 3
    ffn = (2 * tokens * d * config["moe_num_primary_experts"]     # router
           + 3 * 2 * balanced_rows(config, tokens) * d
           * config["moe_ffn_hidden_size"])
    return proj + attn + ffn


def train_step_flops(config, rows, seq):
    """Required FLOPs of one training step on ``rows`` sequences of ``seq``
    tokens: (layers + the sliced output head) x 3."""
    fwd = sum(layer_fwd_flops(config, kind[0], rows, seq)
              for kind in layer_kinds(config))
    fwd += 2 * rows * seq * config["hidden_size"] * config["vocab_size"]
    return TRAIN_MULTIPLIER * fwd
