"""Required work of a ``laguna`` training step on one chip's share, from shapes
alone: the same count whatever implements the layer. 2 FLOP per multiply-add;
elementwise work (norms, SiLU, softmax, rope, the gate's sigmoid) is not
counted; recomputation is never counted.

Attention is counted at what the mask REQUIRES: in a full layer query i meets
keys 0..i, T(T+1)/2 pairs; in a window layer of width W the last W of them,
W(W+1)/2 + (T-W)W pairs. The experts are counted at the balanced load: of the
tokens x ``num_experts_per_tok`` assignments, the share ``held / num_experts``
meets an expert held here, whatever buffer the program computes over. The
output head is the slice held.
"""

from __future__ import annotations

from benchmark.references.laguna import held, layer_kinds

TRAIN_MULTIPLIER = 3   # forward + backward (dX and dW: twice the forward)


def attention_pairs(config, kind, seq):
    """(query, key) pairs a head and row of one layer of ``kind``."""
    if kind[0] == "sliding_attention" and config["sliding_window"] < seq:
        w = config["sliding_window"]
        return w * (w + 1) // 2 + (seq - w) * w
    return seq * (seq + 1) // 2


def attention_layer_work(config, kind, rows, seq, itemsize=2):
    """``{"flops", "bytes"}`` of one layer's attention, forward + backward:
    QK^T and PV forward, dV, dP, dQ, dK backward, 2 FLOP x head_dim a pair
    each (the scores recomputed inside a flash backward are not required
    work). Bytes: Q, K, V read and O written once forward; Q, K, V, O, dO read
    and dQ, dK, dV written once backward, the key/value heads at their own
    count."""
    H, KV, hd = kind[1], config["num_key_value_heads"], config["head_dim"]
    pairs = attention_pairs(config, kind, seq)
    q_like = rows * H * seq * hd * itemsize
    kv_like = rows * KV * seq * hd * itemsize
    return {"flops": 6 * 2 * hd * pairs * rows * H,
            "bytes": (2 * q_like + 2 * kv_like) + (4 * q_like + 4 * kv_like)}


def attention_work(config, rows, seq, layer_type):
    """Summed over the layers of ``layer_type``; None where there is none."""
    found = [attention_layer_work(config, kind, rows, seq)
             for kind in layer_kinds(config) if kind[0] == layer_type]
    if not found:
        return None
    return {k: sum(f[k] for f in found) for k in ("flops", "bytes")}


def balanced_rows(config, tokens):
    """Assignments that meet a held expert when the router is balanced."""
    return tokens * config["num_experts_per_tok"] * held(config)[1] \
        // config["num_experts"]


def experts_work(config, rows, seq, itemsize=2):
    """The held routed experts' three products over all sparse layers, forward
    + backward, at the balanced load. Bytes: the rows in and out and each held
    expert's three matrices, read forward and backward and their gradients
    written (float32 masters aside)."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    sparse = sum(kind[2] == "sparse" for kind in layer_kinds(config))
    if not sparse:
        return None
    n = balanced_rows(config, rows * seq)
    weights = 3 * held(config)[1] * d * f * itemsize
    return {"flops": sparse * TRAIN_MULTIPLIER * 3 * 2 * n * d * f,
            "bytes": sparse * (3 * weights + 4 * n * d * itemsize)}


def layer_fwd_flops(config, kind, rows, seq):
    """One layer's forward products and attention."""
    d, hd = config["hidden_size"], config["head_dim"]
    H, KV = kind[1], config["num_key_value_heads"]
    tokens = rows * seq
    proj = 2 * tokens * d * (2 * H * hd + 2 * KV * hd)    # q, k, v, o
    if config["gating"]:
        proj += 2 * tokens * d * H
    attn = attention_layer_work(config, kind, rows, seq)["flops"] // 3
    if kind[2] == "dense":
        ffn = 3 * 2 * tokens * d * config["intermediate_size"]
    else:
        ffn = (2 * tokens * d * config["num_experts"]         # router
               + 3 * 2 * tokens * d * config["shared_expert_intermediate_size"]
               + 3 * 2 * balanced_rows(config, tokens) * d
               * config["moe_intermediate_size"])
    return proj + attn + ffn


def train_step_flops(config, rows, seq):
    """Required FLOPs of one training step on ``rows`` sequences of ``seq``
    tokens: (layers + the sliced output head) x 3."""
    fwd = sum(layer_fwd_flops(config, kind, rows, seq)
              for kind in layer_kinds(config))
    fwd += 2 * rows * seq * config["hidden_size"] * config["vocab_size"]
    return TRAIN_MULTIPLIER * fwd
