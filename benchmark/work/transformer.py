"""Required work of a GPT-2-shaped decoder step, from shapes alone: the same
count whatever implements the layer. 2 FLOP per multiply-add; elementwise work
(LayerNorm, GELU, softmax) is not counted; recomputation is never counted.

Attention is counted at what a causal model REQUIRES: query i meets keys 0..i,
T(T+1)/2 pairs of the T*T square, so a kernel that skips masked blocks cannot
read above 100 % of a roofline built on this count.
"""

from __future__ import annotations

from benchmark.references.transformer import d_ff

TRAIN_MULTIPLIER = 3   # forward + backward (dX and dW: twice the forward)


def causal_attention_fwd_flops(rows, heads, seq, head_dim):
    """QK^T and PV over the causal pairs: 2 products x 2 FLOP x head_dim per
    (query, key) pair, T(T+1)/2 pairs per head and row."""
    return rows * heads * 2 * 2 * head_dim * (seq * (seq + 1) // 2)


def causal_attention_bwd_flops(rows, heads, seq, head_dim):
    """dV, dP, dQ, dK: four products over the same pairs. The recomputation of
    the scores inside a flash backward is not required work."""
    return 2 * causal_attention_fwd_flops(rows, heads, seq, head_dim)


def attention_fwd_bytes(rows, heads, seq, head_dim, itemsize):
    """Read Q, K, V once, write O once."""
    return 4 * rows * heads * seq * head_dim * itemsize


def attention_bwd_bytes(rows, heads, seq, head_dim, itemsize):
    """Read Q, K, V, O, dO; write dQ, dK, dV."""
    return 8 * rows * heads * seq * head_dim * itemsize


def block_matmul_fwd_flops(tokens, d_model, ff):
    """QKV projection, attention output projection, MLP up and down."""
    return 2 * tokens * (d_model * 3 * d_model + d_model * d_model
                         + 2 * d_model * ff)


def logits_fwd_flops(tokens, d_model, vocab):
    return 2 * tokens * d_model * vocab


def train_step_flops(config, rows, seq):
    """Required FLOPs of one training step on ``rows`` sequences of ``seq``
    tokens: (blocks' products + causal attention + tied logits) x 3."""
    d, L, H = config["n_embd"], config["n_layer"], config["n_head"]
    tokens = rows * seq
    fwd = (L * (block_matmul_fwd_flops(tokens, d, d_ff(config))
                + causal_attention_fwd_flops(rows, H, seq, d // H))
           + logits_fwd_flops(tokens, d, config["vocab_size"]))
    return TRAIN_MULTIPLIER * fwd


def attention_work(config, rows, seq, itemsize=2):
    """(fwd_flops, fwd_bytes, bwd_flops, bwd_bytes) of causal attention over
    all layers of one step."""
    L, H = config["n_layer"], config["n_head"]
    hd = config["n_embd"] // H
    return (L * causal_attention_fwd_flops(rows, H, seq, hd),
            L * attention_fwd_bytes(rows, H, seq, hd, itemsize),
            L * causal_attention_bwd_flops(rows, H, seq, hd),
            L * attention_bwd_bytes(rows, H, seq, hd, itemsize))
