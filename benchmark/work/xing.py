"""Required work of a ``xing`` training step on one chip's share, from shapes
alone: the same count whatever implements the layer. 2 FLOP per multiply-add;
norms, SiLU, softmax, rope, Sinkhorn and the bias update are not counted;
recomputation is never counted.

Latent attention is ``work/glm``'s count at this configuration's widths: the
core at q / k width ``qk_nope_head_dim + qk_rope_head_dim`` (192) and v width
``v_head_dim`` (128), causal, over the heads held, in every layer and the
module's. The held experts at the balanced load, the shared expert and the two
heads as there.

The hyper-connections' maps (``hc_work``) are counted a sublayer and token,
with n = ``hc_mult`` streams of C = ``hidden_size``: forward, the product of
vec(x) (n C) with the three phi (n + n + n² columns), the mix into the
sublayer's input (n C), the streams' mix (n² C) and the output's add (n C);
the backward twice the forward. Bytes, every stream element in the compute
dtype: forward, ``hc_pre`` reads x and writes the sublayer's input, ``hc_post``
reads x and the sublayer's output and writes x' ((3n + 2) C); backward,
``hc_post`` reads dx', x and the output and writes dx and the output's
gradient ((3n + 2) C), ``hc_pre`` reads the input's gradient, x and dx and
writes dx ((3n + 1) C). The maps themselves (n(n + 2) floats a token) and phi
are left out: under a hundredth of the streams' bytes.
"""

from __future__ import annotations

from benchmark.references.glm import held
from benchmark.references.xing import routed
from benchmark.work import glm

TRAIN_MULTIPLIER = glm.TRAIN_MULTIPLIER


def latent_layers(config):
    """Layers with latent attention and hyper-connections: the stack's and
    the module's."""
    return len(config["layers_held"]) + config["num_nextn_predict_layers"]


def routed_layers(config):
    return sum(routed(config)) + config["num_nextn_predict_layers"]


def attention_work(config, rows, seq):
    """The attention core, forward + backward, summed over every layer."""
    one = glm.attention_layer_work(config, rows, seq)
    return {k: latent_layers(config) * v for k, v in one.items()}


def hc_work(config, rows, seq, itemsize=2):
    """``{"flops", "bytes"}`` of the hyper-connections' maps, forward +
    backward, over the two sublayers of every layer and the module's."""
    n, C = config["hc_mult"], config["hidden_size"]
    tokens = rows * seq * 2 * latent_layers(config)
    fwd = 2 * n * C * n * (n + 2) + 2 * C * (n + n * n + n)
    return {"flops": TRAIN_MULTIPLIER * fwd * tokens,
            "bytes": ((3 * n + 2) + (3 * n + 2) + (3 * n + 1)) * C * itemsize
            * tokens}


def experts_work(config, rows, seq, itemsize=2):
    """The held routed experts' three products over all routed layers (the
    module's too), forward + backward, at the balanced load."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    L = routed_layers(config)
    n = glm.balanced_rows(config, rows * seq)
    weights = 3 * held(config)[1] * d * f * itemsize
    return {"flops": L * TRAIN_MULTIPLIER * 3 * 2 * n * d * f,
            "bytes": L * (3 * weights + 4 * n * d * itemsize)}


def train_step_flops(config, rows, seq):
    """Required FLOPs of one training step on ``rows`` sequences of ``seq``
    tokens: (layers + the module's join and layer + the two sliced heads +
    the maps) x 3."""
    tokens, d = rows * seq, config["hidden_size"]
    fwd = sum(glm.latent_fwd_flops(config, rows, seq) + glm.ffn_fwd_flops(
        config, r, tokens) for r in routed(config))
    mtp = config["num_nextn_predict_layers"]
    fwd += mtp * (2 * tokens * 2 * d * d
                  + glm.latent_fwd_flops(config, rows, seq)
                  + glm.ffn_fwd_flops(config, True, tokens))
    fwd += (1 + mtp) * 2 * tokens * d * config["vocab_size"]
    return TRAIN_MULTIPLIER * fwd + hc_work(config, rows, seq)["flops"]
