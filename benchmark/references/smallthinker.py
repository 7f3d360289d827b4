"""Plain reference for the ``smallthinker`` family (PowerInfer
SmallThinker-21BA3B-Instruct, "SmallThinker: A Family of Efficient Large
Language Models Natively Trained for Local Deployment", read from its public
``config.json``): a pre-norm decoder whose every layer has routed experts and
no dense MLP, whose router reads the layer's input AHEAD of attention, and
whose layers are either full-causal without any position signal or windowed
with a rotary embedding; its mean next-token loss, gradients and the AdamW
update, in straightforward ``jax.numpy`` and float32.

The equations of layer ``i`` (sizes are the config's; ``E`` =
``moe_num_primary_experts``, ``K`` = ``moe_num_active_primary_experts``)::

    h  = RMSNorm(x; g1)
    p  = softmax(h W_r) over all E          (moe_primary_router_apply_softmax)
    e_K = the K largest of p;  w = p[e_K] / sum(p[e_K])        (norm_topk_prob)
    q, k, v = h Wq, h Wk, h Wv     heads of head_dim; H / KV query heads share
                                   a key/value head, consecutive ones
    rope_layout[i] == 1: q, k turned over the whole head in rotate_half pairs
                         (j, j + head_dim / 2) at rope_theta; == 0: not turned
    sliding_window_layout[i] == 1: key j is seen by query t iff
                         0 <= t - j < sliding_window_size; == 0: iff j <= t
    x1 = x + softmax(q k^T / sqrt(head_dim)) v Wo
    u  = RMSNorm(x1; g2)
    m  = sum_{e in e_K} w_e (relu(u G_e) * (u U_e)) D_e                 (ReGLU)
    x2 = x1 + m

No bias, no q/k norm, no shared expert, no dense layer, no scale on ``w``; the
head is untied. The model: ``x = E[tokens]``, the layers, ``RMSNorm(.; g_f)``,
``logits = . W_head``, the loss the mean cross-entropy of the next token.

Independent of the code under test: imports nothing of the program, makes its
own weights from the seed, and is told only sizes (the configuration file) and
token batches.

**The chip's share** (``experts_held`` = [first, end)): the router keeps its
published width and its experts per token; of the routed sum only the terms of
the experts held here are computed, what the absent experts would add is left
out, and that partial result goes on to the next layer. The vocabulary is the
slice ``vocab_size`` of the published one. With ``experts_held`` = [0, ``E``)
this is the whole model.

What the config leaves open is read as the configuration's ``assumed`` says
(ISSUE 34): the router reads the NORMED block input ``h`` (the alternative,
the raw residual ``x``, is the one argument of ``routing`` in ``layer``); the
catalog speaks of "primary + secondary experts", the config has primary keys
only and nothing is computed for a secondary level; no auxiliary or balancing
loss; N(0, ``initializer_range``) on every matrix, gains 1.

Departures from a textbook forward pass, each for memory only (7.9 GB of
float32 weights and moments and 2.6 GB of gradients have to fit beside one
16384-token row's activations on a 16 GB chip): a layer works on one row of
the batch at a time (``lax.map``) and is rematerialised in the backward pass
(``jax.checkpoint``); attention takes its queries in blocks of
``QUERY_BLOCK`` against all keys, dense and masked by position, each block
rematerialised; the held experts are a loop (``lax.scan``) over the experts,
each computed on EVERY token of the row and weighted by ``w`` laid out
one-hot over the experts, zero where the token did not choose it (no sort, no
buffer, no grouped product); the head and the loss take ``HEAD_BLOCK`` tokens
at a time, rematerialised, so that a row's vocabulary-wide logits never live
whole.

``precision`` selects what the matrix products are computed in
(``references/numerics``): ``"float32"`` is THE reference, ``"bfloat16"`` what
the configuration states, ``"fp8"`` and ``"int8"`` the controls one step below.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.references.numerics import matmul, seed_key

QUERY_BLOCK = 128
HEAD_BLOCK = 1024   # tokens whose vocabulary-wide logits live at a time

# faults of the ARCHITECTURE that ``first_steps`` can plant (the limits of a
# cell have to catch each): which tensor the router reads, which layers turn,
# which layers keep a window
ARCHITECTURE_FAULTS = ("router_from_ln2", "rope_on_full", "window_dropped")


# --- the configuration, as the reference reads it ---------------------------

def held(config):
    """(first, count) of the routed experts this chip holds."""
    first, end = config.get("experts_held",
                            [0, config["moe_num_primary_experts"]])
    return first, end - first


def layer_kinds(config):
    """Per layer kept: (windowed, turned by rope), the first
    ``num_hidden_layers`` entries of the two published layouts."""
    L = config["num_hidden_layers"]
    return [(bool(w), bool(r)) for w, r in zip(
        config["sliding_window_layout"][:L], config["rope_layout"][:L])]


def layer_shapes(config):
    """``{leaf: shape}`` of one layer (every layer has the same), matrices as
    [in, out]."""
    d, hd = config["hidden_size"], config["head_dim"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    f, count = config["moe_ffn_hidden_size"], held(config)[1]
    return {"input_norm": (d,), "q_proj": (d, H * hd), "k_proj": (d, KV * hd),
            "v_proj": (d, KV * hd), "o_proj": (H * hd, d), "post_norm": (d,),
            "router": (d, config["moe_num_primary_experts"]),
            "experts_gate": (count, d, f), "experts_up": (count, d, f),
            "experts_down": (count, f, d)}


def top_shapes(config):
    d, V = config["hidden_size"], config["vocab_size"]
    return {"embed": (V, d), "head": (d, V), "norm_f": (d,)}


def num_params(config):
    size = lambda shapes: sum(math.prod(s) for s in shapes.values())
    return config["num_hidden_layers"] * size(layer_shapes(config)) \
        + size(top_shapes(config))


def init_weights(config, seed):
    """N(0, ``initializer_range``) for every matrix, gains 1, from the seed.
    ``{"embed", "head", "norm_f", "layers": [{leaf: array}]}``. One jitted
    call, float32."""
    std = config["assumed"]["initializer_range"]
    L = config["num_hidden_layers"]

    @jax.jit
    def make(key):
        def fill(key, shapes):
            keys = jax.random.split(key, len(shapes))
            return {name: (jnp.ones(shape, jnp.float32) if len(shape) == 1
                           else std * jax.random.normal(k, shape, jnp.float32))
                    for k, (name, shape) in zip(keys, sorted(shapes.items()))}

        keys = jax.random.split(key, L + 1)
        top = fill(keys[0], top_shapes(config))
        top["layers"] = [fill(k, layer_shapes(config)) for k in keys[1:]]
        return top

    return make(seed_key(seed, stream=1))


# --- the layer equations ------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_tables(config, T):
    """cos, sin [T, head_dim / 2] at ``rope_theta``, no scaling
    (``rope_scaling`` null), made in float64 on the host."""
    hd = config["head_dim"]
    inv = float(config["rope_theta"]) ** (
        -np.arange(0, hd, 2, dtype=np.float64) / hd)
    angles = np.arange(T, dtype=np.float64)[:, None] * inv
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def rotate(x, cos, sin):
    """x [T, heads, head_dim]: dims (j, j + head_dim / 2) are a pair
    (``rotate_half``), turned by the position's angle."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(precision, window, q, k, v):
    """q [T, H, hd]; k, v [T, KV, hd]: softmax(q k^T / sqrt(hd)) v under the
    mask, an explicit one by position: causal, and inside ``window`` keys
    where the layer has one. Queries in blocks against all keys."""
    T, H, hd = q.shape
    KV = k.shape[1]
    qb = min(QUERY_BLOCK, T)
    assert T % qb == 0
    q = q.reshape(T // qb, qb, KV, H // KV, hd)
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qi, start = args
        s = matmul(qi, k, precision, "qkgd,tkd->kgqt") / math.sqrt(hd)
        dist = (start + jnp.arange(qb))[:, None] - key_pos[None, :]
        keep = dist >= 0
        if window is not None:
            keep &= dist < window
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return matmul(p, v, precision, "kgqt,tkd->qkgd")

    return jax.lax.map(block, (q, jnp.arange(0, T, qb))).reshape(T, H, hd)


def routing(config, precision, r, router):
    """``w`` [T, E]: each token's weight on each expert, zero where it did not
    choose it, from what the router reads, ``r`` [T, d]: softmax over all E,
    the K largest, renormalised to sum 1."""
    E = config["moe_num_primary_experts"]
    K = config["moe_num_active_primary_experts"]
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("the reference is written for the published router: "
                         "softmax over all experts, the chosen renormalised")
    p = jax.nn.softmax(matmul(r, router, precision), axis=-1)
    top, chosen = jax.lax.top_k(p, K)
    top = top / top.sum(-1, keepdims=True)
    return (jax.nn.one_hot(chosen, E, dtype=r.dtype) * top[..., None]).sum(1)


def experts(config, precision, u, w, lp):
    """sum over the experts HELD HERE of w_e ReGLU_e(u), u [T, d], w [T, E]:
    one held expert after the other, each on every token."""
    first, count = held(config)
    w = w[:, first:first + count]

    @jax.checkpoint
    def one(we, gate, up, down):
        act = jax.nn.relu(matmul(u, gate, precision)) \
            * matmul(u, up, precision)
        return we[:, None] * matmul(act, down, precision)

    # the running sum stays outside the rematerialised part, so the backward
    # pass keeps no copy of it per expert
    total, _ = jax.lax.scan(lambda total, e: (total + one(*e), None),
                            jnp.zeros_like(u), (w.T, lp["experts_gate"],
                                                lp["experts_up"],
                                                lp["experts_down"]))
    return total


def layer(config, precision, kind, fault, x, lp):
    """One layer on one row, x [T, d]. ``fault`` (None, or one of
    ``ARCHITECTURE_FAULTS``) plants what a cell's limits have to catch."""
    T, d = x.shape
    windowed, turned = kind
    hd = config["head_dim"]
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    h = rms_norm(x, lp["input_norm"], eps)
    # THE ROUTER READS h, ahead of attention (``assumed.router_input``; the
    # alternative reading is the raw residual x: this one argument)
    w = routing(config, precision, h, lp["router"])
    q = matmul(h, lp["q_proj"], precision).reshape(T, H, hd)
    k = matmul(h, lp["k_proj"], precision).reshape(T, KV, hd)
    v = matmul(h, lp["v_proj"], precision).reshape(T, KV, hd)
    if turned or fault == "rope_on_full":
        cos, sin = rope_tables(config, T)
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    window = config["sliding_window_size"] \
        if windowed and fault != "window_dropped" else None
    o = attention(precision, window, q, k, v)
    x = x + matmul(o.reshape(T, H * hd), lp["o_proj"], precision)
    u = rms_norm(x, lp["post_norm"], eps)
    if fault == "router_from_ln2":
        w = routing(config, precision, u, lp["router"])
    return x + experts(config, precision, u, w, lp)


def hidden(config, precision, params, inputs, fault=None):
    """The last layer's output [B, T, d], before the final norm."""
    x = params["embed"][inputs]
    for kind, lp in zip(layer_kinds(config), params["layers"]):
        row = jax.checkpoint(
            functools.partial(layer, config, precision, kind, fault))
        x = jax.checkpoint(lambda x, lp, row=row: jax.lax.map(
            lambda xr: row(xr, lp), x))(x, lp)
    return x


def logits(config, precision, params, inputs):
    """[B, T, vocab_size]: for the tests' small sizes, whole."""
    x = rms_norm(hidden(config, precision, params, inputs), params["norm_f"],
                 config["rms_norm_eps"])
    return matmul(x, params["head"], precision)


def loss_fn(config, precision, params, tokens, fault=None):
    """Mean next-token negative log-likelihood of ``tokens`` [B, T+1].
    ``fault`` ``"half_batch"`` (for the tests of the comparison): only the
    first half of the rows count, or of the one row's positions where the
    batch is one row."""
    if fault == "half_batch":
        B, T = tokens.shape[0], tokens.shape[1] - 1
        tokens = tokens[:B // 2] if B > 1 else tokens[:, :T // 2 + 1]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = hidden(config, precision, params, inputs,
               fault if fault in ARCHITECTURE_FAULTS else None)
    B, T, d = x.shape
    hb = min(HEAD_BLOCK, T)
    assert T % hb == 0

    @jax.checkpoint
    def block_nll(args):
        xb, tb = args
        xb = rms_norm(xb, params["norm_f"], config["rms_norm_eps"])
        logp = jax.nn.log_softmax(matmul(xb, params["head"], precision),
                                  axis=-1)
        return -jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]

    return jax.lax.map(block_nll, (x.reshape(-1, hb, d),
                                   targets.reshape(-1, hb))).mean()


# --- AdamW and the readings -----------------------------------------------------

def adamw(opt_conf, params, grads, m, v, t):
    """AdamW; the decay on every matrix, the RMSNorm gains exempt."""
    b1, b2 = opt_conf["beta1"], opt_conf["beta2"]
    lr, eps, wd = (opt_conf["learning_rate"], opt_conf["eps"],
                   opt_conf["weight_decay"])

    def upd(p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        step = (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            step = step + wd * p
        return p - lr * step, m2, v2

    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree):
    """L2 norm of every leaf under flat names: ``{"embed": x, "b0.q_proj": y}``
    (device scalars)."""
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a)))
    flat = {k: norm(a) for k, a in tree.items() if k != "layers"}
    for i, lp in enumerate(tree["layers"]):
        flat.update({f"b{i}.{k}": norm(a) for k, a in lp.items()})
    return flat


def _host(norms):
    return {k: float(v) for k, v in jax.device_get(norms).items()}


@functools.lru_cache(maxsize=None)
def _programs(config_json, precision, fault):
    """The jitted step and change-of-parameters, built once per process for one
    (configuration, precision, fault)."""
    config = json.loads(config_json)
    opt_conf = config["assumed"]["optimizer"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, tokens):
        loss, grads = jax.value_and_grad(functools.partial(
            loss_fn, config, precision))(params, tokens, fault)
        norms = leaf_norms(grads)
        new_p, m2, v2 = adamw(opt_conf, params, grads, m, v, t)
        if fault == "state_unchanged":
            new_p = params
        return new_p, m2, v2, loss, norms

    @functools.partial(jax.jit, donate_argnums=(0,))
    def change(params, start):
        return leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))

    return step, change


def first_steps(config, seed, batches, precision="float32", fault=None):
    """Follow the first ``len(batches)`` training steps from the seed's weights.
    Returns what the comparison reads: each step's loss, the per-leaf norm of
    the first gradient, and the per-leaf norm of the parameters' change over
    the steps.

    ``fault`` plants one of the faults the comparison has to catch (read on the
    chip when limits are set, and in ``benchmark/tests``): ``"half_batch"``
    takes the mean over half the batch only; ``"state_unchanged"`` returns the
    parameters as they were; ``"router_from_ln2"`` feeds the router what the
    experts read; ``"rope_on_full"`` turns q and k in the full layers too;
    ``"window_dropped"`` runs the window layers full-causal."""
    step, change = _programs(json.dumps(config, sort_keys=True), precision,
                             fault)
    params = init_weights(config, seed)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norm = [], None
    for i, tokens in enumerate(batches):
        params, m, v, loss, gn = step(params, m, v, float(i + 1),
                                      jnp.asarray(tokens, jnp.int32))
        losses.append(float(loss))
        if i == 0:
            grad_norm = _host(gn)
    del m, v   # the seed's weights are made again beside the parameters alone
    delta = _host(change(params, init_weights(config, seed)))
    del params
    return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta}
