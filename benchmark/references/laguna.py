"""Plain reference for the ``laguna`` family (poolside Laguna-XS.2, read from its
public ``config.json``): a pre-norm decoder with RMSNorm, bias-free
projections, grouped-query attention of head size ``head_dim`` whose layers
are ``full_attention`` or ``sliding_attention`` with their own query-head
count and rotary embedding (half-split pairs; partial and YaRN-scaled on full
layers), a per-head sigmoid gate on the attention output, a SwiGLU MLP in the
``dense`` layers and, in the ``sparse`` ones, a shared expert plus routed
SwiGLU experts (sigmoid scores over all ``num_experts``, the
``num_experts_per_tok`` largest normalised to sum 1, times
``moe_routed_scaling_factor``), an output head of its own; its mean
next-token loss, gradients and the AdamW update, in straightforward
``jax.numpy`` and float32.

Independent of the code under test: imports nothing of the program, makes its
own weights from the seed, and is told only sizes (the configuration file) and
token batches.

**The chip's share** (``experts_held`` = [first, count)): the router keeps its
published width and its experts per token; of the routed sum only the terms of
the experts held here are computed, what the absent experts would add is left
out, and that partial result goes on to the next layer. The vocabulary is the
slice ``vocab_size`` of the published one. With ``experts_held`` = [0,
``num_experts``) this is the whole model.

What the config leaves open is read as the configuration's ``assumed`` says
(ISSUE 28): ``gating`` a per-head sigmoid gate from the layer's normed input;
no q/k norm; no selection bias and no auxiliary loss.

Departures from a textbook forward pass, each for memory only (8.3 GB of
float32 weights and moments and 2.8 GB of gradients have to fit beside one
row's activations on a 16 GB chip): a layer works on one row of the batch at a
time (``lax.map``) and is rematerialised in the backward pass
(``jax.checkpoint``), row by row; attention takes its queries in blocks of
``QUERY_BLOCK`` against all keys, dense and masked; the held experts are
computed on every token of the row, ``EXPERT_GROUP`` of them at a time, and
selected by a weight that is zero where the token did not choose them; the
head and the loss take a row at a time too.

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
EXPERT_GROUP = 8   # held experts computed at a time


# --- the configuration, as the reference reads it ---------------------------

def held(config):
    """(first, count) of the routed experts this chip holds."""
    first, end = config.get("experts_held", [0, config["num_experts"]])
    return first, end - first


def layer_kinds(config):
    """Per layer kept: (attention type, query heads, MLP kind)."""
    L = config["num_hidden_layers"]
    return list(zip(config["layer_types"][:L],
                    config["num_attention_heads_per_layer"][:L],
                    config["mlp_layer_types"][:L]))


def layer_shapes(config, kind):
    """``{leaf: shape}`` of one layer, matrices as [in, out]."""
    _, H, mlp = kind
    d, hd = config["hidden_size"], config["head_dim"]
    KV = config["num_key_value_heads"]
    shapes = {"input_norm": (d,), "q_proj": (d, H * hd),
              "k_proj": (d, KV * hd), "v_proj": (d, KV * hd),
              "o_proj": (H * hd, d), "post_norm": (d,)}
    if config["gating"]:
        shapes["g_proj"] = (d, H)
    if mlp == "dense":
        f = config["intermediate_size"]
        shapes.update(gate_proj=(d, f), up_proj=(d, f), down_proj=(f, d))
    else:
        f, fs = (config["moe_intermediate_size"],
                 config["shared_expert_intermediate_size"])
        count = held(config)[1]
        shapes.update(router=(d, config["num_experts"]),
                      experts_gate=(count, d, f), experts_up=(count, d, f),
                      experts_down=(count, f, d), shared_gate=(d, fs),
                      shared_up=(d, fs), shared_down=(fs, d))
    return shapes


def init_weights(config, seed):
    """N(0, ``initializer_range``) for every matrix, gains 1, from the seed.
    ``{"embed", "head", "norm_f", "layers": [{leaf: array}]}``. One jitted
    call, float32."""
    d, V = config["hidden_size"], config["vocab_size"]
    std = config["assumed"]["initializer_range"]
    kinds = layer_kinds(config)

    @jax.jit
    def make(key):
        def fill(key, shapes):
            keys = jax.random.split(key, len(shapes))
            return {name: (jnp.ones(shape, jnp.float32) if len(shape) == 1
                           else std * jax.random.normal(k, shape, jnp.float32))
                    for k, (name, shape) in zip(keys, sorted(shapes.items()))}

        keys = jax.random.split(key, len(kinds) + 1)
        top = fill(keys[0], {"embed": (V, d), "head": (d, V), "norm_f": (d,)})
        top["layers"] = [fill(k, layer_shapes(config, kind))
                         for k, kind in zip(keys[1:], kinds)]
        return top

    return make(seed_key(seed, stream=1))


# --- the layer equations ------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_tables(rope, head_dim, T):
    """cos, sin [T, rot/2] of one layer type's ``rope_parameters`` entry: the
    first ``rot = head_dim * partial_rotary_factor`` dims of a head are
    rotated. ``default``: theta ** (-2i / rot). ``yarn`` (Peng et al. 2023,
    as the config's keys are read by Hugging Face's
    ``_compute_yarn_parameters``): frequency i is interpolated (divided by
    ``factor``) where its dim turns fewer than ``beta_slow`` times over the
    original context, kept where it turns more than ``beta_fast`` times, a
    linear ramp between; cos and sin are scaled by ``attention_factor``."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = rope["rope_theta"]
    inv = theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    scale = 1.0
    if rope["rope_type"] == "yarn":
        orig = rope["original_max_position_embeddings"]

        def dim_of(turns):
            return rot * math.log(orig / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(dim_of(rope["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
        inv = inv / rope["factor"] * ramp + inv * (1 - ramp)
        scale = rope.get("attention_factor") \
            or 0.1 * math.log(rope["factor"]) + 1.0
    elif rope["rope_type"] != "default":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    angles = np.arange(T, dtype=np.float64)[:, None] * inv
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32))


def rotate(x, cos, sin):
    """x [T, heads, head_dim]: dims (i, i + rot/2) of the first ``rot`` are a
    pair (``rotate_half``); the rest pass."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def attention(config, precision, kind, q, k, v):
    """q [T, H, hd]; k, v [T, KV, hd]: softmax(q k^T / sqrt(hd)) v, causal,
    and on ``sliding_attention`` layers key j is seen by query i iff
    0 <= i - j < ``sliding_window``; consecutive H / KV query heads share a
    key/value head. Queries in blocks against all keys, each block
    rematerialised in the backward pass."""
    T, H, hd = q.shape
    KV = k.shape[1]
    window = config["sliding_window"] if kind[0] == "sliding_attention" \
        else None
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

    o = jax.lax.map(block, (q, jnp.arange(0, T, qb)))
    return o.reshape(T, H, hd)


def swiglu(precision, h, gate, up, down):
    return matmul(jax.nn.silu(matmul(h, gate, precision))
                  * matmul(h, up, precision), down, precision)


def experts(config, precision, h, lp):
    """shared(h) + scale * sum over the chosen experts HELD HERE of
    w_e expert_e(h), h [T, d]. Every held expert on every token, weighted by
    ``w`` [T, count], zero where the token did not choose it, ``EXPERT_GROUP``
    experts at a time."""
    E, K = config["num_experts"], config["num_experts_per_tok"]
    first, count = held(config)
    logits = matmul(h, lp["router"], precision)       # [T, E], float32
    scores = jax.nn.sigmoid(logits)
    top, chosen = jax.lax.top_k(scores, K)
    top = top / top.sum(-1, keepdims=True) * config["moe_routed_scaling_factor"]
    w = (jax.nn.one_hot(chosen, E, dtype=h.dtype) * top[..., None]).sum(1)
    w = w[:, first:first + count]

    @jax.checkpoint
    def group(h, w, gate, up, down):
        act = jax.nn.silu(matmul(h, gate, precision, "td,edf->etf")) \
            * matmul(h, up, precision, "td,edf->etf")
        return matmul(act * w.T[:, :, None], down, precision, "etf,efd->td")

    routed = 0.0
    for s in range(0, count, EXPERT_GROUP):
        e = slice(s, s + EXPERT_GROUP)
        routed = routed + group(h, w[:, e], lp["experts_gate"][e],
                                lp["experts_up"][e], lp["experts_down"][e])
    return routed + swiglu(precision, h, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"])


def layer(config, precision, kind, x, lp):
    """One layer on one row, x [T, d]."""
    T, d = x.shape
    _, H, mlp = kind
    hd, KV = config["head_dim"], config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    h = rms_norm(x, lp["input_norm"], eps)
    q = matmul(h, lp["q_proj"], precision).reshape(T, H, hd)
    k = matmul(h, lp["k_proj"], precision).reshape(T, KV, hd)
    v = matmul(h, lp["v_proj"], precision).reshape(T, KV, hd)
    cos, sin = rope_tables(config["rope_parameters"][kind[0]], hd, T)
    o = attention(config, precision, kind, rotate(q, cos, sin),
                  rotate(k, cos, sin), v)
    if config["gating"]:
        o = o * jax.nn.sigmoid(matmul(h, lp["g_proj"], precision))[..., None]
    x = x + matmul(o.reshape(T, H * hd), lp["o_proj"], precision)
    h = rms_norm(x, lp["post_norm"], eps)
    if mlp == "dense":
        return x + swiglu(precision, h, lp["gate_proj"], lp["up_proj"],
                          lp["down_proj"])
    return x + experts(config, precision, h, lp)


def loss_fn(config, precision, params, tokens, rows=None):
    """Mean next-token negative log-likelihood of ``tokens`` [B, T+1]. ``rows``
    (a fault for the tests of the comparison): only those rows count."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if rows is not None:
        inputs, targets = inputs[rows], targets[rows]
    x = params["embed"][inputs]
    for kind, lp in zip(layer_kinds(config), params["layers"]):
        row = jax.checkpoint(functools.partial(layer, config, precision, kind))
        x = jax.checkpoint(lambda x, lp, row=row: jax.lax.map(
            lambda xr: row(xr, lp), x))(x, lp)
    @jax.checkpoint
    def row_nll(args):
        xr, tr = args
        xr = rms_norm(xr, params["norm_f"], config["rms_norm_eps"])
        logp = jax.nn.log_softmax(matmul(xr, params["head"], precision),
                                  axis=-1)
        return -jnp.take_along_axis(logp, tr[:, None], axis=-1)[:, 0]

    return jax.lax.map(row_nll, (x, targets)).mean()


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
def _programs(config_json, precision, fault, half):
    """The jitted step and change-of-parameters, built once per process for one
    (configuration, precision, fault)."""
    config = json.loads(config_json)
    opt_conf = config["assumed"]["optimizer"]
    rows = slice(0, half) if fault == "half_batch" else None

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, tokens):
        loss, grads = jax.value_and_grad(
            functools.partial(loss_fn, config, precision))(params, tokens, rows)
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
    chip when limits are set, and in ``benchmark/tests``): ``"half_batch"`` takes
    the mean over the first half of the rows only; ``"state_unchanged"`` returns
    the parameters as they were."""
    step, change = _programs(json.dumps(config, sort_keys=True), precision,
                             fault, max(1, batches[0].shape[0] // 2))
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
