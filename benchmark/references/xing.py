"""Plain reference for the ``xing`` family (XingChen-AGI Xing4.0-29B-A4B,
``model_type`` ``xing4_0``, read from its public ``config.json``): the
``glm`` family's decoder (latent attention in every layer, dense leading
layers, routed experts with a shared expert under a router whose selection
carries a bias that every step moves, a multi-token-prediction module beside
the head) with three changes, in straightforward ``jax.numpy`` and float32:

* **the residual path** is manifold-constrained hyper-connections (mHC,
  DeepSeek, arXiv:2512.24880): ``hc_mult`` = n streams x in R^{n x C}, and
  each sublayer F (attention, FFN) of a layer is wrapped as::

      x~     = RMSNorm(vec(x)) over n·C, no gain
      H_pre  = sigmoid(a_pre · x~ phi_pre + b_pre)                     1 x n
      H_post = 2 sigmoid(a_post · x~ phi_post + b_post)                1 x n
      H_res  = SinkhornKnopp(exp(clamp(a_res · mat(x~ phi_res) + b_res)))
      x'     = H_res x + H_post^T F(RMSNorm(H_pre x))

  SinkhornKnopp: ``hc_sinkhorn_iters`` rounds of a row normalisation then a
  column one, each sum plus ``hc_eps``; the clamp is [``mhc_h_res_clamp_min``,
  ``mhc_h_res_clamp_max``]. The stream starts as n copies of the embedding
  (and, in the module, of the join) and is summed back to one before a final
  norm (Zhu et al. 2024, arXiv:2409.19606);
* **q / k and v of their own widths**: ``qk_nope_head_dim +
  qk_rope_head_dim`` against ``v_head_dim``;
* **YaRN on the latent attention**, as DeepSeek's code reads
  ``rope_scaling``: the ``qk_rope_head_dim`` dims turn at YaRN's frequencies,
  cos and sin times mscale(factor, mscale) / mscale(factor, mscale_all_dim),
  and the WHOLE logit's softmax scale is ``(nope + rope)^-1/2`` times
  mscale(factor, mscale_all_dim)², with mscale(s, m) = 0.1 m ln s + 1.

The router (sigmoid scores, ``noaux_tc`` with one group, top
``num_experts_per_tok`` of score + bias, the chosen normalised and scaled), the
experts, the heads, AdamW and the bias's update are ``references/glm``'s pure
functions at this configuration's numbers. Independent of the code under
test: imports nothing of the program, makes its own weights from the seed,
and is told only sizes (the configuration file) and token batches.

**The chip's share**: ``layers_held`` are the published layers run here (the
first ``first_k_dense_replace`` are dense); ``num_attention_heads`` is the
heads held, each with its columns of W_uq and W_ukv and its rows of W_o,
while W_dq, W_dkv and the two latent norms are whole; what the absent heads
would add to W_o's output is left out. ``experts_held`` and the vocabulary
slice as in ``references/glm``.

What the config leaves open is read as the configuration's ``assumed`` says:
the maps' initialisation (``hc_init``), the rope pairs, the module's block,
gamma, lambda.

Departures from a textbook forward pass, each for memory only: ``glm``'s
(a layer on one row at a time, rematerialised; queries in blocks of
``QUERY_BLOCK`` against all keys; the held experts one after another on every
token; each head ``HEAD_BLOCK`` tokens at a time); and a step is two
programs, the gradient and the update, with AdamW's moments in the host's
memory while the gradient is made: at 4096 tokens the four float32 streams
make the one-program step 17.6 GB (9.48 of state, 7.99 of temporaries,
compiled for the v5e), past the chip's 16.9.

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

from benchmark.references import glm
# ``flat_leaves`` (the program's flat leaf names), ``held`` and ``leaf_norms``
# are the glm reference's, read through this module by the driver and tests
from benchmark.references.glm import (adamw, bias_reading, flat_leaves, held,
                                      head_sum, leaf_norms, moved_bias,
                                      rms_norm, rotate, summed, swiglu)
from benchmark.references.numerics import matmul, seed_key

QUERY_BLOCK = glm.QUERY_BLOCK

# faults of the ARCHITECTURE that ``first_steps`` can plant (a cell's limits
# have to catch each, or PERF.md says which they do not): H_res by a row
# normalisation alone, no Sinkhorn; H_post at sigmoid, not 2 sigmoid; YaRN's
# mscale² left out of the softmax scale; one residual stream in place of n
# (the maps of stream 0 alone)
ARCHITECTURE_FAULTS = ("res_row_softmax", "post_sigmoid", "no_mscale",
                       "one_stream")
BIAS_FAULTS = glm.BIAS_FAULTS
HC_MAPS = ("phi_pre", "phi_post", "phi_res", "alpha", "b_pre", "b_post",
           "b_res")
SUBLAYERS = ("attn_hc", "mlp_hc")


# --- the configuration, as the reference reads it ---------------------------

def routed(config):
    """Per layer held: whether it is routed."""
    return [i >= config["first_k_dense_replace"]
            for i in config["layers_held"]]


def hc_shapes(config):
    """``{leaf: shape}`` of one sublayer's maps, by ``HC_MAPS``."""
    n, d = config["hc_mult"], config["hidden_size"]
    return {"phi_pre": (n * d, n), "phi_post": (n * d, n),
            "phi_res": (n * d, n * n), "alpha": (3,), "b_pre": (n,),
            "b_post": (n,), "b_res": (n * n,)}


def layer_shapes(config, is_routed):
    shapes = glm.layer_shapes(config, is_routed)
    for sub in SUBLAYERS:
        shapes.update({f"{sub}_{k}": s for k, s in hc_shapes(config).items()})
    return shapes


def bias_keys(config):
    keys = [f"b{i}" for i, r in enumerate(routed(config)) if r]
    return keys + ["mtp"] * config["num_nextn_predict_layers"]


def num_params(config):
    """Weights and the routers' biases (``e_score_correction_bias``)."""
    size = lambda shapes: sum(math.prod(s) for s in shapes.values())
    n = sum(size(layer_shapes(config, r)) for r in routed(config))
    if config["num_nextn_predict_layers"]:
        n += size(glm.mtp_shapes(config)) + size(layer_shapes(config, True))
    return n + size(glm.top_shapes(config)) \
        + len(bias_keys(config)) * config["n_routed_experts"]


def init_weights(config, seed):
    """N(0, ``initializer_range``) for every matrix, gains 1, and the maps as
    ``assumed.hc_init`` says, from the seed. ``{"embed", "head", "norm_f",
    "layers": [{leaf: array}], "mtp": {..., "layer": {leaf: array}}}``. One
    jitted call, float32."""
    if config["num_nextn_predict_layers"] != 1:
        raise ValueError("the reference is written for one MTP module")
    std = config["assumed"]["initializer_range"]
    alpha = config["assumed"]["hc_init"]["alpha"]

    def value(name, key, shape):
        if name.endswith("_alpha"):
            return jnp.full(shape, alpha, jnp.float32)
        if "_hc_b_" in name:
            return jnp.zeros(shape, jnp.float32)
        if len(shape) == 1:
            return jnp.ones(shape, jnp.float32)
        return std * jax.random.normal(key, shape, jnp.float32)

    @jax.jit
    def make(key):
        def fill(key, shapes):
            keys = jax.random.split(key, len(shapes))
            return {name: value(name, k, shape)
                    for k, (name, shape) in zip(keys, sorted(shapes.items()))}

        kinds = routed(config)
        keys = jax.random.split(key, len(kinds) + 3)
        top = fill(keys[0], glm.top_shapes(config))
        top["layers"] = [fill(k, layer_shapes(config, r))
                         for k, r in zip(keys[1:], kinds)]
        top["mtp"] = fill(keys[-2], glm.mtp_shapes(config))
        top["mtp"]["layer"] = fill(keys[-1], layer_shapes(config, True))
        return top

    return make(seed_key(seed, stream=1))


def init_bias(config):
    return {k: jnp.zeros((config["n_routed_experts"],), jnp.float32)
            for k in bias_keys(config)}


# --- YaRN, as DeepSeek's code reads ``rope_scaling`` --------------------------

def mscale(scale, m):
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def softmax_scale(config, fault=None):
    """``(nope + rope)^-1/2 x mscale(factor, mscale_all_dim)²``."""
    rs = config["rope_scaling"]
    base = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    if fault == "no_mscale" or not rs.get("mscale_all_dim"):
        return base
    return base * mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def rope_tables(config, T):
    """cos, sin [T, rope / 2] at YaRN's frequencies, made in float64 on the
    host: a dim that turns more than ``beta_fast`` times over the original
    context keeps ``theta^(-2i / rope)``, one that turns fewer than
    ``beta_slow`` times takes it over ``factor``, a linear ramp between."""
    rs, dim = config["rope_scaling"], config["qk_rope_head_dim"]
    base, factor = float(config["rope_theta"]), float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def find_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(find_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(find_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    angles = np.arange(T, dtype=np.float64)[:, None] * inv
    cs = mscale(factor, rs["mscale"]) / mscale(factor, rs["mscale_all_dim"])
    return (jnp.asarray(np.cos(angles) * cs, jnp.float32),
            jnp.asarray(np.sin(angles) * cs, jnp.float32))


# --- the layer equations ------------------------------------------------------

def attention(precision, q, k, v, scale):
    """q, k [T, H, dk]; v [T, H, dv]: causal softmax(q k^T · scale) v, an
    explicit mask by position. Queries in blocks against all keys."""
    T, H, dk = q.shape
    qb = min(QUERY_BLOCK, T)
    assert T % qb == 0
    key_pos = jnp.arange(T)

    @jax.checkpoint
    def block(args):
        qi, start = args
        s = matmul(qi, k, precision, "qhd,thd->hqt") * scale
        keep = (start + jnp.arange(qb))[:, None] >= key_pos[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return matmul(p, v, precision, "hqt,thd->qhd")

    out = jax.lax.map(block, (q.reshape(T // qb, qb, H, dk),
                              jnp.arange(0, T, qb)))
    return out.reshape(T, H, v.shape[-1])


def latent_attention(config, precision, fault, h, lp):
    """The attention sublayer's output before W_o, [T, H * v_head_dim]."""
    T = h.shape[0]
    H = config["num_attention_heads"]
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    kvr, eps = config["kv_lora_rank"], config["rms_norm_eps"]
    q = matmul(rms_norm(matmul(h, lp["q_a_proj"], precision), lp["q_a_norm"],
                        eps), lp["q_b_proj"], precision).reshape(T, H, -1)
    ckv = matmul(h, lp["kv_a_proj"], precision)
    c, k_pe = rms_norm(ckv[:, :kvr], lp["kv_a_norm"], eps), ckv[:, kvr:]
    kv = matmul(c, lp["kv_b_proj"], precision).reshape(T, H, nope + dv)
    cos, sin = rope_tables(config, T)
    k_pe = rotate(jnp.broadcast_to(k_pe[:, None, :], (T, H, rope)), cos, sin)
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    return attention(precision, q, k, kv[..., nope:],
                     softmax_scale(config, fault)).reshape(T, H * dv)


def sinkhorn(m, iters, eps):
    """Row then column normalisation, ``iters`` times, as a loop."""
    def one(_, m):
        m = m / (m.sum(-1, keepdims=True) + eps)
        return m / (m.sum(-2, keepdims=True) + eps)
    return jax.lax.fori_loop(0, iters, one, m)


def hc_maps(config, precision, fault, x, hp):
    """``(H_pre [T, n], H_post [T, n], H_res [T, n, n])`` of one sublayer
    from the streams x [T, n, C] and its leaves ``hp`` (``HC_MAPS``)."""
    T, n, C = x.shape
    xn = rms_norm(x.reshape(T, n * C), 1.0, config["rms_norm_eps"])
    a = hp["alpha"]
    pre = jax.nn.sigmoid(a[0] * matmul(xn, hp["phi_pre"], precision)
                         + hp["b_pre"])
    post = (1.0 if fault == "post_sigmoid" else 2.0) * jax.nn.sigmoid(
        a[1] * matmul(xn, hp["phi_post"], precision) + hp["b_post"])
    res = (a[2] * matmul(xn, hp["phi_res"], precision)
           + hp["b_res"]).reshape(T, n, n)
    m = jnp.exp(jnp.clip(res, config["mhc_h_res_clamp_min"],
                         config["mhc_h_res_clamp_max"]))
    if fault == "res_row_softmax":
        return pre, post, m / m.sum(-1, keepdims=True)
    return pre, post, sinkhorn(m, config["hc_sinkhorn_iters"],
                               config["hc_eps"])


def sublayer_maps(config, lp, sub, fault):
    """A sublayer's leaves; with ``"one_stream"``, those of stream 0 alone."""
    hp = {k: lp[f"{sub}_{k}"] for k in HC_MAPS}
    if fault != "one_stream":
        return hp
    C, n = config["hidden_size"], config["hc_mult"]
    return dict(hp, phi_pre=hp["phi_pre"][:C, :1],
                phi_post=hp["phi_post"][:C, :1],
                phi_res=hp["phi_res"][:C, :1], b_pre=hp["b_pre"][:1],
                b_post=hp["b_post"][:1], b_res=hp["b_res"][:1])


def streams(config, x, fault=None):
    """n copies of x [..., T, C] as [..., T, n, C]."""
    n = 1 if fault == "one_stream" else config["hc_mult"]
    return jnp.broadcast_to(x[..., None, :], x.shape[:-1] + (n, x.shape[-1]))


def wrapped(config, precision, fault, x, lp, sub, norm, F):
    """One sublayer F under its maps: ``x' = H_res x + H_post^T F(norm(H_pre
    x))`` on the streams x [T, n, C]."""
    pre, post, res = hc_maps(config, precision, fault, x,
                             sublayer_maps(config, lp, sub, fault))
    h = rms_norm(matmul(pre, x, precision, "tn,tnc->tc"), lp[norm],
                 config["rms_norm_eps"])
    y, aux = F(h)
    return matmul(res, x, precision, "tmn,tnc->tmc") \
        + post[..., None] * y[:, None, :], aux


def layer(config, precision, is_routed, fault, x, lp, b):
    """One layer on one row's streams, x [T, n, C]: ``(x out, load [E])``
    (load 0 in a dense layer)."""
    def attend(h):
        return matmul(latent_attention(config, precision, fault, h, lp),
                      lp["o_proj"], precision), None

    def ffn(u):
        if not is_routed:
            return swiglu(precision, u, lp["gate_proj"], lp["up_proj"],
                          lp["down_proj"]), \
                jnp.zeros((config["n_routed_experts"],), jnp.int32)
        w, load = glm.routing(config, precision, fault, u, lp["router"], b)
        shared = swiglu(precision, u, lp["shared_gate"], lp["shared_up"],
                        lp["shared_down"])
        return shared + glm.experts(config, precision, u, w, lp), load

    x, _ = wrapped(config, precision, fault, x, lp, "attn_hc", "input_norm",
                   attend)
    return wrapped(config, precision, fault, x, lp, "mlp_hc", "post_norm",
                   ffn)


def rows(config, precision, is_routed, fault, x, lp, b):
    """``layer`` over the rows of x [B, T, n, C], one at a time,
    rematerialised: ``(x out, the load summed over the rows)``."""
    row = jax.checkpoint(
        functools.partial(layer, config, precision, is_routed, fault))

    @jax.checkpoint
    def walk(x, lp, b):
        out, load = jax.lax.map(lambda xr: row(xr, lp, b), x)
        return out, load.sum(0)

    return walk(x, lp, b)


def hidden(config, precision, params, bias, inputs, fault=None):
    """``(the streams summed after the last layer [B, T, C], {bias key:
    load})``."""
    x = streams(config, params["embed"][inputs], fault)
    loads = {}
    for i, (is_routed, lp) in enumerate(zip(routed(config),
                                            params["layers"])):
        x, load = rows(config, precision, is_routed, fault, x, lp,
                       bias.get(f"b{i}"))
        if is_routed:
            loads[f"b{i}"] = load
    return x.sum(-2), loads


def logits(config, precision, params, bias, inputs):
    """[B, T, vocab_size] of the main head: for the tests' small sizes."""
    x, _ = hidden(config, precision, params, bias, inputs)
    return matmul(rms_norm(x, params["norm_f"], config["rms_norm_eps"]),
                  params["head"], precision)


def mtp_loss(config, precision, params, bias, hidden_state, inputs, targets,
             fault=None):
    """``(L_mtp, load)``: the module on the main stack's final-normed state
    [B, T, d], predicting t_{i+2} = targets[i + 1] at the T - 1 positions
    that have one; its layer runs on n copies of the join."""
    mp, eps = params["mtp"], config["rms_norm_eps"]
    emb = params["embed"][targets]                      # t_{i+1}
    x = matmul(jnp.concatenate([rms_norm(emb, mp["enorm"], eps),
                                rms_norm(hidden_state, mp["hnorm"], eps)],
                               -1), mp["eh_proj"], precision)
    x, load = rows(config, precision, True, fault, streams(config, x, fault),
                   mp["layer"], bias["mtp"])
    x = rms_norm(x.sum(-2), mp["norm"], eps)
    B, T = targets.shape
    later = jnp.concatenate([targets[:, 1:], targets[:, -1:]], 1)
    mask = jnp.concatenate([jnp.ones((B, T - 1)), jnp.zeros((B, 1))], 1)
    return head_sum(config, precision, params, x, later, mask) \
        / (B * (T - 1)), load


def loss_fn(config, precision, params, bias, tokens, fault=None):
    """``(L_main + lambda L_mtp, {bias key: load [E]})`` of ``tokens``
    [B, T+1]; ``"half_batch"``: the first half of the rows, or of the one
    row's positions."""
    if fault == "half_batch":
        B, T = tokens.shape[0], tokens.shape[1] - 1
        tokens = tokens[:B // 2] if B > 1 else tokens[:, :T // 2 + 1]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    arch = fault if fault in ARCHITECTURE_FAULTS else None
    x, loads = hidden(config, precision, params, bias, inputs, arch)
    h = rms_norm(x, params["norm_f"], config["rms_norm_eps"])
    main = head_sum(config, precision, params, h, targets,
                    jnp.ones(targets.shape)) / targets.size
    extra, loads["mtp"] = mtp_loss(config, precision, params, bias, h,
                                   inputs, targets, arch)
    return main + config["assumed"]["mtp_loss_weight"] * extra, loads


# --- the steps and the readings -----------------------------------------------

def train_step(config, precision, fault, params, bias, m, v, t, tokens):
    """One step: ``(params, bias, m, v, loss, grads, loads)``."""
    (loss, loads), grads = jax.value_and_grad(
        lambda p: loss_fn(config, precision, p, bias, tokens, fault),
        has_aux=True)(params)
    new_p, m2, v2 = adamw(config["assumed"]["optimizer"], params, grads, m, v,
                          t)
    if fault == "state_unchanged":
        new_p = params
    sign = BIAS_FAULTS.get(fault, 1.0)
    return new_p, moved_bias(config, bias, loads, sign), m2, v2, loss, grads, \
        loads


@functools.lru_cache(maxsize=None)
def _programs(config_json, precision, fault):
    """The jitted gradient, update and change-of-parameters, built once per
    process for one (configuration, precision, fault): ``train_step`` in two
    programs, so that AdamW's moments need not be on the device while the
    gradient is made."""
    config = json.loads(config_json)

    @jax.jit
    def gradient(params, bias, tokens):
        (loss, loads), grads = jax.value_and_grad(
            lambda p: loss_fn(config, precision, p, bias, tokens, fault),
            has_aux=True)(params)
        return loss, grads, leaf_norms(grads), loads

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
    def update(params, grads, m, v, bias, t, loads):
        new_p, m2, v2 = adamw(config["assumed"]["optimizer"], params, grads,
                              m, v, t)
        if fault == "state_unchanged":
            new_p = params
        new_b = moved_bias(config, bias, loads, BIAS_FAULTS.get(fault, 1.0))
        moves = jax.tree.map(lambda a, b: a - b, new_b, bias)
        return new_p, new_b, m2, v2, bias_reading(config, moves, loads)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def change(params, start):
        return leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))

    return gradient, update, change


def first_steps(config, seed, batches, precision="float32", fault=None):
    """Follow the first ``len(batches)`` training steps from the seed's weights,
    as ``references/glm.first_steps`` does: each step's loss, the per-leaf
    norm of the first gradient with each router's ``bias_reading`` beside
    them, the per-leaf norm of the parameters' change with the readings
    added up beside them. ``fault``: ``"half_batch"``, ``"state_unchanged"``,
    one of ``BIAS_FAULTS`` or of ``ARCHITECTURE_FAULTS``."""
    gradient, update, change = _programs(json.dumps(config, sort_keys=True),
                                         precision, fault)
    params, bias = init_weights(config, seed), init_bias(config)
    host = lambda d: {k: float(x) for k, x in jax.device_get(d).items()}
    losses, grad_norm, readings, moments = [], None, [], None
    for i, tokens in enumerate(batches):
        loss, grads, gn, loads = gradient(params, bias,
                                          jnp.asarray(tokens, jnp.int32))
        m, v = (jax.tree.map(jnp.zeros_like, (params, params)) if i == 0
                else jax.device_put(moments))
        params, bias, m, v, read = update(params, grads, m, v, bias,
                                          float(i + 1), loads)
        del grads
        losses.append(float(loss))
        readings.append(host(read))
        if i == 0:
            grad_norm = {**host(gn), **readings[0]}
        # the moments wait in the host's memory while the next gradient is
        # made; the last step's are not needed
        moments = jax.device_get((m, v)) if i + 1 < len(batches) else None
        del m, v
    # the seed's weights are made again beside the parameters alone
    delta = host(change(params, init_weights(config, seed)))
    del params
    return {"loss": losses, "grad_norm": grad_norm,
            "delta_norm": {**delta, **summed(readings)}}
