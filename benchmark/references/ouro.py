"""Plain reference for the ``ouro`` family (ByteDance Ouro-2.6B, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741, read from its
public ``config.json``): a dense decoder whose whole stack of layers is run
``total_ut_steps`` times over the same weights, with an exit after every run;
its training loss over the exits, gradients and the AdamW update, in
straightforward ``jax.numpy`` and float32.

The equations (sizes and ``total_ut_steps`` are the config's; what it leaves
open is read as the configuration's ``assumed`` says, ISSUE 32):

* Layer, with four RMSNorms (a norm before each sublayer and one on its
  OUTPUT, before the residual sum)::

      a = Attn(RMSNorm_1(x));   x = x + RMSNorm_2(a)
      m = SwiGLU(RMSNorm_3(x)); x = x + RMSNorm_4(m)

  ``Attn``: bias-free q, k, v of ``num_attention_heads`` heads of
  ``head_dim``; rotary embedding over the whole head in ``rotate_half``
  pairs (i, i + head_dim/2) at ``rope_theta``; causal
  ``softmax(q k^T / sqrt(head_dim)) v``; a bias-free output projection.
  ``SwiGLU(h) = (silu(h W_gate) * (h W_up)) W_down``.
* Model: ``h^0 = E[tokens]``; for t = 1..R (R = ``total_ut_steps``):
  ``h^t = RMSNorm_f(Stack(h^(t-1)))``, the SAME layers and the same final norm
  every time; the normed state is what exit t reads and what run t+1 starts
  from. ``logits^t = h^t W_head`` (untied); ``lam^t = sigmoid(h^t . w_g +
  b_g)`` per token.
* Loss, per token i with target y_i: ``L_i^t = CE(logits_i^t, y_i)``;
  ``p_i(t) = lam_i^t prod_{j<t} (1 - lam_i^j)`` for t < R and ``p_i(R) =
  prod_{j<R} (1 - lam_i^j)``; ``loss = mean_i [sum_t p_i(t) L_i^t - beta
  H(p_i)]``, ``H`` the entropy of ``p_i`` and beta
  ``assumed.exit_entropy_beta``. Gradients flow through ``p`` into the gate
  and through every exit into the shared stack.
* Inference as published (``early_exit_threshold`` 1.0): all R runs, the last
  exit's logits (``exits`` returns every exit's).

Independent of the code under test: imports nothing of the program, makes its
own weights from the seed, and is told only sizes (the configuration file) and
token batches.

Departures from a textbook forward pass, each for memory only (6.7 GB of
float32 weights and moments and 2.2 GB of gradients beside the activations of
``total_ut_steps`` x ``num_hidden_layers`` layer applications on a 16 GB
chip; compiled for the v5e at 7 layers: 6.1 GB of temporaries, where R x L
applications and R exits written in a row, a batch row at a time, took 13.3
GB and three minutes to compile): the runs are a ``lax.scan`` whose body is
the loop over the layers and the run's exit, so that the shared weights'
gradient (the head's too) is one accumulator and the program one run's size;
a run is rematerialised in the backward pass (``jax.checkpoint``), and so is
each layer inside it, and each block of ``QUERY_BLOCK`` queries, which
attention takes against all keys, dense and masked; an exit's head, loss and
gate take ``EXIT_BLOCK`` tokens at a time and are rematerialised, so that one
block's vocabulary-wide logits live at a time.

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
EXIT_BLOCK = 1024   # tokens of an exit whose logits live at a time


# --- the configuration, as the reference reads it ---------------------------

def layer_shapes(config):
    """``{leaf: shape}`` of one layer, matrices as [in, out]."""
    d, f = config["hidden_size"], config["intermediate_size"]
    qd = config["num_attention_heads"] * config["head_dim"]
    kvd = config["num_key_value_heads"] * config["head_dim"]
    return {"input_norm": (d,), "q_proj": (d, qd), "k_proj": (d, kvd),
            "v_proj": (d, kvd), "o_proj": (qd, d), "attn_out_norm": (d,),
            "pre_mlp_norm": (d,), "gate_proj": (d, f), "up_proj": (d, f),
            "down_proj": (f, d), "mlp_out_norm": (d,)}


def top_shapes(config):
    d, V = config["hidden_size"], config["vocab_size"]
    return {"embed": (V, d), "head": (d, V), "norm_f": (d,),
            "gate_w": (d, 1), "gate_b": (1,)}


def num_params(config):
    count = lambda shapes: sum(math.prod(s) for s in shapes.values())
    return count(top_shapes(config)) \
        + config["num_hidden_layers"] * count(layer_shapes(config))


def init_weights(config, seed):
    """N(0, ``initializer_range``) for every matrix, gains 1, the gate's bias
    0, from the seed. ``{"embed", "head", "norm_f", "gate_w", "gate_b",
    "layers": [{leaf: array}]}``. One jitted call, float32."""
    std = config["assumed"]["initializer_range"]

    @jax.jit
    def make(key):
        def fill(key, shapes):
            keys = jax.random.split(key, len(shapes))
            return {name: (std * jax.random.normal(k, shape, jnp.float32)
                           if len(shape) == 2 else
                           jnp.full(shape, float(name != "gate_b"),
                                    jnp.float32))
                    for k, (name, shape) in zip(keys, sorted(shapes.items()))}

        keys = jax.random.split(key, config["num_hidden_layers"] + 1)
        top = fill(keys[0], top_shapes(config))
        top["layers"] = [fill(k, layer_shapes(config)) for k in keys[1:]]
        return top

    return make(seed_key(seed, stream=1))


# --- the layer equations ------------------------------------------------------

def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope_tables(config, T):
    """cos, sin [T, head_dim/2]: frequency i is ``rope_theta ** (-2i /
    head_dim)``."""
    hd = config["head_dim"]
    inv = config["rope_theta"] ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    angles = np.arange(T, dtype=np.float64)[:, None] * inv
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def rotate(x, cos, sin):
    """x [T, heads, head_dim]: dims (i, i + head_dim/2) are a pair
    (``rotate_half``)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(precision, q, k, v):
    """q [T, H, hd]; k, v [T, KV, hd]: causal softmax(q k^T / sqrt(hd)) v;
    consecutive H / KV query heads share a key/value head (the published
    model has H = KV). Queries in blocks against all keys, each block
    rematerialised in the backward pass."""
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
        keep = (start + jnp.arange(qb))[:, None] >= key_pos[None, :]
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return matmul(p, v, precision, "kgqt,tkd->qkgd")

    o = jax.lax.map(block, (q, jnp.arange(0, T, qb)))
    return o.reshape(T, H, hd)


def layer(config, precision, x, lp):
    """One layer on one row of the batch, x [T, d]."""
    T, d = x.shape
    H, KV, hd = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    eps = config["rms_norm_eps"]
    h = rms_norm(x, lp["input_norm"], eps)
    q = matmul(h, lp["q_proj"], precision).reshape(T, H, hd)
    k = matmul(h, lp["k_proj"], precision).reshape(T, KV, hd)
    v = matmul(h, lp["v_proj"], precision).reshape(T, KV, hd)
    cos, sin = rope_tables(config, T)
    o = attention(precision, rotate(q, cos, sin), rotate(k, cos, sin), v)
    a = matmul(o.reshape(T, H * hd), lp["o_proj"], precision)
    x = x + rms_norm(a, lp["attn_out_norm"], eps)
    h = rms_norm(x, lp["pre_mlp_norm"], eps)
    m = matmul(jax.nn.silu(matmul(h, lp["gate_proj"], precision))
               * matmul(h, lp["up_proj"], precision), lp["down_proj"],
               precision)
    return x + rms_norm(m, lp["mlp_out_norm"], eps)


def every_run(config, precision, params, inputs, read, runs=None):
    """``read(h^t)`` of every run t = 1..R, stacked on a leading axis: the
    stack applied to ``inputs`` [B, T], after every run the final norm, whose
    output ``h^t`` [B, T, d] is what the exit reads and what the next run
    starts from. The runs are a ``lax.scan`` whose body is the loop over the
    layers (each over the whole batch): the same ``params`` every time round. ``runs`` (a fault for the
    tests of the comparison): fewer runs than ``total_ut_steps``."""
    rows = jax.checkpoint(jax.vmap(functools.partial(layer, config, precision),
                                   in_axes=(0, None)))

    @jax.checkpoint
    def run(x, _):
        for lp in params["layers"]:
            x = rows(x, lp)
        x = rms_norm(x, params["norm_f"], config["rms_norm_eps"])
        return x, read(x)

    return jax.lax.scan(run, params["embed"][inputs], None,
                        length=runs or config["total_ut_steps"])[1]


def gate(precision, params, h):
    """``lam`` [...]: the probability of leaving at this exit, given that the
    token got here."""
    return jax.nn.sigmoid(matmul(h, params["gate_w"], precision)[..., 0]
                          + params["gate_b"][0])


def exit_distribution(lams):
    """``p`` [R, ...] from the R exits' ``lam``: ``p(t) = lam^t prod_{j<t}
    (1 - lam^j)``, and the last exit takes what is left."""
    surv, p = jnp.ones_like(lams[0]), []
    for lam in lams[:-1]:
        p.append(lam * surv)
        surv = surv * (1.0 - lam)
    return jnp.stack(p + [surv])


def exits(config, precision, params, inputs):
    """Every exit's logits [R, B, T, V] and the exit distribution [R, B, T]:
    the whole model at once, for the tests at a small size. The last exit's
    logits are the model as it is served."""
    logits, lams = every_run(
        config, precision, params, inputs,
        lambda h: (matmul(h, params["head"], precision),
                   gate(precision, params, h)))
    return logits, exit_distribution(list(lams))


def loss_fn(config, precision, params, tokens, rows=None, runs=None):
    """``mean_i [sum_t p_i(t) L_i^t - beta H(p_i)]`` of ``tokens`` [B, T+1].
    ``rows`` and ``runs`` (faults for the tests of the comparison): only those
    rows count; fewer runs, and so fewer exits."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if rows is not None:
        inputs, targets = inputs[rows], targets[rows]
    B, T = targets.shape
    eb = min(EXIT_BLOCK, T)
    assert T % eb == 0

    @jax.checkpoint
    def block_exit(args):
        hb, tb = args
        logp = jax.nn.log_softmax(matmul(hb, params["head"], precision),
                                  axis=-1)
        return (-jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0],
                gate(precision, params, hb))

    def exit_of(h):   # (L^t, lam^t), each [B T / eb, eb]
        return jax.lax.map(block_exit, (h.reshape(B * T // eb, eb, -1),
                                        targets.reshape(B * T // eb, eb)))

    nll, lams = every_run(config, precision, params, inputs, exit_of, runs)
    p = exit_distribution(list(lams))
    entropy = -(p * jnp.log(p)).sum(0)
    beta = config["assumed"]["exit_entropy_beta"]
    return ((p * nll).sum(0) - beta * entropy).mean()


# --- AdamW and the readings -----------------------------------------------------

def adamw(opt_conf, params, grads, m, v, t):
    """AdamW; the decay on every matrix (the gate's ``[d, 1]`` is one), the
    RMSNorm gains and the gate's bias exempt."""
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
    runs = config["total_ut_steps"] - 1 if fault == "one_run_fewer" else None

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, tokens):
        loss, grads = jax.value_and_grad(functools.partial(
            loss_fn, config, precision))(params, tokens, rows, runs)
        norms = leaf_norms(grads)
        new_p, m2, v2 = adamw(opt_conf, params, grads, m, v, t)
        if fault == "state_unchanged":
            new_p = params
        return new_p, m2, v2, loss, norms

    @jax.jit
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
    the parameters as they were; ``"one_run_fewer"`` runs the stack
    ``total_ut_steps - 1`` times, so that the last exit is left out and the
    one before it takes what is left."""
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
