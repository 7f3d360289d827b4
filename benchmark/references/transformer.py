"""Plain reference for the ``gpt2`` family: a pre-LN decoder-only LM with learned
positions, LayerNorm with biases, tanh-GELU MLP and tied logits (Radford et al.
2019), its mean next-token loss, gradients and the AdamW update, in
straightforward ``jax.numpy``.

Independent of the code under test: imports nothing of the program, makes its
own weights from the seed, and is told only sizes (the configuration file) and
token batches. Departures from a textbook forward pass, each for memory only:
layers are stacked on a leading axis and scanned, and each layer is
rematerialised in the backward pass (``jax.checkpoint``), so the float32
reference fits beside nothing else on one 16 GB chip.

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

from benchmark.references.numerics import matmul, seed_key

# leaves that hold the q, k and v projections side by side: their norms are
# read per third, since a key's bias has no gradient under softmax
FUSED_QKV = ("qkv", "qkv_b")


def d_ff(config):
    """The MLP width: ``n_inner``, which GPT-2's config leaves null for 4 d."""
    return config.get("n_inner") or 4 * config["n_embd"]


def init_weights(config, seed):
    """GPT-2's initialisation from the seed: N(0, 0.02), residual output
    projections scaled by 1/sqrt(2L), gains 1, biases 0. Layers stacked on
    axis 0. One jitted call, float32."""
    d, f, L = config["n_embd"], d_ff(config), config["n_layer"]
    V, P = config["vocab_size"], config["n_positions"]
    std = config["initializer_range"]

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 6)
        n = lambda k, shape, s: s * jax.random.normal(k, shape, jnp.float32)
        rs = std / math.sqrt(2 * L)
        return {
            "wte": n(ks[0], (V, d), std), "wpe": n(ks[1], (P, d), std),
            "lnf_g": jnp.ones((d,)), "lnf_b": jnp.zeros((d,)),
            "blocks": {
                "ln1_g": jnp.ones((L, d)), "ln1_b": jnp.zeros((L, d)),
                "qkv": n(ks[2], (L, d, 3 * d), std),
                "qkv_b": jnp.zeros((L, 3 * d)),
                "proj": n(ks[3], (L, d, d), rs), "proj_b": jnp.zeros((L, d)),
                "ln2_g": jnp.ones((L, d)), "ln2_b": jnp.zeros((L, d)),
                "fc": n(ks[4], (L, d, f), std), "fc_b": jnp.zeros((L, f)),
                "out": n(ks[5], (L, f, d), rs), "out_b": jnp.zeros((L, d)),
            },
        }

    return make(seed_key(seed, stream=1))


def _layer_norm(x, g, b, eps):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(config, precision, x, bp):
    B, T, d = x.shape
    H = config["n_head"]
    hd = d // H
    eps = config["layer_norm_epsilon"]
    h = _layer_norm(x, bp["ln1_g"], bp["ln1_b"], eps)
    qkv = matmul(h, bp["qkv"], precision) + bp["qkv_b"]
    q, k, v = (a.reshape(B, T, H, hd) for a in jnp.split(qkv, 3, axis=-1))
    s = matmul(q, k, precision, "bqhd,bkhd->bhqk") / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = matmul(p, v, precision, "bhqk,bkhd->bqhd").reshape(B, T, d)
    x = x + matmul(o, bp["proj"], precision) + bp["proj_b"]
    h = _layer_norm(x, bp["ln2_g"], bp["ln2_b"], eps)
    m = _gelu_tanh(matmul(h, bp["fc"], precision) + bp["fc_b"])
    return x + matmul(m, bp["out"], precision) + bp["out_b"]


def loss_fn(config, precision, params, tokens, rows=None):
    """Mean next-token negative log-likelihood of ``tokens`` [B, T+1]. ``rows``
    (a fault for the tests of the comparison): only those rows count."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    if rows is not None:
        inputs, targets = inputs[rows], targets[rows]
    T = inputs.shape[1]
    x = params["wte"][inputs] + params["wpe"][:T]
    body = jax.checkpoint(functools.partial(_block, config, precision))
    x, _ = jax.lax.scan(lambda c, bp: (body(c, bp), None), x, params["blocks"])
    x = _layer_norm(x, params["lnf_g"], params["lnf_b"],
                    config["layer_norm_epsilon"])
    logits = matmul(x, params["wte"], precision, "btd,vd->btv")
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def _decays(path):
    """AdamW's decay discipline (GPT-2): matrices only; biases, LayerNorm
    gains and the position table are exempt."""
    name = path[-1].key
    return name in ("wte", "qkv", "proj", "fc", "out")


def adamw(opt_conf, params, grads, m, v, t):
    b1, b2 = opt_conf["beta1"], opt_conf["beta2"]
    lr, eps, wd = (opt_conf["learning_rate"], opt_conf["eps"],
                   opt_conf["weight_decay"])

    def upd(path, p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        step = (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t)) + eps)
        if _decays(path):
            step = step + wd * p
        return p - lr * step, m2, v2

    out = jax.tree_util.tree_map_with_path(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def leaf_norms(tree):
    """L2 norm of every leaf, stacked layers one norm per layer and the fused
    qkv leaves one per third: device arrays, flattened on the host to
    ``{"wte": x, "b0.fc": y, "b0.qkv.k": z, ...}`` by :func:`flatten_norms`."""
    per_layer = lambda a: jnp.sqrt(jnp.sum(jnp.square(a),
                                           axis=tuple(range(1, a.ndim))))
    top = {k: jnp.sqrt(jnp.sum(jnp.square(a))) for k, a in tree.items()
           if k != "blocks"}
    blocks = {}
    for k, a in tree["blocks"].items():
        if k in FUSED_QKV:
            for part, third in zip("qkv", jnp.split(a, 3, axis=-1)):
                blocks[f"{k}.{part}"] = per_layer(third)
        else:
            blocks[k] = per_layer(a)
    return {"top": top, "blocks": blocks}


def flatten_norms(norms):
    norms = jax.device_get(norms)
    flat = {k: float(v) for k, v in norms["top"].items()}
    for k, vec in norms["blocks"].items():
        for i, v in enumerate(vec):
            flat[f"b{i}.{k}"] = float(v)
    return flat


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
        new_p, m2, v2 = adamw(opt_conf, params, grads, m, v, t)
        if fault == "state_unchanged":
            new_p = params
        return new_p, m2, v2, loss, leaf_norms(grads)

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
            grad_norm = flatten_norms(gn)
    delta = flatten_norms(change(params, init_weights(config, seed)))
    del params, m, v
    return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta}
