"""Plain reference for the ``resnet_v1`` family: ResNet v1 with bottleneck blocks
(He et al. 2015, arXiv:1512.03385) -- 7x7/2 stem, 3x3/2 max pool, four stages of
1x1 -> 3x3 -> 1x1 bottlenecks with the stride in the first 1x1 and a projection
shortcut at each stage entry, batch normalisation in training mode (the batch's
own moments) after every convolution, global average pool, a dense softmax
classifier -- its mean cross-entropy, gradients, and SGD with Nesterov momentum
in the form ``v = mu v + g; p -= lr (g + mu v)``, in straightforward
``jax.numpy``, NHWC.

Independent of the code under test: imports nothing of the program, makes its
own weights from the seed, and is told only sizes and batches. One departure
from a textbook pass, for memory only: each bottleneck is rematerialised in the
backward pass (``jax.checkpoint``) so the float32 activations of a batch of 128
fit on one 16 GB chip. Layer names follow ``<block>_<a|b|c|sc>_{conv,bn}`` so the
readings of both sides meet leaf by leaf.

``precision`` (``references/numerics``): ``"float32"`` is THE reference,
``"bfloat16"`` what the configuration states, ``"fp8"`` and ``"int8"`` the
controls, applied to every convolution and to the classifier.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmark.references.numerics import round_operand, round_result, seed_key


def plan(config):
    """The convolutions of the network in order, as
    ``(name, kernel, stride, pad, c_in, c_out, h_in, w_in)``, then the blocks as
    ``(name, has_projection, stride)``, then the classifier's input width.
    Shapes from the configuration alone."""
    h, w = config["height"], config["width"]
    convs, blocks = [], []
    c0 = config["stem_width"]
    convs.append(("conv1", 7, 2, 3, config["channels"], c0, h, w))
    h, w = (h + 6 - 7) // 2 + 1, (w + 6 - 7) // 2 + 1
    h, w = (h + 2 - 3) // 2 + 1, (w + 2 - 3) // 2 + 1
    c_in = c0
    for s, n_blocks in enumerate(config["stages"]):
        mid = c0 * 2 ** s
        c_out = mid * config["bottleneck_expansion"]
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            name = f"s{s}b{b}"
            ho, wo = (h - 1) // stride + 1, (w - 1) // stride + 1
            convs.append((f"{name}_a", 1, stride, 0, c_in, mid, h, w))
            convs.append((f"{name}_b", 3, 1, 1, mid, mid, ho, wo))
            convs.append((f"{name}_c", 1, 1, 0, mid, c_out, ho, wo))
            if b == 0:
                convs.append((f"{name}_sc", 1, stride, 0, c_in, c_out, h, w))
            blocks.append((name, b == 0, stride))
            c_in, h, w = c_out, ho, wo
    return convs, blocks, c_in


def init_weights(config, seed):
    """He-normal convolutions (HWIO), BN gains 1 and shifts 0, classifier
    N(0, 1/fan_in) with zero bias. One jitted call, float32."""
    convs, _, c_last = plan(config)
    branch_gain = config["assumed"]["batch_norm"].get("residual_gain", 1.0)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(convs) + 1)
        p = {}
        for k, (name, ks, _, _, ci, co, _, _) in zip(keys, convs):
            std = (2.0 / (ks * ks * ci)) ** 0.5
            p[f"{name}_conv"] = {
                "W": std * jax.random.normal(k, (ks, ks, ci, co), jnp.float32)}
            # the last BN of a residual branch starts small (Goyal et al.
            # 2017 start it at 0): at gain 1 throughout, a 50-layer BatchNorm
            # net is chaotic at initialisation and no precision can be told
            # from another by its gradients (PERF.md section 2)
            gain = branch_gain if name.endswith("_c") else 1.0
            p[f"{name}_bn"] = {"gamma": jnp.full((co,), gain, jnp.float32),
                               "beta": jnp.zeros((co,))}
        n = config["n_classes"]
        p["fc"] = {"W": c_last ** -0.5 * jax.random.normal(
            keys[-1], (c_last, n), jnp.float32), "b": jnp.zeros((n,))}
        return p

    return make(seed_key(seed, stream=1))


def _conv(x, w, stride, pad, precision):
    x, w = round_operand(x, precision), round_operand(w, precision)
    with jax.default_matmul_precision("highest"):
        return round_result(jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC")), precision)


def _bn(x, p, eps):
    mean = x.mean(axis=(0, 1, 2))
    var = ((x - mean) ** 2).mean(axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["gamma"] + p["beta"]


def _conv_bn(params, name, x, stride, pad, precision, eps, relu=True):
    y = _bn(_conv(x, params[f"{name}_conv"]["W"], stride, pad, precision),
            params[f"{name}_bn"], eps)
    return jax.nn.relu(y) if relu else y


def _bottleneck(precision, eps, projection, stride, bp, x):
    cb = functools.partial(_conv_bn, bp, precision=precision, eps=eps)
    y = cb("a", x, stride, 0)
    y = cb("b", y, 1, 1)
    y = cb("c", y, 1, 0, relu=False)
    sc = cb("sc", x, stride, 0, relu=False) if projection else x
    return jax.nn.relu(y + sc)


def loss_fn(config, precision, params, images, labels, rows=None):
    """Mean softmax cross-entropy of one batch: ``images`` [N, H, W, C]
    float32, ``labels`` [N, classes] one-hot. ``rows`` (a planted fault): only
    those rows are run."""
    if rows is not None:
        images, labels = images[rows], labels[rows]
    eps = config["assumed"]["batch_norm"]["eps"]
    _, blocks, _ = plan(config)
    x = _conv_bn(params, "conv1", images, 2, 3, precision, eps)
    x = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))   # zeros: x >= 0 here
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "VALID")
    for name, projection, stride in blocks:
        parts = ("a", "b", "c") + (("sc",) if projection else ())
        bp = {f"{part}_{kind}": params[f"{name}_{part}_{kind}"]
              for part in parts for kind in ("conv", "bn")}
        body = jax.checkpoint(functools.partial(
            _bottleneck, precision, eps, projection, stride))
        x = body(bp, x)
    x = x.mean(axis=(1, 2))
    w, b = params["fc"]["W"], params["fc"]["b"]
    with jax.default_matmul_precision("highest"):
        logits = round_operand(x, precision) @ round_operand(w, precision) + b
    return -(labels * jax.nn.log_softmax(logits, axis=-1)).sum() / labels.shape[0]


def leaf_norms(tree):
    return {f"{layer}.{k}": jnp.sqrt(jnp.sum(jnp.square(a)))
            for layer, leaves in tree.items() for k, a in leaves.items()}


@functools.lru_cache(maxsize=None)
def _programs(config_json, precision, fault, half):
    config = json.loads(config_json)
    upd = config["assumed"]["updater"]
    lr, mu = upd["learning_rate"], upd["momentum"]
    rows = slice(0, half) if fault == "half_batch" else None

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, vel, images, labels):
        loss, grads = jax.value_and_grad(functools.partial(
            loss_fn, config, precision))(params, images, labels, rows)
        vel2 = jax.tree.map(lambda v, g: mu * v + g, vel, grads)
        new_p = jax.tree.map(lambda p, g, v: p - lr * (g + mu * v),
                             params, grads, vel2)
        if fault == "state_unchanged":
            new_p = params
        return new_p, vel2, loss, leaf_norms(grads)

    @jax.jit
    def change(params, start):
        return leaf_norms(jax.tree.map(lambda a, b: a - b, params, start))

    return step, change


def first_steps(config, seed, batches, precision="float32", fault=None):
    """Follow the first ``len(batches)`` steps from the seed's weights; see
    ``references/transformer.first_steps`` for what is returned and for the
    planted faults."""
    step, change = _programs(json.dumps(config, sort_keys=True), precision,
                             fault, max(1, batches[0][0].shape[0] // 2))
    params = init_weights(config, seed)
    vel = jax.tree.map(jnp.zeros_like, params)
    losses, grad_norm = [], None
    for i, (images, labels) in enumerate(batches):
        params, vel, loss, gn = step(params, vel, jnp.asarray(images),
                                     jnp.asarray(labels))
        losses.append(float(loss))
        if i == 0:
            grad_norm = {k: float(v) for k, v in jax.device_get(gn).items()}
    delta = change(params, init_weights(config, seed))
    delta = {k: float(v) for k, v in jax.device_get(delta).items()}
    del params, vel
    return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta}
