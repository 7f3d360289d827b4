"""Fully-sharded data parallelism (ZeRO-3 style) over a device mesh.

BEYOND-reference capability (SURVEY §2.4: "no ZeRO/FSDP-style sharding" in
the reference): parameters, gradients, and optimizer state live SHARDED
along the data axis — each device holds 1/N of every tensor — and the full
parameter is materialized only transiently for compute:

- forward/backward: ``all_gather`` each param shard just before use. The
  autodiff transpose of ``all_gather`` is ``psum_scatter`` (reduce-scatter),
  so ``jax.grad`` of the gathered-forward IS the ZeRO gradient flow: every
  device ends holding exactly its gradient shard, summed across the data
  axis — no hand-written reduce-scatter schedule.
- update: applied shard-locally (optimizer state is sharded for free).
- batch: sharded over the same axis (standard DP).

Peak per-device parameter memory is size/N at rest and one layer's full
params transiently — the ZeRO-3 memory curve, expressed as two collectives
XLA schedules onto ICI.

``FSDPMLP`` mirrors the other model-parallel composers: a self-contained
trainable module (sharded params, donated jitted step) used by
``dryrun_multichip`` and the parity tests.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from deeplearning4j_tpu.parallel.sharding_core import pad_to_multiple

__all__ = ["FSDPMLP", "FSDPTrainer"]

# flat-shard padding comes from the sharding core (this module is the
# explicit shard_map twin of the core's GSPMD ZeRO level 3 — same at-rest
# 1/N layout, hand-placed collectives instead of annotations)
_pad_to = pad_to_multiple


class FSDPMLP:
    """L-layer tanh MLP + softmax head, every parameter flattened, padded
    to the mesh size, and sharded P("data") at rest; gathered on use.

    Layer widths: n_in -> hidden*(L-1) -> n_out.
    """

    def __init__(self, mesh: Mesh, n_in: int, hidden: int, n_out: int,
                 n_layers: int = 2, lr: float = 0.1, seed: int = 0):
        if n_layers < 1:
            raise ValueError("need at least one layer")
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.N = mesh.shape[self.axis]
        self.lr = lr
        dims = ([n_in] + [hidden] * (n_layers - 1) + [n_out])
        self.shapes = []
        for i in range(n_layers):
            self.shapes.append((f"W{i}", (dims[i], dims[i + 1])))
            self.shapes.append((f"b{i}", (dims[i + 1],)))
        keys = jax.random.split(jax.random.PRNGKey(seed), n_layers)
        host = {}
        for i in range(n_layers):
            scale = (2.0 / (dims[i] + dims[i + 1])) ** 0.5
            host[f"W{i}"] = scale * jax.random.normal(
                keys[i], (dims[i], dims[i + 1]))
            host[f"b{i}"] = jnp.zeros((dims[i + 1],))
        # flatten + pad each param to a multiple of N, shard along dim 0
        sh = NamedSharding(mesh, P(self.axis))
        self.params = {}
        for name, shape in self.shapes:
            flat = host[name].reshape(-1)
            padded = jnp.zeros((_pad_to(flat.size, self.N),), flat.dtype)
            padded = padded.at[:flat.size].set(flat)
            self.params[name] = jax.device_put(padded, sh)
        self._step = self._build_step()

    # ---- sharded computation -----------------------------------------

    def _gathered(self, shard, name_shape):
        """all_gather a local shard back to the full (unpadded, reshaped)
        parameter. Inside shard_map; the grad transpose is psum_scatter."""
        name, shape = name_shape
        full = jax.lax.all_gather(shard, self.axis, tiled=True)
        return full[:math.prod(shape)].reshape(shape)

    def _forward_from_shards(self, params, x):
        L = len(self.shapes) // 2
        h = x
        for i in range(L):
            W = self._gathered(params[f"W{i}"], self.shapes[2 * i])
            b = self._gathered(params[f"b{i}"], self.shapes[2 * i + 1])
            z = h @ W + b
            h = jnp.tanh(z) if i < L - 1 else z
        return h

    def _build_step(self):
        mesh, axis, lr, N = self.mesh, self.axis, self.lr, self.N

        def local_loss(params, x, y):
            logits = self._forward_from_shards(params, x)
            return -jnp.sum(y * jax.nn.log_softmax(logits))

        def step(params, x, y):
            local_sum, grads = jax.value_and_grad(local_loss)(params, x, y)
            # grads arrive SHARDED: all_gather's transpose reduce-scattered
            # them across the data axis already — no further collective
            n_global = jnp.asarray(x.shape[0] * N, jnp.float32)
            new = jax.tree.map(lambda p, g: p - lr * g / n_global,
                               params, grads)
            loss = jax.lax.psum(local_sum, axis) / n_global
            return new, loss

        spec = {name: P(axis) for name, _ in self.shapes}
        sharded = shard_map(
            step, mesh=mesh,
            in_specs=(spec, P(axis, None), P(axis, None)),
            out_specs=(spec, P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0,))

    def fit_batch(self, x, y):
        if x.shape[0] % self.N != 0:
            raise ValueError(
                f"batch {x.shape[0]} must be a multiple of the mesh size "
                f"({self.N})")
        if y.shape[0] != x.shape[0]:
            raise ValueError(
                f"labels have {y.shape[0]} rows for {x.shape[0]} examples"
                " (a mismatch would silently broadcast inside the sharded"
                " loss)")
        sh = NamedSharding(self.mesh, P(self.axis, None))
        xs = jax.device_put(jnp.asarray(x, jnp.float32), sh)
        ys = jax.device_put(jnp.asarray(y, jnp.float32), sh)
        self.params, loss = self._step(self.params, xs, ys)
        return loss   # device scalar: the host loop must not sync per step

    # ---- oracle / introspection --------------------------------------

    def gathered_params(self) -> dict:
        """Full (unpadded) host copies — for parity checks and export."""
        out = {}
        for name, shape in self.shapes:
            flat = np.asarray(self.params[name])
            out[name] = flat[:int(np.prod(shape))].reshape(shape)
        return out

    def shard_fraction(self) -> float:
        """Fraction of total parameter elements resident per device
        (≈ 1/N — the ZeRO-3 at-rest memory claim, testable)."""
        total = sum(v.size for v in self.params.values())
        per_dev = 0
        for v in self.params.values():
            db = v.sharding.shard_shape(v.shape)
            per_dev += int(np.prod(db))
        return per_dev / total

    def predict(self, x) -> np.ndarray:
        p = self.gathered_params()
        h = np.asarray(x, np.float32)
        L = len(self.shapes) // 2
        for i in range(L):
            z = h @ p[f"W{i}"] + p[f"b{i}"]
            h = np.tanh(z) if i < L - 1 else z
        e = np.exp(h - h.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)


class FSDPTrainer:
    """Generic ZeRO-style trainer: shard ANY params pytree at rest.

    Takes a model's pure loss function and its parameter pytree; every
    leaf is flattened, padded to the mesh size, and sharded ``P(axis)``
    (so are the Adam moments). Each step all_gathers leaves transiently,
    evaluates the loss, and — via the all_gather transpose — receives
    gradients already reduce-scattered back to shards; the update is
    shard-local. At-rest per-device memory for params+optimizer is 1/N.

    Contract: ``loss_fn(params, *batch_shard) -> LOCAL MEAN loss`` over
    this device's batch shard; batch arrays are sharded on their leading
    axis (must divide the mesh size). With equal shard sizes the psum of
    local means / N equals the global mean exactly. Used by
    ``TransformerLM`` via ``models.transformer`` integration and tested
    against unsharded training in tests/test_model_parallelism.py.
    """

    def __init__(self, mesh: Mesh, params, loss_fn, *, lr=1e-3, beta1=0.9,
                 beta2=0.999, eps=1e-8, weight_decay=0.0,
                 weight_decay_mask=None):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.N = mesh.shape[self.axis]
        self.lr, self.b1, self.b2, self.eps = lr, beta1, beta2, eps
        self.wd = weight_decay
        self.loss_fn = loss_fn
        leaves, self.treedef = jax.tree.flatten(params)
        # per-leaf decay gate (pytree of 0/1 matching params); default: all
        if weight_decay_mask is None:
            self.wd_gates = [1.0] * len(leaves)
        else:
            gates = jax.tree.leaves(weight_decay_mask)
            if len(gates) != len(leaves):
                raise ValueError(
                    f"weight_decay_mask has {len(gates)} leaves for "
                    f"{len(leaves)} params")
            self.wd_gates = [float(g) for g in gates]
        self.shapes = [l.shape for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        sh = NamedSharding(mesh, P(self.axis))
        def shard_leaf(l):
            flat = jnp.ravel(l)
            padded = jnp.zeros((_pad_to(flat.size, self.N),), flat.dtype)
            return jax.device_put(padded.at[:flat.size].set(flat), sh)
        self.shards = [shard_leaf(l) for l in leaves]
        self.m = [jax.device_put(jnp.zeros_like(s), sh) for s in self.shards]
        self.v = [jax.device_put(jnp.zeros_like(s), sh) for s in self.shards]
        self.iteration = 0
        self.score_ = float("nan")
        self._steps = {}   # batch-spec tuple -> compiled step

    # ---- sharded computation -----------------------------------------
    def _unflatten_full(self, shards):
        full = []
        for s, shape, dt in zip(shards, self.shapes, self.dtypes):
            g = jax.lax.all_gather(s, self.axis, tiled=True)
            full.append(g[:math.prod(shape)].reshape(shape).astype(dt))
        return jax.tree.unflatten(self.treedef, full)

    def _build_step(self, batch_specs):
        mesh, axis, N = self.mesh, self.axis, self.N
        lr, b1, b2, eps, wd = self.lr, self.b1, self.b2, self.eps, self.wd

        def local_loss(shards, *batch):
            return self.loss_fn(self._unflatten_full(shards), *batch)

        def step(shards, m, v, t, *batch):
            local_mean, grads = jax.value_and_grad(local_loss)(shards, *batch)
            # grads are shard-local SUMS over devices (psum_scatter from the
            # all_gather transpose); /N turns them into grads of the mean
            t = t + 1
            new_s, new_m, new_v = [], [], []
            for s, g, mm, vv, wg in zip(shards, grads, m, v, self.wd_gates):
                g = g / N
                m2 = b1 * mm + (1 - b1) * g
                v2 = b2 * vv + (1 - b2) * g * g
                mhat = m2 / (1 - b1 ** t)
                vhat = v2 / (1 - b2 ** t)
                new_s.append(s - lr * (mhat / (jnp.sqrt(vhat) + eps)
                                       + wd * wg * s))
                new_m.append(m2)
                new_v.append(v2)
            loss = jax.lax.psum(local_mean, axis) / N
            return new_s, new_m, new_v, t, loss

        pspec = [P(axis)] * len(self.shards)
        sharded = shard_map(
            step, mesh=mesh,
            in_specs=(pspec, pspec, pspec, P()) + batch_specs,
            out_specs=(pspec, pspec, pspec, P(), P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0, 1, 2))

    def fit_batch(self, *batch):
        arrs = []
        specs = []
        for a in batch:
            a = jnp.asarray(a)
            if a.shape[0] % self.N:
                raise ValueError(
                    f"batch dim {a.shape[0]} must divide the mesh size "
                    f"({self.N})")
            spec = P(self.axis, *([None] * (a.ndim - 1)))
            arrs.append(jax.device_put(a, NamedSharding(self.mesh, spec)))
            specs.append(spec)
        key = tuple(specs)
        step = self._steps.get(key)
        if step is None:   # a different batch arity/rank needs its own specs
            step = self._steps[key] = self._build_step(key)
        self.shards, self.m, self.v, self.iteration, loss = step(
            self.shards, self.m, self.v, self.iteration, *arrs)
        self.score_ = loss   # device scalar, synced lazily on read
        return self.score_

    # ---- introspection ------------------------------------------------
    def gathered_params(self):
        """Full host-side params pytree (export / eval oracle)."""
        full = []
        for s, shape, dt in zip(self.shards, self.shapes, self.dtypes):
            flat = np.asarray(s)
            full.append(flat[:int(np.prod(shape))].reshape(shape).astype(dt))
        return jax.tree.unflatten(self.treedef, full)

    def shard_fraction(self) -> float:
        total = sum(s.size for s in self.shards)
        per_dev = sum(int(np.prod(s.sharding.shard_shape(s.shape)))
                      for s in self.shards)
        return per_dev / total
