"""Tensor (model) parallelism over a device mesh.

BEYOND-reference capability (the reference's distributed story is data
parallelism only — SURVEY §2.4 explicitly lists no tensor/pipeline
parallelism): shard the feature dimension of wide layers across a ``model``
mesh axis so a network too large for one chip's HBM trains across chips,
composing with the data axis (2-D ``(data, model)`` mesh).

Design (the Megatron column/row-parallel pair, expressed with ``shard_map``
so the collective placement is explicit and rides ICI):

- column-parallel Dense: W (in, out/M) per shard → local matmul, activations
  stay sharded over ``model``; no collective.
- row-parallel Dense: W (in/M, out) per shard consuming the sharded
  activations → partial products summed with ``psum`` over ``model``.
- loss/labels replicated across ``model``, sharded over ``data``; gradient
  psum over ``data`` is inserted by the same shard_map.

``TensorParallelMLP`` is a self-contained trainable module (params held
sharded, one jitted donated step) used by ``dryrun_multichip`` to validate
the tp×dp composition compiles and executes.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["tp_mesh", "TensorParallelMLP"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _allreduce_identity_bwd(x, axis):
    """psum whose BACKWARD is identity.

    Inside shard_map the transpose of ``psum`` is another ``psum``; when the
    cotangent is already replicated across the axis (the row-parallel
    pattern: everything after the collective is computed identically on
    every model shard), that transpose multiplies upstream gradients by the
    axis size. The correct vjp for "sum partials → replicated output" with a
    replicated cotangent is identity (Megatron's g/f conjugate operators)."""
    return jax.lax.psum(x, axis)


def _ari_fwd(x, axis):
    return jax.lax.psum(x, axis), None


def _ari_bwd(axis, _, g):
    return (g,)


_allreduce_identity_bwd.defvjp(_ari_fwd, _ari_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _identity_allreduce_bwd(x, axis):
    """Identity whose BACKWARD is psum — Megatron's ``f`` conjugate to
    ``_allreduce_identity_bwd``'s ``g``: a replicated activation entering a
    column-parallel region receives only the LOCAL shard's cotangent per
    device; the complete cotangent is their all-reduce."""
    return x


def _iab_fwd(x, axis):
    return x, None


def _iab_bwd(axis, _, g):
    return (jax.lax.psum(g, axis),)


_identity_allreduce_bwd.defvjp(_iab_fwd, _iab_bwd)


def tp_mesh(n_data: int, n_model: int, devices=None) -> Mesh:
    """(data, model) 2-D mesh — the sharding core's canonical axes."""
    from deeplearning4j_tpu.parallel.sharding_core import mesh_2d
    return mesh_2d(n_data, n_model, ("data", "model"), devices)


class TensorParallelMLP:
    """2-layer MLP with column→row parallel hidden layer + replicated
    softmax head, trained by one donated jitted step over a (data, model)
    mesh."""

    def __init__(self, mesh: Mesh, n_in: int, hidden: int, n_out: int,
                 lr: float = 0.1, seed: int = 0):
        if hidden % mesh.shape["model"] != 0:
            raise ValueError("hidden must divide the model axis")
        self.mesh = mesh
        self.lr = lr
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 2)
        scale1 = (2.0 / (n_in + hidden)) ** 0.5
        scale2 = (2.0 / (hidden + n_out)) ** 0.5
        host = {
            "W1": scale1 * jax.random.normal(k1, (n_in, hidden)),   # column
            "b1": jnp.zeros((hidden,)),
            "W2": scale2 * jax.random.normal(k2, (hidden, n_out)),  # row
            "b2": jnp.zeros((n_out,)),
        }
        from deeplearning4j_tpu.parallel.sharding_core import place_tree
        self.params = place_tree(self.mesh, host, self.param_specs())
        self._step = self._build_step()

    def param_specs(self):
        return {
            "W1": P(None, "model"),   # column-parallel
            "b1": P("model"),
            "W2": P("model", None),   # row-parallel
            "b2": P(),                # replicated
        }

    def _build_step(self):
        mesh = self.mesh
        lr = self.lr

        n_data = mesh.shape["data"]

        def local_loss(params, x, y):
            # x: (B/data, n_in) local; W1/W2 local column/row shards, so the
            # shared forward's W2 matmul yields a PARTIAL product here
            partial = TensorParallelMLP._forward(params, x)
            logits = _allreduce_identity_bwd(partial, "model") + params["b2"]
            logp = jax.nn.log_softmax(logits)
            return -jnp.sum(y * logp)   # LOCAL sum; normalized below

        def step(params, x, y):
            local_sum, grads = jax.value_and_grad(local_loss)(params, x, y)
            n_global = jnp.asarray(x.shape[0] * n_data, jnp.float32)
            # every parameter is replicated over 'data' (sharding only uses
            # 'model'), so its gradient is the data-psum of the local grads
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g, "data") / n_global, grads)
            loss = jax.lax.psum(local_sum, "data") / n_global
            new = jax.tree.map(lambda p, g: p - lr * g, params, grads)
            return new, loss

        sharded = shard_map(
            step, mesh=mesh,
            in_specs=(
                {"W1": P(None, "model"), "b1": P("model"),
                 "W2": P("model", None), "b2": P()},
                P("data", None), P("data", None)),
            out_specs=(
                {"W1": P(None, "model"), "b1": P("model"),
                 "W2": P("model", None), "b2": P()},
                P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0,))

    def fit_batch(self, x, y):
        n_data = self.mesh.shape["data"]
        if x.shape[0] % n_data != 0:
            raise ValueError(
                f"batch size {x.shape[0]} must be a multiple of the data "
                f"axis ({n_data})")
        xs = jax.device_put(jnp.asarray(x),
                            NamedSharding(self.mesh, P("data", None)))
        ys = jax.device_put(jnp.asarray(y),
                            NamedSharding(self.mesh, P("data", None)))
        self.params, loss = self._step(self.params, xs, ys)
        return loss   # device scalar: the host loop must not sync per step

    @staticmethod
    def _forward(params, x):
        """The model function — shared by training (under shard_map, where
        the W2 matmul is a partial sum collected by the collective) and by
        gathered single-device inference."""
        h = jnp.tanh(x @ params["W1"] + params["b1"])
        return h @ params["W2"]

    def predict(self, x) -> np.ndarray:
        host = {k: jnp.asarray(np.asarray(v)) for k, v in self.params.items()}
        logits = self._forward(host, jnp.asarray(np.asarray(x)))
        return np.asarray(jax.nn.softmax(logits + host["b2"], axis=-1))
