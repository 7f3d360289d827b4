"""Pipeline (stage) parallelism over a device mesh.

BEYOND-reference capability (SURVEY §2.4: the reference has no pipeline
parallelism — its distributed story is data parallelism only): split a deep
network into S stages laid out along a ``pipe`` mesh axis, one stage's
parameters resident per device, and stream M microbatches through the
stages GPipe-style so all stages compute concurrently after the fill phase.

Design (idiomatic XLA: one ``lax.scan`` over ticks inside ``shard_map``,
activations handed stage-to-stage with ``lax.ppermute`` so the transfer is
a neighbor-exchange riding ICI, not a gather):

- stage parameters are stacked on a leading (S, ...) axis sharded
  ``P("pipe", ...)`` — each device holds exactly its stage slice.
- a tick applies the local stage to the current activation, then rotates
  activations forward one stage with ``ppermute``. ``T = M + S - 1`` ticks
  drain the pipeline (fill bubble included, the GPipe schedule).
- stage 0 injects microbatch ``t`` on tick ``t``; the last stage computes
  the loss for microbatch ``t - (S-1)`` on tick ``t``. Contributions are
  where-masked and psum'd over ``pipe`` so every device reports the scalar.
- backward is jax.grad through the scan: the transpose of ``ppermute`` is
  the reverse rotation, so XLA derives the reverse-order backward pipeline
  (B after F per microbatch) with no hand-written schedule.
- composes with data parallelism over a 2-D ``(data, pipe)`` mesh: batch
  sharded over ``data``, gradient psum over ``data`` as usual.

``PipelineParallelNet`` mirrors ``TensorParallelMLP``: a self-contained
trainable module (sharded params, one donated jitted step) used by
``dryrun_multichip`` to validate the pp×dp composition.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["pp_mesh", "PipelineParallelNet"]


def pp_mesh(n_data: int, n_pipe: int, devices=None) -> Mesh:
    """(data, pipe) 2-D mesh."""
    from deeplearning4j_tpu.parallel.sharding_core import mesh_2d
    return mesh_2d(n_data, n_pipe, ("data", "pipe"), devices)


class PipelineParallelNet:
    """S-stage residual-MLP pipeline with a replicated input projection on
    stage 0 and softmax head on the last stage, trained by one donated
    jitted step over a (data, pipe) mesh with M microbatches per step.

    Width ``d`` is uniform across stages so the activation handed between
    stages is a fixed (mb, d) buffer — the shape ``ppermute`` rotates.
    """

    def __init__(self, mesh: Mesh, n_in: int, d: int, n_out: int,
                 n_micro: int, lr: float = 0.1, seed: int = 0):
        self.mesh = mesh
        self.n_stages = mesh.shape["pipe"]
        self.n_micro = int(n_micro)
        if self.n_micro < 1:
            raise ValueError("need at least one microbatch")
        self.n_in, self.d, self.n_out = n_in, d, n_out
        self.lr = lr
        S = self.n_stages
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        host = {
            # stacked per-stage block weights: device s holds slice s
            "W": (2.0 / (2 * d)) ** 0.5 * jax.random.normal(k1, (S, d, d)),
            "b": jnp.zeros((S, d)),
            # boundary projections, replicated (used on one stage each)
            "Win": (2.0 / (n_in + d)) ** 0.5 * jax.random.normal(k2, (n_in, d)),
            "Wout": (2.0 / (d + n_out)) ** 0.5 * jax.random.normal(k3, (d, n_out)),
        }
        from deeplearning4j_tpu.parallel.sharding_core import place_tree
        self.params = place_tree(self.mesh, host, self.param_specs())
        self._step = self._build_step()

    def param_specs(self):
        return {
            "W": P("pipe", None, None),
            "b": P("pipe", None),
            "Win": P(),
            "Wout": P(),
        }

    def _build_step(self):
        mesh = self.mesh
        S, M, lr = self.n_stages, self.n_micro, self.lr
        n_data = mesh.shape["data"]
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def local_loss(params, xs, ys):
            # xs: (M, mb, n_in) local to this data shard; params["W"] is the
            # local (1, d, d) stage slice under shard_map
            Ws = params["W"][0]
            bs = params["b"][0]
            stage = jax.lax.axis_index("pipe")
            is_first = (stage == 0)
            is_last = (stage == S - 1)
            mb = xs.shape[1]

            def tick(carry, t):
                state, loss_sum = carry
                # stage 0 injects microbatch t (clamped: past the fill
                # phase the injected value is stale but never reaches the
                # loss — its contribution is masked below)
                feed = jnp.tanh(
                    xs[jnp.clip(t, 0, M - 1)] @ params["Win"])
                x = jnp.where(is_first & (t < M), feed, state)
                h = x + jnp.tanh(x @ Ws + bs)          # residual block
                # last stage: microbatch m = t - (S-1) finishes this tick
                m = t - (S - 1)
                logits = h @ params["Wout"]
                logp = jax.nn.log_softmax(logits)
                contrib = -jnp.sum(ys[jnp.clip(m, 0, M - 1)] * logp)
                valid = is_last & (m >= 0) & (m < M)
                loss_sum = loss_sum + jnp.where(valid, contrib, 0.0)
                state = jax.lax.ppermute(h, "pipe", fwd_perm)
                return (state, loss_sum), None

            init = (jnp.zeros((mb, self.d), xs.dtype), jnp.asarray(0.0))
            (_, loss_sum), _ = jax.lax.scan(
                tick, init, jnp.arange(M + S - 1))
            return loss_sum

        def step(params, xs, ys):
            local_sum, grads = jax.value_and_grad(local_loss)(params, xs, ys)
            n_global = jnp.asarray(M * xs.shape[1] * n_data, jnp.float32)
            # replicated params (Win/Wout) have nonzero grad only on the
            # stage that uses them; stage-stacked params only locally. psum
            # over BOTH axes re-replicates / data-averages in one pass:
            # - over 'data': standard DP gradient sum (all params)
            # - over 'pipe': Win/Wout grads live on one stage; W/b grads are
            #   local-only under P("pipe") out_specs so pipe-psum must skip
            #   them (their out_spec keeps them per-stage).
            gW = jax.lax.psum(grads["W"], "data")
            gb = jax.lax.psum(grads["b"], "data")
            gin = jax.lax.psum(grads["Win"], ("data", "pipe"))
            gout = jax.lax.psum(grads["Wout"], ("data", "pipe"))
            loss = jax.lax.psum(local_sum, ("data", "pipe")) / n_global
            new = {
                "W": params["W"] - lr * gW / n_global,
                "b": params["b"] - lr * gb / n_global,
                "Win": params["Win"] - lr * gin / n_global,
                "Wout": params["Wout"] - lr * gout / n_global,
            }
            return new, loss

        specs = {"W": P("pipe", None, None), "b": P("pipe", None),
                 "Win": P(), "Wout": P()}
        sharded = shard_map(
            step, mesh=mesh,
            in_specs=(specs, P(None, "data", None), P(None, "data", None)),
            out_specs=(specs, P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0,))

    def fit_batch(self, x, y):
        """One pipelined step. x: (N, n_in), y: (N, n_out) one-hot; N must
        split into n_micro microbatches × the data axis."""
        n_data = self.mesh.shape["data"]
        N = x.shape[0]
        if N % (self.n_micro * n_data) != 0:
            raise ValueError(
                f"batch {N} must be a multiple of n_micro*data "
                f"({self.n_micro}*{n_data})")
        mb = N // (self.n_micro * n_data)
        # graftlint: disable=G001 -- host microbatch reshape of the incoming host batch, before device transfer
        xs = np.asarray(x, np.float32).reshape(
            self.n_micro, n_data * mb, self.n_in)
        # graftlint: disable=G001 -- host microbatch reshape of the incoming host batch, before device transfer
        ys = np.asarray(y, np.float32).reshape(
            self.n_micro, n_data * mb, self.n_out)
        sh = NamedSharding(self.mesh, P(None, "data", None))
        xs = jax.device_put(jnp.asarray(xs), sh)
        ys = jax.device_put(jnp.asarray(ys), sh)
        self.params, loss = self._step(self.params, xs, ys)
        return loss   # device scalar: the host loop must not sync per step

    def predict(self, x) -> np.ndarray:
        """Gathered single-device forward (parity oracle for tests)."""
        host = {k: np.asarray(v) for k, v in self.params.items()}
        h = np.tanh(np.asarray(x, np.float32) @ host["Win"])
        for s in range(self.n_stages):
            h = h + np.tanh(h @ host["W"][s] + host["b"][s])
        logits = h @ host["Wout"]
        e = np.exp(logits - logits.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    def reference_loss(self, x, y) -> float:
        """Unpipelined loss for the same params/batch — the parity oracle:
        the pipelined step must compute exactly this (GPipe is math-
        preserving, unlike async pipelines)."""
        p = np.asarray(self.predict(x))
        return float(-np.sum(np.asarray(y) * np.log(p + 1e-12)) / x.shape[0])
