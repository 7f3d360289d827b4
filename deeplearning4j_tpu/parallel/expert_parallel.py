"""Expert parallelism: a mixture-of-experts layer sharded over an
``expert`` mesh axis.

BEYOND-reference capability (SURVEY §2.4: the reference has no MoE and no
expert parallelism): E expert MLPs live one-per-device along the ``expert``
axis; each device routes its local tokens (top-1 softmax gate, capacity
bounded), exchanges them with ``all_to_all`` so every expert receives the
tokens routed to it from every peer, applies its expert, and returns the
outputs with the inverse ``all_to_all``. Both exchanges are single XLA
collectives riding ICI — the Switch-Transformer dispatch, not a gather.

Capacity discipline (static shapes for XLA): each device may send at most
``capacity`` tokens to each expert; overflow tokens are dropped (their
combine weight is zero → they pass through the residual path unchanged),
exactly the Switch/GShard behavior.

``ExpertParallelMoE`` mirrors ``TensorParallelMLP``: self-contained
trainable module (sharded params, donated jitted step) used by
``dryrun_multichip`` to validate the ep composition; ``reference_forward``
is the dense single-device oracle the tests compare against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ep_mesh", "ExpertParallelMoE"]


def ep_mesh(n_experts: int, devices=None) -> Mesh:
    """1-D (expert,) mesh — one expert shard per device."""
    from deeplearning4j_tpu.parallel.parallel_wrapper import data_parallel_mesh
    devices = devices if devices is not None else jax.devices()
    if len(devices) < n_experts:
        raise ValueError(f"need {n_experts} devices, have {len(devices)}")
    return data_parallel_mesh(devices[:n_experts], axis="expert")


def _slots_for(expert_id, E, capacity):
    """Send-buffer slot per token for a given routing: slot = how many
    earlier local tokens picked the same expert; keep = fit under
    capacity."""
    onehot = jax.nn.one_hot(expert_id, E, dtype=jnp.int32)   # (T, E)
    slot = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - 1, expert_id[:, None], axis=1)[:, 0]
    return slot, slot < capacity


def _exchange_apply(x, expert_id, expert_fn, E, capacity, axis):
    """One dispatch round for a GIVEN routing (T,)-ids: scatter into the
    per-expert send buffer, ``all_to_all`` out, apply this device's
    ``expert_fn``, inverse-exchange, gather back per token. Unweighted;
    dropped (over-capacity) tokens contribute zero both ways."""
    T, d = x.shape
    slot, keep = _slots_for(expert_id, E, capacity)
    # invariant: dropped tokens (slot >= capacity) must stay in-bounds
    # for the scatter/gather below WITHOUT relying on JAX's implicit
    # out-of-bounds semantics — clip them to slot 0 and let the keep
    # mask zero their contribution both ways
    slot = jnp.where(keep, slot, 0)
    send = jnp.zeros((E, capacity, d), x.dtype)
    send = send.at[expert_id, slot].add(jnp.where(keep[:, None], x, 0.0))
    # all_to_all: dim 0 (expert) scattered, peer dim gathered →
    # (E, capacity, d) where row p = tokens peer p sent to MY expert
    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    out = expert_fn(recv.reshape(E * capacity, d)).reshape(E, capacity, -1)
    back = jax.lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                              tiled=True)
    y = back[expert_id, slot]                # (T, d)
    return jnp.where(keep[:, None], y, 0.0)


def switch_dispatch_apply(x, gate_w, expert_fn, E, capacity, axis):
    """The Switch dispatch core, shared by ``ExpertParallelMoE`` and the
    EP transformer trainer: top-1 route local tokens ``x`` (T, d) with
    gate ``gate_w`` (d, E), exchange with ``all_to_all``, apply this
    device's ``expert_fn`` to the (E*capacity, d) received slots, inverse-
    exchange, and combine weighted by the gate probability. Dropped
    (over-capacity) tokens contribute zero both ways — they ride the
    caller's residual. Returns (output (T, d), gate probs (T, E))."""
    gate_logits = (x @ gate_w).astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert_id = jnp.argmax(probs, axis=-1)
    prob = jnp.max(probs, axis=-1)
    y = _exchange_apply(x, expert_id, expert_fn, E, capacity, axis)
    return prob[:, None].astype(y.dtype) * y, probs


def topk_dispatch_apply(x, gate_w, expert_fn, E, capacity, axis, k):
    """GShard-style top-k routing: each token goes to its k most probable
    experts (k dispatch rounds, 2 collectives each), combined with the
    top-k gate probabilities renormalized to sum 1. k=1 differs from
    ``switch_dispatch_apply`` only by that renormalization (Switch keeps
    the raw probability). Returns (output (T, d), gate probs (T, E))."""
    gate_logits = (x @ gate_w).astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                  # (T, k)
    w = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    y = 0.0
    for r in range(k):
        yr = _exchange_apply(x, topi[:, r], expert_fn, E, capacity, axis)
        y = y + w[:, r:r + 1].astype(yr.dtype) * yr
    return y, probs


class ExpertParallelMoE:
    """Residual MoE block: y = x + combine(expert_{route(x)}(x)), with a
    shared linear head for classification, trained over an (expert,) mesh.

    Parameters: gate (d, E) replicated; per-expert MLP (E, d, h), (E, h, d)
    sharded ``P("expert", ...)``; head (d, n_out) replicated.
    """

    def __init__(self, mesh: Mesh, d: int, hidden: int, n_out: int,
                 capacity: int = 0, lr: float = 0.1, seed: int = 0):
        self.mesh = mesh
        self.E = mesh.shape["expert"]
        self.d, self.hidden, self.n_out = d, hidden, n_out
        self.capacity = capacity            # 0 = derive from batch at call
        self.lr = lr
        E = self.E
        ks = jax.random.split(jax.random.PRNGKey(seed), 4)
        host = {
            "gate": 0.1 * jax.random.normal(ks[0], (d, E)),
            "W1": (2.0 / (d + hidden)) ** 0.5
                  * jax.random.normal(ks[1], (E, d, hidden)),
            "W2": (2.0 / (hidden + d)) ** 0.5
                  * jax.random.normal(ks[2], (E, hidden, d)),
            "head": (2.0 / (d + n_out)) ** 0.5
                    * jax.random.normal(ks[3], (d, n_out)),
        }
        from deeplearning4j_tpu.parallel.sharding_core import place_tree
        self.params = place_tree(self.mesh, host, self.param_specs())
        self._step_cache = {}

    def param_specs(self):
        return {
            "gate": P(),
            "W1": P("expert", None, None),
            "W2": P("expert", None, None),
            "head": P(),
        }

    # ---- the sharded computation -------------------------------------

    @staticmethod
    def _moe_block(params, x_local, E, capacity):
        """Inside shard_map over 'expert': x_local (T, d) tokens resident on
        this device; returns (T, d) MoE output (residual added by caller)."""
        def expert_fn(tokens_flat):
            h = jax.nn.relu(tokens_flat @ params["W1"][0])
            return h @ params["W2"][0]

        y, _ = switch_dispatch_apply(x_local, params["gate"], expert_fn,
                                     E, capacity, "expert")
        return y

    def _build_step(self, capacity):
        mesh = self.mesh
        E, lr = self.E, self.lr

        def local_loss(params, x, y):
            out = x + ExpertParallelMoE._moe_block(params, x, E, capacity)
            logp = jax.nn.log_softmax(out @ params["head"])
            return -jnp.sum(y * logp)

        def step(params, x, y, n_global):
            local_sum, grads = jax.value_and_grad(local_loss)(params, x, y)
            # replicated params: psum grads over 'expert' (each device saw
            # different tokens); expert shards: grads already local-only
            gg = jax.lax.psum(grads["gate"], "expert")
            gh = jax.lax.psum(grads["head"], "expert")
            loss = jax.lax.psum(local_sum, "expert") / n_global
            new = {
                "gate": params["gate"] - lr * gg / n_global,
                "W1": params["W1"] - lr * grads["W1"] / n_global,
                "W2": params["W2"] - lr * grads["W2"] / n_global,
                "head": params["head"] - lr * gh / n_global,
            }
            return new, loss

        specs = {"gate": P(), "W1": P("expert", None, None),
                 "W2": P("expert", None, None), "head": P()}
        sharded = shard_map(
            step, mesh=mesh,
            in_specs=(specs, P("expert", None), P("expert", None), P()),
            out_specs=(specs, P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0,))

    def _capacity_for(self, tokens_per_device):
        # default: every local token could pick the same expert → lossless
        return self.capacity or tokens_per_device

    def _train_signature(self, capacity):
        """Blessed key for the per-capacity sharded-step cache: capacity
        is batch-shape-derived (a host int — ctor cap or N // E), so it
        must route through a builder to keep the signature inventory
        statically enumerable (siglint G025)."""
        return ("moe_step", capacity)

    def fit_batch(self, x, y):
        """x: (N, d) tokens, y: (N, n_out) one-hot; N divisible by E."""
        N = x.shape[0]
        if N % self.E != 0:
            raise ValueError(f"batch {N} must be a multiple of E={self.E}")
        cap = self._capacity_for(N // self.E)
        sig = self._train_signature(cap)
        if sig not in self._step_cache:
            self._step_cache[sig] = self._build_step(cap)
        sh = NamedSharding(self.mesh, P("expert", None))
        xs = jax.device_put(jnp.asarray(x, jnp.float32), sh)
        ys = jax.device_put(jnp.asarray(y, jnp.float32), sh)
        self.params, loss = self._step_cache[sig](
            self.params, xs, ys, jnp.asarray(N, jnp.float32))
        return loss   # device scalar: the host loop must not sync per step

    # ---- dense oracle -------------------------------------------------

    def reference_forward(self, x) -> np.ndarray:
        """Single-device dense routing oracle: with per-device capacity ≥
        local tokens nothing drops, so the sharded block must match this
        (up to routing tie-breaks) — the tests' parity bar."""
        p = {k: np.asarray(v) for k, v in self.params.items()}
        x = np.asarray(x, np.float32)
        logits = x @ p["gate"]
        e = np.exp(logits - logits.max(-1, keepdims=True))
        probs = e / e.sum(-1, keepdims=True)
        eid = probs.argmax(-1)
        out = np.zeros_like(x)
        for i in range(x.shape[0]):
            h = np.maximum(x[i] @ p["W1"][eid[i]], 0.0)
            out[i] = probs[i, eid[i]] * (h @ p["W2"][eid[i]])
        y = x + out
        logits = y @ p["head"]
        e = np.exp(logits - logits.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)
