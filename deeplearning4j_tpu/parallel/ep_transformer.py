"""Expert-parallel MoE-TransformerLM training (Switch dispatch).

BEYOND-reference capability: the MoE LM's expert weights live one shard
per device along an ``expert`` mesh axis; the batch is sharded over the
same axis (data parallelism for the dense blocks), and each MoE FFN
dispatches tokens to their routed expert with a single ``all_to_all``
and returns them with the inverse exchange — the Switch-Transformer /
GShard pattern, two collectives per MoE layer riding ICI:

- dense blocks, attention, embeddings: replicated params, local batch
  shard, grads completed by one psum over ``expert`` after the backward
  (the PP/SP discipline: collectives outside the differentiated region
  except the dispatch itself, whose all_to_all transposes to the
  inverse all_to_all);
- MoE blocks: gate replicated; expert MLPs (E, d, h)/(E, h, d) sharded
  ``P("expert")`` — grads arrive shard-local, no psum;
- capacity is lossless by default (each device can send its whole local
  token set to one expert), so routing reproduces the dense oracle
  (``models.moe_transformer.MoETransformerLM``) exactly and the parity
  tests pin it; pass ``capacity`` to trade exactness for bounded
  buffers (dropped tokens ride the residual, Switch semantics);
- the load-balance aux loss is computed per-device over LOCAL tokens
  and averaged across the mesh — the standard EP approximation of the
  global Switch aux (exact when shards are statistically identical);
  parity tests run with ``aux_weight=0`` where the math must be exact.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.moe_transformer import (MoETransformerConfig,
                                                       MoETransformerLM)
from deeplearning4j_tpu.models.transformer import (_adamw_apply,
                                                   _block_apply,
                                                   _forward_tokens, _lr_at)
from deeplearning4j_tpu.parallel.expert_parallel import (
    switch_dispatch_apply, topk_dispatch_apply)

__all__ = ["EPTransformerLM"]


def _moe_ffn_ep(bp, h, n_experts, capacity, axis, top_k=1):
    """Routed FFN on a local [B, T, d] shard inside ``shard_map``: the
    shared dispatch core with this family's gelu+bias expert MLP.
    top_k=1 is the Switch dispatch; top_k>=2 the GShard k-round combine
    (k all_to_all pairs). Returns (output, local aux loss)."""
    B, T, d = h.shape

    def expert_fn(tokens_flat):
        mid = jax.nn.gelu(tokens_flat @ bp["W1"][0] + bp["W1_b"][0])
        return mid @ bp["W2"][0] + bp["W2_b"][0]

    if top_k == 1:
        y, probs = switch_dispatch_apply(h.reshape(-1, d), bp["gate"],
                                         expert_fn, n_experts, capacity,
                                         axis)
    else:
        y, probs = topk_dispatch_apply(h.reshape(-1, d), bp["gate"],
                                       expert_fn, n_experts, capacity,
                                       axis, top_k)
    eid = jnp.argmax(probs, axis=-1)
    f = jax.nn.one_hot(eid, n_experts, dtype=jnp.float32).mean(axis=0)
    p = probs.mean(axis=0)
    aux = n_experts * jnp.sum(f * p)
    return y.reshape(B, T, d), aux


class EPTransformerLM:
    """Expert-parallel trainer for the MoE LM family."""

    def __init__(self, mesh: Mesh, config: MoETransformerConfig,
                 axis: str = "expert", capacity: int = 0):
        if config.dropout:
            raise ValueError("EP trainer runs dropout-free (eval parity)")
        if config.block_size:
            raise ValueError("EP trainer uses dense attention; block_size "
                             "is not supported here")
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no {axis!r} axis: {mesh.axis_names}")
        if config.n_experts != mesh.shape[axis]:
            raise ValueError(
                f"n_experts {config.n_experts} must equal the expert axis "
                f"size ({mesh.shape[axis]}) — one expert shard per device")
        self.mesh = mesh
        self.axis = axis
        self.E = config.n_experts
        self.capacity = capacity        # 0 = lossless (local token count)
        self.conf = config
        full = MoETransformerLM(config).init().params   # same init
        self._moe_layers = {i for i in range(config.n_layers)
                            if config.is_moe_layer(i)}
        self.params = self._shard_params(full)
        self.opt_state = {
            "m": jax.tree.map(jnp.zeros_like, self.params),
            "v": jax.tree.map(jnp.zeros_like, self.params),
        }
        self.iteration = 0
        self.score_ = float("nan")
        self._step_cache = {}

    # ---- parameter layout ---------------------------------------------
    _EXPERT_LEAVES = ("W1", "W1_b", "W2", "W2_b")

    def _shard_params(self, full):
        """Expert leaves → P(axis) on their leading E dim; all else
        replicated."""
        self._specs = jax.tree_util.tree_map_with_path(
            lambda path, a: (P(self.axis)
                             if path[-1].key in self._EXPERT_LEAVES
                             else P()),
            full)
        from deeplearning4j_tpu.parallel.sharding_core import place_tree
        return place_tree(self.mesh, full, self._specs)

    # ---- sharded loss --------------------------------------------------
    def _local_loss(self, params, tokens, targets, capacity):
        c = self.conf
        auxes = []
        spec = c.one_block("EPTransformerLM")

        def moe_block(bp, xx):
            cell = {}

            def ffn(bp2, hloc):
                y, aux = _moe_ffn_ep(bp2, hloc, self.E, capacity, self.axis,
                                     c.router_top_k)
                cell["aux"] = aux
                return y

            out = _block_apply(c, bp, xx, spec, ffn=ffn)
            return out, cell["aux"]

        def dense_block(bp, xx):
            return _block_apply(c, bp, xx, spec)

        def apply(i, bp, x):
            if i in self._moe_layers:
                blk = jax.checkpoint(moe_block) if c.remat else moe_block
                x, aux = blk(bp, x)
                auxes.append(aux)
                return x
            blk = jax.checkpoint(dense_block) if c.remat else dense_block
            return blk(bp, x)

        logits = _forward_tokens(c, params, tokens, apply)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        n_local = nll.size
        # local objective SUM: ce + aux scaled to token units so the
        # outside psum/n_tokens yields mean ce + aux_weight * mean aux
        aux_total = sum(auxes, jnp.float32(0.0))
        return nll.sum() + c.aux_weight * aux_total * n_local

    # ---- training ------------------------------------------------------
    def _build_step(self, capacity):
        c = self.conf
        axis = self.axis
        specs = self._specs
        opt_specs = {"m": specs, "v": specs}

        def is_expert_leaf(path):
            return path[-1].key in self._EXPERT_LEAVES

        def step(params, opt, it, tokens, targets):
            local_sum, grads = jax.value_and_grad(self._local_loss)(
                params, tokens, targets, capacity)
            n_tokens = jnp.asarray(
                tokens.shape[0] * tokens.shape[1] * self.E, jnp.float32)
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: (g if is_expert_leaf(path)
                                 else jax.lax.psum(g, axis)) / n_tokens,
                grads)
            loss = jax.lax.psum(local_sum, axis) / n_tokens
            t = it + 1
            new_p, new_opt = _adamw_apply(c, params, grads, opt, t,
                                          _lr_at(c, t))
            return new_p, new_opt, t, loss

        sharded = shard_map(
            step, mesh=self.mesh,
            in_specs=(specs, opt_specs, P(), P(axis, None), P(axis, None)),
            out_specs=(specs, opt_specs, P(), P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0, 1))

    def fit_batch(self, tokens, targets=None):
        tokens = jnp.asarray(tokens, jnp.int32)
        if targets is None:
            tokens, targets = tokens[:, :-1], tokens[:, 1:]
        else:
            targets = jnp.asarray(targets, jnp.int32)
        B, T = tokens.shape
        if B % self.E:
            raise ValueError(
                f"batch {B} must be a multiple of the expert axis "
                f"({self.E})")
        cap = self.capacity or (B // self.E) * T   # lossless default
        sh = NamedSharding(self.mesh, P(self.axis, None))
        tokens = jax.device_put(tokens, sh)
        targets = jax.device_put(targets, sh)
        step = self._step_cache.get(cap)
        if step is None:
            step = self._step_cache[cap] = self._build_step(cap)
        (self.params, self.opt_state, self.iteration,
         loss) = step(self.params, self.opt_state, self.iteration,
                      tokens, targets)
        self.score_ = loss   # device scalar, synced lazily on read
        return self.score_
