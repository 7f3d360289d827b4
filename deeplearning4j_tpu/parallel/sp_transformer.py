"""Sequence-parallel TransformerLM training (ring attention).

BEYOND-reference long-context capability (SURVEY §5.7: the reference's
only answer to long sequences is truncated BPTT): shard the SEQUENCE
axis over a ``seq`` mesh axis so a context too long for one chip's
activation memory trains across N chips:

- every device holds a [B, T/N] token shard; embeddings, blocks, and the
  logits head run on local shards (activation memory O(T/N) per device);
- attention is the exact ring: K/V shards rotate with ``lax.ppermute``
  while the flash recurrence accumulates (``parallel.sequence_parallel.
  ring_attention``), so transfers ride ICI and no device ever
  materializes the full sequence — the Ring Attention construction;
- parameters are replicated; each device's loss covers its token shard,
  so per-device grads are partials completed by ONE psum over ``seq``
  after the backward (collectives stay outside the differentiated
  region for everything except the ring itself, whose ppermute
  transposes to the reverse rotation);
- the update is the shared ``_adamw_apply`` (same decay discipline and
  lr schedule as the single-chip model).

Initialized from ``TransformerLM(config).init()`` at the same seed:
N-way sequence sharding reproduces single-device training exactly
(ring attention is exact, not approximate — tested to fp tolerance).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM,
                                                   _adamw_apply,
                                                   _block_apply, _full_heads,
                                                   _layer_norm, _lr_at)
from deeplearning4j_tpu.parallel.sequence_parallel import ring_attention
from deeplearning4j_tpu.parallel.sharding_core import ShardingCore

__all__ = ["SPTransformerLM"]


class SPTransformerLM:
    """Ring-attention sequence-parallel trainer for the LM family."""

    def __init__(self, mesh: Mesh, config: TransformerConfig,
                 axis: str = "seq"):
        self._spec = config.one_block("SPTransformerLM")
        if config.dropout:
            raise ValueError("SP trainer runs dropout-free (eval parity)")
        if config.block_size:
            raise ValueError(
                "SP attention is the ring recurrence; block_size (single-"
                "device flash) does not apply")
        if config.window:
            raise ValueError(
                "the ring recurrence has no sliding-window support; "
                "use window on the single-device/dp paths")
        if config.pos_embed != "learned":
            raise ValueError("SP trainer slices the learned wpe per shard")
        self.mesh = mesh
        self.axis = axis
        self.N = mesh.shape[axis]
        self.conf = config
        # the SP mesh shards the SEQUENCE axis — there is no batch-like
        # axis a ZeRO level could shard state over, so the core's
        # degenerate (batch_axis=None) plan places params whole per
        # device; replicated placement lives in the audited core, not in
        # a hand-rolled binding (the G020 ownership contract)
        self.core = ShardingCore(mesh, batch_axis=None)
        self.params = self.core.place_replicated(
            TransformerLM(config).init().params)   # same init as 1-chip
        self.opt_state = {
            "m": jax.tree.map(jnp.zeros_like, self.params),
            "v": jax.tree.map(jnp.zeros_like, self.params),
        }
        self.iteration = 0
        self.score_ = float("nan")
        self._step = None

    # ---- sharded forward ----------------------------------------------
    def _block_local(self, bp, x):
        """The canonical ``_block_apply`` math on a [B, T/N, d] shard with
        the attention swapped for the ring (everything else is per-token
        and shards trivially)."""
        # the ring assumes MHA: the block hands over the grouped K/V
        ring = lambda q, k, v: ring_attention(
            q, *_full_heads(q.shape[1] // k.shape[1], k, v),
            axis_name=self.axis, causal=True)
        return _block_apply(self.conf, bp, x, self._spec, attend=ring)

    def _local_loss(self, params, tokens, targets):
        """tokens/targets: [B, T/N] local shards; returns the local nll
        SUM (the seq-psum happens outside the grad)."""
        c = self.conf
        tl = tokens.shape[1]
        off = jax.lax.axis_index(self.axis) * tl
        wpe = jax.lax.dynamic_slice_in_dim(params["wpe"], off, tl, axis=0)
        x = params["wte"][tokens] + wpe
        cd = c.compute_dtype
        if cd:
            x = x.astype(cd)
            params = jax.tree.map(
                lambda a: a.astype(cd)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        for i in range(c.n_layers):
            blk = (jax.checkpoint(self._block_local) if c.remat
                   else self._block_local)
            x = blk(params[f"b{i}"], x)
        x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
        logits = (x @ params["wte"].T).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return nll.sum()

    # ---- training ------------------------------------------------------
    def _build_step(self):
        c = self.conf
        axis = self.axis

        def step(params, opt, it, tokens, targets):
            local_sum, grads = jax.value_and_grad(self._local_loss)(
                params, tokens, targets)
            n_tokens = jnp.asarray(
                tokens.shape[0] * tokens.shape[1] * self.N, jnp.float32)
            # every param is replicated but each device saw only its token
            # shard: one psum completes the grads; /n_tokens turns grads
            # of the sum into grads of the global token mean
            grads = jax.tree.map(
                lambda g: jax.lax.psum(g, axis) / n_tokens, grads)
            loss = jax.lax.psum(local_sum, axis) / n_tokens
            t = it + 1
            new_p, new_opt = _adamw_apply(c, params, grads, opt, t,
                                          _lr_at(c, t))
            return new_p, new_opt, t, loss

        rep = jax.tree.map(lambda _: P(), self.params)
        opt_rep = {"m": rep, "v": rep}
        sharded = shard_map(
            step, mesh=self.mesh,
            in_specs=(rep, opt_rep, P(), P(None, axis), P(None, axis)),
            out_specs=(rep, opt_rep, P(), P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0, 1))

    def fit_batch(self, tokens, targets=None):
        """tokens: (B, T+1) next-token setup or (B, T) with ``targets``;
        T must be a multiple of the seq axis size."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if targets is None:
            tokens, targets = tokens[:, :-1], tokens[:, 1:]
        else:
            targets = jnp.asarray(targets, jnp.int32)
        if tokens.shape[1] % self.N:
            raise ValueError(
                f"sequence length {tokens.shape[1]} must be a multiple of "
                f"the seq axis ({self.N})")
        if tokens.shape[1] > self.conf.max_len:
            # dynamic_slice would silently CLAMP the per-shard wpe offset
            # (wrong positions, finite loss) instead of failing like the
            # other trainers do
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds max_len "
                f"{self.conf.max_len}")
        sh = self.core.sharding(P(None, self.axis))
        tokens = jax.device_put(tokens, sh)
        targets = jax.device_put(targets, sh)
        if self._step is None:
            self._step = self._build_step()
        (self.params, self.opt_state, self.iteration,
         loss) = self._step(self.params, self.opt_state, self.iteration,
                            tokens, targets)
        self.score_ = loss   # device scalar, synced lazily on read
        return self.score_
