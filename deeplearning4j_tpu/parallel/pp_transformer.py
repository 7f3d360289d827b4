"""Pipeline-parallel TransformerLM training (GPipe schedule).

BEYOND-reference capability (SURVEY §2.4: the reference's distributed
story is data-parallel only): lay the LM's blocks out in S stages along a
``pipe`` mesh axis — each device resident-holds ``n_layers/S`` blocks —
and stream M microbatches through with the same one-``lax.scan``
neighbor-exchange design as ``PipelineParallelNet``:

- block params are STACKED on a leading (S, blocks_per_stage, ...) axis
  sharded ``P("pipe", ...)``; embeddings (tied wte feeds stage 0's embed
  AND the last stage's logits), wpe, and the final LN are replicated;
- a tick applies this device's blocks, then rotates activations forward
  one stage with ``lax.ppermute`` (a neighbor exchange riding ICI);
  ``M + S - 1`` ticks drain the pipeline — the GPipe fill bubble;
- stage 0 injects embedded microbatch ``t`` on tick ``t``; the last stage
  computes masked loss contributions; backward is ``jax.grad`` through
  the scan (``ppermute`` transposes to the reverse rotation, so XLA
  derives the reverse-order backward pipeline with no hand schedule);
- collectives stay OUTSIDE the differentiated region (the MLP pipeline's
  discipline): per-device grads are psum'd over ``pipe`` only for the
  replicated leaves, then the shared ``_adamw_apply`` runs shard-local.

GPipe is math-preserving: initialized from ``TransformerLM(config)
.init()`` at the same seed, S-stage training reproduces the single-device
model's losses exactly (tested to fp tolerance).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM,
                                                   _adamw_apply,
                                                   _block_apply, _layer_norm,
                                                   _lr_at)

__all__ = ["PPTransformerLM"]

# block leaves that are matmul weight matrices (GPT-2 decay discipline);
# the stacked (S, bps, ...) layout breaks the ndim>=2 heuristic, so the
# PP decay mask is name-keyed
_DECAYED_BLOCK_LEAVES = frozenset({"qkv", "proj", "fc", "out"})


class PPTransformerLM:
    """GPipe-scheduled trainer for the TransformerLM family."""

    def __init__(self, mesh: Mesh, config: TransformerConfig,
                 n_micro: int, axis: str = "pipe"):
        self._spec = config.one_block("PPTransformerLM")
        if config.dropout:
            raise ValueError("PP trainer runs dropout-free (eval parity)")
        if config.pos_embed != "learned":
            raise ValueError("PP trainer assumes the learned wpe table")
        self.mesh = mesh
        self.axis = axis
        self.S = mesh.shape[axis]
        self.M = int(n_micro)
        if self.M < 1:
            raise ValueError("need at least one microbatch")
        if config.n_layers % self.S:
            raise ValueError(
                f"n_layers {config.n_layers} must divide into {self.S} "
                f"stages")
        self.bps = config.n_layers // self.S
        self.conf = config
        full = TransformerLM(config).init().params   # same init as 1-chip
        self.params = self._shard_params(full)
        self.opt_state = {
            "m": jax.tree.map(jnp.zeros_like, self.params),
            "v": jax.tree.map(jnp.zeros_like, self.params),
        }
        self.iteration = 0
        self.score_ = float("nan")
        self._step = None

    # ---- parameter layout ---------------------------------------------
    def _param_specs(self):
        blocks = {k: P(self.axis) for k in self._block_keys}
        return {"wte": P(), "wpe": P(), "lnf_g": P(), "lnf_b": P(),
                "blocks": blocks}

    def _shard_params(self, full):
        c = self.conf
        self._block_keys = sorted(full["b0"].keys())
        stacked = {}
        for key in self._block_keys:
            rows = []
            for s in range(self.S):
                rows.append(jnp.stack(
                    [full[f"b{s * self.bps + j}"][key]
                     for j in range(self.bps)]))
            stacked[key] = jnp.stack(rows)       # (S, bps, ...)
        out = {"wte": full["wte"], "wpe": full["wpe"],
               "lnf_g": full["lnf_g"], "lnf_b": full["lnf_b"],
               "blocks": stacked}
        from deeplearning4j_tpu.parallel.sharding_core import place_tree
        return place_tree(self.mesh, out, self._param_specs())

    def _decay_mask(self):
        blocks = {k: (1.0 if k in _DECAYED_BLOCK_LEAVES else 0.0)
                  for k in self._block_keys}
        return {"wte": 1.0, "wpe": 0.0, "lnf_g": 0.0, "lnf_b": 0.0,
                "blocks": blocks}

    # ---- pipelined loss ------------------------------------------------
    def _local_loss(self, params, tokens, targets):
        """tokens/targets: (M, mb, T) replicated; returns this device's
        masked loss SUM (collectives happen outside the grad)."""
        c, S, M = self.conf, self.S, self.M
        mb, T = tokens.shape[1], tokens.shape[2]
        stage = jax.lax.axis_index(self.axis)
        is_first = (stage == 0)
        is_last = (stage == S - 1)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        cd = c.compute_dtype
        if cd:   # bf16 compute against f32 masters, like the 1-chip model
            params = jax.tree.map(
                lambda a: a.astype(cd)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        local_blocks = {k: params["blocks"][k][0]     # (bps, ...)
                        for k in self._block_keys}

        blk = lambda bp, x: _block_apply(c, bp, x, self._spec)
        if c.remat:
            blk = jax.checkpoint(blk)   # closure over config: only arrays
                                        # cross the checkpoint boundary

        def apply_stage(x):
            for j in range(self.bps):
                bp = {k: local_blocks[k][j] for k in self._block_keys}
                x = blk(bp, x)
            return x

        def embed(t, state):
            return (params["wte"][tokens[jnp.clip(t, 0, M - 1)]]
                    + params["wpe"][:T]).astype(state.dtype)

        def head(x, m):
            """Loss head for microbatch m — ~a block's worth of FLOPs at
            real vocab sizes, so it runs under ``lax.cond`` only on the
            last stage's draining ticks instead of masked-everywhere."""
            h = _layer_norm(x, params["lnf_g"], params["lnf_b"])
            logits = (h @ params["wte"].T).astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            tg = targets[jnp.clip(m, 0, M - 1)]
            return -jnp.take_along_axis(
                logp, tg[..., None], axis=-1)[..., 0].sum()

        def tick(carry, t):
            state, loss_sum = carry
            x = jax.lax.cond(is_first & (t < M),
                             lambda s: embed(t, s), lambda s: s, state)
            x = apply_stage(x)
            # last stage: microbatch m = t - (S-1) finishes this tick
            m = t - (S - 1)
            valid = is_last & (m >= 0) & (m < M)
            loss_sum = loss_sum + jax.lax.cond(
                valid, lambda xx: head(xx, m),
                lambda xx: jnp.float32(0.0), x)
            state = jax.lax.ppermute(x, self.axis, fwd_perm)
            return (state, loss_sum), None

        init = (jnp.zeros((mb, T, c.d_model), cd or jnp.float32),
                jnp.asarray(0.0))
        (_, loss_sum), _ = jax.lax.scan(tick, init, jnp.arange(M + S - 1))
        return loss_sum

    # ---- training ------------------------------------------------------
    def _build_step(self):
        c = self.conf
        specs = self._param_specs()
        opt_specs = {"m": specs, "v": specs}
        mask = self._decay_mask()

        def step(params, opt, it, tokens, targets):
            local_sum, grads = jax.value_and_grad(self._local_loss)(
                params, tokens, targets)
            n_tokens = jnp.asarray(
                self.M * tokens.shape[1] * tokens.shape[2], jnp.float32)
            # replicated leaves: each stage contributes its own partial
            # (wte via embed on stage 0 + logits on the last stage; lnf on
            # the last stage only) — one psum over pipe completes them.
            # Stage-stacked block grads are exact locally. Grads of a SUM
            # loss are divided to grads of the token mean.
            for name in ("wte", "wpe", "lnf_g", "lnf_b"):
                grads[name] = jax.lax.psum(grads[name], self.axis) / n_tokens
            grads["blocks"] = jax.tree.map(lambda g: g / n_tokens,
                                           grads["blocks"])
            loss = jax.lax.psum(local_sum, self.axis) / n_tokens
            t = it + 1
            new_p, new_opt = _adamw_apply(c, params, grads, opt, t,
                                          _lr_at(c, t), mask=mask)
            return new_p, new_opt, t, loss

        sharded = shard_map(
            step, mesh=self.mesh,
            in_specs=(specs, opt_specs, P(), P(), P()),
            out_specs=(specs, opt_specs, P(), P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0, 1))

    def fit_batch(self, tokens, targets=None):
        """tokens: (N, T+1) next-token setup, or (N, T) with ``targets``;
        N must be a multiple of ``n_micro``."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if targets is None:
            tokens, targets = tokens[:, :-1], tokens[:, 1:]
        else:
            targets = jnp.asarray(targets, jnp.int32)
        N, T = tokens.shape
        if N % self.M:
            raise ValueError(
                f"batch {N} must be a multiple of n_micro ({self.M})")
        mb = N // self.M
        rep = NamedSharding(self.mesh, P())
        toks = jax.device_put(tokens.reshape(self.M, mb, T), rep)
        tgts = jax.device_put(targets.reshape(self.M, mb, T), rep)
        if self._step is None:
            self._step = self._build_step()
        (self.params, self.opt_state, self.iteration,
         loss) = self._step(self.params, self.opt_state, self.iteration,
                            toks, tgts)
        self.score_ = loss   # device scalar, synced lazily on read
        return self.score_

    # ---- introspection -------------------------------------------------
    def shard_fraction(self) -> float:
        total = per_dev = 0
        for a in jax.tree.leaves(self.params):
            total += a.size
            per_dev += int(np.prod(a.sharding.shard_shape(a.shape)))
        return per_dev / total
