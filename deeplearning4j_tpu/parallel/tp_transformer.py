"""Tensor-parallel TransformerLM training (Megatron partitioning).

BEYOND-reference capability (the reference's only distributed story is
data-parallel parameter averaging, SURVEY §2.4): shard the transformer's
matmuls across a ``model`` mesh axis so a model too wide for one chip's
HBM trains across N chips with TWO psums per block — the Megatron-LM
pattern, expressed as ``shard_map`` + XLA collectives over ICI:

- attention: qkv projections COLUMN-parallel (each device owns H/N whole
  heads — the d/N column slice is head-aligned), attention runs on local
  heads only, the output projection is ROW-parallel and one ``psum``
  rebuilds the residual;
- MLP: up-projection column-parallel, GELU local, down-projection
  row-parallel + ``psum``;
- embeddings, LayerNorms, and the tied logits matmul stay replicated
  (vocab-parallel logits are a further step the test sizes don't need);
- the AdamW update (same formulas + GPT-2 decay mask as the single-chip
  ``TransformerLM``) is SHARD-LOCAL: ``shard_map``'s autodiff transposes
  the forward psums so each device ends holding exactly its parameter
  shard's gradient — optimizer state is sharded for free, like ZeRO.

Initialized from ``TransformerLM(config).init()`` at the same seed, so
N-way training is directly comparable to (and tested against) the
single-device model: same init, same math, same losses to fp tolerance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM,
                                                   _adamw_apply, _layer_norm,
                                                   _lr_at)
from deeplearning4j_tpu.parallel.sequence_parallel import dense_attention
from deeplearning4j_tpu.parallel.tensor_parallel import (
    _allreduce_identity_bwd, _identity_allreduce_bwd)

__all__ = ["TPTransformerLM"]


class TPTransformerLM:
    """Megatron-partitioned trainer for the TransformerLM family."""

    def __init__(self, mesh: Mesh, config: TransformerConfig,
                 axis: str = "model", data_axis: str = "data"):
        """1-D ``(model,)`` mesh → pure TP. 2-D ``(data, model)`` mesh →
        TP×DP: params sharded over ``model`` and replicated over ``data``
        (axes a spec doesn't name are replicated), batch sharded over
        ``data``, one gradient psum over ``data`` per step."""
        config.one_block("TPTransformerLM")
        if config.dropout:
            raise ValueError("TP trainer runs dropout-free (eval parity)")
        if config.block_size:
            raise ValueError(
                "TP trainer uses dense attention over local heads; "
                "block_size (flash recurrence) is not supported here")
        if config.kv_group > 1 or config.window:
            raise ValueError(
                "TP trainer re-derives the MHA qkv partitioning; GQA "
                "(kv_group > 1) and sliding window are not supported here")
        if config.pos_embed != "learned":
            raise ValueError("TP trainer assumes the learned wpe table")
        self.mesh = mesh
        if axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has no model axis {axis!r} (axes: "
                f"{mesh.axis_names}) — pass axis=<your name> or rename")
        extra = [a for a in mesh.axis_names if a not in (axis, data_axis)]
        if extra:
            raise ValueError(
                f"mesh axes {extra} are neither the model axis ({axis!r}) "
                f"nor the data axis ({data_axis!r}) — the batch would be "
                f"silently replicated over them")
        self.axis = axis
        self.N = mesh.shape[axis]
        self.data_axis = data_axis if data_axis in mesh.axis_names else None
        self.n_data = mesh.shape[data_axis] if self.data_axis else 1
        self.conf = config
        if config.n_heads % self.N:
            raise ValueError(
                f"n_heads {config.n_heads} must divide the model axis "
                f"({self.N}) — column slices must be head-aligned")
        if config.d_ff % self.N:
            raise ValueError(
                f"d_ff {config.d_ff} must divide the model axis ({self.N})")
        full = TransformerLM(config).init().params   # same init as 1-chip
        self.params = self._shard_params(full)
        self.opt_state = {
            "m": jax.tree.map(jnp.zeros_like, self.params),
            "v": jax.tree.map(jnp.zeros_like, self.params),
        }
        self.iteration = 0
        self.score_ = float("nan")
        self._specs = self._param_specs()
        self._step = None

    # ---- parameter layout ---------------------------------------------
    def _block_layout(self, bp):
        """(d, 3d) [q|k|v] concat → separate column-parallel Wq/Wk/Wv."""
        d = self.conf.d_model
        wq, wk, wv = (bp["qkv"][:, :d], bp["qkv"][:, d:2 * d],
                      bp["qkv"][:, 2 * d:])
        bq, bk, bv = (bp["qkv_b"][:d], bp["qkv_b"][d:2 * d],
                      bp["qkv_b"][2 * d:])
        return {
            "ln1_g": bp["ln1_g"], "ln1_b": bp["ln1_b"],
            "wq": wq, "wk": wk, "wv": wv, "bq": bq, "bk": bk, "bv": bv,
            "proj": bp["proj"], "proj_b": bp["proj_b"],
            "ln2_g": bp["ln2_g"], "ln2_b": bp["ln2_b"],
            "fc": bp["fc"], "fc_b": bp["fc_b"],
            "out": bp["out"], "out_b": bp["out_b"],
        }

    def _block_specs(self):
        col = P(None, self.axis)      # column-parallel weight
        colb = P(self.axis)           # its bias (per-column)
        row = P(self.axis, None)      # row-parallel weight
        rep = P()
        return {
            "ln1_g": rep, "ln1_b": rep,
            "wq": col, "wk": col, "wv": col,
            "bq": colb, "bk": colb, "bv": colb,
            "proj": row, "proj_b": rep,
            "ln2_g": rep, "ln2_b": rep,
            "fc": col, "fc_b": colb,
            "out": row, "out_b": rep,
        }

    def _param_specs(self):
        specs = {"wte": P(), "wpe": P(), "lnf_g": P(), "lnf_b": P()}
        for i in range(self.conf.n_layers):
            specs[f"b{i}"] = self._block_specs()
        return specs

    def _shard_params(self, full):
        out = {"wte": full["wte"], "wpe": full["wpe"],
               "lnf_g": full["lnf_g"], "lnf_b": full["lnf_b"]}
        for i in range(self.conf.n_layers):
            out[f"b{i}"] = self._block_layout(full[f"b{i}"])
        from deeplearning4j_tpu.parallel.sharding_core import place_tree
        return place_tree(self.mesh, out, self._param_specs())

    # ---- sharded forward ----------------------------------------------
    def _block_local(self, bp, x):
        """One block on THIS device's head/ff shard; two f→…→g regions."""
        c = self.conf
        B, T, d = x.shape
        h_local = c.n_heads // self.N
        hd = d // c.n_heads
        f = lambda a: _identity_allreduce_bwd(a, self.axis)
        g = lambda a: _allreduce_identity_bwd(a, self.axis)
        # LN stays OUTSIDE the f..g region: its output cotangent must be
        # the all-reduced (complete) one so LN param grads are exact
        hloc = f(_layer_norm(x, bp["ln1_g"], bp["ln1_b"]))
        q = hloc @ bp["wq"] + bp["bq"]          # (B, T, d/N) local heads
        k = hloc @ bp["wk"] + bp["bk"]
        v = hloc @ bp["wv"] + bp["bv"]
        split = lambda a: a.reshape(B, T, h_local, hd).transpose(0, 2, 1, 3)
        o = dense_attention(split(q), split(k), split(v), causal=True)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, d // self.N)
        part = o @ bp["proj"]                   # row-parallel partial
        x = x + g(part) + bp["proj_b"]
        hloc = f(_layer_norm(x, bp["ln2_g"], bp["ln2_b"]))
        h1 = jax.nn.gelu(hloc @ bp["fc"] + bp["fc_b"])   # (B, T, ff/N)
        part2 = h1 @ bp["out"]
        x = x + g(part2) + bp["out_b"]
        return x

    def _forward_local(self, params, tokens):
        c = self.conf
        T = tokens.shape[1]
        x = params["wte"][tokens] + params["wpe"][:T]
        cd = c.compute_dtype
        if cd:   # bf16 compute against f32 masters, like the 1-chip model
            x = x.astype(cd)
            params = jax.tree.map(
                lambda a: a.astype(cd)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
        for i in range(c.n_layers):
            blk = (jax.checkpoint(self._block_local) if c.remat
                   else self._block_local)
            x = blk(params[f"b{i}"], x)
        x = _layer_norm(x, params["lnf_g"], params["lnf_b"])
        return (x @ params["wte"].T).astype(jnp.float32)

    def _loss_local(self, params, tokens, targets):
        logits = self._forward_local(params, tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return nll.mean()

    # ---- training ------------------------------------------------------
    def _build_step(self):
        c = self.conf
        pspec = self._specs
        da, n_data = self.data_axis, self.n_data
        batch_spec = P(da, None) if da else P()

        def step(params, opt, it, tokens, targets):
            loss, grads = jax.value_and_grad(self._loss_local)(
                params, tokens, targets)
            # with the f/g conjugate ops in place, replicated-param grads
            # arrive complete and identical on every device; sharded-param
            # grads arrive shard-local — the update is device-local either
            # way (the same _adamw_apply as the 1-chip model and ViT).
            if da:
                # TP×DP: each data shard saw its own batch slice; grads of
                # the global-batch mean are the data-axis mean of the
                # per-shard-mean grads (equal shard sizes)
                grads = jax.tree.map(
                    lambda g: jax.lax.psum(g, da) / n_data, grads)
                loss = jax.lax.psum(loss, da) / n_data
            t = it + 1
            new_p, new_opt = _adamw_apply(c, params, grads, opt, t,
                                          _lr_at(c, t))
            return new_p, new_opt, t, loss

        sharded = shard_map(
            step, mesh=self.mesh,
            in_specs=(pspec, {"m": pspec, "v": pspec}, P(),
                      batch_spec, batch_spec),
            out_specs=(pspec, {"m": pspec, "v": pspec}, P(), P()),
            check_vma=False)
        return jax.jit(sharded, donate_argnums=(0, 1))

    def fit_batch(self, tokens, targets=None):
        tokens = jnp.asarray(tokens, jnp.int32)
        if targets is None:
            tokens, targets = tokens[:, :-1], tokens[:, 1:]
        else:
            targets = jnp.asarray(targets, jnp.int32)
        if self.data_axis and tokens.shape[0] % self.n_data:
            raise ValueError(
                f"batch {tokens.shape[0]} must be a multiple of the data "
                f"axis ({self.n_data})")
        sh = NamedSharding(self.mesh, P(self.data_axis, None)
                           if self.data_axis else P())
        tokens = jax.device_put(tokens, sh)
        targets = jax.device_put(targets, sh)
        if self._step is None:
            self._step = self._build_step()
        (self.params, self.opt_state, self.iteration,
         loss) = self._step(self.params, self.opt_state, self.iteration,
                            tokens, targets)
        self.score_ = loss   # device scalar, synced lazily on read
        return self.score_

    # ---- introspection -------------------------------------------------
    def shard_fraction(self) -> float:
        """Per-device fraction of total parameter elements (→ ~(rep +
        sharded/N)/total — the TP memory claim, testable)."""
        total = per_dev = 0
        for a in jax.tree.leaves(self.params):
            total += a.size
            per_dev += int(np.prod(a.sharding.shard_shape(a.shape)))
        return per_dev / total

    def gathered_logits(self, tokens):
        """Full-model logits for parity checks (no update)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        if getattr(self, "_fwd", None) is None:   # compile once, not per call
            self._fwd = jax.jit(shard_map(
                self._forward_local, mesh=self.mesh,
                in_specs=(self._specs, P()), out_specs=P(),
                check_vma=False))
        return np.asarray(self._fwd(self.params, tokens))
