"""Mixture-of-Experts TransformerLM — switch-routed FFN blocks.

BEYOND-reference capability (the reference has neither attention nor
MoE): every ``moe_every``-th block's dense FFN is replaced by a top-1
switch layer — E expert MLPs, softmax gate, tokens routed to their
argmax expert and combined weighted by the gate probability, plus the
Switch-Transformer load-balancing auxiliary loss
``E * Σ_e f_e · P_e`` (f_e = fraction of tokens routed to expert e,
P_e = mean gate probability of e).

This single-device model computes routing DENSELY (every expert runs
every token, the one-hot combine selects) — exact top-1 semantics with
no capacity drops, the parity oracle for the expert-parallel trainer
(``parallel.ep_transformer.EPTransformerLM``) whose ``all_to_all``
dispatch must reproduce it. Attention, AdamW, decay discipline, lr
schedule, and the fit/listener surface are all inherited from
``TransformerLM`` (the MoE FFN threads through ``_block_apply``'s
``ffn`` seam).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM,
                                                   _block_apply,
                                                   _forward_tokens)

__all__ = ["MoETransformerConfig", "MoETransformerLM"]


@dataclass
class MoETransformerConfig(TransformerConfig):
    n_experts: int = 4
    moe_every: int = 2          # every k-th block is MoE (1 = all blocks)
    d_expert: int = 0           # expert hidden width; 0 = d_ff
    aux_weight: float = 0.01    # Switch load-balance loss weight
    router_top_k: int = 1       # 1 = Switch; 2 = GShard top-2 combine

    def __post_init__(self):
        super().__post_init__()
        # the Switch family's layers are told apart by ``moe_every`` alone
        self.one_block("the Switch-routed MoE family (MoETransformerLM, "
                       "EPTransformerLM)")
        if self.n_experts < 2:
            raise ValueError("need at least 2 experts")
        if self.moe_every < 1:
            raise ValueError("moe_every must be >= 1")
        if not 1 <= self.router_top_k <= self.n_experts:
            raise ValueError(
                f"router_top_k {self.router_top_k} must be in "
                f"[1, n_experts={self.n_experts}]")

    def is_moe_layer(self, i: int) -> bool:
        """Blocks moe_every-1, 2*moe_every-1, ... are MoE (the GShard
        every-other-layer placement for moe_every=2)."""
        return (i + 1) % self.moe_every == 0


def moe_ffn_dense(bp, h, n_experts, top_k=1):
    """Exact top-k routed FFN, densely computed: every expert processes
    every token, the weighted k-hot combine selects the routed ones.
    top_k=1 is Switch (raw top probability as the combine weight);
    top_k>=2 is the GShard combine (top-k probabilities renormalized to
    sum 1). Returns (output, aux_loss)."""
    probs = jax.nn.softmax((h @ bp["gate"]).astype(jnp.float32), axis=-1)
    hid = jnp.einsum("btd,edh->beth", h, bp["W1"]) \
        + bp["W1_b"][None, :, None, :]
    hid = jax.nn.gelu(hid)
    out = jnp.einsum("beth,ehd->betd", hid, bp["W2"]) \
        + bp["W2_b"][None, :, None, :]
    if top_k == 1:
        eid = jnp.argmax(probs, axis=-1)                   # (B, T)
        onehot = jax.nn.one_hot(eid, n_experts, dtype=probs.dtype)
        combine = onehot * jnp.max(probs, axis=-1)[..., None]
    else:
        topv, topi = jax.lax.top_k(probs, top_k)           # (B, T, k)
        w = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
        khot = jax.nn.one_hot(topi, n_experts, dtype=probs.dtype)
        combine = (khot * w[..., None]).sum(-2)            # (B, T, E)
        onehot = khot[..., 0, :]                           # first choice
    y = jnp.einsum("betd,bte->btd", out, combine.astype(out.dtype))
    # load-balance aux over first-choice assignments (Switch/GShard):
    # E * sum_e f_e * P_e over all tokens in the batch
    f = onehot.reshape(-1, n_experts).mean(axis=0)
    p = probs.reshape(-1, n_experts).mean(axis=0)
    aux = n_experts * jnp.sum(f * p)
    return y, aux


class MoETransformerLM(TransformerLM):
    """TransformerLM with switch-MoE FFN blocks."""

    def init(self):
        super().init()
        c = self.conf
        d = c.d_model
        h = c.d_expert or c.d_ff
        E = c.n_experts
        std = 0.02
        rs = std / math.sqrt(2 * c.n_layers)
        base = jax.random.PRNGKey(c.seed + 101)
        for i in range(c.n_layers):
            if not c.is_moe_layer(i):
                continue
            k1, k2, k3 = jax.random.split(jax.random.fold_in(base, i), 3)
            bp = self.params[f"b{i}"]
            for key in ("fc", "fc_b", "out", "out_b"):
                del bp[key]
            bp["gate"] = 0.1 * jax.random.normal(k1, (d, E))
            bp["W1"] = std * jax.random.normal(k2, (E, d, h))
            bp["W1_b"] = jnp.zeros((E, h))
            bp["W2"] = rs * jax.random.normal(k3, (E, h, d))
            bp["W2_b"] = jnp.zeros((E, d))
        self.params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                                   self.params)
        self.opt_state = {
            "m": jax.tree.map(jnp.zeros_like, self.params),
            "v": jax.tree.map(jnp.zeros_like, self.params),
        }
        return self

    # ---- forward with aux accumulation --------------------------------
    def _logits_aux(self, params, tokens, rng=None):
        c = self.conf
        rngs = (jax.random.split(rng, c.n_layers)
                if rng is not None and c.dropout > 0 else [None] * c.n_layers)
        auxes = []
        spec = c.one_block("MoETransformerLM")

        def moe_block(bp, xx, rr):
            """Block returning (x, aux) so the aux crosses the
            jax.checkpoint boundary as a real output (a closure-smuggled
            tracer would leak under remat)."""
            cell = {}

            def moe_ffn(bp2, hloc):
                y, aux = moe_ffn_dense(bp2, hloc, c.n_experts,
                                       c.router_top_k)
                cell["aux"] = aux
                return y

            out = _block_apply(c, bp, xx, spec, drop=self._drop, rng=rr,
                               ffn=moe_ffn, plan=self._shard_plan)
            return out, cell["aux"]

        def apply(i, bp, x):
            if c.is_moe_layer(i):
                blk = jax.checkpoint(moe_block) if c.remat else moe_block
                x, aux = blk(bp, x, rngs[i])
                auxes.append(aux)   # appended OUTSIDE the checkpoint
                return x
            blk = functools.partial(self._block, spec)
            return (jax.checkpoint(blk) if c.remat else blk)(bp, x, rngs[i])

        logits = _forward_tokens(c, params, tokens, apply)
        return logits, sum(auxes, jnp.float32(0.0))

    def _logits(self, params, tokens, rng=None):
        return self._logits_aux(params, tokens, rng)[0]

    def _loss(self, params, tokens, targets, mask, rng=None):
        logits, aux = self._logits_aux(params, tokens, rng)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        m = jnp.ones_like(nll) if mask is None else mask.astype(nll.dtype)
        ce = (nll * m).sum() / jnp.maximum(m.sum(), 1.0)
        return ce + self.conf.aux_weight * aux

    def eval_loss(self, tokens):
        """Held-out mean next-token NLL WITHOUT the aux term: the
        training objective includes the load-balance penalty, but
        held-out likelihood (and perplexity) must not."""
        tokens = jnp.asarray(tokens, jnp.int32)
        logits = self._logits(self.params, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, tokens[:, 1:][..., None], axis=-1)[..., 0]
        return float(nll.mean())

    # perplexity() inherits from the base and now exponentiates the pure
    # cross-entropy above
    eval_ce = eval_loss

    def generate(self, *a, **kw):
        raise NotImplementedError(
            "KV-cache generation is not implemented for the MoE family; "
            "use output() for scoring or the dense TransformerLM for "
            "sampling")
