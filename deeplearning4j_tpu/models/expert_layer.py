"""A routed expert layer that is told which experts it holds.

The layer of an expert-parallel deployment as ONE of its chips runs it: the
router scores every token over all ``n_experts``, picks ``top_k``, and this
chip computes exactly the part of

    y = [shared(h)] + scale * sum_{e in top_k(r)} w_e(r) expert_e(h)
    expert_e(h) = (act(h G_e) * (h U_e)) D_e

that its own experts ``held = (first, count)`` give. ``r`` is what the router
reads: ``h`` itself, or (``router_input="block"``) the block's normed input,
ahead of attention; ``w`` the ``top_k`` largest sigmoid scores normalised to
sum 1, or (``scoring="softmax"``) the softmax over the ``top_k`` chosen
logits; ``act`` SiLU (SwiGLU) or (``gate="relu"``) ReLU (ReGLU); ``scale``
defaults to 1, and the shared expert, which every chip computes alike, is
there only where ``d_shared`` is not 0. What the absent experts would add is
left out and the partial result goes on; nothing here stands in for the other
chips or for their exchange. With ``held = (0, n_experts)`` it is the whole
layer.

The layer is two halves with a seam between them. ``decide`` is the index
half: scores, top k, the sort by held expert, the group sizes and the
statistics (a ``Routing``). ``apply`` gathers the rows, runs the three
grouped products and adds each weighted row back to its token.
``expert_ffn`` is one after the other on one tensor; a block whose router
reads its input calls ``decide`` ahead of attention and hands the
``Routing`` to ``expert_ffn`` after it.

How: the ``tokens x top_k`` assignments are sorted by the expert they meet
(those that meet no held expert last), the first ``rows`` of them are
gathered into a static buffer, and the three products of the gated experts
run as grouped matrix products over the held experts
(``jax.lax.ragged_dot``: on a TPU the compiler's grouped-matmul kernel,
forward and backward), each row weighted and added back to its token. The
rows of the buffer that no assignment fills are rows of zeros in the last
expert's group.

**No token is dropped silently.** The buffer holds ``row_buffer`` times the
balanced load ``tokens * top_k * count / n_experts`` (never more than
``tokens * top_k``, where the layer is exact for every routing). Assignments
past it are left out AND counted: ``rows_over_buffer`` in the returned
statistics, which ``TransformerLM`` accumulates on the device and
``moe_counters()`` reads; the benchmark's driver fails every step of a window
in which it is not 0.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["Experts", "Routing", "decide", "apply", "expert_ffn", "glu",
           "STATS"]

# the statistics of one call, int32 scalars (summed over layers by the caller)
STATS = ("local_rows", "rows_computed", "rows_over_buffer", "peak_group_rows")

_GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}


@dataclass(frozen=True)
class Experts:
    """The expert layers of a model (``TransformerConfig.experts``)."""
    n_experts: int                         # the router's width
    top_k: int
    d_expert: int                          # a routed expert's gated width
    held: Optional[Tuple[int, int]] = None   # (first, count); None = all
    scale: float = 1.0                     # on the chosen weights, which sum to 1
    d_shared: int = 0                      # shared expert's width; 0 = none
    row_buffer: float = 2.0                # x the balanced load (see above)
    # the chosen experts' weights: "sigmoid" scores normalised to sum 1, or
    # the "softmax" over the chosen logits (= softmax over all, the top_k
    # largest, renormalised)
    scoring: str = "sigmoid"
    gate: str = "silu"                     # the experts' gate: "silu" | "relu"
    # what the router reads: "ffn", the tensor the experts read, or "block",
    # the block's normed input, so that the decision is there before attention
    router_input: str = "ffn"

    def __post_init__(self):
        first, count = self.held_range
        for name, value, known in (
                ("scoring", self.scoring, ("sigmoid", "softmax")),
                ("gate", self.gate, tuple(_GATES)),
                ("router_input", self.router_input, ("ffn", "block"))):
            if value not in known:
                raise ValueError(f"unknown {name} {value!r}")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} must be in "
                             f"[1, n_experts={self.n_experts}]")
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"experts held {self.held} lie outside "
                             f"[0, n_experts={self.n_experts})")
        if self.row_buffer <= 0:
            raise ValueError("row_buffer must be > 0")

    @property
    def held_range(self):
        return self.held if self.held is not None else (0, self.n_experts)

    def rows(self, tokens):
        """The static row buffer for ``tokens`` tokens: ``row_buffer`` times
        the balanced load, a multiple of 8, at most every assignment."""
        count = self.held_range[1]
        balanced = tokens * self.top_k * count / self.n_experts
        rows = 8 * math.ceil(self.row_buffer * balanced / 8)
        return min(rows, tokens * self.top_k)


class Routing(NamedTuple):
    """What ``decide`` hands ``apply``: the weight of every assignment, and
    which assignment fills which row of the buffer (``dispatch``)."""
    weights: jax.Array       # [N, top_k] float32
    order: jax.Array         # [rows] int32 into the flattened [N * top_k]
    valid: jax.Array         # [rows] bool: the row holds an assignment
    group_sizes: jax.Array   # [count] int32, summing to rows
    stats: dict              # STATS, int32 scalars


def glu(h, gate, up, down, act=jax.nn.silu):
    """A gated MLP, ``(act(h gate) * (h up)) down``: SwiGLU with SiLU."""
    return (act(h @ gate) * (h @ up)) @ down


def route(ex, h, router):
    """``(weights [N, top_k] float32, experts [N, top_k] int32)``: router
    logits in float32 and the ``top_k`` largest, weighted as ``ex.scoring``
    says (sigmoid scores normalised to sum 1, or the softmax over the chosen
    logits: both orders agree on who is chosen) and scaled. (Switch's top-1
    routing with a capacity is still ``moe_transformer.moe_ffn_dense``:
    ROADMAP S7.)"""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
    if ex.scoring == "softmax":
        top, chosen = jax.lax.top_k(logits, ex.top_k)
        return jax.nn.softmax(top, axis=-1) * ex.scale, chosen
    w, chosen = jax.lax.top_k(jax.nn.sigmoid(logits), ex.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
    return w * ex.scale, chosen


def dispatch(ex, chosen, tokens):
    """Which assignment fills which row of the buffer. ``chosen``: [N, top_k]
    expert ids. Returns ``(assignment [rows] int32 into the flattened
    [N * top_k], valid [rows] bool, group_sizes [count] int32 that sum to
    ``rows``, stats)``; rows are grouped by held expert, in the experts'
    order, the rows that hold no assignment last."""
    first, count = ex.held_range
    rows = ex.rows(tokens)
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)[:rows].astype(jnp.int32)
    # assignments that meet held expert e or one before it, for each e
    upto = (key[None, :] <= jnp.arange(count)[:, None]).sum(1, dtype=jnp.int32)
    ends = jnp.minimum(upto, rows)
    # The rows past the last assignment join the last held expert's group (as
    # rows of zeros, see apply): every row of the buffer then lies in a
    # group. What a grouped product leaves in rows outside every group is
    # undefined on the TPU (zeros on the CPU), and the backward pass would
    # scatter it into real tokens' gradients.
    group_sizes = jnp.diff(ends, prepend=0).at[-1].add(rows - ends[-1])
    local_rows = upto[-1]
    stats = {"local_rows": local_rows,
             "rows_computed": jnp.int32(rows),
             "rows_over_buffer": jnp.maximum(local_rows - rows, 0),
             # the fullest held expert's assignments, buffer or no buffer
             "peak_group_rows": jnp.diff(upto, prepend=0).max()}
    return order, jnp.arange(rows) < ends[-1], group_sizes, stats


def decide(ex, router, r):
    """The index half: ``r`` [N, d], what the router reads of the layer's N
    tokens, to their ``Routing``."""
    with jax.named_scope("block.router"):
        w, chosen = route(ex, r, router)
    with jax.named_scope("block.moe_dispatch"):
        return Routing(w, *dispatch(ex, chosen, r.shape[0]))


def apply(ex, ep, flat, routing):
    """The held experts on the tokens ``flat`` [N, d] as ``routing`` says,
    with the parameters ``ep``: ``W_gate``, ``W_up`` [count, d, d_expert],
    ``W_down`` [count, d_expert, d] of the held experts; ``sh_gate``,
    ``sh_up``, ``sh_down`` where there is a shared expert. Returns [N, d]."""
    scope = jax.named_scope
    w, order, valid, group_sizes, _ = routing
    act = _GATES[ex.gate]
    with scope("block.moe_dispatch"):
        token = order // ex.top_k
        # a row that holds no assignment is a row of zeros with weight 0:
        # 0 through an expert is 0, forward and backward
        rows = jnp.where(valid[:, None], flat[token], 0)
        w_rows = jnp.where(valid, w.reshape(-1)[order], 0.0)
    with scope("block.experts"):
        grouped = lambda a, b: jax.lax.ragged_dot(a, b, group_sizes)
        out = grouped(act(grouped(rows, ep["W_gate"]))
                      * grouped(rows, ep["W_up"]), ep["W_down"])
    with scope("block.moe_dispatch"):
        y = jnp.zeros(flat.shape, jnp.float32).at[token].add(
            out.astype(jnp.float32) * w_rows[:, None]).astype(flat.dtype)
    if ex.d_shared:
        with scope("block.shared_expert"):
            y = y + glu(flat, ep["sh_gate"], ep["sh_up"], ep["sh_down"], act)
    return y


def expert_ffn(ex, ep, h, routing=None):
    """The layer on ``h`` [B, T, d]: ``decide`` from ``ep["router"]`` [d,
    n_experts] and ``h``'s tokens (or the ``routing`` the caller decided
    earlier, from another tensor of the same tokens), then ``apply``.
    Returns ``(y, stats)``."""
    flat = h.reshape(-1, h.shape[-1])
    if routing is None:
        routing = decide(ex, ep["router"], flat)
    return apply(ex, ep, flat, routing).reshape(h.shape), routing.stats
