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
run as grouped matrix products over the held experts, each row weighted and
added back to its token. The group sizes sum to the rows that hold an
assignment, not to the buffer: **the rows of the buffer that no assignment
fills lie in no group**, and the products (forward, input gradient, weight
gradient) visit only the row tiles that a group has a row in
(``ops/pallas_kernels.grouped_matmul``, a Pallas product whose grid is as
long as the groups' tiles; off the TPU, without the interpreter flag,
``jax.lax.ragged_dot`` over the same group sizes). What a product leaves in
the rows outside every group is undefined, so they are masked where they are
used: ``apply`` selects by ``valid`` on the way in and on the way out, which
also keeps a cotangent there from any token and any weight.

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

from deeplearning4j_tpu.ops.pallas_kernels import (group_tiles,
                                                   grouped_matmul,
                                                   grouped_row_tile,
                                                   pallas_supported)

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
    group_sizes: jax.Array   # [count] int32, summing to the valid rows
    stats: dict              # STATS, int32 scalars
    # the walk of the Pallas grouped products over the buffer's row tiles
    # (``pallas_kernels.group_tiles``); None where ``ragged_dot`` runs them
    tiles: Optional[jax.Array] = None


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
    the valid rows, stats, tiles)``; rows are grouped by held expert, in the
    experts' order, the rows that hold no assignment last and in no group.
    ``tiles`` is the walk of the Pallas products over the row tiles that hold
    a group's rows, where they run (``Routing.tiles``)."""
    first, count = ex.held_range
    rows = ex.rows(tokens)
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True)[:rows].astype(jnp.int32)
    # assignments that meet held expert e or one before it, for each e
    upto = (key[None, :] <= jnp.arange(count)[:, None]).sum(1, dtype=jnp.int32)
    ends = jnp.minimum(upto, rows)
    # The rows past the last assignment lie in no group: the products do not
    # visit their tiles. What a grouped product leaves in rows outside every
    # group is undefined on the TPU (zeros on the CPU): apply masks them by
    # ``valid`` where it uses them, forward and backward.
    group_sizes = jnp.diff(ends, prepend=0)
    local_rows = upto[-1]
    tiles, visited = None, ends[-1]
    if pallas_supported():
        row_tile = _row_tile(ex, tokens)
        tiles = group_tiles(group_sizes, rows, row_tile)
        visited = tiles[-1] * row_tile
    stats = {"local_rows": local_rows,
             # the rows the products visit: their row tiles, partial ones
             # whole; the groups' sum where ragged_dot runs them
             "rows_computed": visited,
             "rows_over_buffer": jnp.maximum(local_rows - rows, 0),
             # the fullest held expert's assignments, buffer or no buffer
             "peak_group_rows": jnp.diff(upto, prepend=0).max()}
    return order, jnp.arange(rows) < ends[-1], group_sizes, stats, tiles


def _row_tile(ex, tokens):
    """The grouped products' row tile, from a held expert's rows at an even
    load."""
    return grouped_row_tile(tokens * ex.top_k // ex.n_experts)


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
    w, order, valid, group_sizes, _, tiles = routing
    act = _GATES[ex.gate]
    with scope("block.moe_dispatch"):
        token = order // ex.top_k
        # a row that holds no assignment lies in no group: the select keeps
        # what the input gradient leaves there (undefined) from any token
        rows = jnp.where(valid[:, None], flat[token], 0)
        w_rows = jnp.where(valid, w.reshape(-1)[order], 0.0)
    with scope("block.experts"):
        if tiles is None:
            grouped = lambda a, b: jax.lax.ragged_dot(a, b, group_sizes)
        else:
            row_tile = _row_tile(ex, flat.shape[0])
            grouped = lambda a, b: grouped_matmul(a, b, tiles, row_tile)
        out = grouped(act(grouped(rows, ep["W_gate"]))
                      * grouped(rows, ep["W_up"]), ep["W_down"])
    with scope("block.moe_dispatch"):
        # ... and what the products leave there (undefined) from any token's
        # sum: selected after the weighting, a weight of 0 would not do
        # (0 * NaN is NaN). Backward the select zeroes the row's cotangent,
        # and what the row's weight then collects (0 * NaN) is dropped by
        # the select on w_rows above.
        weighted = jnp.where(valid[:, None],
                             out.astype(jnp.float32) * w_rows[:, None], 0.0)
        y = jnp.zeros(flat.shape, jnp.float32).at[token].add(
            weighted).astype(flat.dtype)
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
