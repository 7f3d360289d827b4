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
half: scores, top k, the sort by held expert, the group sizes, each
assignment's row and the statistics (a ``Routing``). ``apply`` gathers the
rows, runs the three grouped products and adds each weighted row back to its
token. ``expert_ffn`` is one after the other on one tensor; a block whose
router reads its input calls ``decide`` ahead of attention and hands the
``Routing`` to ``expert_ffn`` after it.

How: the ``tokens x top_k`` assignments are sorted by the expert they meet
(those that meet no held expert last), the first ``rows`` of them fill a
static buffer, and the three products of the gated experts run as grouped
matrix products over the held experts, each row weighted and added back to
its token. The group sizes sum to the rows that hold an assignment, not to
the buffer: **the rows of the buffer that no assignment fills lie in no
group**, and on the TPU (and under the interpreter flag) everything follows
one walk over the row tiles that a group has a row in
(``ops/pallas_kernels.group_tiles``): the products, forward, input gradient
and weight gradient (``grouped_matmul``), and the rows around them.
``gather_rows`` fills those tiles, one DMA a row that holds an assignment and
zeros for the rest of a visited tile; ``combine_rows`` adds a token's weighted
rows in float32, fetching only the rows its assignments fill (``Routing.pos``;
an assignment without a row is skipped, not weighted by zero: ``0 * NaN``);
each is the other's transpose, so the backward moves no other row either.
**A row tile past the last group is never written, by the gather or by a
product, and never read**: what it holds is undefined, forward and backward
(``moe.rows_computed`` counts the rows of the walk). Off the TPU, without the
interpreter flag, the products are ``jax.lax.ragged_dot`` over the same group
sizes and the rows move by XLA's gather and float32 scatter-add over the
whole buffer, selected by ``valid`` on the way in and, after the weighting,
on the way out: the tests' oracle.

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

from deeplearning4j_tpu.ops.pallas_kernels import (combine_rows, gather_rows,
                                                   group_tiles,
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
    # [N, top_k] int32: the row an assignment fills, the inverse of ``order``
    # on the valid rows; -1 where it met no held expert or fell past the buffer
    pos: jax.Array
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
    [N * top_k], its inverse [N, top_k] int32 on the valid rows and -1
    elsewhere, valid [rows] bool, group_sizes [count] int32 that sum to the
    valid rows, stats, tiles)``; rows are grouped by held expert, in the
    experts' order, the rows that hold no assignment last and in no group.
    ``tiles`` is the walk of the Pallas kernels over the row tiles that hold
    a group's rows, where they run (``Routing.tiles``)."""
    first, count = ex.held_range
    rows = ex.rows(tokens)
    local = chosen.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    by_expert = jnp.argsort(key, stable=True).astype(jnp.int32)
    order = by_expert[:rows]
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
    valid = jnp.arange(rows) < ends[-1]
    # the inverse of the sort is a second sort (XLA's scatter of as many row
    # numbers takes five times as long on the chip)
    pos = jnp.argsort(by_expert).astype(jnp.int32)
    pos = jnp.where(pos < ends[-1], pos, -1).reshape(chosen.shape)
    return order, pos, valid, group_sizes, stats, tiles


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
    gather, grouped, combine = _along_the_walk(ex, flat, routing) \
        if routing.tiles is not None else _over_the_buffer(ex, flat, routing)
    act = _GATES[ex.gate]
    with scope("block.moe_dispatch"):
        # the one array, a reference a product: backward their cotangents
        # are summed where the rows are moved, not over the whole buffer
        for_gate, for_up = gather()
    with scope("block.experts"):
        out = grouped(act(grouped(for_gate, ep["W_gate"]))
                      * grouped(for_up, ep["W_up"]), ep["W_down"])
    with scope("block.moe_dispatch"):
        y = combine(out)
    if ex.d_shared:
        with scope("block.shared_expert"):
            y = y + glu(flat, ep["sh_gate"], ep["sh_up"], ep["sh_down"], act)
    return y


def _along_the_walk(ex, flat, routing):
    """``(gather, grouped product, combine)`` on the TPU: all three follow
    the walk ``routing.tiles`` over the row tiles that hold an assignment."""
    w, order, pos, _, _, _, tiles = routing
    row_tile = _row_tile(ex, flat.shape[0])
    # one DMA a row that holds an assignment; a token's sum over the rows
    # its assignments fill, in float32: an assignment without a row is
    # skipped, not weighted by zero
    return (lambda: gather_rows(flat, order, pos, tiles, row_tile, 2),
            lambda a, b: grouped_matmul(a, b, tiles, row_tile),
            lambda out: combine_rows(out, w, order, pos, tiles, row_tile))


def _over_the_buffer(ex, flat, routing):
    """The same three off the TPU, the tests' oracle: ``ragged_dot`` over the
    group sizes between XLA's gather and float32 scatter-add over the whole
    buffer. A row that holds no assignment lies in no group: what a product
    leaves there is undefined, so it is selected away by ``valid`` on the way
    in (which keeps the input gradient there from any token) and on the way
    out."""
    w, order, _, valid, group_sizes, _, _ = routing
    token = order // ex.top_k

    def combine(out):
        # selected after the weighting, a weight of 0 would not do (0 * NaN
        # is NaN). Backward the select zeroes the row's cotangent, and what
        # the row's weight then collects (0 * NaN) is dropped by the select
        # on the rows' weights.
        w_rows = jnp.where(valid, w.reshape(-1)[order], 0.0)
        weighted = jnp.where(
            valid[:, None], out.astype(jnp.float32) * w_rows[:, None], 0.0)
        return jnp.zeros(flat.shape, jnp.float32).at[token].add(
            weighted).astype(flat.dtype)

    return (lambda: (jnp.where(valid[:, None], flat[token], 0),) * 2,
            lambda a, b: jax.lax.ragged_dot(a, b, group_sizes), combine)


def expert_ffn(ex, ep, h, routing=None):
    """The layer on ``h`` [B, T, d]: ``decide`` from ``ep["router"]`` [d,
    n_experts] and ``h``'s tokens (or the ``routing`` the caller decided
    earlier, from another tensor of the same tokens), then ``apply``.
    Returns ``(y, stats)``."""
    flat = h.reshape(-1, h.shape[-1])
    if routing is None:
        routing = decide(ex, ep["router"], flat)
    return apply(ex, ep, flat, routing).reshape(h.shape), routing.stats
