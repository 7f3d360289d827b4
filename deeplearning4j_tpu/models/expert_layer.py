"""A routed expert layer that is told which experts it holds.

The layer of an expert-parallel deployment as ONE of its chips runs it: the
router scores every token over all ``n_experts``, picks ``top_k``, and this
chip computes exactly the part of

    y = shared(h) + scale * sum_{e in top_k(h)} w_e(h) expert_e(h)

that its own experts ``held = (first, count)`` give (plus the shared expert,
which every chip computes alike). What the absent experts would add is left
out and the partial result goes on; nothing here stands in for the other
chips or for their exchange. With ``held = (0, n_experts)`` it is the whole
layer.

How: the ``tokens x top_k`` assignments are sorted by the expert they meet
(those that meet no held expert last), the first ``rows`` of them are
gathered into a static buffer, and the three products of the SwiGLU experts
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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["Experts", "expert_ffn", "swiglu", "STATS"]

# the statistics of one call, int32 scalars (summed over layers by the caller)
STATS = ("local_rows", "rows_computed", "rows_over_buffer")


@dataclass(frozen=True)
class Experts:
    """The expert layers of a model (``TransformerConfig.experts``)."""
    n_experts: int                         # the router's width
    top_k: int
    d_expert: int                          # a routed expert's SwiGLU width
    held: Optional[Tuple[int, int]] = None   # (first, count); None = all
    scale: float = 1.0                     # on the chosen weights, which sum to 1
    d_shared: int = 0                      # shared expert's width; 0 = none
    row_buffer: float = 2.0                # x the balanced load (see above)

    def __post_init__(self):
        first, count = self.held_range
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


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def route(ex, h, router):
    """``(weights [N, top_k] float32, experts [N, top_k] int32)``: router
    logits in float32, sigmoid scores, the ``top_k`` largest, normalised to
    sum 1 and scaled. (The one scoring any caller has; Switch's softmax
    routing is still ``moe_transformer.moe_ffn_dense``: ROADMAP S7.)"""
    logits = jnp.dot(h, router, preferred_element_type=jnp.float32)
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
    # rows of zeros, see expert_ffn): every row of the buffer then lies in a
    # group. What a grouped product leaves in rows outside every group is
    # undefined on the TPU (zeros on the CPU), and the backward pass would
    # scatter it into real tokens' gradients.
    group_sizes = jnp.diff(ends, prepend=0).at[-1].add(rows - ends[-1])
    local_rows = upto[-1]
    stats = {"local_rows": local_rows,
             "rows_computed": jnp.int32(rows),
             "rows_over_buffer": jnp.maximum(local_rows - rows, 0)}
    return order, jnp.arange(rows) < ends[-1], group_sizes, stats


def expert_ffn(ex, ep, h):
    """The layer on ``h`` [B, T, d] with the parameters ``ep``: ``router``
    [d, n_experts]; ``W_gate``, ``W_up`` [count, d, d_expert], ``W_down``
    [count, d_expert, d] of the held experts; ``sh_gate``, ``sh_up``,
    ``sh_down`` where there is a shared expert. Returns ``(y, stats)``."""
    B, T, d = h.shape
    scope = jax.named_scope
    flat = h.reshape(B * T, d)
    with scope("block.router"):
        w, chosen = route(ex, flat, ep["router"])
    with scope("block.moe_dispatch"):
        order, valid, group_sizes, stats = dispatch(ex, chosen, B * T)
        token = order // ex.top_k
        # a row that holds no assignment is a row of zeros with weight 0:
        # 0 through an expert is 0, forward and backward
        rows = jnp.where(valid[:, None], flat[token], 0)
        w_rows = jnp.where(valid, w.reshape(-1)[order], 0.0)
    with scope("block.experts"):
        grouped = lambda a, b: jax.lax.ragged_dot(a, b, group_sizes)
        out = grouped(jax.nn.silu(grouped(rows, ep["W_gate"]))
                      * grouped(rows, ep["W_up"]), ep["W_down"])
    with scope("block.moe_dispatch"):
        y = jnp.zeros((B * T, d), jnp.float32).at[token].add(
            out.astype(jnp.float32) * w_rows[:, None]).astype(h.dtype)
    if ex.d_shared:
        with scope("block.shared_expert"):
            y = y + swiglu(flat, ep["sh_gate"], ep["sh_up"], ep["sh_down"])
    return y.reshape(B, T, d), stats
