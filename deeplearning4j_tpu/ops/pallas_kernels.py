"""Pallas TPU kernels for hot ops.

The reference accelerates its hot layers with hand-written cuDNN calls
(SURVEY §2.3); the TPU analog is Pallas kernels tiled for the MXU. Shipping
kernels: flash attention forward (fused QKᵀ → online softmax → V in VMEM,
grid over (batch·heads, query blocks), K/V streamed block-by-block with the
running-max/sum recurrence — no O(T²) score materialization in HBM) and the
matching FlashAttention-2-style backward (a dQ kernel streaming K/V blocks
and a dK/dV kernel streaming Q/dO blocks, both recomputing P from the
forward's saved logsumexp — nothing O(T²) is ever stored; the logsumexp
and the backward's delta cross the kernels' boundary one float32 a query
row, ``_stat_rows``). The grid of all
three is ``(rows of batch·heads, steps)``: the steps are the live (Q block,
K block) pairs of a row and no others (``flash_walk``; a block pair the
causal mask or the window leaves empty is no grid step), read from an int32
table that reaches the kernels and their index maps by scalar prefetch.
Inside a block the three share one step: ``flash_plan`` derives heads a
grid step and compute tiles from the shapes, ``_branches`` says which blocks
need a mask, ``_tiles`` which tiles of a block hold anything to compute; the
walk says which step of a row writes the accumulators and which one
finalises.

``DL4J_TPU_FLASH_BWD=scan`` falls the backward to the mathematically
identical lax.scan implementation
(``parallel/sequence_parallel.blockwise_attention``) via the same
custom_vjp seam (the previous default, kept as an escape hatch).

Off the TPU the kernels run only in interpreter mode, a test setting
(``DL4J_TPU_PALLAS_INTERPRET=1``); without it callers decline up front
(``pallas_supported``) and take the pure-JAX path. On a TPU the setting is
an error, so an interpreted kernel can never stand in for a compiled one.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.config import env_flag, env_str

NEG_INF = -1e30

# Grid steps of the flash kernels in the programs traced so far, summed over
# the call sites (a layer's forward is one, its backward two: dQ and dK/dV),
# each call's rows of n times the steps of its walk; and how many of them
# hold an in-mask entry. They move where a program is traced, never in a
# step. live / walked is 1 since the grid is the walk; under the rectangle
# grid it was 136/256 on a causal row of 16 blocks, 31/256 under a window
# of one block.
_STEPS_WALKED = obs.gauge(
    "flash.steps_walked", "Grid steps of the flash kernels traced so far")
_STEPS_LIVE = obs.gauge(
    "flash.steps_live", "Of flash.steps_walked, the steps on a block pair "
    "with an in-mask entry")
# Of the live steps of the calls WITH A WINDOW traced so far (rows of n times
# the walk's steps, as above), how many take the ``edge`` branch, every tile
# masked by position, and not the unmasked ``full`` one (``_branches``): all
# of them where the window is one block, 56 of 252 a row at a window of eight.
_WINDOW_LIVE = obs.gauge(
    "flash.window_steps_live", "Live grid steps of the flash kernels with a "
    "window traced so far")
_WINDOW_EDGE = obs.gauge(
    "flash.window_steps_edge", "Of flash.window_steps_live, the steps that "
    "mask every tile by position (the edge branch)")
# What lse and delta take in HBM where they cross the boundary of those
# kernels, from shapes: 4·n·T bytes a statistic a kernel (the forward writes
# lse; dQ and dK/dV each read lse and delta). It was 512·n·T while the
# forward and dQ kernels took them lane-replicated.
_STAT_BYTES = obs.gauge(
    "flash.stat_bytes", "Bytes of lse and delta crossing the boundary of "
    "the flash kernels traced so far")

# Per-query-row scalars (running max/sum, logsumexp, delta) live INSIDE the
# forward and dQ kernels as lane-replicated columns [rows, _LANES]: a tile
# of scores is [q, k], and a column broadcasts over its keys for nothing.
# They cross the kernels' boundary one float32 a row (``_stat_rows``); the
# turn between the two is made in VMEM, once a Q block.
_LANES = 128


def _interpret_mode():
    interpret = env_flag("DL4J_TPU_PALLAS_INTERPRET")
    if interpret and jax.default_backend() == "tpu":
        raise RuntimeError(
            "DL4J_TPU_PALLAS_INTERPRET is set on a TPU: interpreter mode is "
            "a CPU test setting and would replace the compiled kernels; "
            "unset it")
    return interpret


def pallas_supported():
    """True when the pallas path can run: on TPU, or interpreter forced.
    A backend that fails to initialise raises here, as it would anywhere."""
    return _interpret_mode() or jax.default_backend() == "tpu"


class FlashPlan(NamedTuple):
    """What one grid step of the flash kernels works on, beyond the
    ``(block_q, block_k)`` block the DMA moves: ``heads`` rows of n
    (batch·heads) a step, and the ``(tile_q, tile_k)`` tile of scores the
    products and the softmax are computed on inside the block."""
    heads: int
    tile_q: int
    tile_k: int


# what the hungriest kernel's blocks and scratch may take of a core's VMEM
# (16 MiB scoped by default on a v5e; a raised limit read slower there), the
# tiles' temporaries left aside
_VMEM_BUDGET = 8 * 2 ** 20
# the rows of a step are unrolled: each is a copy of the step's code
_MAX_HEADS = 8


def _flash_vmem_bytes(heads, block_q, block_k, d, itemsize):
    """VMEM of the hungriest of the three kernels, the dQ one: every input
    and output block twice (the pipeline double-buffers them), lse and
    delta among them at one float32 a row, and its float32 scratch once:
    the accumulator and the two statistics turned into lane-replicated
    columns (the forward holds m and l the same way and one block less)."""
    q_like = block_q * d * itemsize            # q, dO, dQ
    k_like = block_k * d * itemsize            # k, v
    stat_rows = block_q * 4                    # lse, delta as they arrive
    stat_cols = block_q * _LANES * 4           # and as the tiles take them
    return heads * (2 * (3 * q_like + 2 * k_like + 2 * stat_rows)
                    + 2 * stat_cols + block_q * d * 4)


def _tile(block):
    return 256 if block % 256 == 0 else block


def flash_plan(n, t, d, itemsize, block_q, block_k, kv_group=1):
    """The ``FlashPlan`` of a call, from its shapes alone (every reading:
    PERF.md, PR 27, a v5e). Which blocks a row's grid steps visit is the
    other half of a call's statics: ``flash_walk``.

    Tiles: 256 on a side of the block that divides by it, else the whole
    side -- the step the kernels took before they had tiles. A [512, 512]
    float32 tile of scores is four register files that live in VMEM
    between every two vector passes, and a causal block on the diagonal
    leaves out the tiles above it; 128-tiles leave out more and read
    slower (more, smaller products).

    Heads: a grid step has a fixed cost, and the rows of a step fill each
    other's waits, so where a row of n is one or two blocks several rows
    share a step: the largest power of two that divides n, up to
    ``_MAX_HEADS``, whose blocks stay inside ``_VMEM_BUDGET``.
    Grouped-query attention keeps one row a step: its K/V index map
    serves one K/V head a step."""
    heads = 1
    if kv_group == 1 and t // block_q <= 2 and t // block_k <= 2:
        while (n % (2 * heads) == 0 and 2 * heads <= _MAX_HEADS
               and _flash_vmem_bytes(2 * heads, block_q, block_k, d,
                                     itemsize) <= _VMEM_BUDGET):
            heads *= 2
    return FlashPlan(heads, _tile(block_q), _tile(block_k))


def _keep(offset, shape, window, transposed=False):
    """The in-mask entries of a tile of scores: q_pos >= k_pos, and with
    ``window`` q_pos - k_pos < window. ``offset`` is q_pos - k_pos of the
    tile's first entry (an int, or a traced scalar); queries run along dim
    0, along dim 1 where ``transposed``."""
    q_dim, k_dim = (1, 0) if transposed else (0, 1)
    dist = (offset + jax.lax.broadcasted_iota(jnp.int32, shape, q_dim)
            - jax.lax.broadcasted_iota(jnp.int32, shape, k_dim))
    keep = dist >= 0
    if window is not None:
        keep &= dist < window
    return keep


def _block_live(qi, kb, block_q, block_k, window):
    """Whether a (q-block, k-block) pair has any in-mask entry: some
    k ≤ q (causal), and with a window, some q − k < window."""
    live = kb * block_k < (qi + 1) * block_q
    if window is not None:
        live &= qi * block_q + 1 - window < (kb + 1) * block_k
    return live


def _block_full(qi, kb, block_q, block_k, window):
    """Whether every pair of a live (q-block, k-block) pair is in the mask:
    the block lies under the diagonal and, with a window, inside it."""
    full = (kb + 1) * block_k - 1 <= qi * block_q
    if window is not None:
        full &= (qi + 1) * block_q - 1 - kb * block_k < window
    return full


class FlashWalk(NamedTuple):
    """The grid steps of one row of n (batch·heads) in one of the flash
    kernels: step ``s`` works on Q block ``q[s]`` and K block ``k[s]``;
    bit 0 of ``flags[s]`` says that the step is the first of its outer
    block (it writes the accumulators, the others add to them), bit 1 that
    it is the last (it finalises). int32 arrays, one entry a step.
    ``live`` is the number of pairs of the row's rectangle that hold an
    in-mask entry: every one of them is walked, once, and nothing else, so
    ``steps == live``."""
    q: np.ndarray
    k: np.ndarray
    flags: np.ndarray
    live: int

    @property
    def steps(self):
        return len(self.q)

    @property
    def table(self):
        """What the kernels are handed: ``q``, ``k`` and ``flags`` end to
        end, so step ``s`` reads entries ``s``, ``steps + s`` and
        ``2 * steps + s``."""
        return np.concatenate([self.q, self.k, self.flags])

    @property
    def single(self):
        """Every outer block is one step: nothing to carry between steps."""
        return bool((self.flags == _FIRST | _LAST).all())


_FIRST, _LAST = 1, 2
# The table lives in SMEM for the whole call, 12 bytes a step: this many
# steps are 768 KiB of the v5e's 1 MiB (tests/test_aot_compile.py compiles a
# walk of this size for the chip). A causal row of 361 blocks (T 184,832 at
# block 512) or a non-causal one of 256 fits; a window's walk grows with T
# alone. Past it ``flash_walk`` raises and names the way out, larger blocks:
# nothing walks dead blocks instead.
MAX_WALK_STEPS = 2 ** 16


@functools.lru_cache(maxsize=64)
def flash_walk(causal, window, block_q, block_k, t, inner):
    """The ``FlashWalk`` of a row of n, from the statics of a call alone.

    ``inner`` "k" (forward, dQ): Q block by Q block, the live K blocks of
    each ascending -- the order in which the rectangle's live steps came,
    so the sums keep their order. ``inner`` "q" (dK/dV): K block by K block,
    its live Q blocks ascending. Every outer block has a live pair (the one
    on the diagonal), so every output block is written."""
    n_qb, n_kb = t // block_q, t // block_k
    live = (_block_live(np.arange(n_qb)[:, None], np.arange(n_kb)[None, :],
                        block_q, block_k, window) if causal
            else np.ones((n_qb, n_kb), bool))
    if inner == "k":
        q, k = np.nonzero(live)
        outer = q
    else:
        k, q = np.nonzero(live.T)
        outer = k
    if len(q) > MAX_WALK_STEPS:
        raise ValueError(
            f"flash attention at T {t} in blocks of {block_q} x {block_k} "
            f"walks {len(q)} block pairs a row, more than the "
            f"{MAX_WALK_STEPS} its table holds: use larger blocks")
    edge = np.flatnonzero(np.diff(outer)) + 1      # where a new outer starts
    assert len(edge) + 1 == (n_qb if inner == "k" else n_kb)
    flags = np.zeros(len(q), np.int32)
    flags[np.r_[0, edge]] |= _FIRST
    flags[np.r_[edge - 1, len(q) - 1]] |= _LAST
    q, k = q.astype(np.int32), k.astype(np.int32)
    for shared in (q, k, flags):       # one walk serves every call: cached
        shared.setflags(write=False)
    return FlashWalk(q, k, flags, np.count_nonzero(live))


def _branches(qi, kb, first, *, causal, block_q, block_k, window, n_qb,
              inner):
    """The ``(kind, first, condition)`` branches a grid step chooses from
    (a condition of None: always); every step of the walk is a live block
    and takes exactly one. ``first`` is whether the step is the first of
    its outer block (``FlashWalk``), which writes the accumulators where
    the others add to them (so nothing zero-fills them): a traced boolean,
    or None where every outer block is one step. ``inner`` names the blocks
    the steps of an outer block walk, "k" or "q".

    full  every pair of the block is in the mask: no mask is built
    diag  the block on the diagonal of square blocks without a window: the
          mask is the same in every such block, and tiles above the
          diagonal are left out
    edge  any other block that the diagonal or the window's edge crosses:
          every tile masked by its positions in the sequence"""
    aligned = causal and window is None and block_q == block_k
    if not causal:
        kinds = [("full", None)]
    elif aligned:
        kinds = ([("diag", None)] if n_qb == 1 else
                 [("diag", kb == qi), ("full", kb < qi)])
    else:
        full = _block_full(qi, kb, block_q, block_k, window)
        kinds = [("full", full), ("edge", jnp.logical_not(full))]
    firsts = ([(True, None)] if first is None else
              [(True, first), (False, jnp.logical_not(first))])

    def possible(kind, is_first):
        if not aligned or first is None:
            return True
        if inner == "q":    # a K block meets its diagonal block first
            return is_first == (kind == "diag")
        # K block 0 is first; a full block after it takes 3 blocks a row
        return is_first or kind == "diag" or n_qb > 2

    def both(a, b):
        return b if a is None else a if b is None else a & b

    return [(kind, is_first, both(c, when)) for kind, c in kinds
            for is_first, when in firsts if possible(kind, is_first)]


def _tiles(kind, block_q, block_k, plan):
    """``[(q0, k0, masked)]``: the tiles of a block of ``kind`` that hold an
    in-mask entry, by their first row and column inside the block."""
    out = []
    for q0 in range(0, block_q, plan.tile_q):
        for k0 in range(0, block_k, plan.tile_k):
            if kind == "diag":
                if k0 > q0 + plan.tile_q - 1:
                    continue
                masked = k0 + plan.tile_k - 1 > q0
            else:
                masked = kind == "edge"
            out.append((q0, k0, masked))
    return out


def _strips(tiles, by):
    """``tiles`` grouped by their first row (``by`` 0) or column (1):
    ``[(start, [(other start, masked)])]``."""
    strips = {}
    for tile in tiles:
        strips.setdefault(tile[by], []).append((tile[1 - by], tile[2]))
    return sorted(strips.items())


def _offset(kind, qi, kb, block_q, block_k, q0, k0):
    """q_pos - k_pos of a tile's first entry: inside a diagonal block the
    block's own position cancels."""
    local = q0 - k0
    return local if kind == "diag" else qi * block_q - kb * block_k + local


def _run_branches(branches, heads, fn):
    """``fn(h, kind, first)`` for each of the step's rows of n, under the
    branch that holds. The rows are unrolled, not looped over: they share
    nothing, and in one basic block the scheduler fills one row's waits (an
    MXU result, a lane reduction) with the next row's work."""
    from jax.experimental import pallas as pl

    def step(kind, first):
        for h in range(heads):
            fn(h, kind, first)

    for kind, first, condition in branches:
        if condition is None:
            step(kind, first)
        else:
            pl.when(condition)(functools.partial(step, kind, first))


def _step(table, steps, single):
    """``(qi, kb, first, last)`` of this grid step, read from the walk's
    table in SMEM (``FlashWalk.table``); ``first`` and ``last`` are None
    where every outer block is one step."""
    from jax.experimental import pallas as pl

    s = pl.program_id(1)
    qi, kb = table[s], table[steps + s]
    if single:
        return qi, kb, None, None
    flags = table[2 * steps + s]
    return qi, kb, (flags & _FIRST) != 0, (flags & _LAST) != 0


def _lanes(x, width):
    """A per-row statistic held lane-replicated as [rows, _LANES], as
    [rows, width] (or [rows, 1] to broadcast, where width is no multiple of
    the lanes)."""
    reps, rem = divmod(width, _LANES)
    if rem:
        return x[:, :1]
    return x if reps == 1 else jnp.tile(x, (1, reps))


def _stat_rows(n, t, heads, block_q):
    """How a per-row statistic (lse, delta) crosses the boundary of all
    three kernels: ``(shape, block, index map)`` of one float32 a query row,
    ``[n, T / block_q, 1, block_q]`` in blocks of this step's ``heads`` rows
    of n by one Q block (the map on ``(row, Q block, K block)``, as
    ``_walk_call`` takes it). A block's last two dims equal the array's, so
    Mosaic takes it at any block size, and a [1, block_q] row of it is
    block_q floats in HBM and in VMEM: nothing is replicated, and the array
    the forward kernel writes is the one the two backward kernels read."""
    return ((n, t // block_q, 1, block_q), (heads, 1, 1, block_q),
            lambda b, qi, kb: (b, qi, 0, 0))


def _stat_row(ref, h, q0, tq):
    """The [1, tq] piece from column ``q0`` of the step's head ``h`` in a
    block of ``_stat_rows``."""
    from jax.experimental import pallas as pl

    return ref[h, 0, :, pl.ds(q0, tq)]


def _as_row(col):
    """A column of statistics, [rows, 1] or lane-replicated [rows, _LANES],
    as the [1, rows] row the boundary holds (``_stat_rows``): the diagonal
    of the column spread over the lanes, 128 rows at a time -- a select
    and a sum over sublanes that adds zeros, so the values are the
    column's own bits. (A transpose of the replicated column is the same
    row and read slower in the forward: PERF.md, PR 33.)"""
    rows = col.shape[0]
    group = _LANES if rows % _LANES == 0 else rows
    if col.shape[1] not in (1, group):     # replicated wider than a group
        col = col[:, :1]
    diagonal = (jax.lax.broadcasted_iota(jnp.int32, (group, group), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (group, group), 1))
    pieces = [jnp.where(diagonal, col[r0:r0 + group], 0.0).sum(
        axis=0, keepdims=True) for r0 in range(0, rows, group)]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=1)


def _as_column(row):
    """A [1, rows] row of statistics as the lane-replicated column
    [rows, _LANES] that broadcasts over the keys of a [q, k] tile: the
    row on every sublane, transposed."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


_NT = (((1,), (1,)), ((), ()))     # a @ bᵀ
_NN = (((1,), (0,)), ((), ()))     # a @ b


def _dot(a, b, dims):
    """Operands as they come (bfloat16 tiles go to the MXU as bfloat16),
    float32 accumulation."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _flash_kernel(table, q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, plan,
                  block_q, block_k, n_qb, steps, single, causal, scale,
                  window=None):
    """One (``plan.heads`` rows of batch·head, step of the walk) grid step.
    The innermost grid dimension walks the live K/V blocks of a Q block
    sequentially on the same core, so the VMEM scratch accumulators
    (running max m, running sum l, unnormalized output) persist across it —
    only one K/V block is VMEM-resident at a time, which is what keeps T
    unbounded (the full-K/V variant OOMs VMEM at T≈8k). Where every Q block
    has one live K/V block (``single``) there is nothing to carry and no
    scratch.

    Inside the block a strip of ``plan.tile_q`` query rows takes ONE
    online-softmax update over all its live tiles. Scores, m, l and the
    accumulator are float32; P meets V in V's dtype.

    m and l are kept lane-replicated as [heads, block_q, _LANES]; the
    logsumexp they end in leaves as one float32 a row (``_stat_rows``),
    turned where the Q block is finalised."""
    from jax.experimental import pallas as pl

    qi, kb, first, last = _step(table, steps, single)
    tq, tk = plan.tile_q, plan.tile_k
    if not single:
        m_scr, l_scr, acc_scr = scratch

    def _compute(h, kind, first):
        for q0, row in _strips(_tiles(kind, block_q, block_k, plan), 0):
            rows = pl.ds(q0, tq)
            q = q_ref[h, rows, :] * scale              # [tq, d]
            s, v = [], []
            for k0, masked in row:
                s_t = _dot(q, k_ref[h, pl.ds(k0, tk), :], _NT)   # [tq, tk]
                if masked:
                    s_t = jnp.where(
                        _keep(_offset(kind, qi, kb, block_q, block_k, q0,
                                      k0), s_t.shape, window), s_t, NEG_INF)
                s.append(s_t)
                v.append(v_ref[h, pl.ds(k0, tk), :])
            m_cur = functools.reduce(jnp.maximum, s).max(
                axis=-1, keepdims=True)                # [tq, 1]
            if first:
                m_new = m_tile = m_cur
            else:
                m_prev = m_scr[h, rows, :]             # [tq, 128], lanes equal
                m_new = jnp.maximum(m_prev, m_cur)     # broadcast over lanes
                m_tile = _lanes(m_new, tk)
                correction = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF,
                                               m_prev - m_new))
            p = [jnp.exp(s_t - m_tile) for s_t in s]
            l_cur = functools.reduce(jnp.add, p).sum(axis=-1, keepdims=True)
            pv = functools.reduce(jnp.add, [
                _dot(p_t.astype(v_t.dtype), v_t, _NN)
                for p_t, v_t in zip(p, v)])            # [tq, d]
            if single:
                o_ref[h, rows, :] = (
                    pv / jnp.maximum(l_cur, 1e-30)).astype(o_ref.dtype)
                lse_ref[h, 0, :, rows] = _as_row(_lse(m_new, l_cur))
            elif first:
                m_scr[h, rows, :] = jnp.broadcast_to(m_new, (tq, _LANES))
                l_scr[h, rows, :] = jnp.broadcast_to(l_cur, (tq, _LANES))
                acc_scr[h, rows, :] = pv
            else:
                m_scr[h, rows, :] = m_new
                l_scr[h, rows, :] = l_scr[h, rows, :] * correction + l_cur
                acc_scr[h, rows, :] = (acc_scr[h, rows, :]
                                       * correction[:, :1] + pv)

    _run_branches(_branches(qi, kb, first, causal=causal, block_q=block_q,
                            block_k=block_k, window=window, n_qb=n_qb,
                            inner="k"),
                  plan.heads, _compute)

    if not single:
        @pl.when(last)
        def _finalize():
            for h in range(plan.heads):
                l_fin = l_scr[h]                       # [block_q, 128]
                o_ref[h] = (acc_scr[h] / jnp.maximum(l_fin[:, :1], 1e-30)
                            ).astype(o_ref.dtype)
                lse_ref[h, 0] = _as_row(_lse(m_scr[h], l_fin))


def _lse(m, l):
    """The logsumexp residual for the backward's P recomputation, a column
    like m and l. A fully masked row (l == 0; only padded rows can hit
    this) gets +LARGE so exp(s - lse) underflows to an exact 0 instead of
    NaN."""
    return jnp.where(l > 0.0, m + jnp.log(jnp.maximum(l, 1e-30)), -NEG_INF)


def _walk_call(kernel, walk, rows, *, in_specs, out_specs, out_shape,
               scratch_shapes, interpret):
    """The ``pallas_call`` of ``kernel`` over the grid ``(rows, steps of
    the walk)``. The walk's table is prefetched into SMEM ahead of the
    arrays (one operand: each costs a call a copy of its own): the kernel
    takes it as its first ref with ``steps`` and ``single`` as statics
    (``_step``). A spec is ``(block shape, index map)``, the map written on
    ``(row, Q block, K block)`` and handed the step's two here; every block
    lives in VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    steps = walk.steps

    def spec(shape, index):
        return pl.BlockSpec(
            shape, lambda b, s, table: index(b, table[s], table[steps + s]),
            memory_space=pltpu.VMEM)

    call = pl.pallas_call(
        functools.partial(kernel, steps=steps, single=walk.single),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows, steps),
            in_specs=[spec(*x) for x in in_specs],
            out_specs=[spec(*x) for x in out_specs],
            scratch_shapes=scratch_shapes),
        interpret=interpret)
    return functools.partial(call, walk.table)


def _q_block(b, qi, kb):
    """Index map of a block of Q-like rows: the step's Q block."""
    return b, qi, 0


def _k_block(b, qi, kb):
    return b, kb, 0


def _kv_block(kv_group):
    """Index map of a block of K or V: the step's K block of the K/V head
    that the row's group of ``kv_group`` query heads shares."""
    return lambda b, qi, kb: (b // kv_group, kb, 0)


def _statics(q, causal, block_q, block_k, window, kv_group, inners):
    """What the kernels are built from besides the arrays, read where the
    call is made: ``_flash_forward`` and ``_flash_backward`` are traced once
    for each value of it. ``inners`` names the walks of the kernels this
    call site adds to the program ("k": forward or dQ, "q": dK/dV), for the
    gauges: they count here, once a call site, and not inside the shared
    trace. The forward ("k" alone) has one statistic at its boundary, lse;
    each kernel of the backward ("kq") has two, lse and delta."""
    n, t, d = q.shape
    plan = flash_plan(n, t, d, q.dtype.itemsize, block_q, block_k, kv_group)
    for inner in inners:
        walk = flash_walk(causal, window, block_q, block_k, t, inner)
        counts = [(_STEPS_WALKED, walk.steps), (_STEPS_LIVE, walk.live)]
        if causal and window is not None:
            edge = np.count_nonzero(~_block_full(walk.q, walk.k, block_q,
                                                 block_k, window))
            counts += [(_WINDOW_LIVE, walk.live), (_WINDOW_EDGE, edge)]
        for gauge, steps in counts:
            gauge.set(gauge.value + n // plan.heads * steps)
    statistics = 1 if inners == "k" else 2
    _STAT_BYTES.set(_STAT_BYTES.value + len(inners) * statistics * 4 * n * t)
    return dict(causal=causal, block_q=block_q, block_k=block_k,
                window=window, kv_group=kv_group, interpret=_interpret_mode(),
                plan=plan)


# Traced once for each shape and inlined where it is called (``inline``: no
# call, no name of its own in the name stack): the unrolled layers of a
# model then share one trace of the kernels, and JAX lowers equations with
# the same parameters once for all of them. Without it a step of 24 layers
# traced and lowered the three kernels 24 times, 77 s of a run's set-up.
_trace_once = functools.partial(
    jax.jit, inline=True,
    static_argnames=("causal", "block_q", "block_k", "window", "kv_group",
                     "plan", "interpret"))


@_trace_once
def _flash_forward(q, k, v, *, causal, block_q, block_k, window, kv_group,
                   plan, interpret):
    """q: [n, T, d]; k/v: [n // kv_group, T, d] (n = batch·q-heads).
    ``kv_group`` > 1 is grouped-query attention: consecutive runs of
    kv_group query heads share one K/V head, mapped by the BlockSpec
    index (no materialized repeat). T must divide by the blocks."""
    from jax.experimental.pallas import tpu as pltpu

    n, t, d = q.shape
    hb = plan.heads
    walk = flash_walk(causal, window, block_q, block_k, t, "k")
    kernel = functools.partial(
        _flash_kernel, plan=plan, block_q=block_q, block_k=block_k,
        n_qb=t // block_q, causal=causal, scale=1.0 / (d ** 0.5),
        window=window)
    kv_index = _kv_block(kv_group)
    stat_shape, stat_block, stat_index = _stat_rows(n, t, hb, block_q)
    scratch = [] if walk.single else [
        pltpu.VMEM((hb, block_q, _LANES), jnp.float32),   # running max
        pltpu.VMEM((hb, block_q, _LANES), jnp.float32),   # running sum
        pltpu.VMEM((hb, block_q, d), jnp.float32),        # unnormalized out
    ]
    return _walk_call(
        kernel, walk, n // hb,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(stat_shape, jnp.float32)],   # lse
        in_specs=[((hb, block_q, d), _q_block),
                  ((hb, block_k, d), kv_index),
                  ((hb, block_k, d), kv_index)],
        out_specs=[((hb, block_q, d), _q_block),
                   (stat_block, stat_index)],
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)


def _flash_dq_kernel(table, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                     dq_ref, *scratch, plan, block_q, block_k, n_qb, steps,
                     single, causal, scale, window=None):
    """dQ pass: for a fixed Q block, stream its live K/V blocks (the steps
    of the walk) and accumulate dQ = Σ_kb dS @ K, with P recomputed from
    the saved logsumexp (FlashAttention-2 eq. 12-16), tile by tile. The
    softmax scale rides in q for the scores, as in the forward, and meets
    dQ once, as it is written.

    lse and delta arrive as rows, one float32 a query (``_stat_rows``), and
    the [q, k] tiles want them as columns: the block's index does not
    change along a Q block's steps, so its first step turns both
    (``_as_column``) into VMEM scratch and the others read that; where
    every Q block is one step (``single``) a strip turns its own piece."""
    from jax.experimental import pallas as pl

    qi, kb, first, last = _step(table, steps, single)
    tq, tk = plan.tile_q, plan.tile_k
    if not single:
        dq_scr, lse_scr, delta_scr = scratch

    def _compute(h, kind, first):
        if first and not single:
            # the statistics' block stays through the Q block's steps: the
            # turn is made in its first and kept for the others
            turned = [_as_column(ref[h, 0]) for ref in (lse_ref, delta_ref)]
            lse_scr[h], delta_scr[h] = turned
        for q0, row in _strips(_tiles(kind, block_q, block_k, plan), 0):
            rows = pl.ds(q0, tq)
            q = q_ref[h, rows, :] * scale              # [tq, d]
            g = g_ref[h, rows, :]                      # [tq, d] dO
            if single:
                lse, delta = (_as_column(_stat_row(ref, h, q0, tq))
                              for ref in (lse_ref, delta_ref))
            elif first:
                lse, delta = (x[q0:q0 + tq] for x in turned)
            else:
                lse, delta = lse_scr[h, rows, :], delta_scr[h, rows, :]
            lse = _lanes(lse, tk)                      # [tq, tk]
            delta = _lanes(delta, tk)                  # rowsum(dO*O)
            dq = None
            for k0, masked in row:
                k_t = k_ref[h, pl.ds(k0, tk), :]       # [tk, d]
                s = _dot(q, k_t, _NT)                  # [tq, tk]
                if masked:
                    s = jnp.where(
                        _keep(_offset(kind, qi, kb, block_q, block_k, q0,
                                      k0), s.shape, window), s, NEG_INF)
                p = jnp.exp(s - lse)
                dp = _dot(g, v_ref[h, pl.ds(k0, tk), :], _NT)
                ds = p * (dp - delta)
                part = _dot(ds.astype(k_t.dtype), k_t, _NN)      # [tq, d]
                dq = part if dq is None else dq + part
            if single:
                dq_ref[h, rows, :] = (dq * scale).astype(dq_ref.dtype)
            elif first:
                dq_scr[h, rows, :] = dq
            else:
                dq_scr[h, rows, :] += dq

    _run_branches(_branches(qi, kb, first, causal=causal, block_q=block_q,
                            block_k=block_k, window=window, n_qb=n_qb,
                            inner="k"),
                  plan.heads, _compute)

    if not single:
        @pl.when(last)
        def _finalize():
            dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(table, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *scratch, plan, block_q, block_k, n_qb,
                      steps, single, causal, scale, window=None):
    """dK/dV pass: for a fixed K/V block, stream its live Q/dO blocks (the
    steps of the walk); dV = Σ_qb Pᵀ dO, dK = Σ_qb dSᵀ Q. The tiles are
    computed transposed, keys along dim 0 (Sᵀ = K Qᵀ, dPᵀ = V dOᵀ), so that
    both sums are plain products with nothing to turn, and lse and delta
    broadcast over the keys as the rows they arrive as (``_stat_rows``)."""
    from jax.experimental import pallas as pl

    qi, kb, first, last = _step(table, steps, single)
    tq, tk = plan.tile_q, plan.tile_k
    if not single:
        dk_scr, dv_scr = scratch

    def _compute(h, kind, first):
        for k0, col in _strips(_tiles(kind, block_q, block_k, plan), 1):
            rows = pl.ds(k0, tk)
            k_t = k_ref[h, rows, :]                    # [tk, d]
            v_t = v_ref[h, rows, :]
            dk = dv = None
            for q0, masked in col:
                q = q_ref[h, pl.ds(q0, tq), :] * scale           # [tq, d]
                g = g_ref[h, pl.ds(q0, tq), :]
                lse = _stat_row(lse_ref, h, q0, tq)    # [1, tq]
                delta = _stat_row(delta_ref, h, q0, tq)
                s = _dot(k_t, q, _NT)                  # [tk, tq]
                if masked:
                    s = jnp.where(
                        _keep(_offset(kind, qi, kb, block_q, block_k, q0,
                                      k0), s.shape, window, transposed=True),
                        s, NEG_INF)
                p = jnp.exp(s - lse)
                dp = _dot(v_t, g, _NT)                 # [tk, tq]
                ds = p * (dp - delta)
                dv_part = _dot(p.astype(g.dtype), g, _NN)        # [tk, d]
                dk_part = _dot(ds.astype(q.dtype), q, _NN)       # q carries scale
                dv = dv_part if dv is None else dv + dv_part
                dk = dk_part if dk is None else dk + dk_part
            if single:
                dk_ref[h, rows, :] = dk.astype(dk_ref.dtype)
                dv_ref[h, rows, :] = dv.astype(dv_ref.dtype)
            elif first:
                dk_scr[h, rows, :] = dk
                dv_scr[h, rows, :] = dv
            else:
                dk_scr[h, rows, :] += dk
                dv_scr[h, rows, :] += dv

    _run_branches(_branches(qi, kb, first, causal=causal, block_q=block_q,
                            block_k=block_k, window=window, n_qb=n_qb,
                            inner="q"),
                  plan.heads, _compute)

    if not single:
        @pl.when(last)
        def _finalize():
            dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
            dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_3d(q, k, v, causal, block_q, block_k, window=None,
                        kv_group=1):
    out, _lse = _flash_forward(
        q, k, v,
        **_statics(q, causal, block_q, block_k, window, kv_group, "k"))
    return out


# The two residuals of a flash call that only the forward kernel can produce,
# as ``jax.ad_checkpoint.checkpoint_name`` names them: a ``jax.checkpoint``
# whose policy saves these names keeps them and runs no forward kernel in its
# backward (``models/transformer._remat``); under no policy a name is nothing.
FLASH_RESIDUALS = ("flash_out", "flash_lse")


def _flash_fwd(q, k, v, causal, block_q, block_k, window=None, kv_group=1):
    out, lse = _flash_forward(
        q, k, v,
        **_statics(q, causal, block_q, block_k, window, kv_group, "k"))
    # what the kernel wrote is what is named: one float32 a row
    out, lse = map(checkpoint_name, (out, lse), FLASH_RESIDUALS)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, window, kv_group, residuals, g):
    if env_str("DL4J_TPU_FLASH_BWD") == "scan":
        # escape hatch: the rematerializing lax.scan backward (dense
        # oracle when a window is set — the scan has no window support).
        # GQA rides jnp.repeat, whose adjoint sums the group back down.
        from deeplearning4j_tpu.parallel.sequence_parallel import (
            blockwise_attention, dense_attention)
        q, k, v = residuals[:3]

        def rep(x):
            return jnp.repeat(x, kv_group, axis=0) if kv_group > 1 else x
        if window is not None:
            _, vjp = jax.vjp(
                lambda a, b, c: dense_attention(a, rep(b), rep(c),
                                                causal=causal,
                                                window=window), q, k, v)
        else:
            _, vjp = jax.vjp(
                lambda a, b, c: blockwise_attention(a, rep(b), rep(c),
                                                    causal=causal,
                                                    block_size=block_k),
                q, k, v)
        return vjp(g)
    return _flash_backward(
        *residuals, g,
        **_statics(residuals[0], causal, block_q, block_k, window, kv_group,
                   "kq"))


@_trace_once
def _flash_backward(q, k, v, out, lse, g, *, causal, block_q, block_k, window,
                    kv_group, plan, interpret):
    """dQ, dK, dV of ``_flash_forward`` from its residuals and dO."""
    from jax.experimental.pallas import tpu as pltpu

    n, t, d = q.shape
    hb = plan.heads
    static = dict(plan=plan, block_q=block_q, block_k=block_k,
                  n_qb=t // block_q, causal=causal, scale=1.0 / (d ** 0.5),
                  window=window)
    stat_shape, stat_block, stat_index = _stat_rows(n, t, hb, block_q)
    # delta_i = Σ_d dO ⊙ O — a cheap fused elementwise+reduce; XLA keeps it
    # out of the kernels' VMEM budget and writes it as lse came. The dK/dV
    # kernel, whose tiles are [k, q], broadcasts the rows as they are; the
    # dQ kernel turns them into columns in VMEM
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = delta.reshape(stat_shape)
    kv_index = _kv_block(kv_group)

    walk = flash_walk(causal, window, block_q, block_k, t, "k")
    dq, = _walk_call(
        functools.partial(_flash_dq_kernel, **static), walk, n // hb,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
        in_specs=[((hb, block_q, d), _q_block),
                  ((hb, block_k, d), kv_index),
                  ((hb, block_k, d), kv_index),
                  ((hb, block_q, d), _q_block),
                  (stat_block, stat_index),
                  (stat_block, stat_index)],
        out_specs=[((hb, block_q, d), _q_block)],
        scratch_shapes=[] if walk.single else [
            pltpu.VMEM((hb, block_q, d), jnp.float32),        # dQ
            pltpu.VMEM((hb, block_q, _LANES), jnp.float32),   # lse, turned
            pltpu.VMEM((hb, block_q, _LANES), jnp.float32)],  # delta, turned
        interpret=interpret,
    )(q, k, v, g, lse, delta)

    # dK/dV walk K block by K block. With GQA the kernel accumulates PER
    # Q-HEAD (output shaped like q); the group-sum down to the kv heads
    # happens outside — revisiting one output block from different
    # outer-grid steps would race.
    walk = flash_walk(causal, window, block_q, block_k, t, "q")
    dk, dv = _walk_call(
        functools.partial(_flash_dkv_kernel, **static), walk, n // hb,
        out_shape=[jax.ShapeDtypeStruct((n, t, d), k.dtype),
                   jax.ShapeDtypeStruct((n, t, d), v.dtype)],
        in_specs=[((hb, block_q, d), _q_block),
                  ((hb, block_k, d), kv_index),
                  ((hb, block_k, d), kv_index),
                  ((hb, block_q, d), _q_block),
                  (stat_block, stat_index),
                  (stat_block, stat_index)],
        out_specs=[((hb, block_k, d), _k_block),
                   ((hb, block_k, d), _k_block)],
        scratch_shapes=[] if walk.single else [
            pltpu.VMEM((hb, block_k, d), jnp.float32),
            pltpu.VMEM((hb, block_k, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, g, lse, delta)
    if kv_group > 1:
        dk = dk.astype(jnp.float32).reshape(
            n // kv_group, kv_group, t, d).sum(1).astype(k.dtype)
        dv = dv.astype(jnp.float32).reshape(
            n // kv_group, kv_group, t, d).sum(1).astype(v.dtype)
    return dq, dk, dv


_flash_attention_3d.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=False, block_q=512, block_k=512,
                    window=None):
    """Pallas flash attention. q: [..., T, d]; exact softmax attention.

    k/v may carry FEWER heads than q (grouped-query attention): with head
    axis -3, q [..., Hq, T, d] against k/v [..., Hkv, T, d] where
    Hq % Hkv == 0 — consecutive runs of Hq/Hkv query heads share a K/V
    head via the kernel's BlockSpec index map (no materialized repeat,
    and dK/dV group-sum on the backward).

    Pads T to the block size; leading dims are collapsed into the grid.
    Differentiable (pallas FlashAttention-2 backward; DL4J_TPU_FLASH_BWD=scan
    for the rematerializing fallback). ``window`` (requires causal) limits
    each query to the last ``window`` positions — sliding-window attention.

    The blocks are what the DMA moves. The grid walks the block pairs that
    hold an in-mask entry and no others (``flash_walk``): a block above the
    diagonal or wholly outside the window is no grid step in the forward,
    the dQ or the dK/dV kernel, so compute and grid scale O(T·window)
    instead of O(T²/2), and a causal row takes n(n+1)/2 steps of its n²
    pairs. A row may walk up to ``MAX_WALK_STEPS`` pairs (a causal T of
    184,832 at block 512); past it the call raises and asks for larger
    blocks. What a grid step does inside a block (heads a step, compute
    tiles, which blocks build a mask) follows from the shapes:
    ``flash_plan``. Products take their
    operands in the inputs' dtype (bfloat16 in, bfloat16 operands) and
    accumulate in float32; the softmax statistics are float32. What is
    kept for the backward besides q, k, v and the output is the logsumexp,
    one float32 a query row as the forward kernel wrote it
    (``[n, T / block_q, 1, block_q]``, ``FLASH_RESIDUALS``); it and the
    backward's delta reach both backward kernels in that form
    (``_stat_rows``): no lane-replicated ``[n, T, 128]`` copy of either
    exists outside the kernels, at any block size. Measured on
    a v5e (PERF.md, PR 27; bfloat16, d 64, causal, forward + dQ + dK/dV
    device ms a call): [128, 1024, 64] at block 512 1.44 (2.38 before PR
    27), [512, 256, 64] at block 256 0.64 (1.56), [64, 1024, 64] at block
    128 3.59, five times block 512's time a row: small blocks pay per grid
    step; with the statistics a value a row (PR 33) a call and the XLA
    passes around it read 1.75 ms where 2.07 and 0.92 where 1.41 (host
    clock). No route through the lax.scan path has been timed on a chip.
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        window = int(window)
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    orig_shape = q.shape
    t = q.shape[-2]
    d = q.shape[-1]
    n_q = int(np.prod(q.shape[:-2], dtype=np.int64)) if q.shape[:-2] else 1
    n_kv = int(np.prod(k.shape[:-2], dtype=np.int64)) if k.shape[:-2] else 1
    if n_q % n_kv:
        raise ValueError(f"q heads {q.shape[:-2]} not a multiple of "
                         f"k/v heads {k.shape[:-2]}")
    kv_group = n_q // n_kv
    if kv_group > 1 and (k.ndim < 3 or q.shape[:-3] != k.shape[:-3]
                         or q.shape[-3] % k.shape[-3]):
        raise ValueError("GQA requires identical batch dims and the head "
                         f"axis at -3: q {q.shape} vs k {k.shape}")
    block_q = min(block_q, max(8, t))
    block_k = min(block_k, max(8, t))

    pad_q = (-t) % block_q
    pad_k = (-t) % block_k
    pad = max(pad_q, pad_k)

    def prep(x):
        x = x.reshape((-1, t, d))
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad), (0, 0)])
        return x

    q3, k3, v3 = prep(q), prep(k), prep(v)
    if pad and not causal:
        # padded keys must not attend: shift their scores to -inf by giving
        # them a key vector that produces NEG_INF bias — simplest correct
        # route is the causal=False masked fallback below
        from deeplearning4j_tpu.parallel.sequence_parallel import \
            blockwise_attention
        if kv_group > 1:
            k = jnp.repeat(k, kv_group, axis=-3)
            v = jnp.repeat(v, kv_group, axis=-3)
        out = blockwise_attention(q, k, v, causal=False, block_size=block_k)
        return out
    out = _flash_attention_3d(q3, k3, v3, causal, block_q, block_k, window,
                              kv_group)
    if pad:
        out = out[:, :t]
    return out.reshape(orig_shape)


# ---------------------------------------------------------------------------
# grouped matrix products over the row tiles that hold a group's rows
# (``models/expert_layer.apply``): rows sorted by group, group e's rows
# against ``w[e]``. The grid's row axis is a walk like the flash kernels':
# one step a (row tile, group) pair that share a row, read from an int32
# table by scalar prefetch, and as long as the pairs there are, a value
# computed from the group sizes (the scheme of
# ``jax.experimental.pallas.ops.tpu.megablox``). A row tile that lies in no
# group is never fetched and never written: what the forward and the input
# gradient leave there is undefined, and the weight gradient does not read
# it.
# ---------------------------------------------------------------------------

_ROW_TILE = obs.gauge(
    "moe.row_tile", "Rows of a tile of the Pallas grouped expert products "
    "traced last (not set where ragged_dot runs them, off the TPU)")

# A product's blocks (double-buffered), accumulator and float32 result may
# take this much of a core's VMEM: under the 16 MiB scoped by default on a
# v5e, with room for what Mosaic adds (tests/test_aot_compile.py compiles the
# cells' widths for the described chip).
_GROUPED_VMEM = 12 * 2 ** 20
_MAX_ROW_TILE = 256


def grouped_row_tile(even_group):
    """The row tile of the grouped products for groups of ``even_group``
    rows at an even load: the largest power of two that leaves an even group
    at least two tiles (a group meets a partial tile at each end, and visits
    it whole), from 8 to 256. At 128 rows a tile of a [2560, 768] weight is
    128 FLOP a byte of weight fetched, under the v5e's 240; at 256 the MXU
    bounds it, and on a v5e 512 read no faster on groups of 1536 rows and
    slower on groups of 512 (PERF.md, PR 35)."""
    tile = 8
    while tile * 2 <= min(even_group // 2, _MAX_ROW_TILE):
        tile *= 2
    return tile


def group_tiles(group_sizes, rows, row_tile):
    """The walk of the grouped products over ``rows`` rows in tiles of
    ``row_tile``: an int32 table ``[offsets (groups + 1) | group (V) | tile
    (V) | steps (1)]`` with ``V = ceil(rows / row_tile) + groups - 1`` the
    most steps there can be. Step ``s < steps`` works on the rows of
    ``group[s]`` that lie in row tile ``tile[s]``; a group of no rows takes
    one step (of no rows: its weight gradient is written, as zeros), any
    other one a tile it has a row in, in order, so a tile that two groups
    share is visited by consecutive steps. ``group_sizes`` may sum to fewer
    than ``rows``: the tiles past the last group are no step."""
    groups = group_sizes.shape[0]
    group_sizes = group_sizes.astype(jnp.int32)
    most = _most_steps(rows, row_tile, groups)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first = starts // row_tile
    steps_of = jnp.where(group_sizes > 0,
                         (ends - 1) // row_tile - first + 1, 1)
    upto = jnp.cumsum(steps_of)
    group = jnp.repeat(jnp.arange(groups, dtype=jnp.int32), steps_of,
                       total_repeat_length=most)
    tile = first[group] + jnp.arange(most, dtype=jnp.int32) \
        - (upto - steps_of)[group]
    tile = jnp.clip(tile, 0, -(-rows // row_tile) - 1)
    _ROW_TILE.set(row_tile)
    return jnp.concatenate([jnp.zeros(1, jnp.int32), ends, group, tile,
                            upto[-1:]]).astype(jnp.int32)


def _most_steps(rows, row_tile, groups):
    return -(-rows // row_tile) + groups - 1


def _step_group(table, groups, s):
    """The group of step ``s`` of the walk ``table`` (``group_tiles``)."""
    return table[groups + 1 + s]


def _step_tile(table, groups, most, s):
    return table[groups + 1 + most + s]


class _GroupedStep(NamedTuple):
    """What a grid step of a grouped product reads from the table."""
    group: jax.Array
    lo: jax.Array        # the group's first row, and one past its last
    hi: jax.Array
    row0: jax.Array      # the tile's first row
    inside: jax.Array    # the whole tile lies in the group: nothing to mask


def _grouped_step(table, s, groups, most, row_tile):
    g = _step_group(table, groups, s)
    lo, hi = table[g], table[g + 1]
    row0 = _step_tile(table, groups, most, s) * row_tile
    return _GroupedStep(g, lo, hi, row0,
                        (lo <= row0) & (row0 + row_tile <= hi))


def _rows_in_group(step, shape):
    row = step.row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (row >= step.lo) & (row < step.hi)


def _gmm_kernel(table, a_ref, w_ref, o_ref, *scratch, groups, most, row_tile,
                k_steps, dims):
    """``o[tile rows of the group] = a[those rows] @ w[group]`` (``dims``
    _NN) or ``@ w[group]ᵀ`` (_NT), accumulated in float32 over the
    ``k_steps`` blocks of the contraction and rounded once. Rows of the tile
    outside the group keep what the tile held: the step before wrote them if
    they are another group's, and nothing did if they are no group's."""
    from jax.experimental import pallas as pl

    step = _grouped_step(table, pl.program_id(1), groups, most, row_tile)
    ki = pl.program_id(2)

    def finish(acc):
        @pl.when(step.inside)
        def _whole():
            o_ref[...] = acc.astype(o_ref.dtype)

        @pl.when(jnp.logical_not(step.inside))
        def _part():
            o_ref[...] = jnp.where(_rows_in_group(step, acc.shape),
                                   acc.astype(o_ref.dtype), o_ref[...])

    @pl.when(step.hi > step.lo)
    def _compute():
        part = _dot(a_ref[...], w_ref[...], dims)
        if k_steps == 1:
            finish(part)
            return
        acc_ref, = scratch

        @pl.when(ki == 0)
        def _first():
            acc_ref[...] = part

        @pl.when(ki > 0)
        def _later():
            acc_ref[...] += part

        @pl.when(ki == k_steps - 1)
        def _last():
            finish(acc_ref[...])


_TN = (((0,), (0,)), ((), ()))     # aᵀ @ b


def _tgmm_kernel(table, a_ref, g_ref, o_ref, acc_ref, *, groups, most,
                 row_tile):
    """``o[group] = Σ over the group's row tiles of a[rows]ᵀ @ g[rows]``: the
    steps of a group are consecutive, the first writes the float32
    accumulator, the last rounds it into the output block. Rows of a tile
    outside the group are zeroed in BOTH operands: the other group's are
    finite, what lies past the last group need not be."""
    from jax.experimental import pallas as pl

    s = pl.program_id(2)
    step = _grouped_step(table, s, groups, most, row_tile)
    first = jnp.logical_or(
        s == 0,
        _step_group(table, groups, jnp.maximum(s - 1, 0)) != step.group)
    last = jnp.logical_or(
        s == pl.num_programs(2) - 1,
        _step_group(table, groups, jnp.minimum(s + 1, most - 1))
        != step.group)

    def add(part):
        @pl.when(first)
        def _first():
            acc_ref[...] = part

        @pl.when(jnp.logical_not(first))
        def _later():
            acc_ref[...] += part

    @pl.when(step.inside)
    def _whole():
        add(_dot(a_ref[...], g_ref[...], _TN))

    @pl.when(jnp.logical_not(step.inside))
    def _part():
        a, g = a_ref[...], g_ref[...]
        a = jnp.where(_rows_in_group(step, a.shape), a, jnp.zeros_like(a))
        g = jnp.where(_rows_in_group(step, g.shape), g, jnp.zeros_like(g))
        add(_dot(a, g, _TN))

    @pl.when(last)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _blocks(width):
    """The blocks a ``width``-wide axis may be cut into, widest first: the
    whole axis, then the multiples of 128 lanes that divide it."""
    return [width] + [b for b in range(width - width % 128, 0, -128)
                      if b < width and width % b == 0]


def grouped_plan(row_tile, k, n, itemsize):
    """``(contraction block, width block)`` of a forward or input-gradient
    product ``[row_tile, k] x [k, n]`` and ``(k block, n block)`` of the
    weight gradient's ``[k, n]`` output, from the widths: the widest that
    fit ``_GROUPED_VMEM``. The product keeps the whole contraction in one
    block where it can and cuts the width first: a group's weight block is
    then fetched once a group and not once a row tile, and nothing is
    accumulated across steps. The weight gradient cuts its longer axis."""
    def product_bytes(bk, bn):
        blocks = 2 * itemsize * (row_tile * bk + bk * bn + row_tile * bn)
        return blocks + 4 * row_tile * bn * (1 if bk == k else 2)

    def gradient_bytes(bk, bn):
        return (4 + 2 * itemsize) * bk * bn \
            + 2 * itemsize * row_tile * (bk + bn)

    product = next(((bk, bn) for bk in _blocks(k) for bn in _blocks(n)
                    if product_bytes(bk, bn) <= _GROUPED_VMEM), None)
    gradient = max(((bk, bn) for bk in _blocks(k) for bn in _blocks(n)
                    if gradient_bytes(bk, bn) <= _GROUPED_VMEM),
                   key=lambda b: (b[0] * b[1], min(b)), default=None)
    if product is None or gradient is None:
        raise ValueError(
            f"no blocks of a [{row_tile}, {k}] x [{k}, {n}] grouped product "
            f"fit {_GROUPED_VMEM} bytes of VMEM")
    return product, gradient


@functools.partial(
    jax.jit, inline=True,
    static_argnames=("row_tile", "transposed", "blocks", "interpret"))
def _gmm(a, w, table, *, row_tile, transposed, blocks, interpret):
    """``a`` [m, k] against ``w`` [groups, k, n] (``transposed``: [groups, n,
    k]) to [m, n], over the walk ``table``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = a.shape
    groups = w.shape[0]
    n = w.shape[1] if transposed else w.shape[2]
    bk, bn = blocks
    most = _most_steps(m, row_tile, groups)
    k_steps = k // bk

    def tile_of(s, table):
        return _step_tile(table, groups, most, s)

    if transposed:
        w_spec = pl.BlockSpec(
            (None, bn, bk),
            lambda j, s, ki, t: (_step_group(t, groups, s), j, ki))
    else:
        w_spec = pl.BlockSpec(
            (None, bk, bn),
            lambda j, s, ki, t: (_step_group(t, groups, s), ki, j))
    kernel = functools.partial(
        _gmm_kernel, groups=groups, most=most, row_tile=row_tile,
        k_steps=k_steps, dims=_NT if transposed else _NN)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn, table[-1], k_steps),
            in_specs=[
                pl.BlockSpec((row_tile, bk),
                             lambda j, s, ki, t: (tile_of(s, t), ki)),
                w_spec],
            out_specs=pl.BlockSpec((row_tile, bn),
                                   lambda j, s, ki, t: (tile_of(s, t), j)),
            scratch_shapes=[] if k_steps == 1 else [
                pltpu.VMEM((row_tile, bn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret)(table, a, w)


@functools.partial(
    jax.jit, inline=True,
    static_argnames=("row_tile", "groups", "blocks", "interpret"))
def _tgmm(a, g, table, *, row_tile, groups, blocks, interpret):
    """``a`` [m, k] and ``g`` [m, n] to [groups, k, n]: each group's
    ``a[rows]ᵀ @ g[rows]``, over the walk ``table``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = a.shape
    n = g.shape[1]
    bk, bn = blocks
    most = _most_steps(m, row_tile, groups)

    def tile_of(s, table):
        return _step_tile(table, groups, most, s)

    kernel = functools.partial(_tgmm_kernel, groups=groups, most=most,
                               row_tile=row_tile)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), a.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // bk, n // bn, table[-1]),
            in_specs=[
                pl.BlockSpec((row_tile, bk),
                             lambda i, j, s, t: (tile_of(s, t), i)),
                pl.BlockSpec((row_tile, bn),
                             lambda i, j, s, t: (tile_of(s, t), j))],
            out_specs=pl.BlockSpec(
                (None, bk, bn),
                lambda i, j, s, t: (_step_group(t, groups, s), i, j)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)(table, a, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(a, w, table, row_tile):
    """Rows ``a`` [m, k], sorted by group, against their group's ``w[e]``
    [groups, k, n], to [m, n]: ``jax.lax.ragged_dot`` over the walk
    ``table = group_tiles(group_sizes, m, row_tile)``, which visits only the
    row tiles that hold a group's rows. Forward, input gradient (the same
    product against ``w[e]ᵀ``) and weight gradient (grouped along the
    contracted rows) take their operands as they come and accumulate in
    float32. **Rows outside every group are undefined in the result and in
    the input gradient** (never written: mask them where they are used), and
    are not read by the weight gradient, NaN or not."""
    _, k, n = w.shape
    blocks, _ = grouped_plan(row_tile, k, n, a.dtype.itemsize)
    return _gmm(a, w, table, row_tile=row_tile, transposed=False,
                blocks=blocks, interpret=_interpret_mode())


def _grouped_fwd(a, w, table, row_tile):
    return grouped_matmul(a, w, table, row_tile), (a, w, table)


def _grouped_bwd(row_tile, residuals, g):
    a, w, table = residuals
    groups, k, n = w.shape
    itemsize, interpret = a.dtype.itemsize, _interpret_mode()
    da = _gmm(g, w, table, row_tile=row_tile, transposed=True,
              blocks=grouped_plan(row_tile, n, k, itemsize)[0],
              interpret=interpret)
    dw = _tgmm(a, g, table, row_tile=row_tile, groups=groups,
               blocks=grouped_plan(row_tile, k, n, itemsize)[1],
               interpret=interpret)
    return da, dw.astype(w.dtype), None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


# ---------------------------------------------------------------------------
# the rows around those products, along the same walk
# (``models/expert_layer.apply``): ``gather_rows`` fills the row tiles the
# walk visits with their tokens' rows, ``combine_rows`` adds each token's
# weighted rows back, and each is the other's transpose. One row is one DMA:
# XLA's gather and scatter-add have static shapes and pay for every row of
# the buffer, these pay for the rows that hold an assignment. Mosaic slices
# a tiled array by whole tiles only, and half a 32-bit sublane (one bfloat16
# row) not at all, so a source crosses the boundary as whole words with the
# row as a leading dimension (``_row_words``: ``[n, 1, words]`` uint32, a row
# contiguous in HBM) and is turned back in VMEM.
# ---------------------------------------------------------------------------

# what the combine's fetched rows (one buffer, not double-buffered) may take
# of a core's VMEM; its output blocks and float32 sums take about as much
_COMBINE_VMEM = 4 * 2 ** 20
_MAX_TOKEN_TILE = 256
# rows of a tile turned from fetched words into lanes at a time: what the
# kernels hold in flight is a chunk's, not a tile's
_ROW_CHUNK = 32


def _packs(dtype, d):
    """Two bfloat16 columns ride in one 32-bit word where each half of the
    row is whole lane tiles (both cells' widths are)."""
    return dtype == jnp.bfloat16 and d % (2 * _LANES) == 0


def _words_a_row(dtype, d):
    return d // 2 if _packs(jnp.dtype(dtype), d) else d


def _words_kernel(*refs, d, sources):
    """A tile of rows ``[tile, d]``, the sum of the ``sources`` (rounded to
    their dtype, as XLA's own sum of them would be), as ``[tile, 1, words]``
    uint32: bfloat16 columns ``j`` and ``j + d / 2`` in the low and the high
    half of word ``j``, any other row as its float32 bits."""
    *x_refs, o_ref = refs[-sources - 1:]
    words = o_ref.shape[-1]

    def bits(rows, lanes):
        x = x_refs[0][rows, lanes].astype(jnp.float32)
        for other in x_refs[1:]:
            x = (x + other[rows, lanes].astype(jnp.float32)).astype(
                other.dtype).astype(jnp.float32)
        return jax.lax.bitcast_convert_type(x, jnp.uint32)

    def pack(rows, _, count):
        if words == d:
            word = bits(rows, slice(None))
        else:
            word = (bits(rows, slice(0, words)) >> 16) \
                | (bits(rows, slice(words, d)) & jnp.uint32(0xFFFF0000))
        o_ref[rows] = word.reshape(count, 1, words)

    _for_chunks(x_refs[0].shape[0], pack)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("row_tile", "interpret"))
def _row_words(xs, table=None, *, row_tile, interpret):
    """The sum of the arrays ``xs`` (a tuple, each [n, d]) as ``[n, 1,
    words]`` uint32, a row contiguous and one DMA: two bfloat16 columns a
    word (``_packs``; the halves unpack into lanes ``[:d / 2]`` and ``[d /
    2:]``), or the row's float32 bits. With the walk ``table``, over the row
    tiles it visits alone; the rows of the others are never read or written,
    and ``combine_rows`` fetches none of them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = xs[0].shape
    words = _words_a_row(xs[0].dtype, d)
    if table is None:
        row_tile = min(row_tile, n)
        prefetch, tiles = (), -(-n // row_tile)
    else:
        groups = _table_groups(table, n, row_tile)
        prefetch = (table,)
        tiles = _live_tiles(table, groups, _most_steps(n, row_tile, groups))
    return pl.pallas_call(
        functools.partial(_words_kernel, d=d, sources=len(xs)),
        out_shape=jax.ShapeDtypeStruct((n, 1, words), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch), grid=(tiles,),
            in_specs=[pl.BlockSpec((row_tile, d), lambda i, *_: (i, 0))]
            * len(xs),
            out_specs=pl.BlockSpec((row_tile, 1, words),
                                   lambda i, *_: (i, 0, 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(*prefetch, *xs)


def _unpacked(words, d):
    """The float32 pieces, left to right, of fetched rows ``words`` [r,
    words] uint32 of a ``d``-wide source (``_row_words``)."""
    as_f32 = lambda bits: jax.lax.bitcast_convert_type(bits, jnp.float32)
    if words.shape[1] == d:
        return [as_f32(words)]
    return [as_f32(words << 16), as_f32(words & jnp.uint32(0xFFFF0000))]


def _for_chunks(rows, body):
    """``body(chunk, first row, rows in it)`` over a tile of ``rows`` rows,
    ``_ROW_CHUNK`` at a time, ``chunk`` the index that slices them out of a
    ref: one traced body in a loop where the tile is whole chunks (a
    step's set-up is traced and lowered, not only run), static slices
    otherwise."""
    from jax.experimental import pallas as pl

    if rows % _ROW_CHUNK or rows == _ROW_CHUNK:
        for r0 in range(0, rows, _ROW_CHUNK):
            count = min(_ROW_CHUNK, rows - r0)
            body(slice(r0, r0 + count), r0, count)
        return

    def chunk(c, carry):
        r0 = pl.multiple_of(c * _ROW_CHUNK, _ROW_CHUNK)
        body(pl.ds(r0, _ROW_CHUNK), r0, _ROW_CHUNK)
        return carry

    jax.lax.fori_loop(0, rows // _ROW_CHUNK, chunk, 0)


def _lane_sums(x):
    """A row's sum over ``x``'s columns, as far as lane tiles add to one
    another: [rows, _LANES] partial sums whose own sum is the row's (the
    caller's, outside the kernel: a column one lane wide crosses a kernel's
    boundary an element a DMA), or [rows, 1] where the width is no lane
    tiles."""
    width = x.shape[1]
    if width % _LANES:
        return x.sum(axis=1, keepdims=True)
    return sum(x[:, c:c + _LANES] for c in range(0, width, _LANES))


def _lane_slices(pieces):
    width = pieces[0].shape[1]
    return [slice(i * width, (i + 1) * width) for i in range(len(pieces))]


# one-row copies started, and waited for in one wait, a step of the fetch
# loops: an index's load and the two addresses are a chain of scalar work a
# copy, and several chains share bundles
_FETCH_UNROLL = 8


def _fetch_rows(src_hbm, buf, sem, count, src_row):
    """``count`` one-row copies ``src_hbm[src_row(i)] -> buf[i]``, all in
    flight before the first is waited for."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def start(i):
        # the row clamped here, and the slot a loop's counter: every copy is
        # inside both arrays, which is what lets the callers compile without
        # Mosaic's own check of each, two traps a copy and most of its cost
        row = jnp.clip(src_row(i), 0, src_hbm.shape[0] - 1)
        pltpu.make_async_copy(src_hbm.at[row], buf.at[i], sem).start()

    def wait(rows):
        # a wait is for bytes: any copy of as many rows stands for them
        pltpu.make_async_copy(src_hbm.at[pl.ds(0, rows)],
                              buf.at[pl.ds(0, rows)], sem).wait()

    def start_many(j, carry):
        for u in range(_FETCH_UNROLL):
            start(j * _FETCH_UNROLL + u)
        return carry

    def start_one(i, carry):
        start(i)
        return carry

    def wait_many(j, carry):
        wait(_FETCH_UNROLL)
        return carry

    def wait_one(i, carry):
        wait(1)
        return carry

    many = count // _FETCH_UNROLL
    rest = many * _FETCH_UNROLL, count
    jax.lax.fori_loop(0, many, start_many, 0)
    jax.lax.fori_loop(*rest, start_one, 0)
    jax.lax.fori_loop(0, many, wait_many, 0)
    jax.lax.fori_loop(*rest, wait_one, 0)


def _live_tiles(table, groups, most):
    """The row tiles the walk ``table`` visits are ``0 .. its last step's``:
    the groups abut from row 0, so a tile two groups share counts once."""
    return _step_tile(table, groups, most, table[-1] - 1) + 1


def _gather_kernel(table, token, src_hbm, *refs, groups, row_tile, d, scaled,
                   dotted):
    """Row tile ``i`` of the walk: ``o[r] = src[token[r]]`` (times
    ``scale[r]``) for its rows under ``ends[-1]``, zeros for the rest of the
    tile; ``dotted``: also ``dot[r] = <other[r], src[token[r]]>`` in
    float32, ``other`` read only where the row holds an assignment."""
    from jax.experimental import pallas as pl

    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    other_ref = refs.pop(0) if dotted else None
    o_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dotted else None
    buf, sem = refs
    row0 = pl.program_id(0) * row_tile
    held = jnp.clip(table[groups] - row0, 0, row_tile)
    _fetch_rows(src_hbm, buf, sem, held, lambda r: token[row0 + r])

    def turn(rows, first, count):
        pieces = _unpacked(buf[rows].reshape(count, -1), d)
        lanes = _lane_slices(pieces)
        inside = first + jax.lax.broadcasted_iota(
            jnp.int32, (count, 1), 0) < held
        if dotted:
            dot_ref[rows] = _lane_sums(sum(
                jnp.where(inside, other_ref[rows, at].astype(jnp.float32)
                          * piece, 0.0) for at, piece in zip(lanes, pieces)))
        for at, piece in zip(lanes, pieces):
            if scaled:
                piece = piece * _lanes(scale_ref[rows], piece.shape[1])
            o_ref[rows, at] = jnp.where(inside, piece, 0.0).astype(
                o_ref.dtype)

    _for_chunks(row_tile, turn)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("row_tile", "dtype", "interpret"))
def _gather(src, token, table, scale=None, other=None, *, row_tile, dtype,
            interpret):
    """``src`` [n, d] by ``token`` [rows] over the walk ``table`` to [rows, d]
    ``dtype`` (and, with ``other`` [rows, d], the rows' dots with it, [rows]
    float32); ``scale`` [rows] float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, d = token.shape[0], src.shape[1]
    words = _row_words((src,), row_tile=row_tile, interpret=interpret)
    groups = _table_groups(table, rows, row_tile)
    most = _most_steps(rows, row_tile, groups)
    tile = lambda width: pl.BlockSpec((row_tile, width),
                                      lambda i, *_: (i, 0))
    # a scalar a row crosses the boundary a lane tile wide (the scale
    # replicated, the dots as the partial sums of ``_lane_sums``)
    lanes = (_LANES, 1)[words.shape[-1] % _LANES != 0]
    operands, in_specs = [words], [pl.BlockSpec(memory_space=pl.ANY)]
    out_shape, out_specs = [jax.ShapeDtypeStruct((rows, d), dtype)], [tile(d)]
    if scale is not None:
        operands.append(jnp.broadcast_to(scale[:, None], (rows, lanes)))
        in_specs.append(tile(lanes))
    if other is not None:
        operands.append(other)
        in_specs.append(tile(d))
        out_shape.append(jax.ShapeDtypeStruct((rows, lanes), jnp.float32))
        out_specs.append(tile(lanes))
    kernel = functools.partial(
        _gather_kernel, groups=groups, row_tile=row_tile, d=d,
        scaled=scale is not None, dotted=other is not None)
    out = pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(_live_tiles(table, groups, most),),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((row_tile,) + words.shape[1:],
                                       jnp.uint32),
                            pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        interpret=interpret)(table, token, *operands)
    return out[0] if other is None else (out[0], out[1].sum(axis=1))


def _table_groups(table, rows, row_tile):
    """How many groups the walk ``table`` of ``group_tiles`` has, from its
    length."""
    return (table.shape[0] - 2 * -(-rows // row_tile)) // 3


def combine_plan(top_k, d, dtype):
    """The token tile of ``combine_rows``: the largest power of two, from 8
    to 256, whose ``top_k`` fetched rows a token fit ``_COMBINE_VMEM`` as
    words (128 at top 6 of 2560 and at top 8 of 2048 bfloat16 columns)."""
    words = _words_a_row(dtype, d)
    tile = 8
    while tile < _MAX_TOKEN_TILE \
            and 2 * tile * top_k * words * 4 <= _COMBINE_VMEM:
        tile *= 2
    return tile


def _combine_kernel(counts, row_ref, token_ref, *refs, token_tile, d,
                    weighted):
    """Token tile ``j``: ``o[t] = Σ w · src[row]`` over the tile's
    ``counts[j]`` assignments that have a row, listed ahead of the others
    (``row``, ``token`` within the tile, ``w``): each row one DMA, added to
    its token's float32 sum as it came (whole words a row), the sums turned
    into lanes and rounded once a tile. An assignment with no row is not in
    the list: not fetched, not read, not multiplied."""
    from jax.experimental import pallas as pl

    refs = list(refs)
    w_ref = refs.pop(0) if weighted else None
    src_hbm, o_ref, buf, sums, sem = refs
    count = counts[pl.program_id(0)]
    _fetch_rows(src_hbm, buf, sem, count, lambda i: row_ref[0, i])
    sums[...] = jnp.zeros(sums.shape, jnp.float32)

    def add(i, carry):
        for at, piece in enumerate(_unpacked(buf[i], d)):
            if weighted:
                piece = piece * w_ref[0, i]
            sums[at, token_ref[0, i]] += piece
        return carry

    jax.lax.fori_loop(0, count, add, 0)
    width = sums.shape[-1]

    def turn(rows, _, count):
        for at in range(sums.shape[0]):
            o_ref[rows, at * width:(at + 1) * width] = sums[at, rows].reshape(
                count, width).astype(o_ref.dtype)

    _for_chunks(token_tile, turn)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("row_tile", "dtype", "interpret"))
def _combine(srcs, pos, table, w=None, *, row_tile, dtype, interpret):
    """The sum of ``srcs`` (a tuple, each [rows, d]), laid out as words over
    the walk ``table``'s row tiles, by ``pos`` [n, top_k] (-1: no row) and
    ``w`` [n, top_k] float32 (None: ones) to [n, d] ``dtype``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (n, top_k), d = pos.shape, srcs[0].shape[1]
    words = _row_words(srcs, table, row_tile=row_tile, interpret=interpret)
    token_tile = combine_plan(top_k, d, srcs[0].dtype)
    tiles, slots = -(-n // token_tile), token_tile * top_k
    # a tile's assignments that have a row, ahead of those that have none:
    # the kernel's scalar loops are as long as the rows it fetches
    a_tile = lambda x, fill: jnp.pad(
        x.reshape(-1), (0, tiles * slots - n * top_k),
        constant_values=fill).reshape(tiles, slots)
    row = a_tile(pos, -1)
    token = jnp.broadcast_to(jnp.arange(slots, dtype=jnp.int32) // top_k,
                             row.shape)
    listed = jax.lax.sort(
        ((row < 0).astype(jnp.int32), row, token)
        + (() if w is None else (a_tile(w.astype(jnp.float32), 0.0),)),
        dimension=1, num_keys=1, is_stable=True)[1:]
    counts = (row >= 0).sum(1, dtype=jnp.int32)
    scalars = pl.BlockSpec((None, 1, slots), lambda j, *_: (j, 0, 0),
                           memory_space=pltpu.SMEM)
    kernel = functools.partial(_combine_kernel, token_tile=token_tile, d=d,
                               weighted=w is not None)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((n, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,),
            in_specs=[scalars] * len(listed)
            + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((token_tile, d), lambda j, *_: (j, 0)),
            scratch_shapes=[
                pltpu.VMEM((slots,) + words.shape[1:], jnp.uint32),
                pltpu.VMEM((d // words.shape[-1], token_tile)
                           + words.shape[1:], jnp.float32),
                pltpu.SemaphoreType.DMA(())]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), disable_bounds_checks=True),
        interpret=interpret)(counts, *(x[:, None] for x in listed), words)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def gather_rows(src, order, pos, table, row_tile, copies=1):
    """``rows[r] = src[order[r] // top_k]`` [rows, d] in ``src``'s dtype for
    the row tiles the walk ``table = group_tiles(group_sizes, rows,
    row_tile)`` visits: ``order`` [rows] int32 says which of the ``n x
    top_k`` assignments fills row ``r``, ``pos`` [n, top_k] int32 is its
    inverse on the rows under the groups' sum (-1: the assignment has no
    row). Rows of a visited tile past the groups' sum are zeros; **a row
    tile past the last group is never written**, as ``grouped_matmul``
    leaves it and never reads it. Returns a tuple of ``copies`` references
    to the one array, a reader each: their cotangents come back apart and
    are added a row tile at a time, where XLA's own sum would pass over the
    whole buffer. The cotangent for ``src`` is ``combine_rows`` of that sum
    with unit weights: a token's sum over its rows, in float32, rounded
    once; no cotangent is read from a row outside every group."""
    return (_gather(src, order // pos.shape[1], table, row_tile=row_tile,
                    dtype=src.dtype, interpret=_interpret_mode()),) * copies


def _gather_rows_fwd(src, order, pos, table, row_tile, copies):
    return gather_rows(src, order, pos, table, row_tile, copies), (pos, table)


def _gather_rows_bwd(row_tile, copies, residuals, gs):
    pos, table = residuals
    return _combine(gs, pos, table, row_tile=row_tile, dtype=gs[0].dtype,
                    interpret=_interpret_mode()), None, None, None


gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def combine_rows(out, w, order, pos, table, row_tile):
    """``y[n] = Σ_k w[n, k] · out[pos[n, k]]`` [n, d] in ``out``'s dtype over
    the assignments with ``pos[n, k] >= 0``: the weighted rows of the buffer
    added back to their tokens, a token tile at a time, in float32 and
    rounded once. An assignment without a row is skipped, not fetched and
    multiplied by zero (``0 * NaN``), and no row outside every group is
    read. The cotangents: for ``out``, ``gather_rows`` of ``dy`` times the
    row's weight; for ``w``, the dot of ``out[r]`` and ``dy``'s row, taken in
    the same visit of the row tile (``order``, ``table``, ``row_tile`` as
    ``gather_rows`` takes them: the backward follows the walk)."""
    return _combine((out,), pos, table, w, row_tile=row_tile,
                    dtype=out.dtype, interpret=_interpret_mode())


def _combine_rows_fwd(out, w, order, pos, table, row_tile):
    return combine_rows(out, w, order, pos, table, row_tile), \
        (out, w, order, pos, table)


def _combine_rows_bwd(row_tile, residuals, dy):
    out, w, order, pos, table = residuals
    rows = order.shape[0]
    # the rows' weights in the rows' order, ``w.reshape(-1)[order]`` on the
    # rows that hold an assignment: sorted by row, which on the chip is an
    # eighth of XLA's gather of as many scalars
    w_rows = jax.lax.sort((jnp.where(pos < 0, rows, pos).reshape(-1),
                           w.reshape(-1)), num_keys=1)[1][:rows]
    d_out, dots = _gather(
        dy, order // pos.shape[1], table, w_rows, out,
        row_tile=row_tile, dtype=out.dtype, interpret=_interpret_mode())
    d_w = jnp.where(pos >= 0, dots[jnp.maximum(pos, 0)], 0.0)
    return d_out, d_w.astype(w.dtype), None, None, None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)


# ---------------------------------------------------------------------------
# fused LSTM cell ("Optimizing Performance of Recurrent Neural Networks on
# GPUs", arxiv 1604.01946; the cuDNN RNN fusion strategy, arxiv 1410.0759):
# one kernel per time step fusing the recurrent matmul epilogue
# (h_prev @ RW), the i/f/g/o gate split + sigmoid/tanh activations, the
# peephole contributions and the cell update — the ~10 separate XLA
# element-wise ops the built-in scan body emits per step. The backward is a
# matching single kernel (custom_vjp, the same A/B harness pattern as flash
# attention above): gates recomputed from the saved residuals, all gate
# adjoints + dRW/dh_prev matmuls + peephole grads fused. Wired into
# ``LSTM._scan`` behind DL4J_TPU_LSTM_KERNEL=pallas (nn/layers/recurrent.py).
# ---------------------------------------------------------------------------

def lstm_cell_supported(gate_activation, cell_activation):
    """The kernel implements the standard cell only: sigmoid gates + tanh
    cell/output activation (the GravesLSTM/cuDNN formulation). Exotic
    activations fall back to the built-in scan."""
    return (pallas_supported() and gate_activation == "sigmoid"
            and (cell_activation or "tanh") == "tanh")


def _lstm_cell_fwd_kernel(zx_ref, h_ref, c_ref, rw_ref, p_ref, ho_ref,
                          co_ref, *, n_out, peephole):
    """One fused cell step: z = zx + h_prev @ RW (MXU), then the whole
    gate/cell epilogue on the VPU without touching HBM in between. Whole-
    array blocks: an LSTM step's [B, 4H] working set is KBs-to-low-MBs,
    comfortably VMEM-resident (the flash kernels above are the pattern for
    when that stops being true)."""
    h_prev = h_ref[...].astype(jnp.float32)
    c_prev = c_ref[...].astype(jnp.float32)
    z = zx_ref[...].astype(jnp.float32) + jax.lax.dot_general(
        h_prev, rw_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    i = z[:, :n_out]
    f = z[:, n_out:2 * n_out]
    g = z[:, 2 * n_out:3 * n_out]
    o = z[:, 3 * n_out:]
    if peephole:
        p = p_ref[...].astype(jnp.float32)
        i = i + c_prev * p[0:1]
        f = f + c_prev * p[1:2]
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    c = f * c_prev + i * g
    if peephole:
        o = o + c * p_ref[...].astype(jnp.float32)[2:3]
    o = jax.nn.sigmoid(o)
    h = o * jnp.tanh(c)
    ho_ref[...] = h.astype(ho_ref.dtype)
    co_ref[...] = c.astype(co_ref.dtype)


def _lstm_cell_bwd_kernel(zx_ref, h_ref, c_ref, rw_ref, p_ref, dh_ref,
                          dc_ref, dzx_ref, dhp_ref, dcp_ref, drw_ref,
                          dp_ref, *, n_out, peephole):
    """Fused cell backward: recompute the gates from the residuals (memory-
    light, the flash-backward discipline), then every gate adjoint, the
    dzx/dh_prev/dRW matmul pair and the peephole grads in one kernel."""
    h_prev = h_ref[...].astype(jnp.float32)
    c_prev = c_ref[...].astype(jnp.float32)
    rw = rw_ref[...].astype(jnp.float32)
    z = zx_ref[...].astype(jnp.float32) + jax.lax.dot_general(
        h_prev, rw, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    i = z[:, :n_out]
    f = z[:, n_out:2 * n_out]
    g = z[:, 2 * n_out:3 * n_out]
    o = z[:, 3 * n_out:]
    if peephole:
        p = p_ref[...].astype(jnp.float32)
        i = i + c_prev * p[0:1]
        f = f + c_prev * p[1:2]
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    c = f * c_prev + i * g
    if peephole:
        o = o + c * p[2:3]
    o = jax.nn.sigmoid(o)
    tc = jnp.tanh(c)

    dh = dh_ref[...].astype(jnp.float32)
    dc = dc_ref[...].astype(jnp.float32)
    d_opre = dh * tc * o * (1.0 - o)            # σ'(o_pre) = o(1-o)
    dc_tot = dc + dh * o * (1.0 - tc * tc)      # through h = o·tanh(c)
    if peephole:
        dc_tot = dc_tot + d_opre * p[2:3]       # o_pre = zo + c·P2
    d_ipre = dc_tot * g * i * (1.0 - i)
    d_fpre = dc_tot * c_prev * f * (1.0 - f)
    d_gpre = dc_tot * i * (1.0 - g * g)
    dc_prev = dc_tot * f
    if peephole:
        dc_prev = dc_prev + d_ipre * p[0:1] + d_fpre * p[1:2]
    dz = jnp.concatenate([d_ipre, d_fpre, d_gpre, d_opre], axis=1)
    dzx_ref[...] = dz.astype(dzx_ref.dtype)
    dhp_ref[...] = jax.lax.dot_general(
        dz, rw, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dhp_ref.dtype)
    dcp_ref[...] = dc_prev.astype(dcp_ref.dtype)
    drw_ref[...] = jax.lax.dot_general(
        h_prev, dz, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(drw_ref.dtype)
    if peephole:
        dp_ref[0:1, :] = jnp.sum(d_ipre * c_prev, axis=0,
                                 keepdims=True).astype(dp_ref.dtype)
        dp_ref[1:2, :] = jnp.sum(d_fpre * c_prev, axis=0,
                                 keepdims=True).astype(dp_ref.dtype)
        dp_ref[2:3, :] = jnp.sum(d_opre * c, axis=0,
                                 keepdims=True).astype(dp_ref.dtype)


def _lstm_cell_call(zx, h_prev, c_prev, rw, peep):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_out = h_prev.shape[1]
    kernel = functools.partial(_lstm_cell_fwd_kernel, n_out=n_out,
                               peephole=peep is not None)
    p_arg = (jnp.zeros((3, n_out), h_prev.dtype),) if peep is None else (peep,)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    h, c = pl.pallas_call(
        kernel,
        name="lstm_fwd",
        out_shape=[jax.ShapeDtypeStruct(h_prev.shape, h_prev.dtype),
                   jax.ShapeDtypeStruct(c_prev.shape, c_prev.dtype)],
        in_specs=[vmem() for _ in range(5)],
        out_specs=[vmem(), vmem()],
        interpret=_interpret_mode(),
    )(zx, h_prev, c_prev, rw, *p_arg)
    return h, c


def _lstm_cell_bwd_call(zx, h_prev, c_prev, rw, peep, dh, dc):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_out = h_prev.shape[1]
    peephole = peep is not None
    kernel = functools.partial(_lstm_cell_bwd_kernel, n_out=n_out,
                               peephole=peephole)
    p_arg = jnp.zeros((3, n_out), h_prev.dtype) if peep is None else peep
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    dzx, dhp, dcp, drw, dp = pl.pallas_call(
        kernel,
        name="lstm_bwd",
        out_shape=[jax.ShapeDtypeStruct(zx.shape, zx.dtype),
                   jax.ShapeDtypeStruct(h_prev.shape, h_prev.dtype),
                   jax.ShapeDtypeStruct(c_prev.shape, c_prev.dtype),
                   jax.ShapeDtypeStruct(rw.shape, rw.dtype),
                   jax.ShapeDtypeStruct((3, n_out), rw.dtype)],
        in_specs=[vmem() for _ in range(7)],
        out_specs=[vmem() for _ in range(5)],
        interpret=_interpret_mode(),
    )(zx, h_prev, c_prev, rw, p_arg, dh, dc)
    return dzx, dhp, dcp, drw, (dp if peephole else None)


@jax.custom_vjp
def _lstm_cell_plain(zx, h_prev, c_prev, rw):
    return _lstm_cell_call(zx, h_prev, c_prev, rw, None)


def _plain_fwd(zx, h_prev, c_prev, rw):
    return _lstm_cell_call(zx, h_prev, c_prev, rw, None), (zx, h_prev,
                                                           c_prev, rw)


def _plain_bwd(res, g):
    dh, dc = g
    dzx, dhp, dcp, drw, _ = _lstm_cell_bwd_call(*res, None, dh, dc)
    return dzx, dhp, dcp, drw


_lstm_cell_plain.defvjp(_plain_fwd, _plain_bwd)


@jax.custom_vjp
def _lstm_cell_peep(zx, h_prev, c_prev, rw, peep):
    return _lstm_cell_call(zx, h_prev, c_prev, rw, peep)


def _peep_fwd(zx, h_prev, c_prev, rw, peep):
    return _lstm_cell_call(zx, h_prev, c_prev, rw, peep), (zx, h_prev,
                                                           c_prev, rw, peep)


def _peep_bwd(res, g):
    dh, dc = g
    return _lstm_cell_bwd_call(*res, dh, dc)


_lstm_cell_peep.defvjp(_peep_fwd, _peep_bwd)


def lstm_cell(zx, h_prev, c_prev, rw, peep=None):
    """Fused LSTM cell step: ``(h, c)`` from the packed input projection
    ``zx`` [B, 4H] (W/bias matmul done once for all steps outside the
    scan), previous state [B, H], recurrent weights ``rw`` [H, 4H] and
    optional peephole weights ``peep`` [3, H] (Graves formulation; rows
    i/f/o). Gate packing order [i, f, g, o] matches ``_lstm_gates``.
    Differentiable via the fused backward kernel."""
    if peep is None:
        return _lstm_cell_plain(zx, h_prev, c_prev, rw)
    return _lstm_cell_peep(zx, h_prev, c_prev, rw, peep)
