"""Pallas TPU kernels for hot ops.

The reference accelerates its hot layers with hand-written cuDNN calls
(SURVEY §2.3); the TPU analog is Pallas kernels tiled for the MXU. Shipping
kernels: flash attention forward (fused QKᵀ → online softmax → V in VMEM,
grid over (batch·heads, query blocks), K/V streamed block-by-block with the
running-max/sum recurrence — no O(T²) score materialization in HBM) and the
matching FlashAttention-2-style backward (a dQ kernel streaming K/V blocks
and a dK/dV kernel streaming Q/dO blocks, both recomputing P from the
forward's saved logsumexp — nothing O(T²) is ever stored).

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

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.config import env_flag, env_str

NEG_INF = -1e30

# Per-query-row scalars (running max/sum, logsumexp, delta) cross the
# kernel boundary lane-replicated as [..., rows, _LANES]: Mosaic wants the
# last two dims of every block divisible by (8, 128) or equal to the
# array's, which a [1, block_q] block of an [n, T] array is not.
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


def _causal_mask(s, qi, kb, block_q, block_k, window=None):
    """Mask entries of the [bq, bk] scores with q_pos < k_pos (and, with
    ``window``, entries more than window-1 positions in the past) to
    NEG_INF."""
    shape = s.shape
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    return jnp.where(keep, s, NEG_INF)


def _block_live(qi, kb, block_q, block_k, window):
    """Whether a (q-block, k-block) pair has any in-mask entry: some
    k ≤ q (causal), and with a window, some q − k < window."""
    live = kb * block_k < (qi + 1) * block_q
    if window is not None:
        live &= qi * block_q - (kb + 1) * block_k + 1 < window
    return live


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, block_q, block_k, causal, scale, window=None):
    """One (batch·head, q-block, k-block) grid step. The innermost grid
    dimension walks K/V blocks sequentially on the same core, so the VMEM
    scratch accumulators (running max m, running sum l, unnormalized output)
    persist across it — only one K/V block is VMEM-resident at a time, which
    is what keeps T unbounded (the full-K/V variant OOMs VMEM at T≈8k).

    m/l are stored lane-replicated as [block_q, _LANES]."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0] * scale                       # [block_q, d]
        k_blk = k_ref[0]                           # [block_k, d]
        v_blk = v_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [block_q, block_k]
        if causal:
            s = _causal_mask(s, qi, kb, block_q, block_k, window=window)
        m_prev = m_scr[...]                        # [block_q, 128], lanes equal
        l_prev = l_scr[...]
        m_cur = s.max(axis=-1, keepdims=True)      # [block_q, 1]
        m_new = jnp.maximum(m_prev, m_cur)         # broadcast over lanes
        correction = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF,
                                       m_prev - m_new))
        p = jnp.exp(s - m_new[:, :1])
        l_new = l_prev * correction + p.sum(axis=-1, keepdims=True)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc_scr[...] * correction[:, :1] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # blocks with no in-mask entry (above the diagonal, or entirely
        # beyond the sliding window) contribute nothing — skip them
        @pl.when(_block_live(qi, kb, block_q, block_k, window))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kb == n_kb - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...][:, :1], 1e-30)).astype(o_ref.dtype)
        m_fin = m_scr[...]                         # [block_q, 128]
        l_fin = l_scr[...]
        # logsumexp residual for the backward's P recomputation, written
        # lane-replicated like m/l (see _LANES). A fully masked row
        # (l == 0; only padded rows can hit this) gets +LARGE so
        # exp(s - lse) underflows to an exact 0 instead of NaN.
        lse_ref[0] = jnp.where(l_fin > 0.0,
                               m_fin + jnp.log(jnp.maximum(l_fin, 1e-30)),
                               -NEG_INF)


def _flash_forward(q, k, v, *, causal, block_q, block_k, window=None,
                   kv_group=1):
    """q: [n, T, d]; k/v: [n // kv_group, T, d] (n = batch·q-heads).
    ``kv_group`` > 1 is grouped-query attention: consecutive runs of
    kv_group query heads share one K/V head, mapped by the BlockSpec
    index (no materialized repeat). T must divide by the blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, t, d = q.shape
    scale = 1.0 / (d ** 0.5)
    kernel = functools.partial(_flash_kernel, block_q=block_q, block_k=block_k,
                               causal=causal, scale=scale, window=window)
    grid = (n, t // block_q, t // block_k)
    g = kv_group
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((n, t, _LANES), jnp.float32)],  # lse
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running sum
            pltpu.VMEM((block_q, d), jnp.float32),     # unnormalized out
        ],
        interpret=_interpret_mode(),
    )(q, k, v)


def _flash_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, dq_ref,
                     dq_scr, *, block_q, block_k, causal, scale,
                     window=None):
    """dQ pass: for a fixed Q block, stream K/V blocks (innermost grid dim)
    and accumulate dQ = Σ_kb dS @ K, with P recomputed from the saved
    logsumexp (FlashAttention-2 eq. 12-16)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kb = pl.program_id(2)
    n_kb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[0]                               # [bq, d]
        k_blk = k_ref[0]                           # [bk, d]
        v_blk = v_ref[0]
        g = g_ref[0].astype(jnp.float32)           # [bq, d] dO
        lse = lse_ref[0][:, :1]                    # [bq, 1]
        delta = delta_ref[0][:, :1]                # [bq, 1] rowsum(dO*O)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, kb, block_q, block_k, window=window)
        p = jnp.exp(s - lse)                       # [bq, bk]
        dp = jax.lax.dot_general(
            g, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, bk]
        ds = p * (dp - delta) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(_block_live(qi, kb, block_q, block_k, window))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kb == n_kb - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *, block_q, block_k,
                      causal, scale, window=None):
    """dK/dV pass: for a fixed K/V block, stream Q/dO blocks (innermost
    grid dim); dV = Σ_qb Pᵀ dO, dK = Σ_qb dSᵀ Q."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    qi = pl.program_id(2)
    n_qb = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[0]                               # [bq, d]
        k_blk = k_ref[0]                           # [bk, d]
        v_blk = v_ref[0]
        g = g_ref[0].astype(jnp.float32)           # [bq, d]
        lse = lse_ref[0][:, :1]                    # [bq, 1]
        delta = delta_ref[0][:, :1]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, kb, block_q, block_k, window=window)
        p = jnp.exp(s - lse)                       # [bq, bk]
        # Pᵀ dO and dSᵀ Q contract the query axis (dim 0 of both operands):
        # the per-row lse/delta broadcast along lanes as in the dQ kernel
        dv_scr[...] += jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bk, d]
        dp = jax.lax.dot_general(
            g, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bq, bk]
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)    # [bk, d]

    if causal:
        # a Q block with no in-mask entry for this K block contributes
        # nothing here (above the diagonal / beyond the window)
        @pl.when(_block_live(qi, kb, block_q, block_k, window))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_3d(q, k, v, causal, block_q, block_k, window=None,
                        kv_group=1):
    out, _lse = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, window=window,
                               kv_group=kv_group)
    return out


def _flash_fwd(q, k, v, causal, block_q, block_k, window=None, kv_group=1):
    out, lse = _flash_forward(q, k, v, causal=causal, block_q=block_q,
                              block_k=block_k, window=window,
                              kv_group=kv_group)
    # keep one lane: the saved residual is [n, T], not 128x that
    return out, (q, k, v, out, lse[..., 0])


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

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, out, lse = residuals
    n, t, d = q.shape
    scale = 1.0 / (d ** 0.5)
    # delta_i = Σ_d dO ⊙ O — a cheap fused elementwise+reduce; XLA keeps it
    # out of the kernels' VMEM budget
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    lse, delta = (jnp.broadcast_to(x[..., None], (n, t, _LANES))
                  for x in (lse, delta))

    gk = kv_group
    qkvg_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // gk, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // gk, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          window=window),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(n, t // block_q, t // block_k),
        in_specs=qkvg_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret_mode(),
    )(q, k, v, g, lse, delta)

    # dk/dv grid: (n, K blocks, Q blocks) — the index maps swap i/j roles.
    # With GQA the kernel accumulates PER Q-HEAD (output shaped like q);
    # the group-sum down to the kv heads happens outside — revisiting one
    # output block from different outer-grid steps would race.
    dkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b // gk, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b // gk, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          window=window),
        out_shape=[jax.ShapeDtypeStruct((n, t, d), k.dtype),
                   jax.ShapeDtypeStruct((n, t, d), v.dtype)],
        grid=(n, t // block_k, t // block_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interpret_mode(),
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
    each query to the last ``window`` positions — sliding-window attention;
    fully out-of-window blocks are skipped in BOTH directions, so compute
    scales O(T·window) instead of O(T²/2). Block defaults of 512 measured
    fastest on v5e at T=8k (≈10% over the lax.scan path; 128-blocks are ~35%
    slower from grid overhead).
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
