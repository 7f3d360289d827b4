"""Long-context attention: blockwise (flash) attention + ring attention
sequence parallelism.

The reference predates attention entirely — its only long-sequence story is
truncated BPTT (SURVEY §5.7) — but a TPU-native framework must scale context
as a first-class capability: sequences are sharded over a mesh axis and
attention runs as a ring, each device computing its queries against the
rotating K/V shards via ``jax.lax.ppermute`` over ICI.

Implementation notes (TPU-first):
- ``blockwise_attention`` is the flash-attention recurrence (running max /
  running sum) expressed with ``lax.scan`` over K/V blocks — O(block) memory
  instead of O(T²), static shapes, autodiff-friendly (XLA rematerializes).
- ``ring_attention`` nests that recurrence over devices: the *outer* loop
  rotates K/V shards around the ring (ppermute), the running softmax
  statistics are carried across steps, so the result is EXACTLY softmax
  attention over the full sequence — verified against dense attention in
  tests on the 8-device CPU mesh.
- Causal masking works across shards by tracking absolute position offsets.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e30


def _attend_block(q, k, v, bias, m_prev, l_prev, o_prev, scale=None):
    """One flash-attention accumulation step.

    q: [..., Tq, d]; k/v: [..., Tk, d] (v may be [..., Tk, dv]); bias:
    broadcastable to [..., Tq, Tk]; ``scale`` multiplies the scores (None:
    ``1 / sqrt(d)``). carries: m (running max, [..., Tq]), l (running sum),
    o (unnormalized out).
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k)
    s = s / jnp.sqrt(q.shape[-1]).astype(q.dtype) if scale is None \
        else s * jnp.asarray(scale, q.dtype)
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    # guard: fully-masked rows keep m at NEG_INF; exp(NEG_INF - NEG_INF) would
    # be exp(0)=1, so clamp the correction when nothing has been seen yet
    correction = jnp.exp(jnp.where(m_prev == NEG_INF, NEG_INF, m_prev - m_new))
    p = jnp.exp(s - m_new[..., None])
    l_new = l_prev * correction + p.sum(axis=-1)
    o_new = o_prev * correction[..., None] + jnp.einsum("...qk,...kd->...qd", p, v)
    return m_new, l_new, o_new


def _finalize(m, l, o):
    return o / jnp.maximum(l, 1e-30)[..., None]


def blockwise_attention(q, k, v, *, causal=False, block_size=128, mask=None,
                        scale=None):
    """Memory-efficient exact attention (flash recurrence via lax.scan).

    q/k/v: [batch, T, d] (or [batch, heads, T, d]; v's last dim may be its
    own). ``mask``: [batch, Tk] key-validity mask. Returns
    softmax(QKᵀ·scale)V with O(T·block) memory, ``scale`` None for 1/√d.
    """
    tq = q.shape[-2]
    tk = k.shape[-2]
    pad = (-tk) % block_size
    if pad:
        padk = [(0, 0)] * (k.ndim - 2) + [(0, pad), (0, 0)]
        k = jnp.pad(k, padk)
        v = jnp.pad(v, padk)
        key_valid = jnp.arange(tk + pad) < tk
    else:
        key_valid = None
    n_blocks = k.shape[-2] // block_size

    # [n_blocks, ..., block, d] leading-axis stacking for scan
    def to_blocks(x):
        xs = jnp.moveaxis(x, -2, 0)
        xs = xs.reshape((n_blocks, block_size) + x.shape[:-2] + x.shape[-1:])
        return jnp.moveaxis(xs, 1, -2)

    kb = to_blocks(k)
    vb = to_blocks(v)

    q_pos = jnp.arange(tq)
    batch_shape = q.shape[:-2]
    m0 = jnp.full(batch_shape + (tq,), NEG_INF, q.dtype)
    l0 = jnp.zeros(batch_shape + (tq,), q.dtype)
    o0 = jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)

    def step(carry, inp):
        m, l, o = carry
        bi, kblk, vblk = inp
        k_pos = bi * block_size + jnp.arange(block_size)
        bias = jnp.zeros((tq, block_size), q.dtype)
        if causal:
            bias = jnp.where(q_pos[:, None] >= k_pos[None, :], bias, NEG_INF)
        if key_valid is not None:
            valid = k_pos < tk
            bias = jnp.where(valid[None, :], bias, NEG_INF)
        if mask is not None:
            # mask: [batch, Tk(padded slice)] → bias [batch, 1?, Tq, block]
            mblk = jax.lax.dynamic_slice_in_dim(
                jnp.pad(mask, [(0, 0), (0, pad)]) if pad else mask,
                bi * block_size, block_size, axis=1)
            extra = jnp.where(mblk > 0, 0.0, NEG_INF).astype(q.dtype)
            extra = extra[:, None, :] if q.ndim == 3 else extra[:, None, None, :]
            bias = bias + extra
        m, l, o = _attend_block(q, kblk, vblk, bias, m, l, o, scale)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(
        step, (m0, l0, o0), (jnp.arange(n_blocks), kb, vb))
    return _finalize(m, l, o)


def ring_attention(q, k, v, *, axis_name, causal=False, mask=None):
    """Exact attention over a sequence sharded on ``axis_name`` — call inside
    ``shard_map``. Each device holds [batch, T/n, d] shards; K/V rotate around
    the ring with ``ppermute`` while the flash recurrence accumulates, so
    activation memory stays O(T/n) per device and transfers ride ICI.
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    t_local = q.shape[-2]
    q_pos = (my * t_local + jnp.arange(t_local)).astype(jnp.int32)

    batch_shape = q.shape[:-2]
    m0 = jnp.full(batch_shape + (t_local,), NEG_INF, q.dtype)
    l0 = jnp.zeros(batch_shape + (t_local,), q.dtype)
    o0 = jnp.zeros(q.shape, q.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        m, l, o, k_cur, v_cur, mask_cur = carry
        src = (my - i) % n  # which shard we currently hold
        k_pos = (src * t_local + jnp.arange(t_local)).astype(jnp.int32)
        bias = jnp.zeros((t_local, t_local), q.dtype)
        if causal:
            bias = jnp.where(q_pos[:, None] >= k_pos[None, :], bias, NEG_INF)
        if mask_cur is not None:
            extra = jnp.where(mask_cur > 0, 0.0, NEG_INF).astype(q.dtype)
            extra = extra[:, None, :] if q.ndim == 3 else extra[:, None, None, :]
            bias = bias + extra
        m, l, o = _attend_block(q, k_cur, v_cur, bias, m, l, o)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        mask_nxt = (jax.lax.ppermute(mask_cur, axis_name, perm)
                    if mask_cur is not None else None)
        return (m, l, o, k_nxt, v_nxt, mask_nxt), None

    carry = (m0, l0, o0, k, v, mask)
    for i in range(n):  # n is static (mesh size) — unrolled ring
        carry, _ = step(carry, i)
    m, l, o = carry[:3]
    return _finalize(m, l, o)


_SP_ATTENTION_CACHE = {}
_ULYSSES_CACHE = {}
_CACHE_MAX = 16


def _mesh_key(mesh):
    """Cache key by mesh *contents*, not identity: two equal meshes built
    from the same devices hit the same compiled program, and a caller that
    constructs a fresh Mesh per call no longer recompiles every time (nor
    pins every Mesh it ever made in module state)."""
    return (tuple(d.id for d in mesh.devices.flat), mesh.devices.shape,
            mesh.axis_names)


def _cache_get(cache, key):
    """LRU hit: re-insert so eviction order tracks recency, not insertion —
    otherwise the hottest program is the first evicted at capacity."""
    fn = cache.pop(key, None)
    if fn is not None:
        cache[key] = fn
    return fn


def _cache_put(cache, key, fn):
    if len(cache) >= _CACHE_MAX:    # bound module-level state: drop LRU
        cache.pop(next(iter(cache)))
    cache[key] = fn


def sequence_parallel_attention(q, k, v, mesh: Mesh, *, axis="seq",
                                causal=False):
    """Shard [batch, T, d] over ``axis`` of ``mesh`` and run ring attention.

    The host-level entry point: q/k/v are global arrays; output is the exact
    dense-attention result, computed with T/n-sized shards per device. The
    jitted shard_map is memoized per (mesh, axis, causal) so repeated calls
    hit the compilation cache.
    """
    spec = P(None, axis, None)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))

    key = (_mesh_key(mesh), axis, causal)
    fn = _cache_get(_SP_ATTENTION_CACHE, key)
    if fn is None:
        fn = jax.jit(shard_map(
            functools.partial(ring_attention, axis_name=axis, causal=causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
        _cache_put(_SP_ATTENTION_CACHE, key, fn)
    return fn(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, *, axis="seq", causal=False):
    """DeepSpeed-Ulysses-style context parallelism: the all-to-all
    counterpart to ring attention (the task's "ring attention OR
    all-to-all sequence parallelism" — both are provided).

    Inputs are [batch, T, H, D] multi-head tensors sharded over T along
    ``axis``. Two XLA ``all_to_all`` collectives reshard sequence→heads
    (each device then holds the FULL sequence for H/N of the heads, so
    plain dense attention runs locally with no per-step communication)
    and heads→sequence on the way back. Communication volume is O(T·H·D/N)
    per device — two collectives total, vs the ring's N-1 ppermute steps;
    the trade is that H must divide by the mesh axis.
    """
    n = mesh.shape[axis]
    if q.shape[2] % n != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[2]}) divisible by the '{axis}' "
            f"axis ({n}); use ring attention for head counts that don't")
    if q.shape[1] % n != 0:
        raise ValueError(
            f"ulysses needs sequence length ({q.shape[1]}) divisible by "
            f"the '{axis}' axis ({n}); pad the sequence or use blockwise "
            f"attention")

    def local(ql, kl, vl):
        # local [B, T/N, H, D] → all_to_all → [B, T, H/N, D]
        def to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)
        qh, kh, vh = to_heads(ql), to_heads(kl), to_heads(vl)
        # dense attention over the full sequence for the local heads
        oh = dense_attention(jnp.swapaxes(qh, 1, 2), jnp.swapaxes(kh, 1, 2),
                             jnp.swapaxes(vh, 1, 2), causal=causal)
        oh = jnp.swapaxes(oh, 1, 2)          # back to [B, T, H/N, D]
        # heads → sequence: inverse exchange
        return jax.lax.all_to_all(oh, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

    spec = P(None, axis, None, None)
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    key = (_mesh_key(mesh), axis, causal)
    fn = _cache_get(_ULYSSES_CACHE, key)
    if fn is None:   # memoize like _SP_ATTENTION_CACHE: jit caches by
        fn = jax.jit(shard_map(   # function identity, so a fresh
            local, mesh=mesh,          # closure per call would recompile
            in_specs=(spec, spec, spec), out_specs=spec))
        _cache_put(_ULYSSES_CACHE, key, fn)
    return fn(q, k, v)


def dense_attention(q, k, v, *, causal=False, mask=None, window=None,
                    scale=None):
    """Reference O(T²) attention (test oracle). ``window`` (requires
    causal): each query sees only the last ``window`` positions —
    sliding-window attention. ``scale`` multiplies the scores (None:
    ``1 / sqrt(d)``); v may have a width of its own."""
    s = jnp.einsum("...qd,...kd->...qk", q, k)
    s = s / jnp.sqrt(q.shape[-1]).astype(q.dtype) if scale is None \
        else s * jnp.asarray(scale, q.dtype)
    tq, tk = s.shape[-2], s.shape[-1]
    if causal:
        cm = jnp.tril(jnp.ones((tq, tk), bool))
        if window is not None:
            cm &= ~jnp.tril(jnp.ones((tq, tk), bool), -int(window))
        s = jnp.where(cm, s, NEG_INF)
    elif window is not None:
        raise ValueError("window requires causal=True")
    if mask is not None:
        mm = mask[:, None, :] if q.ndim == 3 else mask[:, None, None, :]
        s = jnp.where(mm > 0, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v)
