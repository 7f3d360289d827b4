"""Decoder-only Transformer language model — TPU-first, beyond-reference.

The reference's only sequence machinery is the RNN stack (SURVEY §5.7); a
modern framework needs a transformer family. This one is built the TPU way
rather than as layer-zoo glue:

- the WHOLE train step (forward, loss, backward, AdamW update) is one
  jitted XLA program with donated param/optimizer buffers;
- attention has two in-model paths: dense O(T²) for short sequences and
  the blockwise flash recurrence (``parallel/sequence_parallel.
  blockwise_attention``) for long ones — and the model's step also jits
  under ``shard_map`` for data/sequence parallelism (the ring/Ulysses
  modules in ``parallel/`` share the same attention math);
- ``compute_dtype='bfloat16'`` runs forward/backward in bf16 against f32
  masters (MXU-friendly), ``remat=True`` wraps each block in
  ``jax.checkpoint`` to trade FLOPs for activation HBM: a block keeps its
  input and, on the flash route, the kernel's attention output and
  logsumexp (``B·T·H·hd`` compute-dtype elements + ``B·H·T`` float32 a
  layer), so its backward recomputes everything but the forward kernel;
- generation is a ``lax.scan`` over a preallocated KV cache — static
  shapes, one compiled program for the whole sampling loop;
- a looped model (``loops``: the whole stack run several times over the
  same weights; ``post_norm``, ``exit_gate``) is the same walk
  (``_stack_runs``) for training and serving, a KV cache entry a run and
  layer, and a loss over every run's exit (``_exit_terms``).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, replace
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.config import env_int, env_str

from deeplearning4j_tpu.models.expert_layer import (STATS, Experts, decide,
                                                    expert_ffn, glu)
from deeplearning4j_tpu.ops.pallas_kernels import (FLASH_RESIDUALS,
                                                   flash_attention,
                                                   pallas_supported)
from deeplearning4j_tpu.parallel.sequence_parallel import (
    blockwise_attention, dense_attention)


def _rope_cos_sin(rope, hd, positions):
    """cos/sin tables for rotary embeddings at ``positions`` (any shape),
    returned shaped positions.shape + [rot/2], in f32; ``rot`` = the rotated
    share of the head's ``hd`` dims."""
    rot = rope.rotated(hd)
    if rope.yarn_factor is None:
        inv = rope.base ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
        ang = positions.astype(jnp.float32)[..., None] * inv
        return jnp.cos(ang), jnp.sin(ang)
    inv, factor = rope.yarn_frequencies(rot)
    ang = positions.astype(jnp.float32)[..., None] \
        * jnp.asarray(inv, jnp.float32)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def _apply_rope(x, cos, sin, layout="interleaved", rot=None):
    """Rotate pairs of the head dim. x: [..., T, hd]; cos/sin: [T, rot/2]
    (broadcast over the leading dims). ``rot`` (None: the whole head) is how
    many leading dims are rotated; the rest pass as they are.
    ``interleaved``: pairs (2i, 2i+1); ``half``: pairs (i, i + rot/2),
    Hugging Face's ``rotate_half``."""
    xr = x if rot is None else x[..., :rot]
    if layout == "half":
        # The two halves apart, joined afterwards: on the v5e the compiler
        # makes of this one fusion with two outputs, rooted in a tuple that
        # carries no name stack, so its time reads under no scope. Written
        # as ONE expression (x cos + rotate_half(x) sin) it reads under the
        # layer's scope and costs 10 ms more a step of the 8k cell (the
        # halves then cross lanes): PERF.md section 6, PR 28.
        x1, x2 = jnp.split(xr, 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    else:
        x1, x2 = xr[..., 0::2], xr[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                        axis=-1).reshape(xr.shape)
    if rot is not None:
        out = jnp.concatenate([out, x[..., rot:]], axis=-1)
    return out.astype(x.dtype)


def _full_heads(kv_group, k, v):
    """Expand GQA K/V to full query heads for routes that assume MHA.
    The grouping convention (consecutive query heads share a kv head)
    must match the pallas kernels' b // kv_group index map."""
    if kv_group > 1:
        k = jnp.repeat(k, kv_group, axis=1)
        v = jnp.repeat(v, kv_group, axis=1)
    return k, v


def _blockwise_route(c, q, k, v, plan=None, window=None):
    """Route the block_size attention: the pallas flash kernel (fused fwd
    + FlashAttention-2 bwd, ops/pallas_kernels.py) when the platform
    supports it, else the mathematically identical lax.scan recurrence.
    DL4J_TPU_LM_ATTN forces {pallas, scan}; read at TRACE time (the step
    jits once), so set it before the first fit_batch. A sliding window
    (``window``, the layer's) rides the pallas route — the scan has no
    window support, so that combination falls back to masked dense
    attention. Head count, group and head size are the arrays' own.

    ``plan`` is the model's ``ShardingCore`` when it was ``shard()``-ed:
    GSPMD refuses to partition a Mosaic kernel ("wrap the call in a
    shard_map"), so under a data-parallel plan the kernel runs per batch
    shard inside one — attention never mixes batch rows."""
    mode = env_str("DL4J_TPU_LM_ATTN")
    if mode in ("auto", "pallas"):
        if mode == "pallas" or pallas_supported():
            # GQA rides the kernel's index map — no repeat materialized
            attend = functools.partial(
                flash_attention, causal=True, block_q=c.block_size,
                block_k=c.block_size, window=window)
            if plan is not None and plan.batch_axis:
                spec = plan.batch_spec()
                attend = jax.shard_map(
                    attend, mesh=plan.mesh, in_specs=(spec, spec, spec),
                    out_specs=spec, check_vma=False)
            return attend(q, k, v)
    # the JAX fallbacks want full heads
    k, v = _full_heads(q.shape[1] // k.shape[1], k, v)
    if window is not None:
        return dense_attention(q, k, v, causal=True, window=window)
    return blockwise_attention(q, k, v, causal=True,
                               block_size=c.block_size)

__all__ = ["TransformerConfig", "TransformerLM", "LayerSpec", "Rope",
           "Experts", "SCOPES"]

# The fixed vocabulary of ``jax.named_scope``s the LM step enters: what the
# profiler's trace names a device op by (the stat ``tf_op`` of its event,
# ``jit(step)/transpose(jvp(block.ln1))/mul``) and what the benchmark's
# per-layer metrics read, token by token (``benchmark/scope_reduce.py``,
# PERF.md section 3). Rules, each held by tests/test_scopes.py:
# * no layer index (``block``, never ``b7``): the trace's reduction sums the
#   unrolled layers' copies of one op into one row by its instruction's name;
# * no name that is also a JAX primitive or transform (``transpose``, ``mul``);
# * entered INSIDE the differentiated function, never around
#   ``jax.value_and_grad``, and ONE scope element an op, the block's written
#   ``block.attn`` (not ``attn`` inside ``block``, and no ``/``). XLA names a
#   custom call after the piece of its name stack between the last two ``/``,
#   and a transform wraps only the scope element next to it:
#   ``jvp(block.attn)/pallas_call`` is the instruction ``%jvp_block.attn_``,
#   where ``jvp(block)/attn/pallas_call`` would be ``%attn``. The benchmark's
#   accepted ``flash_*_roofline`` readers tell the forward kernel from the
#   backward ones by ``%jvp`` / ``%transpose`` at the start of that name; for
#   the same reason the flash ``pallas_call``s take no ``name=`` (it enters a
#   scope of its own). tests/test_aot_compile.py holds this on the program
#   compiled for the chip.
SCOPES = ("embed", "block", "ln1", "qkv", "attn", "proj", "ln2", "mlp",
          "final_ln", "logits_loss", "grad_clip", "optimizer",
          # a model with a per-layer list (``TransformerConfig.layers``):
          # attention by the layer's kind in ``attn``'s place, the gate on
          # its output, and the expert layer's parts in ``mlp``'s place
          "attn_full", "attn_window", "attn_gate", "router", "moe_dispatch",
          "experts", "shared_expert",
          # a looped model (``TransformerConfig.loops``, ``post_norm``,
          # ``exit_gate``): the norm on each sublayer's output, and an exit's
          # gate, exit distribution, entropy and weighting of its loss
          "attn_norm", "mlp_norm", "exit_gate")


@dataclass(frozen=True)
class Rope:
    """One layer's rotary embedding. ``share``: the leading share of a
    head's dims that is rotated (``partial_rotary_factor``). YaRN where
    ``yarn_factor`` is set; ``attention_factor`` None takes YaRN's own
    ``0.1 ln(factor) + 1``, on cos and sin."""
    base: float = 10000.0
    share: float = 1.0
    yarn_factor: Optional[float] = None
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def rotated(self, hd):
        """How many of a head's ``hd`` dims are rotated."""
        return math.floor(hd * self.share)

    def yarn_frequencies(self, rot):
        """``(frequencies [rot/2], the factor on cos and sin)`` under YaRN
        (Peng et al. 2023, as Hugging Face's ``_compute_yarn_parameters``
        reads a config): dims that turn more than ``yarn_beta_fast`` times
        over the original context keep ``base ** (-2i / rot)``, those that
        turn less than ``yarn_beta_slow`` times take it divided by
        ``yarn_factor``, a linear ramp between."""
        def dim_of(turns):   # the dim that turns ``turns`` times
            return rot * math.log(self.yarn_original_len
                                  / (turns * 2 * math.pi)) \
                / (2 * math.log(self.base))

        low = max(math.floor(dim_of(self.yarn_beta_fast)), 0)
        high = min(math.ceil(dim_of(self.yarn_beta_slow)), rot - 1)
        if low == high:
            high += 0.001
        inv = []
        for i in range(rot // 2):
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            plain = self.base ** (-2 * i / rot)
            inv.append(plain / self.yarn_factor * ramp + plain * (1 - ramp))
        factor = self.attention_factor
        if factor is None:
            factor = 0.1 * math.log(self.yarn_factor) + 1.0
        return inv, factor


@dataclass(frozen=True)
class LayerSpec:
    """What one layer of a per-layer list states (``TransformerConfig
    .layers``); None takes the model's own setting. A layer states "no
    position at all" by a rope that rotates none of a head's dims,
    ``rope=Rope(share=0.0)``: the block then builds no cos / sin and turns
    nothing (``rope=None`` is the model's rope, never "none")."""
    window: Optional[int] = None      # None = full causal attention
    n_heads: Optional[int] = None     # query heads (the K/V heads are the model's)
    rope: Optional[Rope] = None
    ffn: str = "dense"                # "dense" | "experts"

    @property
    def attn_scope(self):
        return "block.attn_window" if self.window else "block.attn_full"


@dataclass
class TransformerConfig:
    vocab_size: int
    max_len: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    dropout: float = 0.0           # residual-branch dropout (train only)
    learning_rate: float = 3e-4
    lr_schedule: str = "constant"  # "constant" | "cosine"
    warmup_steps: int = 0          # linear warmup before the schedule
    total_steps: int = 10000       # cosine horizon (floor = 10% of peak)
    weight_decay: float = 0.01
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    compute_dtype: Optional[str] = None   # e.g. "bfloat16"
    # a rematerialised block keeps its input and, on the flash route, the
    # kernel's attention output and logsumexp (see _remat for the bytes)
    remat: bool = False
    block_size: Optional[int] = None      # flash-attention block; None=dense
    window: Optional[int] = None          # causal sliding-window width
    n_kv_heads: Optional[int] = None      # GQA: K/V heads (None = MHA)
    pos_embed: str = "learned"            # "learned" (wpe) | "rope"
    rope_base: float = 10000.0
    grad_clip_norm: Optional[float] = None   # global-norm gradient clip
    label_smoothing: float = 0.0
    z_loss: float = 0.0                   # PaLM logit-normalizer penalty
    ema_decay: Optional[float] = None     # Polyak weight averaging
    seed: int = 0
    # ---- the layer's vocabulary; the defaults are the GPT-2 block --------
    norm: str = "layernorm"               # "layernorm" (gain + bias) | "rmsnorm"
    norm_eps: float = 1e-5
    bias: bool = True                     # biases on every projection
    ffn: str = "gelu"                     # "gelu" | "swiglu"
    tie_embeddings: bool = True           # False: an output head of its own
    head_dim: Optional[int] = None        # None = d_model // n_heads
    rope_layout: str = "interleaved"      # "interleaved" | "half" (rotate_half)
    attn_gate: bool = False               # per-head sigmoid gate on attention's output
    experts: Optional[Experts] = None     # the expert layers' settings
    # per-layer list, one LayerSpec a layer: attention kind and window,
    # query heads, rope, FFN kind. None: every layer the model's settings.
    layers: Optional[Tuple[LayerSpec, ...]] = None
    # ---- a looped model; the defaults are one run and one exit -----------
    # how many times the whole stack of layers runs over the SAME weights;
    # the final norm closes every run, and its output is both what that
    # run's exit reads and what the next run starts from
    loops: int = 1
    post_norm: bool = False               # a norm on each sublayer's OUTPUT too
    # a learned exit gate (d_model -> 1, with bias) after every run: the
    # training loss is the expectation of the runs' losses under the gate's
    # exit distribution less ``exit_entropy`` times its entropy; ``output``,
    # ``eval_loss`` and the served programs read the last run's exit
    exit_gate: bool = False
    exit_entropy: float = 0.0

    def __post_init__(self):
        if self.head_dim is None and self.d_model % self.n_heads:
            raise ValueError(
                f"d_model {self.d_model} not divisible by n_heads "
                f"{self.n_heads}")
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if self.pos_embed not in ("learned", "rope"):
            raise ValueError(f"unknown pos_embed {self.pos_embed!r}")
        if self.loops < 1:
            raise ValueError(f"loops must be >= 1, got {self.loops}")
        if self.ema_decay is not None and not 0.0 < self.ema_decay < 1.0:
            raise ValueError(f"ema_decay must be in (0, 1), "
                             f"got {self.ema_decay}")
        for name, value, known in (
                ("norm", self.norm, ("layernorm", "rmsnorm")),
                ("ffn", self.ffn, ("gelu", "swiglu")),
                ("rope_layout", self.rope_layout, ("interleaved", "half"))):
            if value not in known:
                raise ValueError(f"unknown {name} {value!r}")
        if self.layers is not None:
            self.layers = tuple(self.layers)
            if len(self.layers) != self.n_layers:
                raise ValueError(f"{len(self.layers)} layers listed for "
                                 f"n_layers {self.n_layers}")
        for i in range(self.n_layers):
            spec = self.layer_spec(i)
            if spec.n_heads % self.kv_heads:
                raise ValueError(
                    f"layer {i}: n_heads {spec.n_heads} not divisible by "
                    f"n_kv_heads {self.kv_heads}")
            if spec.window is not None and spec.window < 1:
                raise ValueError(f"layer {i}: window must be >= 1")
            if spec.ffn not in ("dense", "experts"):
                raise ValueError(f"layer {i}: unknown ffn {spec.ffn!r}")
            if spec.ffn == "experts" and self.experts is None:
                raise ValueError(f"layer {i} has experts and the "
                                 "configuration's `experts` is None")
            if self.pos_embed == "rope" and spec.rope.rotated(self.hd) % 2:
                raise ValueError("rope needs an even rotated head dim")

    @property
    def hd(self):
        """A head's size, the same in every layer."""
        return self.head_dim or self.d_model // self.n_heads

    @property
    def kv_heads(self):
        return self.n_kv_heads or self.n_heads

    @property
    def kv_group(self):
        return self.n_heads // self.kv_heads

    @property
    def applications(self):
        """Block applications a forward pass makes, each with a KV cache
        entry of its own where the model is served: ``loops * n_layers``."""
        return self.loops * self.n_layers

    def layer_spec(self, i):
        """Layer ``i``'s ``LayerSpec`` with nothing left None but a full
        layer's ``window``; ``i`` may be an application's index, run after
        run (``i % n_layers`` is its layer)."""
        spec = (self.layers[i % self.n_layers] if self.layers is not None
                else LayerSpec(window=self.window))
        return replace(spec, n_heads=spec.n_heads or self.n_heads,
                       rope=spec.rope or Rope(base=self.rope_base))

    def one_block(self, what):
        """The ``LayerSpec`` every layer shares, for ``what``: a trainer that
        builds ONE block program and runs it for every layer. It refuses a
        per-layer list or experts by name, never trains every layer as
        layer 0, and a looped model (`loops`, `post_norm`, `exit_gate`)
        likewise, never as one run."""
        looped = self.loops > 1 or self.post_norm or self.exit_gate
        if self.layers is not None or self.experts is not None or looped:
            raise NotImplementedError(
                f"{what} runs one block program for every layer and is not "
                "implemented for a configuration with a per-layer list "
                "(`layers`), `experts` or a looped stack (`loops`, "
                "`post_norm`, `exit_gate`); train it through "
                "TransformerLM.fit_batch")
        return self.layer_spec(0)

    @property
    def has_experts(self):
        return any(self.layer_spec(i).ffn == "experts"
                   for i in range(self.n_layers))

    def served_without_experts(self, what):
        """``generate``, beam search and the continuous-batching programs call
        the one ``_block_apply`` and serve every setting it takes but experts,
        which they refuse by name: the expert layer's static row buffer is
        exact only up to ``expert_row_buffer`` times the balanced load, and
        its counters live beside the optimizer's state. Neither has a meaning
        for one decoded token."""
        if self.has_experts:
            raise NotImplementedError(
                f"{what} is not implemented for a configuration with "
                "experts; train and score it through fit_batch / eval_loss / "
                "output")


def _decay_mask(params):
    """GPT-2 decay discipline: weight decay applies only to matmul weight
    matrices — biases (``*_b``, which in stacked/expert layouts can be
    ndim >= 2), LayerNorm gains/biases, and position embeddings are
    exempt. Returns a 0/1 pytree matching ``params``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, a: 1.0 if (a.ndim >= 2
                                and path[-1].key != "wpe"
                                and not path[-1].key.endswith("_b"))
        else 0.0,
        params)


def _layer_norm(x, g, b, eps=1e-5):
    m = x.mean(-1, keepdims=True)
    v = ((x - m) ** 2).mean(-1, keepdims=True)
    return (x - m) / jnp.sqrt(v + eps) * g + b


def _norm(c, p, name, x):
    """The configuration's norm with the parameters ``p[name + "_g"]`` (and
    ``"_b"``): LayerNorm, or RMSNorm (a gain, no mean, no bias)."""
    if c.norm == "rmsnorm":
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x * jax.lax.rsqrt(ms + c.norm_eps).astype(x.dtype)) \
            * p[name + "_g"]
    return _layer_norm(x, p[name + "_g"], p[name + "_b"], c.norm_eps)


def _linear(c, bp, name, x):
    y = x @ bp[name]
    return y + bp[name + "_b"] if c.bias else y


def _block_apply(c, bp, x, spec, drop=None, rng=None, attend=None, ffn=None,
                 plan=None, positions=None):
    """One pre-LN block from its param dict — THE canonical block math,
    shared by TransformerLM's training step (which threads its
    residual-branch dropout in via ``drop``), its served programs (prefill
    and one-token decode, which swap the attention for one over their KV
    cache via ``attend`` and say where the tokens sit via ``positions``),
    the dropout-free PP trainer, the SP trainer (``attend``: the ring), and
    the MoE family (which swaps the dense FFN for expert routing via
    ``ffn``). Any fix here reaches every consumer; only the TP trainer
    re-derives it (its weights are partitioned, so the matmuls are
    structurally different). ``plan`` (the GSPMD ``ShardingCore`` of a
    ``shard()``-ed model) only reaches the flash-kernel route.

    ``spec`` is the layer's resolved ``LayerSpec``: ``c.layer_spec(i)``, or
    ``c.one_block(who)`` in a trainer that runs one block for every layer.
    Every choice it and ``c`` make is made at trace time. ``positions``
    ([T], or [B, T] where the rows differ; None: ``arange(T)``) are what
    rope turns by. ``attend(q, k, v)`` takes ``q`` [B, H, T, hd] and the
    GROUPED ``k``, ``v`` [B, kv_heads, T, hd], rotated, and returns
    [B, H, T, hd]. A layer with experts returns ``(x, statistics)``."""
    B, T, d = x.shape
    hd, H = c.hd, spec.n_heads
    r1 = r2 = None
    if rng is not None:
        r1, r2 = jax.random.split(rng)
    # a sublayer's output onto the residual stream, through its dropout
    add = lambda x, y, r: x + (drop(y, r) if drop else y)
    # ONE scope element an op ("block.ln1", never "ln1" inside "block"): see
    # the note at SCOPES for what the flash kernels' instruction names need
    scope = jax.named_scope
    with scope("block.ln1"):
        hloc = _norm(c, bp, "ln1", x)
    routing = None
    if spec.ffn == "experts" and ffn is None \
            and c.experts.router_input == "block":
        # the router reads what attention reads: the decision is made here,
        # crosses the attention sublayer and is applied after ``ln2``
        routing = decide(c.experts, bp["router"], hloc.reshape(B * T, d))
    with scope("block.qkv"):
        qkv = _linear(c, bp, "qkv", hloc)
    with scope(spec.attn_scope if c.layers is not None else "block.attn"):
        qd, kvd = H * hd, c.kv_heads * hd
        q, k, v = jnp.split(qkv, [qd, qd + kvd], axis=-1)
        split = lambda a, n: a.reshape(B, T, n, hd).transpose(0, 2, 1, 3)
        q = split(q, H)
        k, v = split(k, c.kv_heads), split(v, c.kv_heads)
        if c.pos_embed == "rope" and spec.rope.rotated(hd):
            pos = jnp.arange(T) if positions is None else positions
            cos, sin = _rope_cos_sin(spec.rope, hd, pos)
            if cos.ndim == 3:      # [B, T, rot/2]: the same for every head
                cos, sin = cos[:, None], sin[:, None]
            rot = spec.rope.rotated(hd) if spec.rope.share < 1.0 else None
            q = _apply_rope(q, cos, sin, c.rope_layout, rot)
            k = _apply_rope(k, cos, sin, c.rope_layout, rot)
        if attend is not None:
            o = attend(q, k, v)
        elif c.block_size:
            o = _blockwise_route(c, q, k, v, plan, spec.window)
        else:
            k, v = _full_heads(H // c.kv_heads, k, v)
            o = dense_attention(q, k, v, causal=True, window=spec.window)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, qd)
    if c.attn_gate:
        with scope("block.attn_gate"):
            gate = jax.nn.sigmoid(hloc @ bp["attn_gate"])      # [B, T, H]
            o = (o.reshape(B, T, H, hd)
                 * gate[..., None].astype(o.dtype)).reshape(B, T, qd)
    with scope("block.proj"):
        a = _linear(c, bp, "proj", o)
        if not c.post_norm:
            x = add(x, a, r1)
    if c.post_norm:   # sandwich: the sublayer's output is normed, then summed
        with scope("block.attn_norm"):
            x = add(x, _norm(c, bp, "attn_norm", a), r1)
    with scope("block.ln2"):
        hloc = _norm(c, bp, "ln2", x)
    if spec.ffn == "experts" and ffn is None:
        m, stats = expert_ffn(c.experts, bp, hloc, routing)
        if c.post_norm:
            with scope("block.mlp_norm"):
                m = _norm(c, bp, "mlp_norm", m)
        return add(x, m, r2), stats
    with scope("block.mlp"):
        if ffn is not None:
            m = ffn(bp, hloc)
        elif c.ffn == "swiglu":
            m = glu(hloc, bp["fc_gate"], bp["fc"], bp["out"])
        else:
            m = jax.nn.gelu(hloc @ bp["fc"] + bp["fc_b"]) @ bp["out"] \
                + bp["out_b"]
        if not c.post_norm:
            return add(x, m, r2)
    with scope("block.mlp_norm"):
        return add(x, _norm(c, bp, "mlp_norm", m), r2)


def _remat(blk):
    """``blk`` rematerialised: its backward recomputes it from its input,
    except the two results only the flash forward kernel can produce
    (``FLASH_RESIDUALS``: the attention output, ``B·T·H·hd`` compute-dtype
    elements, and the logsumexp, ``B·H·T`` float32 as the kernel wrote it),
    which are kept, so the kernel runs once a step and not again in the
    backward. Off the flash route nothing carries those names and only the
    input is kept."""
    return jax.checkpoint(
        blk, policy=jax.checkpoint_policies.save_only_these_names(
            *FLASH_RESIDUALS))


def _embed(c, params, tokens, positions=None):
    """Tokens [B, T] to the first block's input: ``wte``, plus the learned
    ``wpe`` at ``positions`` ([T] or [B, T]; None: the first T rows) where the
    model has one (absent under rope: rotary in-block), in the compute dtype.
    A served row coasting past the table's end reads its last row; nobody
    reads what such a row computes."""
    with jax.named_scope("embed"):
        x = params["wte"][tokens]
        if "wpe" in params:
            x = x + (params["wpe"][:tokens.shape[1]] if positions is None else
                     params["wpe"][jnp.clip(positions, 0, c.max_len - 1)])
        return x.astype(c.compute_dtype) if c.compute_dtype else x


def _cast_params(c, params):
    """The parameters as the blocks compute with them: the floating leaves in
    the compute dtype where the model has one (f32 masters stay the
    caller's)."""
    cd = c.compute_dtype
    if not cd:
        return params
    return jax.tree.map(
        lambda a: a.astype(cd) if jnp.issubdtype(a.dtype, jnp.floating)
        else a, params)


def _head(c, params, h):
    """An exit's logits in f32 from the final-normed state ``h``: the
    embedding again where it is tied, else the head."""
    with jax.named_scope("logits_loss"):
        if not c.tie_embeddings:
            return (h @ params["head"]).astype(jnp.float32)
        return (h @ params["wte"].T).astype(jnp.float32)   # tied embeddings


def _stack_runs(c, params, x, apply_block, after_run=None):
    """THE stack walk, for the training step and the served programs alike:
    the ``n_layers`` blocks ``c.loops`` times over the same (cast) ``params``,
    ``apply_block(k, block_params, x)`` for application ``k = run * n_layers +
    layer`` (the layer's index where the stack runs once), and after every
    run the final norm, whose output is what that run's exit reads
    (``after_run(run, params, h)``), what the next run starts from, and for
    the last run what is returned.

    The runs are unrolled, as the layers are. As a ``lax.scan`` over the runs
    the program is one run's size and compiles in half the time, but the
    training step of the 2.6 B looped cell then needs 5 GB more of
    temporaries (13.0 against 7.9 GB at six layers, compiled for the v5e:
    PERF.md section 6, PR 32), and the trace's readers count an instruction's
    mean run once a step (``benchmark/scope_reduce.py``), so every scope
    inside the loop would read at ``1 / loops`` of its time."""
    L = c.n_layers
    for run in range(c.loops):
        for i in range(L):
            x = apply_block(run * L + i, params[f"b{i}"], x)
        with jax.named_scope("final_ln"):
            x = _norm(c, params, "lnf", x)
        if after_run is not None:
            after_run(run, params, x)
    return x


def _forward_states(c, params, tokens, apply_block, after_run=None):
    """Embed + compute_dtype cast + the stack walk (``_stack_runs``):
    ``(the cast parameters, the last run's final-normed state)``."""
    x = _embed(c, params, tokens)
    params = _cast_params(c, params)
    return params, _stack_runs(c, params, x, apply_block, after_run)


def _forward_tokens(c, params, tokens, apply_block):
    """THE canonical token forward: ``_forward_states`` + the last exit's
    logits in f32. Shared by TransformerLM, the MoE family, and the EP
    trainer so the cast/loop/head logic exists once; the served programs
    call the same pieces around their KV caches."""
    return _head(c, *_forward_states(c, params, tokens, apply_block))


# ---- attention over a KV cache: what the served programs hand _block_apply --
def _cache_keep(positions, total, window):
    """Which of a cache's ``total`` entries the query at each of ``positions``
    ([T], or [B, T]) reads: itself and what came before, no further back than
    the layer's ``window`` (a window layer's cache is masked, not
    shortened). [1 | B, T, total] bool."""
    pos = (positions if positions.ndim == 2 else positions[None])[..., None]
    at = jnp.arange(total)
    keep = at <= pos
    if window is not None:
        keep &= at > pos - window
    return keep


def _attend_cache(q, kc, vc, keep):
    """THE attention of the served programs: ``q`` [B, H, T, hd] over a cache
    ``kc``, ``vc`` [B, kv_heads, total, hd] that already holds the T new
    entries, under ``keep`` (``_cache_keep``). Grouped scores: q is regrouped
    onto its kv head, the cache is never repeated."""
    B, H, T, hd = q.shape
    kv = kc.shape[1]
    qh = q.reshape(B, kv, H // kv, T, hd)
    s = jnp.einsum("bkgqd,bktd->bkgqt", qh, kc) / math.sqrt(hd)
    s = jnp.where(keep[:, None, None], s, -1e30)
    o = jnp.einsum("bkgqt,bktd->bkgqd", jax.nn.softmax(s, axis=-1), vc)
    return o.reshape(B, H, T, hd)


# The three cache writers, one a mode. What each leaves in the cache is the
# bit-parity contract between the served programs (tests/test_serving.py);
# which is cheapest no cell can say yet (ROADMAP S6: count bytes first), so
# they are neither merged nor chosen between here.
def _write_at(cache, new, pos):
    """Lock-step decode (``generate``, beam search): every row's one new entry
    ``new`` [B, kv_heads, 1, hd] at the same scalar ``pos``."""
    return jax.lax.dynamic_update_slice_in_dim(cache, new, pos, axis=2)


def _write_rows(cache, new, hit):
    """Continuous decode: row r's new entry where ``hit`` [B, total] is set:
    its own position if the row is active; a finished row coasting past the
    cache end matches nothing."""
    return jnp.where(hit[:, None, :, None], new, cache)


def _write_window(row, pages, hitf, wrote):
    """Prefill: window ``pages`` [kv_heads, W, hd] into ONE slot's cache row
    [kv_heads, total, hd] at the hit positions: a 0/1 einsum (exactly one
    source per written position, so the write is bit-exact) — no
    dynamic_update_slice, so a window running past ``max_len`` clips instead
    of shifting."""
    scat = jnp.einsum("wt,kwd->ktd", hitf, pages)
    return jnp.where(wrote[None, :, None], scat, row)


def _lr_at(c, t):
    """Warmup + optional cosine schedule on the config's learning rate
    (shared by the single-chip step and the TP trainer so an identical
    config can never train at different rates)."""
    lr = jnp.asarray(c.learning_rate, jnp.float32)
    if getattr(c, "lr_schedule", "constant") == "cosine":
        frac = jnp.clip((t - c.warmup_steps)
                        / max(1, c.total_steps - c.warmup_steps),
                        0.0, 1.0)
        lr = lr * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    if getattr(c, "warmup_steps", 0) > 0:
        lr = lr * jnp.minimum(1.0, t / c.warmup_steps)
    return lr


def _adamw_apply(c, params, grads, opt, t, lr_t, mask=None):
    """One bias-corrected AdamW update with the GPT-2 decay mask.

    The single shared optimizer stanza for TransformerLM, ViT, and the
    TP/PP trainers — any fix here (eps placement, decay coupling) reaches
    all of them. ``mask`` overrides the default ndim-based decay mask
    (stage-stacked layouts add leading axes that break the ndim
    heuristic). Returns ``(new_params, new_opt_state)``."""
    b1, b2 = c.beta1, c.beta2

    def upd(p, g, m, v, wd_on):
        m2 = b1 * m + (1 - b1) * g
        v2 = b2 * v + (1 - b2) * g * g
        mhat = m2 / (1 - b1 ** t)
        vhat = v2 / (1 - b2 ** t)
        p2 = p - lr_t * (
            mhat / (jnp.sqrt(vhat) + c.eps) + c.weight_decay * wd_on * p)
        return p2, m2, v2

    out = jax.tree.map(upd, params, grads, opt["m"], opt["v"],
                       mask if mask is not None else _decay_mask(params))
    is_triple = lambda o: isinstance(o, tuple)
    triples, treedef = jax.tree.flatten(out, is_leaf=is_triple)
    new_p, new_m, new_v = (treedef.unflatten(col) for col in zip(*triples))
    return new_p, {"m": new_m, "v": new_v}


def _token_nll(c, logits, targets):
    """Each token's next-token loss [B, T] from its f32 ``logits``, label
    smoothing included."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if c.label_smoothing > 0.0:
        # smoothed CE: (1-a)*nll + a*mean over the vocabulary
        a = c.label_smoothing
        nll = (1.0 - a) * nll - a * logp.mean(-1)
    return nll


def _exit_terms(c, last, ep, h, targets, m, log_surv):
    """One exit of a looped model, from its run's final-normed state ``h``
    and the exit's leaves ``ep`` (the head or the tied embedding, the gate):
    with ``lam = sigmoid(h . w + b)`` a token leaves here with probability
    ``p = lam * surv``, ``surv`` the probability that it left at no earlier
    exit (``log_surv`` [B, T], f32), and at the ``last`` exit with all that
    is left, ``p = surv``. Returns this exit's summand of the loss,
    ``sum_i m_i p_i (L_i + exit_entropy * log p_i)`` (the expected loss less
    ``exit_entropy`` times the exit distribution's entropy, ``-sum p log p``,
    exit by exit), the next exit's ``log_surv`` and the mass ``sum_i m_i
    p_i``. The logits live inside: rematerialised (``_exits_loss``), one
    exit's are alive at a time."""
    logits = _head(c, ep, h)
    with jax.named_scope("logits_loss"):
        nll = _token_nll(c, logits, targets)
        if c.z_loss > 0.0:
            nll = nll + c.z_loss * jax.nn.logsumexp(logits, axis=-1) ** 2
    with jax.named_scope("exit_gate"):
        z = (h @ ep["exit_gate"] + ep["exit_gate_b"])[..., 0] \
            .astype(jnp.float32)
        log_p = log_surv if last else log_surv + jax.nn.log_sigmoid(z)
        p = jnp.exp(log_p) * m
        return ((p * (nll + c.exit_entropy * log_p)).sum(),
                log_surv + jax.nn.log_sigmoid(-z), p.sum())


_APPLICATIONS_DOC = ("Block applications a forward pass of the last traced "
                     "TransformerLM makes: loops x n_layers")
_COUNT_LOW = 1 << 30
_MOE_DOCS = {
    "moe.local_rows": "Expert assignments that met an expert held here, "
                      "since init (read by TransformerLM.moe_counters)",
    "moe.rows_computed": "Rows the grouped expert products visited (their "
                         "row tiles, a partial one whole), since init; the "
                         "rows the layer moves follow the same walk",
    "moe.rows_over_buffer": "Expert assignments left out because the static "
                            "row buffer was full, since init (0 when sound)",
    "moe.peak_group_rows": "Assignments of the fullest held expert of a layer "
                           "and step, summed over layers and steps since init",
    "moe.even_group_rows": "moe.local_rows over the held experts' count: what "
                           "moe.peak_group_rows reads at an even load",
}


_EXIT_DOCS = {
    "exit.mass": "Tokens' summed probability of leaving at this exit of a "
                 "looped model, since init (read by "
                 "TransformerLM.exit_counters)",
    "exit.tokens": "Tokens that counted in a looped model's loss, since init",
}


def _count(total, n):
    """``total`` (int32 [2]: multiples of 2**30, and the rest) plus ``n``: a
    count carried on the device through the steps, read rarely, that a
    32-bit integer alone would not hold for long."""
    low = total[1] + n
    return jnp.stack([total[0] + low // _COUNT_LOW, low % _COUNT_LOW])


class TransformerLM:
    """Pre-LN decoder-only LM. The defaults of ``TransformerConfig`` are the
    GPT-2 block with tied input/output embeddings; its other settings (norm,
    biases, FFN kind, head, rope, gate, the per-layer list, experts) are one
    block and one step, branched at trace time."""

    def __init__(self, config: TransformerConfig):
        obs.compilation.install()
        self.conf = config
        self.params = None
        self.opt_state = None
        self.iteration = 0
        self.score_ = float("nan")
        self._step = None
        self._jit_gen = {}      # blessed _gen_signature -> compiled sampler
        self._jit_decode = {}   # blessed _decode/_admit_signature -> program
        self._data_sharding = None
        self._shard_plan = None   # ShardingCore, set by shard()
        self.listeners = []

    def set_listeners(self, *listeners):
        """IterationListener integration (optimize/listeners.py): the LM
        plugs into the same ScoreIteration/Performance/Profiler listeners
        as MLN/CG."""
        self.listeners = list(listeners)
        return self

    def clone(self):
        """Deep copy (InMemoryModelSaver contract for early stopping) —
        ``type(self)`` so subclasses (MoE) clone as themselves."""
        other = type(self)(self.conf)
        if self.params is not None:
            other.params = jax.tree.map(lambda a: a + 0, self.params)
            other.opt_state = jax.tree.map(lambda a: a + 0, self.opt_state)
        other.iteration = self.iteration
        other.score_ = self.score_
        return other

    def ema_model(self):
        """A clone evaluating with the Polyak-averaged (EMA) weights —
        the standard eval/export checkpoint when ``ema_decay`` is set."""
        if self.opt_state is None or "ema" not in self.opt_state:
            raise ValueError("ema_model() needs ema_decay set before init")
        other = self.clone()
        other.params = jax.tree.map(lambda a: a + 0, self.opt_state["ema"])
        return other

    def fsdp_trainer(self, mesh):
        """ZeRO-style training for this LM: params/grads/Adam moments
        sharded 1/N at rest (parallel.fsdp.FSDPTrainer); feed it
        (tokens, targets) batches; read back full params with
        ``trainer.gathered_params()``."""
        from deeplearning4j_tpu.parallel.fsdp import FSDPTrainer
        if self.params is None:
            self.init()
        c = self.conf

        def loss_fn(params, tokens, targets):
            return self._loss(params, tokens, targets, None)

        return FSDPTrainer(mesh, self.params, loss_fn, lr=c.learning_rate,
                           beta1=c.beta1, beta2=c.beta2, eps=c.eps,
                           weight_decay=c.weight_decay,
                           weight_decay_mask=_decay_mask(self.params))

    def shard(self, mesh, axis="data", level=None):
        """Data-parallel placement over ``mesh`` through the unified
        sharding core (parallel/sharding_core.py, docs/PARALLELISM.md):
        batches shard on ``axis`` and params/optimizer state place at the
        ``DL4J_TPU_DP_SHARD`` ZeRO level (``level`` overrides) — level 0
        replicates everything (the historical behaviour), level 1 shards
        the adamw m/v 1/N, level 2 additionally reduce-scatters gradients
        inside the step, level 3 keeps the params sharded between steps
        and all-gathers them just-in-time for the forward. GSPMD
        partitions the jitted step and places the collectives over ICI
        (ParallelWrapper semantics for the transformer family)."""
        from deeplearning4j_tpu.parallel.sharding_core import ShardingCore
        if self.params is None:
            self.init()
        core = ShardingCore(mesh, level=level, batch_axis=axis)
        self._shard_plan = core
        self._data_sharding = core.data_sharding()
        self.params = core.place_params(self.params)
        self.opt_state = core.place_updater(self.opt_state)
        # control state rides replicated, committed BEFORE the first
        # dispatch so its input shardings equal every later dispatch's
        # (the previous program's mesh-committed outputs) — without this
        # the second-ever dispatch recompiles (the _place_model contract)
        self.iteration = core.place_replicated(
            np.asarray(self.iteration, np.int32))
        if getattr(self, "_rng", None) is None:
            self._rng = jax.random.PRNGKey(self.conf.seed + 1)
        self._rng = core.place_replicated(self._rng)
        self._step = None   # the compiled step bakes the plan in
        return self

    # ---- parameters ----------------------------------------------------
    def init(self):
        c = self.conf
        ks = jax.random.split(jax.random.PRNGKey(c.seed), 4 + 8 * c.n_layers)
        d, h, hd = c.d_model, c.d_ff, c.hd
        std = 0.02
        normal = lambda key, shape, s=std: s * jax.random.normal(key, shape)
        # residual-branch output projections scaled 1/sqrt(2L) (GPT-2)
        rs = std / math.sqrt(2 * c.n_layers)

        def norm(p, name):
            p[name + "_g"] = jnp.ones((d,))
            if c.norm == "layernorm":
                p[name + "_b"] = jnp.zeros((d,))

        def linear(p, name, key, shape, s=std):
            p[name] = normal(key, shape, s)
            if c.bias:
                p[name + "_b"] = jnp.zeros(shape[-1:])

        p = {"wte": normal(ks[0], (c.vocab_size, d))}
        norm(p, "lnf")
        if c.pos_embed == "learned":   # rope needs no position table
            p["wpe"] = normal(ks[1], (c.max_len, d))
        if not c.tie_embeddings:
            p["head"] = normal(ks[2], (d, c.vocab_size))
        if c.exit_gate:
            p["exit_gate"] = normal(ks[3], (d, 1))
            p["exit_gate_b"] = jnp.zeros((1,))
        for i in range(c.n_layers):
            k = ks[4 + 8 * i:4 + 8 * (i + 1)]
            spec = c.layer_spec(i)
            qd = spec.n_heads * hd
            bp = p[f"b{i}"] = {}
            norm(bp, "ln1")
            # GQA shrinks the K/V projections: q keeps n_heads*hd columns,
            # k/v carry kv_heads*hd each (all three d for MHA)
            linear(bp, "qkv", k[0], (d, qd + 2 * c.kv_heads * hd))
            linear(bp, "proj", k[1], (qd, d), rs)
            norm(bp, "ln2")
            if c.post_norm:
                norm(bp, "attn_norm")
                norm(bp, "mlp_norm")
            if c.attn_gate:
                bp["attn_gate"] = normal(k[4], (d, spec.n_heads))
            if spec.ffn == "experts":
                ex = c.experts
                count, f = ex.held_range[1], ex.d_expert
                bp["router"] = normal(k[2], (d, ex.n_experts))
                bp["W_gate"] = normal(k[3], (count, d, f))
                bp["W_up"] = normal(k[5], (count, d, f))
                bp["W_down"] = normal(k[6], (count, f, d), rs)
                if ex.d_shared:
                    k7 = jax.random.split(k[7], 3)
                    bp["sh_gate"] = normal(k7[0], (d, ex.d_shared))
                    bp["sh_up"] = normal(k7[1], (d, ex.d_shared))
                    bp["sh_down"] = normal(k7[2], (ex.d_shared, d), rs)
                continue
            linear(bp, "fc", k[2], (d, h))
            linear(bp, "out", k[3], (h, d), rs)
            if c.ffn == "swiglu":
                bp["fc_gate"] = normal(k[5], (d, h))
        self.params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), p)
        self._init_opt_state()
        return self

    def _init_opt_state(self):
        """AdamW's moments at zero beside ``self.params`` (and the Polyak
        shadow, and the expert layers' counters, where the configuration has
        them)."""
        c = self.conf
        self.opt_state = {
            "m": jax.tree.map(jnp.zeros_like, self.params),
            "v": jax.tree.map(jnp.zeros_like, self.params),
        }
        if c.ema_decay is not None:   # Polyak shadow starts at the init
            self.opt_state["ema"] = jax.tree.map(lambda a: a + 0,
                                                 self.params)
        if c.has_experts:
            self.opt_state["moe"] = {k: jnp.zeros((2,), jnp.int32)
                                     for k in STATS}
        if c.exit_gate:
            self.opt_state["exit"] = {
                "mass": jnp.zeros((c.loops, 2), jnp.int32),
                "tokens": jnp.zeros((2,), jnp.int32)}

    def num_params(self):
        return sum(int(np.prod(a.shape))
                   for a in jax.tree.leaves(self.params))

    def moe_counters(self):
        """The expert layers' counts since ``init``, summed over layers and
        steps: ``moe.local_rows`` (assignments that met a held expert),
        ``moe.rows_computed`` (rows the grouped products visited: their row
        tiles, a partial one whole, the buffer's empty tail not at all; the
        layer MOVES rows along the same walk, ``gather_rows`` into those
        tiles and ``combine_rows`` out of them, a tile two experts share
        once, so this bounds the rows moved as well as multiplied),
        ``moe.rows_over_buffer`` (assignments left out because
        the row buffer was full: 0 in a sound run) and ``moe.peak_group_rows``
        (a layer's and step's fullest held expert's assignments). They are
        carried on the device beside the optimizer's state and fetched HERE,
        one sync, so never call it inside a timed loop; the ``obs.metrics``
        gauges of the same names take what was read. Beside them ``moe.even_group_rows``,
        ``moe.local_rows`` over the held experts' count (linear in a counter,
        so a caller's difference of two reads is right for it too): what
        ``moe.peak_group_rows`` reads when the held experts are evenly
        loaded. ``{}`` for a model without experts."""
        moe = (self.opt_state or {}).get("moe")
        if moe is None:
            return {}
        host = jax.device_get(moe)
        out = {f"moe.{k}": int(v[0]) * _COUNT_LOW + int(v[1])
               for k, v in host.items()}
        out["moe.even_group_rows"] = out["moe.local_rows"] \
            / self.conf.experts.held_range[1]
        for name, n in out.items():
            obs.metrics.gauge(name, _MOE_DOCS[name]).set(n)
        return out

    def exit_counters(self):
        """The exits' counts since ``init``, summed over steps:
        ``exit.mass`` (a list, one an exit: the tokens' summed probability
        of leaving there, a step's sum rounded to whole tokens) and
        ``exit.tokens`` (the tokens that counted in a loss), so that
        ``mass[t] / tokens`` is the share of the loss exit ``t`` carried.
        Carried on the device and fetched HERE as ``moe_counters`` does: one
        sync, never inside a timed loop; the gauges ``exit.tokens`` and
        ``exit.mass.<t>`` (t from 1) take what was read. ``{}`` for a model
        without an exit gate."""
        kept = (self.opt_state or {}).get("exit")
        if kept is None:
            return {}
        host = jax.device_get(kept)
        whole = lambda v: int(v[0]) * _COUNT_LOW + int(v[1])
        out = {"exit.mass": [whole(v) for v in host["mass"]],
               "exit.tokens": whole(host["tokens"])}
        obs.metrics.gauge("exit.tokens", _EXIT_DOCS["exit.tokens"]).set(
            out["exit.tokens"])
        for t, n in enumerate(out["exit.mass"], 1):
            obs.metrics.gauge(f"exit.mass.{t}",
                              _EXIT_DOCS["exit.mass"]).set(n)
        return out

    # ---- forward -------------------------------------------------------
    def _drop(self, x, rng):
        """Inverted dropout on a residual branch; identity when rng is None
        (eval/generate) or rate is 0."""
        rate = self.conf.dropout
        if rng is None or rate <= 0.0:
            return x
        keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
        return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)

    def _block(self, spec, bp, x, rng=None):
        return _block_apply(self.conf, bp, x, spec, drop=self._drop, rng=rng,
                            plan=self._shard_plan)

    def _states(self, params, tokens, rng=None, stats=None, after_run=None):
        """``_forward_states`` with this model's block: dropout where it
        trains with one, each block rematerialised where ``remat`` is set.
        ``stats``: a list that takes each expert layer's statistics (they
        leave a rematerialised block as outputs of it); None drops them."""
        c = self.conf
        n = c.applications
        rngs = (jax.random.split(rng, n)
                if rng is not None and c.dropout > 0 else [None] * n)
        obs.metrics.gauge("lm.block_applications", _APPLICATIONS_DOC).set(n)

        def apply(k, bp, x):
            spec = c.layer_spec(k)
            blk = functools.partial(self._block, spec)
            out = (_remat(blk) if c.remat else blk)(bp, x, rngs[k])
            if spec.ffn != "experts":
                return out
            if stats is not None:
                stats.append(out[1])
            return out[0]

        return _forward_states(c, params, tokens, apply, after_run)

    def _logits(self, params, tokens, rng=None, stats=None):
        """The last exit's logits."""
        return _head(self.conf, *self._states(params, tokens, rng, stats))

    def _loss(self, params, tokens, targets, mask, rng=None, stats=None,
              exits=None):
        """The training loss: the last exit's (every model's only one), or
        with an exit gate the loss over all the exits (``_exits_loss``:
        ``exits``, a list, then takes each exit's mass)."""
        if self.conf.exit_gate:
            return self._exits_loss(params, tokens, targets, mask, rng, stats,
                                    exits)
        return self._last_exit_loss(params, tokens, targets, mask, rng, stats)

    def _last_exit_loss(self, params, tokens, targets, mask, rng=None,
                        stats=None):
        """Mean next-token loss of the last exit's logits: the model as it is
        served (``eval_loss``), and the training loss where there is no exit
        gate."""
        c = self.conf
        logits = self._logits(params, tokens, rng, stats)
        with jax.named_scope("logits_loss"):
            nll = _token_nll(c, logits, targets)
            m = jnp.ones_like(nll) if mask is None else mask.astype(nll.dtype)
            denom = jnp.maximum(m.sum(), 1.0)
            loss = (nll * m).sum() / denom
            if c.z_loss > 0.0:
                # PaLM z-loss: pulls log Z toward 0, stabilizing bf16 logits
                z = jax.nn.logsumexp(logits, axis=-1)
                loss = loss + c.z_loss * ((z ** 2) * m).sum() / denom
            return loss

    def _exits_loss(self, params, tokens, targets, mask, rng, stats, exits):
        """``_loss`` of a model with an exit gate: the expectation of the
        exits' losses under the gate's exit distribution less
        ``exit_entropy`` times its entropy, every run's exit through
        ``_exit_terms``, rematerialised where the blocks are, so that the
        backward holds one exit's vocabulary-wide logits at a time and the
        forward none."""
        c = self.conf
        m = (jnp.ones(targets.shape, jnp.float32) if mask is None
             else mask.astype(jnp.float32))
        total, log_surv = 0.0, jnp.zeros(targets.shape, jnp.float32)
        leaves = ("wte" if c.tie_embeddings else "head", "exit_gate",
                  "exit_gate_b")

        def after_run(run, cast, h):
            nonlocal total, log_surv
            terms = functools.partial(_exit_terms, c, run == c.loops - 1)
            if c.remat:
                terms = jax.checkpoint(terms)
            term, log_surv, mass = terms({k: cast[k] for k in leaves}, h,
                                         targets, m, log_surv)
            total = total + term
            if exits is not None:
                exits.append(mass)

        self._states(params, tokens, rng, stats, after_run)
        return total / jnp.maximum(m.sum(), 1.0)

    # ---- training ------------------------------------------------------
    def _build_step(self):
        c = self.conf
        # GSPMD sharding plan (parallel/sharding_core.py), set by
        # shard(): level >= 2 reduce-scatters grads before the adamw
        # math, level 3 gathers the 1/N param shards just-in-time for
        # the forward; None (unsharded model) traces the plain step
        plan = self._shard_plan

        def step(params, opt, it, rng, tokens, targets, mask):
            rng, sub = jax.random.split(rng)
            fwd_params = params if plan is None else plan.gather_params(params)
            sub = sub if c.dropout > 0 else None
            if c.has_experts or c.exit_gate:
                def loss_and_stats(p):
                    found, exits = [], []
                    loss = self._loss(p, tokens, targets, mask, sub, found,
                                      exits)
                    stats = {k: sum(f[k] for f in found) for k in STATS} \
                        if c.has_experts else None
                    return loss, (stats, exits)
                (loss, (stats, exits)), grads = jax.value_and_grad(
                    loss_and_stats, has_aux=True)(fwd_params)
            else:
                loss, grads = jax.value_and_grad(self._loss)(
                    fwd_params, tokens, targets, mask, sub)
            if plan is not None:
                grads = plan.constrain_grads(grads)
            if c.grad_clip_norm is not None:
                # global-norm clipping (the reference's ClipL2PerParamType
                # role for this family, applied across the whole tree)
                with jax.named_scope("grad_clip"):
                    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                      for g in jax.tree.leaves(grads)))
                    scale = jnp.minimum(1.0, c.grad_clip_norm
                                        / jnp.maximum(gn, 1e-12))
                    grads = jax.tree.map(lambda g: g * scale, grads)
            with jax.named_scope("optimizer"):
                t = it + 1
                new_p, new_opt = _adamw_apply(c, params, grads, opt, t,
                                              _lr_at(c, t))
                if c.ema_decay is not None:
                    d = c.ema_decay
                    new_opt["ema"] = jax.tree.map(
                        lambda e, p: d * e + (1.0 - d) * p, opt["ema"], new_p)
            if c.has_experts:
                new_opt["moe"] = {k: _count(opt["moe"][k], stats[k])
                                  for k in STATS}
            if c.exit_gate:
                # in whole tokens: an exit's summed probability, rounded
                mass = jnp.round(jnp.stack(exits)).astype(jnp.int32)
                counted = targets.size if mask is None else \
                    jnp.sum(mask != 0).astype(jnp.int32)
                new_opt["exit"] = {
                    "mass": jax.vmap(_count)(opt["exit"]["mass"], mass),
                    "tokens": _count(opt["exit"]["tokens"], counted)}
            if plan is not None:
                # pin updated state to its at-rest placement: level <= 2
                # all-gathers the sharded delta onto the replicated
                # params; level 3 keeps the shards between steps
                new_p = plan.constrain_params(new_p)
                new_opt = plan.constrain_updater(new_opt)
            return new_p, new_opt, t, rng, loss

        return jax.jit(step, donate_argnums=(0, 1, 3))

    def fit_batch(self, tokens, targets=None, mask=None):
        """One LM step. ``targets=None`` trains next-token on ``tokens``
        (inputs = tokens[:, :-1], targets = tokens[:, 1:])."""
        if self.params is None:
            self.init()
        with obs.span("lm.h2d"):
            tokens = jnp.asarray(tokens, jnp.int32)
            if targets is None:
                tokens, targets = tokens[:, :-1], tokens[:, 1:]
            else:
                targets = jnp.asarray(targets, jnp.int32)
            if self._data_sharding is not None:
                tokens = jax.device_put(tokens, self._data_sharding)
                targets = jax.device_put(targets, self._data_sharding)
                if mask is not None:
                    mask = jax.device_put(jnp.asarray(mask),
                                          self._data_sharding)
        if getattr(self, "_rng", None) is None:
            self._rng = jax.random.PRNGKey(self.conf.seed + 1)
        if getattr(self, "_it_host", None) is None:
            # host-side mirror of the (device-carried) step counter so the
            # per-step listener callback never forces a device->host fetch
            self._it_host = int(self.iteration)  # graftlint: disable=G001 -- one-time adoption sync, not per-step
        if self._step is None:
            # the first call of a new step traces, lowers and compiles it:
            # bracketed whole, so that the compile log and a trace lay that
            # time to ``lm.step``; the call itself is the path below
            with obs.building("lm.step"):
                self._step = self._build_step()
                return self.fit_batch(tokens, targets, mask)
        with obs.span("lm.step_call"):
            (self.params, self.opt_state, self.iteration, self._rng,
             loss) = self._step(self.params, self.opt_state, self.iteration,
                                self._rng, tokens, targets, mask)
        # device scalar, synced lazily on read (the MLN discipline): the
        # host loop must not block on a device->host fetch every step
        self.score_ = loss
        self._it_host += 1
        if self.listeners:
            with obs.span("lm.listeners"):
                for lst in self.listeners:
                    lst.iteration_done(self, self._it_host)
        return self.score_

    def fit(self, data, *, epochs=1):
        """Train over ``data``: one token batch (array) or an iterable of
        batches — the MLN fit() surface, so the LM drops into
        EarlyStoppingTrainer and listener-driven loops unchanged."""
        is_iterable = (hasattr(data, "__next__") or hasattr(data, "reset")
                       or isinstance(data, (list, tuple)))
        if epochs > 1 and hasattr(data, "__next__") \
                and not hasattr(data, "reset"):
            # a plain generator exhausts after epoch 1 — materialize it so
            # every epoch sees the data
            data = list(data)
        for _ in range(epochs):
            if not is_iterable:
                self.fit_batch(np.asarray(data))
                continue
            if hasattr(data, "reset"):
                data.reset()
            for batch in data:
                self.fit_batch(batch)
        return self

    def eval_loss(self, tokens):
        """Mean next-token NLL on held-out tokens (no update); of the last
        exit where the model has several."""
        tokens = jnp.asarray(tokens, jnp.int32)
        return float(self._last_exit_loss(self.params, tokens[:, :-1],
                                          tokens[:, 1:], None))

    def perplexity(self, tokens):
        return float(np.exp(self.eval_loss(tokens)))

    def output(self, tokens):
        """Logits [B, T, V] as HOST numpy (no update) — the same
        eval-seam contract as MLN/CG output(): one fetch per call, so a
        serving batch's sync happens HERE (timed, metered) and a row
        handed to a slow caller never pins the whole batch's device
        logits buffer."""
        from deeplearning4j_tpu.models._device_state import \
            _OBS_OUTPUT_SECONDS
        with _OBS_OUTPUT_SECONDS.time():
            # graftlint: disable=G001 -- output()'s contract IS the eval seam: it returns host numpy once per request, after the whole program ran
            return np.asarray(
                self._logits(self.params, jnp.asarray(tokens, jnp.int32)))

    # ---- generation ----------------------------------------------------
    def generate(self, prompt, n_new, *, temperature=1.0, seed=0,
                 top_k=None, top_p=None, repetition_penalty=None):
        """Autoregressive sampling: ONE jitted ``lax.scan`` with a
        preallocated KV cache (static shapes; greedy for temperature=0).
        ``top_k`` keeps the k most likely tokens; ``top_p`` keeps the
        smallest nucleus whose probability mass reaches p (composable —
        top_k prunes first). ``repetition_penalty`` > 1 divides the
        logits of every already-emitted token (CTRL-style; applied
        before the filters).

        prompt: [B, P] int tokens; returns [B, P + n_new]."""
        c = self.conf
        prompt = jnp.asarray(prompt, jnp.int32)
        B, P = prompt.shape
        total = P + n_new
        if total > c.max_len:
            raise ValueError(f"P+n_new={total} exceeds max_len={c.max_len}")
        if top_k is not None and not 1 <= int(top_k) <= c.vocab_size:
            raise ValueError(f"top_k must be in [1, {c.vocab_size}]")
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError("top_p must be in (0, 1]")
        if repetition_penalty is not None and float(repetition_penalty) <= 0:
            raise ValueError("repetition_penalty must be > 0")
        sig = self._gen_signature("sample", B, P, n_new,
                                  float(temperature), top_k and int(top_k),
                                  top_p and float(top_p),
                                  repetition_penalty
                                  and float(repetition_penalty))
        fn = self._jit_gen.get(sig)
        if fn is None:
            self._evict_gen()
            fn = self._build_generate(B, P, n_new, float(temperature),
                                      top_k and int(top_k),
                                      top_p and float(top_p),
                                      repetition_penalty
                                      and float(repetition_penalty))
            self._jit_gen[sig] = fn
        # graftlint: disable=G001 -- generate()'s contract: the sampled tokens come back to the host once per request, after the scan ran
        return np.asarray(fn(self.params, prompt, jax.random.PRNGKey(seed)))

    @staticmethod
    def _filter_logits_rows(logits, k, p):
        """Per-ROW top-k/nucleus filtering for the continuous-batching
        decode step: ``k``/``p`` are [B] device vectors riding the slot
        state, so every request's sampler shares one compiled program
        (``k = vocab_size`` / ``p = 1.0`` disable a row). Same semantics
        as :meth:`_filter_logits` (top-k prunes first; nucleus mass over
        the pruned distribution), rank-based so k can vary per row."""
        B, V = logits.shape
        idx = jnp.argsort(-logits, axis=-1)
        srt = jnp.take_along_axis(logits, idx, axis=-1)
        rank_keep = jnp.arange(V)[None, :] < k[:, None]
        probs = jax.nn.softmax(jnp.where(rank_keep, srt, -jnp.inf),
                               axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens BEFORE the mass crosses p (always >= 1 token)
        keep_sorted = rank_keep & ((cum - probs) < p[:, None])
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(B)[:, None], idx].set(keep_sorted)
        return jnp.where(keep, logits, -jnp.inf)

    @staticmethod
    def _filter_logits(logits, top_k, top_p):
        """Top-k / nucleus filtering: out-of-set logits to -inf. Static
        shapes throughout (sort + cumsum), so it jits into the scan."""
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p is not None and top_p < 1.0:
            idx = jnp.argsort(-logits, axis=-1)
            srt = jnp.take_along_axis(logits, idx, axis=-1)
            probs = jax.nn.softmax(srt, axis=-1)
            # keep tokens BEFORE the mass crosses p (always >= 1 token)
            cum = jnp.cumsum(probs, axis=-1)
            keep_sorted = (cum - probs) < top_p
            keep = jnp.zeros_like(keep_sorted).at[
                jnp.arange(logits.shape[0])[:, None], idx].set(keep_sorted)
            logits = jnp.where(keep, logits, -jnp.inf)
        return logits

    def _cache_dtype(self):
        """KV caches follow the compute dtype: a bf16-trained model
        decodes with a half-size cache (and MXU-friendly decode matmuls);
        logits still come back f32 (the _forward_tokens discipline)."""
        return self.conf.compute_dtype or jnp.float32

    # ---- blessed inference-signature builders --------------------------
    def _gen_signature(self, kind, B, P, n_new, *extra):
        """Compiled-sampler cache key (``_jit_gen``): everything a
        ``generate``/``beam_search`` program's trace depends on. The
        BLESSED builder graftlint G017 enforces — ad-hoc tuples beside it
        are findings."""
        return (kind, B, P, n_new) + tuple(extra)

    def _evict_gen(self):
        """FIFO-bound ``_jit_gen`` at ``DL4J_TPU_SERVE_GEN_CACHE``
        signatures before a fresh build: a long-lived server answering
        many distinct (B, P, n_new, sampler) shapes must never pin an
        unbounded set of compiled programs (graftlint G021's concern)."""
        bound = env_int("DL4J_TPU_SERVE_GEN_CACHE", minimum=1)
        while len(self._jit_gen) >= bound:
            self._jit_gen.pop(next(iter(self._jit_gen)))

    def _decode_signature(self, slots, chunk, window):
        """Continuous-batching decode-step cache key (``_jit_decode``):
        slot width, steps-per-dispatch, and the KV attention-window rung
        are the only request-independent trace parameters (max_len/
        dtype/arch ride the conf). ``window`` is one rung of the paged-
        attention ladder — the scheduler dispatches each chunk at the
        smallest rung covering the pool's max active position, so each
        rung is one blessed compiled program."""
        return ("decode", slots, chunk, window)

    def _admit_signature(self, slots):
        """Slot-admission program cache key (``_jit_decode``)."""
        return ("admit", slots)

    def _prefill_signature(self, slots, window):
        """Chunked-prefill program cache key (``_jit_decode``): one
        blessed compiled program per prompt-window rung — a prefill
        dispatch ingests ``window`` prompt tokens for one slot at once
        (traced start offset / valid count, so every window of every
        prompt shares the rung's program)."""
        return ("prefill", slots, window)

    # ---- continuous-batching decode (serving/decode.py drives this) ----
    def _init_decode_state(self, slots, seed=0):
        """Fresh continuous-batching decode state: the PERSISTENT
        [slots, kv_heads, max_len, hd] KV slot pool (allocated once,
        reused across every request — the G021 contract) plus per-row
        counters. HOST mirrors of pos/plen/nnew/active live with the
        scheduler (serving/decode.py); the device copies here are the
        traced truth."""
        c = self.conf
        c.served_without_experts(
            "the continuous-batching KV slot pool (ContinuousLM)")
        hd = c.hd
        total = c.max_len
        cdt = self._cache_dtype()
        S = slots
        return {
            "k": [jnp.zeros((S, c.kv_heads, total, hd), cdt)
                  for _ in range(c.applications)],
            "v": [jnp.zeros((S, c.kv_heads, total, hd), cdt)
                  for _ in range(c.applications)],
            "pos": jnp.zeros((S,), jnp.int32),
            "last": jnp.zeros((S,), jnp.int32),
            "out": jnp.zeros((S, total), jnp.int32),
            "prompts": jnp.zeros((S, total), jnp.int32),
            "plen": jnp.ones((S,), jnp.int32),
            "nnew": jnp.zeros((S,), jnp.int32),
            "temp": jnp.zeros((S,), jnp.float32),
            # per-slot sampler params (the serving tier's per-request
            # top_k/top_p): k = vocab_size and p = 1.0 disable filtering
            # for a row, so the state shape — and with it the decode
            # signature — is identical whether or not a request samples
            "topk": jnp.full((S,), c.vocab_size, jnp.int32),
            "topp": jnp.ones((S,), jnp.float32),
            "active": jnp.zeros((S,), bool),
            # per-row request seeds + a CONSTANT pool base key: sampling
            # keys are derived counter-style as
            # fold_in(fold_in(rng, seed[r]), pos[r]) — never a carried
            # stream, so admit/decode interleaving cannot shift them
            "seed": jnp.zeros((S,), jnp.int32),
            "rng": jax.random.PRNGKey(seed),
        }

    def _build_decode_step(self, S, chunk, W):
        """ONE compiled program advancing every active slot by ``chunk``
        tokens: prompt prefill and sampling share the step (a row whose
        position is still inside its prompt is teacher-forced from the
        slot's prompt buffer; past it, the sampled token feeds back).
        Generated tokens land in the slot's ``out`` row on device — the
        host fetches a row once, when the request completes.

        ``W`` is the KV attention-window rung: the scan runs over the
        FIRST ``W`` positions of the persistent ``max_len`` slot pool
        (one slice before, one write-back after — paged attention), so a
        pool of short conversations pays W-length attention, not
        max_len. The scheduler guarantees every active row's position
        stays below ``W`` for the whole chunk; the causal keep-mask is
        unchanged, so a W == max_len rung is bit-identical to the
        un-paged program."""
        from deeplearning4j_tpu.models._device_state import fuse_unroll
        c = self.conf
        total = c.max_len
        W = min(W, total)
        row_step = self._make_token_step(W, vector_pos=True)
        rows = jnp.arange(S)

        def chunk_run(params, state):
            plen, nnew = state["plen"], state["nnew"]
            prompts, temp = state["prompts"], state["temp"]
            topk, topp = state["topk"], state["topp"]
            active = state["active"]
            # counter-based per-row sampling keys: every step's key is a
            # pure function of (pool base key, request seed, row position),
            # NOT of a carried stream — so a sampled row's tokens are
            # bitwise-reproducible no matter how decode chunks interleave
            # with admits on other slots (the detlint mixed-pool parity
            # gate; a carried pool-wide rng made sampled serving depend on
            # scheduler thread timing)
            base, seeds = state["rng"], state["seed"]
            row_key = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.fold_in(base, s),
                                                p))

            def one(carry, _):
                kcs, vcs, pos, last, out = carry
                subs = row_key(seeds, pos)
                ptok = prompts[rows, jnp.clip(pos, 0, total - 1)]
                cur = jnp.where(pos < plen, ptok, last)
                logits, kcs, vcs = row_step(params, cur, pos, kcs, vcs,
                                            write=active)
                # per-row top-k/top-p as state, not trace parameters:
                # k = vocab / p = 1.0 rows pass through unfiltered, so
                # every sampler mix shares this ONE compiled signature.
                # The filter's argsort is gated behind a traced cond —
                # ONE program either way, but an all-greedy/unfiltered
                # pool (the common serving case) never pays the per-step
                # sort
                need = jnp.any((topk < c.vocab_size) | (topp < 1.0))
                flt = jax.lax.cond(
                    need,
                    lambda lg: self._filter_logits_rows(lg, topk, topp),
                    lambda lg: lg,
                    logits)
                scaled = flt / jnp.maximum(temp, 1e-6)[:, None]
                samp = jnp.where(
                    temp > 0.0,
                    jax.vmap(lambda k, lg: jax.random.categorical(k, lg))(
                        subs, scaled),
                    jnp.argmax(logits, axis=-1)).astype(jnp.int32)
                # the token sampled after position pos sits at generation
                # index pos+1-plen; rows still prefilling (gi < 0) and
                # rows past their request length (gi >= nnew) write nothing
                gi = pos + 1 - plen
                oh = (jnp.arange(total)[None, :] == gi[:, None]) \
                    & (active & (gi >= 0) & (gi < nnew))[:, None]
                out = jnp.where(oh, samp[:, None], out)
                last = jnp.where(active, samp, last)
                pos = pos + active.astype(pos.dtype)
                return (tuple(kcs), tuple(vcs), pos, last, out), None

            if W < total:   # paged: the scan carries only the rung window
                kws = tuple(jax.lax.slice_in_dim(b, 0, W, axis=2)
                            for b in state["k"])
                vws = tuple(jax.lax.slice_in_dim(b, 0, W, axis=2)
                            for b in state["v"])
            else:
                kws, vws = tuple(state["k"]), tuple(state["v"])
            carry = (kws, vws, state["pos"],
                     state["last"], state["out"])
            carry, _ = jax.lax.scan(one, carry, None, length=chunk,
                                    unroll=fuse_unroll(chunk))
            kcs, vcs, pos, last, out = carry
            if W < total:   # write the window back into the donated pool
                kcs = tuple(jax.lax.dynamic_update_slice_in_dim(
                    b, w, 0, axis=2) for b, w in zip(state["k"], kcs))
                vcs = tuple(jax.lax.dynamic_update_slice_in_dim(
                    b, w, 0, axis=2) for b, w in zip(state["v"], vcs))
            return dict(state, k=list(kcs), v=list(vcs), pos=pos,
                        last=last, out=out)

        return jax.jit(chunk_run, donate_argnums=(1,))

    def _build_admit(self, S):
        """Slot (re)assignment as ONE compiled program: the slot index and
        per-request scalars are traced arguments, so admitting into any of
        the ``S`` rows — or freeing one (``active1=0``) — reuses the same
        signature. The freed row's KV cache is NOT cleared: its position
        counter resets to 0 and the causal keep-mask hides every stale
        entry past it."""

        def admit(state, slot, prompt_row, plen1, nnew1, temp1, topk1,
                  topp1, active1, seed1):
            one = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
                buf, jnp.asarray([val]).astype(buf.dtype), slot, axis=0)
            zrow = jnp.zeros((1,) + state["out"].shape[1:],
                             state["out"].dtype)
            return dict(
                state,
                prompts=jax.lax.dynamic_update_slice(
                    state["prompts"], prompt_row[None, :], (slot, 0)),
                out=jax.lax.dynamic_update_slice(state["out"], zrow,
                                                 (slot, 0)),
                pos=one(state["pos"], 0),
                last=one(state["last"], 0),
                plen=one(state["plen"], jnp.maximum(plen1, 1)),
                nnew=one(state["nnew"], nnew1),
                temp=one(state["temp"], temp1),
                topk=one(state["topk"], topk1),
                topp=one(state["topp"], topp1),
                active=one(state["active"], active1),
                seed=one(state["seed"], seed1),
            )

        return jax.jit(admit, donate_argnums=(0,))

    def _decode_fns(self, slots, chunk, window):
        """The (admit, step) compiled pair for a (slot width, KV window
        rung), cached under the blessed ``_decode_signature``/
        ``_admit_signature`` keys — the serving tier's whole steady
        state is the rung-ladder programs plus ONE admit signature (the
        admit program writes whole ``max_len`` rows, so it is
        window-independent)."""
        ks = self._decode_signature(slots, chunk, window)
        if ks not in self._jit_decode:
            self._jit_decode[ks] = self._build_decode_step(slots, chunk,
                                                           window)
        ka = self._admit_signature(slots)
        if ka not in self._jit_decode:
            self._jit_decode[ka] = self._build_admit(slots)
        return self._jit_decode[ka], self._jit_decode[ks]

    def _prefill_fn(self, slots, window):
        """The compiled chunked-prefill program for a prompt-window
        rung, cached under the blessed ``_prefill_signature`` key."""
        kp = self._prefill_signature(slots, window)
        if kp not in self._jit_decode:
            self._jit_decode[kp] = self._build_prefill(slots, window)
        return self._jit_decode[kp]

    def _build_prefill(self, S, W):
        """Chunked prompt prefill as ONE compiled program per window
        rung: ingest ``W`` prompt tokens of ONE slot in a single
        parallel forward (one gemm over the window instead of W serial
        scan steps — the dispatch-count lesson of the fused-RNN loop
        applied to prompts), writing their K/V into the slot's cache
        row. Slot index, window start, valid-token count, and the
        final/inject flags are traced, so every window of every prompt
        shares the rung's program.

        Bit-parity contract: K/V values land EXACTLY as the decode
        step's teacher-forced path would have written them (same
        per-position math, same cache dtype, causal masking over a
        suffix so softmax denominators match), and the scheduler leaves
        ``pos`` at ``plen - 1`` — the decode chunk re-processes the LAST
        prompt token (an idempotent cache write) and samples from its
        logits, so the first sampled token needs no logits output here.

        With ``inject`` set the forward is skipped entirely
        (``lax.cond``) and the provided K/V pages — a prefix-cache hit,
        computed by an earlier dispatch of this same program — are
        written instead. Either way the program returns the window's
        pages ``[applications, kv_heads, W, hd]`` (one entry a run and layer:
        ``c.applications``) so the scheduler can memoise them."""
        c = self.conf
        c.served_without_experts("chunked prefill (ContinuousLM)")
        hd = c.hd
        total = c.max_len
        cdt = self._cache_dtype()
        win = jnp.arange(W)
        tpos = jnp.arange(total)

        def hits(start, nvalid):
            """The window's positions, and where its valid tokens land in a
            cache row: ``hit`` [W, total] as 0/1 in the cache dtype, and which
            of the row's entries are written."""
            pos_w = start + win
            hit = (tpos[None, :] == pos_w[:, None]) \
                & (win < nvalid)[:, None]
            return pos_w, hit.astype(cdt), hit.any(axis=0)

        def forward(params, toks, start, nvalid, krows, vrows):
            pos_w, hitf, wrote = hits(start, nvalid)
            x = _embed(c, params, toks[None], pos_w)         # [1, W, d]
            params = _cast_params(c, params)
            new_k, new_v, pk, pv = [], [], [], []

            def apply(i, bp, x):
                spec = c.layer_spec(i)
                keep = _cache_keep(pos_w, total, spec.window)

                def attend(q, k, v):
                    # window K/V land in the cache row BEFORE attention, so
                    # within-window causality reads them back at cache dtype
                    # — exactly what the decode step's per-token writes see
                    # (the cache stores ROTATED keys)
                    kc = _write_window(krows[i], k[0], hitf, wrote)
                    vc = _write_window(vrows[i], v[0], hitf, wrote)
                    new_k.append(kc)
                    new_v.append(vc)
                    pk.append(k[0].astype(cdt))
                    pv.append(v[0].astype(cdt))
                    return _attend_cache(q, kc[None], vc[None], keep)

                return _block_apply(c, bp, x, spec, attend=attend,
                                    positions=pos_w)

            _stack_runs(c, params, x, apply)
            return (tuple(new_k), tuple(new_v),
                    jnp.stack(pk), jnp.stack(pv))

        def prefill(params, state, slot, toks, start, nvalid, final,
                    inject, ik, iv):
            """toks: [W] i32 (padded past nvalid); ik/iv:
            [applications, kv_heads, W, hd] prefix-cache pages (zeros unless
            ``inject``). Returns (state, k_pages, v_pages)."""
            krows = [jax.lax.dynamic_slice(
                b, (slot, 0, 0, 0), (1, c.kv_heads, total, hd))[0]
                for b in state["k"]]
            vrows = [jax.lax.dynamic_slice(
                b, (slot, 0, 0, 0), (1, c.kv_heads, total, hd))[0]
                for b in state["v"]]

            def reuse(_):
                _, hitf, wrote = hits(start, nvalid)
                ks = tuple(_write_window(r, ik[i], hitf, wrote)
                           for i, r in enumerate(krows))
                vs = tuple(_write_window(r, iv[i], hitf, wrote)
                           for i, r in enumerate(vrows))
                return ks, vs, ik, iv

            new_k, new_v, pk, pv = jax.lax.cond(
                inject, reuse,
                lambda _: forward(params, toks, start, nvalid,
                                  krows, vrows),
                operand=None)
            one = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
                buf, jnp.asarray([val]).astype(buf.dtype), slot, axis=0)
            return dict(
                state,
                k=[jax.lax.dynamic_update_slice(b, r[None], (slot, 0, 0, 0))
                   for b, r in zip(state["k"], new_k)],
                v=[jax.lax.dynamic_update_slice(b, r[None], (slot, 0, 0, 0))
                   for b, r in zip(state["v"], new_v)],
                # the scheduler admits prefilled rows inactive; the FINAL
                # window leaves pos at plen-1 and flips the row live, so
                # the next decode chunk picks it up mid-pool
                pos=one(state["pos"], start + nvalid),
                active=one(state["active"], final),
            ), pk, pv

        return jax.jit(prefill, donate_argnums=(1,))

    def _make_token_step(self, total, *, vector_pos=False):
        """One-token decode step over caches of length ``total``: the one
        ``_block_apply`` with an attention that writes the token's K/V into
        the layer's cache and attends over it. Shared by the sampling and
        beam-search builders (scalar ``pos`` — the whole batch decodes in
        lock-step, cache writes via ``dynamic_update_slice``) and, with
        ``vector_pos=True``, the continuous-batching decode step (per-row
        ``pos[B]`` positions, one-hot cache writes masked by the active-row
        ``write`` arg — rows past the cache end match nothing). Runs in
        the model's compute dtype with f32 logits."""
        c = self.conf
        c.served_without_experts("KV-cache decoding (generate, beam_search, "
                                 "ContinuousLM)")

        def token_step(params, tok, pos, kcs, vcs, write=None):
            """tok: [B]; kcs/vcs: per run and layer (``c.applications``)
            [B, kv_heads, total, hd] caches
            (the GQA cache is kv_group× smaller than MHA's) holding ROTATED
            keys; pos: scalar, or [B] i32 with ``vector_pos``; write: [B]
            bool active-row mask (vector_pos only)."""
            if vector_pos:
                positions = pos[:, None]                     # [B, 1]
                hit = (jnp.arange(total)[None, :] == positions) \
                    & write[:, None]
                put = lambda cache, new: _write_rows(cache, new, hit)
            else:
                positions = jnp.asarray(pos)[None]           # [1]
                put = lambda cache, new: _write_at(cache, new, pos)
            x = _embed(c, params, tok[:, None], positions)   # [B, 1, d]
            params = _cast_params(c, params)
            new_k, new_v = [], []

            def apply(i, bp, x):
                spec = c.layer_spec(i)
                keep = _cache_keep(positions, total, spec.window)

                def attend(q, k, v):
                    kc, vc = put(kcs[i], k), put(vcs[i], v)
                    new_k.append(kc)
                    new_v.append(vc)
                    return _attend_cache(q, kc, vc, keep)

                return _block_apply(c, bp, x, spec, attend=attend,
                                    positions=positions)

            h = _stack_runs(c, params, x, apply)
            return _head(c, params, h)[:, 0], new_k, new_v

        return token_step

    def _build_generate(self, B, P, n_new, temperature, top_k=None,
                        top_p=None, rep_penalty=None):
        c = self.conf
        hd = c.hd
        L = c.applications
        total = P + n_new
        token_step = self._make_token_step(total)

        def run(params, prompt, rng):
            cdt = self._cache_dtype()
            # graftlint: disable=G021 -- known pre-serving-tier shape: per-request KV alloc; continuous batching replaces this with a persistent slot pool (ROADMAP serving tier)
            kcs = [jnp.zeros((B, c.kv_heads, total, hd), cdt)
                   for _ in range(L)]
            # graftlint: disable=G021 -- known pre-serving-tier shape: per-request KV alloc; continuous batching replaces this with a persistent slot pool (ROADMAP serving tier)
            vcs = [jnp.zeros((B, c.kv_heads, total, hd), cdt)
                   for _ in range(L)]
            logits = jnp.zeros((B, c.vocab_size))
            # per-row emitted-token counts for the repetition penalty
            seen = jnp.zeros((B, c.vocab_size), jnp.float32)
            if rep_penalty is not None:
                seen = seen.at[jnp.arange(B)[:, None], prompt].add(1.0)
            # prefill: feed prompt tokens one by one (same compiled body)
            def prefill(carry, i):
                kcs, vcs, _ = carry
                lg, kcs, vcs = token_step(params, prompt[:, i], i, kcs, vcs)
                return (kcs, vcs, lg), None
            (kcs, vcs, logits), _ = jax.lax.scan(
                prefill, (kcs, vcs, logits), jnp.arange(P))

            def sample(carry, i):
                kcs, vcs, logits, rng, seen = carry
                rng, sub = jax.random.split(rng)
                if rep_penalty is not None:
                    # CTRL-style: shrink positive logits / inflate negative
                    # ones of every already-emitted token
                    hit = seen > 0
                    logits = jnp.where(
                        hit, jnp.where(logits > 0, logits / rep_penalty,
                                       logits * rep_penalty), logits)
                if temperature == 0.0:
                    tok = jnp.argmax(logits, axis=-1)
                else:
                    lg = self._filter_logits(logits, top_k, top_p)
                    tok = jax.random.categorical(
                        sub, lg / temperature, axis=-1)
                if rep_penalty is not None:
                    seen = seen.at[jnp.arange(B), tok].add(1.0)
                lg, kcs, vcs = token_step(params, tok, P + i, kcs, vcs)
                return (kcs, vcs, lg, rng, seen), tok

            (_, _, _, _, _), toks = jax.lax.scan(
                sample, (kcs, vcs, logits, rng, seen), jnp.arange(n_new))
            return jnp.concatenate([prompt, toks.T.astype(jnp.int32)], axis=1)

        return jax.jit(run)

    # ---- beam search ---------------------------------------------------
    def beam_search(self, prompt, n_new, *, beams=4):
        """Fixed-horizon beam decoding: the ``beams`` highest-joint-
        log-probability continuations of length ``n_new``, returning the
        best per batch row. One jitted scan over tiled KV caches; parent
        backtracking happens on the host afterwards.

        prompt: [B, P] int tokens; returns [B, P + n_new]."""
        c = self.conf
        prompt = jnp.asarray(prompt, jnp.int32)
        B, P = prompt.shape
        if P + n_new > c.max_len:
            raise ValueError(f"P+n_new={P + n_new} exceeds "
                             f"max_len={c.max_len}")
        if not 1 <= beams <= c.vocab_size:
            raise ValueError(f"beams must be in [1, {c.vocab_size}]")
        sig = self._gen_signature("beam", B, P, n_new, beams)
        fn = self._jit_gen.get(sig)
        if fn is None:
            self._evict_gen()
            fn = self._build_beam(B, P, n_new, beams)
            self._jit_gen[sig] = fn
        # graftlint: disable=G001 -- beam_search's contract: ONE fetch per request after the whole scan ran (the generate() seam)
        toks_t, parents_t, scores = (np.asarray(a)
                                     for a in fn(self.params, prompt))
        # host-side backtrack: follow parents from the best final beam
        # (host numpy from here on — the ints below index host arrays)
        out = np.zeros((B, n_new), np.int32)
        for b in range(B):
            # graftlint: disable=G001 -- indexes the already-fetched host arrays
            w = int(scores[b].argmax())
            for t in range(n_new - 1, -1, -1):
                out[b, t] = toks_t[t, b, w]
                # graftlint: disable=G001 -- indexes the already-fetched host arrays
                w = int(parents_t[t, b, w])
        # graftlint: disable=G001 -- host concat of the fetched result with the host prompt
        return np.concatenate([np.asarray(prompt), out], axis=1)

    def _build_beam(self, B, P, n_new, W):
        c = self.conf
        hd = c.hd
        L = c.applications
        total = P + n_new
        token_step = self._make_token_step(total)

        def run(params, prompt):
            cdt = self._cache_dtype()
            # graftlint: disable=G021 -- known pre-serving-tier shape: per-request beam KV alloc; continuous batching replaces this with a persistent slot pool (ROADMAP serving tier)
            kcs = [jnp.zeros((B, c.kv_heads, total, hd), cdt)
                   for _ in range(L)]
            # graftlint: disable=G021 -- known pre-serving-tier shape: per-request beam KV alloc; continuous batching replaces this with a persistent slot pool (ROADMAP serving tier)
            vcs = [jnp.zeros((B, c.kv_heads, total, hd), cdt)
                   for _ in range(L)]
            logits = jnp.zeros((B, c.vocab_size))

            def prefill(carry, i):
                kcs, vcs, _ = carry
                lg, kcs, vcs = token_step(params, prompt[:, i], i, kcs, vcs)
                return (kcs, vcs, lg), None
            (kcs, vcs, logits), _ = jax.lax.scan(
                prefill, (kcs, vcs, logits), jnp.arange(P))

            # tile rows B -> B*W (beam-major within each batch row)
            tile = lambda a: jnp.repeat(a, W, axis=0)
            kcs = [tile(k) for k in kcs]
            vcs = [tile(v) for v in vcs]
            logits = tile(logits)                        # (BW, V)
            # beam 0 live, the rest -inf so identical first beams don't
            # fill the whole frontier with one token
            scores = jnp.tile(jnp.array([0.0] + [-jnp.inf] * (W - 1),
                                        jnp.float32), (B, 1))    # (B, W)

            def step(carry, i):
                kcs, vcs, logits, scores = carry
                logp = jax.nn.log_softmax(
                    logits.astype(jnp.float32), axis=-1)  # (BW, V)
                cand = scores[..., None] + logp.reshape(
                    B, W, c.vocab_size)                   # (B, W, V)
                top_s, flat = jax.lax.top_k(
                    cand.reshape(B, W * c.vocab_size), W)  # (B, W)
                parent = flat // c.vocab_size              # (B, W)
                tok = (flat % c.vocab_size).astype(jnp.int32)
                # reorder caches onto the surviving beams
                rows = (jnp.arange(B)[:, None] * W + parent).reshape(-1)
                kcs = [k[rows] for k in kcs]
                vcs = [v[rows] for v in vcs]
                lg, kcs, vcs = token_step(params, tok.reshape(-1), P + i,
                                          kcs, vcs)
                return (kcs, vcs, lg, top_s), (tok, parent)

            (_, _, _, scores), (toks_t, parents_t) = jax.lax.scan(
                step, (kcs, vcs, logits, scores), jnp.arange(n_new))
            return toks_t, parents_t, scores

        return jax.jit(run)
