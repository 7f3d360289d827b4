"""What only the chip's compiler can say, asked without the chip.

The Pallas kernels pass every interpret-mode test (tests/test_pallas.py) and
can still be refused by Mosaic — as the flash kernels were, at every shape,
for a block layout the interpreter never checks. The TPU compiler is
installed here and compiles for a chip that is described, not attached
(``jax.experimental.topologies``), so the kernels of the main path are
compiled for the v5e at their real widths in tier-1. A compile that passes is
not a chip run; ``chip_smoke.py`` is.

Named to sort early: tier-1 is cut by its clock and late files never run.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.ops import pallas_kernels as pk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def v5e_devices():
    """The four chips of a described v5e 2x2 host. The persistent
    compilation cache is off around these compiles: an entry written for a
    described chip cannot be read back without one, and the next run would
    warn on every test."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler in this install
        pytest.skip(f"the v5e topology cannot be described here: {e!r}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture
def v5e(v5e_devices):
    """One described chip, as the sharding of a ShapeDtypeStruct."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_devices[0])


def _compile(fn, *shapes):
    """AOT-compile ``fn`` for the described chip; returns the number of
    Mosaic kernels in the compiled program."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert compiled.memory_analysis() is not None
    return compiled.as_text().count("tpu_custom_call")


def _assert_statistics_cross_compact(text, rows_of_n, t, block):
    """In a compiled step: no operand or result of a flash custom call is a
    float32 array of trailing dimension 128 and ``n·T·128`` elements, the
    lane-replicated lse or delta (``rows_of_n``: every n = batch·heads among
    the step's layers); what crosses is ``f32[n, T / block, 1, block]``
    (``f32[n / G, T / block, 1, G·block]`` where a step holds a K/V group of
    G query heads), the forward's result and two operands of each backward
    kernel."""
    import math
    import re
    flash = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "pallas_call" in line and "block.attn" in line]
    assert flash
    compact = 0
    for line in flash:
        for dims in re.findall(r"f32\[([\d,]+)\]", line):
            shape = tuple(int(x) for x in dims.split(","))
            assert not (shape[-1] == 128 and math.prod(shape) in
                        {n * t * 128 for n in rows_of_n}), line[:400]
            compact += shape[1:3] == (t // block, 1) \
                and shape[3] % block == 0 \
                and shape[0] * (shape[3] // block) in rows_of_n
    assert compact == len(flash) * 5 // 3


# (q shape, kv shape, block): the chip_smoke train_lm shape (GPT-2 small,
# batch 8 x 1024, d64), one GQA 16/4 shape at head dim 128, T 2048, and the
# two cells of the benchmark (GPT-2 medium: two blocks a row with the
# diagonal and the dead block; one block a row, several heads a grid step)
FLASH_SHAPES = {
    "train_lm": ((8, 12, 1024, 64), (8, 12, 1024, 64), 512),
    "gqa_d128": ((2, 16, 2048, 128), (2, 4, 2048, 128), 512),
    "gpt2m_t1024": ((8, 16, 1024, 64), (8, 16, 1024, 64), 512),
    "gpt2m_t256": ((32, 16, 256, 64), (32, 16, 256, 64), 256),
    # the third cell (Laguna-XS.2, 2 x 8192 tokens): heads of 128, rows of 16
    # blocks, groups of 6 and 8 query heads a key/value head; the window
    # layers see one block (a fourth entry is the window)
    "laguna_full": ((2, 48, 8192, 128), (2, 8, 8192, 128), 512),
    "laguna_window": ((2, 64, 8192, 128), (2, 8, 8192, 128), 512, 512),
    # the fourth cell (Ouro-2.6B, 2 x 4096 tokens): 16 ungrouped heads of
    # 128, rows of 8 blocks
    "ouro": ((2, 16, 4096, 128), (2, 16, 4096, 128), 512),
    # the fifth cell (SmallThinker-21B-A3B, 1 x 16384 tokens): groups of 7
    # query heads a key/value head, rows of 32 blocks; a window of 4096 is
    # 9 live blocks a row, 7 of them unmasked: the first walk in which the
    # `full` and the `edge` branch of a windowed kernel both run
    "smallthinker_full": ((1, 28, 16384, 128), (1, 4, 16384, 128), 512),
    "smallthinker_window": ((1, 28, 16384, 128), (1, 4, 16384, 128), 512,
                            4096),
    # the sixth cell (GLM-4.7-Flash, 1 x 8192 tokens): latent attention's
    # q, k and v as 20 ungrouped heads of 256 (192 + 64 rope; v 256), rows
    # of 16 blocks: the kernels' first width past 128
    "glm_latent": ((1, 20, 8192, 256), (1, 20, 8192, 256), 512),
    # grouped calls that no step can hold whole keep one query head a step:
    # 32 query heads a key/value head at d 128 pass the VMEM of a step; a
    # row of 300 is one block of 300 rows, whose strips would be 4 rows
    "gqa_32_d128": ((1, 32, 2048, 128), (1, 1, 2048, 128), 512),
    "gqa_t300": ((2, 8, 300, 128), (2, 2, 300, 128), 512),
}


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
@pytest.mark.parametrize("wrt", ["fwd", "dq", "dkv"])
def test_flash_kernels_compile_for_v5e(v5e, shape, wrt):
    q_shape, kv_shape, block, *window = FLASH_SHAPES[shape]
    window = window[0] if window else None
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16, sharding=v5e)
    kv = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16, sharding=v5e)

    def attend(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, block_q=block,
                                  block_k=block, window=window)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    # grad wrt q keeps the dQ kernel and drops dK/dV (dead code), and the
    # other way round; both keep the forward for its residuals
    fn, kernels = {"fwd": (attend, 1),
                   "dq": (jax.grad(loss, argnums=0), 2),
                   "dkv": (jax.grad(loss, argnums=(1, 2)), 2)}[wrt]
    assert _compile(fn, q, kv, kv) == kernels


@pytest.mark.parametrize("wrt", ["fwd", "dq", "dkv"])
def test_flash_kernels_at_a_v_of_its_own_width_compile_for_v5e(v5e, wrt):
    """The seventh cell's latent attention (Xing4.0, 1 x 4096 tokens): 4
    ungrouped heads of q / k 192 (128 + 64 rope) against v 128 under YaRN's
    softmax scale, rows of 8 blocks: v, the output, dO and dV cross the
    kernels' boundary at 128, q, k, dQ and dK at 192."""
    q = jax.ShapeDtypeStruct((1, 4, 4096, 192), jnp.bfloat16, sharding=v5e)
    v = jax.ShapeDtypeStruct((1, 4, 4096, 128), jnp.bfloat16, sharding=v5e)

    def attend(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, block_q=512,
                                  block_k=512, scale=2.00474 / 192 ** 0.5)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    fn, kernels = {"fwd": (attend, 1),
                   "dq": (jax.grad(loss, argnums=0), 2),
                   "dkv": (jax.grad(loss, argnums=(1, 2)), 2)}[wrt]
    assert _compile(fn, q, q, v) == kernels
    assert jax.eval_shape(attend, q, q, v).shape == (1, 4, 4096, 128)


# the grouped products of the two expert cells: (rows of the buffer, k, n,
# held experts, a held expert's rows at an even load)
GROUPED_SHAPES = {
    "smallthinker_gate_up": (98304, 2560, 768, 16, 1536),
    "smallthinker_down": (98304, 768, 2560, 16, 1536),
    "laguna_gate_up": (32768, 2048, 512, 32, 512),
    "laguna_down": (32768, 512, 2048, 32, 512),
    "glm_gate_up": (32768, 2048, 1536, 8, 512),
    "glm_down": (32768, 1536, 2048, 8, 512),
    # the rehearsal twins' widths: narrower than a lane tile, a row tile of 8
    "twin": (64, 64, 32, 8, 16),
}


@pytest.mark.parametrize("shape", sorted(GROUPED_SHAPES))
def test_grouped_products_compile_for_v5e(v5e, shape):
    """Forward, input gradient and weight gradient of the expert layer's
    grouped product at the blocks its plan gives: three Mosaic kernels, each
    inside the VMEM the compiler scopes by default."""
    rows, k, n, groups, even = GROUPED_SHAPES[shape]
    tile = pk.grouped_row_tile(even)
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e)

    def all_three(a, w, sizes, g):
        table = pk.group_tiles(sizes, rows, tile)
        out, vjp = jax.vjp(
            lambda a, w: pk.grouped_matmul(a, w, table, tile), a, w)
        return out, vjp(g)

    assert _compile(all_three, sds((rows, k)), sds((groups, k, n)),
                    sds((groups,), jnp.int32), sds((rows, n))) == 3


# the rows around those products in the two expert cells: (rows of the
# buffer, d, tokens, top_k, held experts, a held expert's rows at an even load)
MOVE_SHAPES = {
    "smallthinker": (98304, 2560, 16384, 6, 16, 1536),
    "laguna": (32768, 2048, 16384, 8, 32, 512),
    # chip_smoke's mixed model: bfloat16 rows too narrow to pack two columns
    # a word go as float32 words
    "mixed": (2048, 128, 2048, 2, 8, 256),
}


@pytest.mark.parametrize("shape", sorted(MOVE_SHAPES))
def test_row_movement_compiles_for_v5e(v5e, shape):
    """``gather_rows`` and ``combine_rows`` and the backward of each at the
    token tile the plan gives: four Mosaic kernels that move rows (the
    backward of each is the other, with the weights and the dots or without)
    and the four passes that lay their sources out as words, each inside the
    VMEM and the SMEM the compiler scopes by default; Mosaic refuses a
    one-row slice of a tiled array, which is why the words are there."""
    rows, d, tokens, top_k, groups, even = MOVE_SHAPES[shape]
    tile = pk.grouped_row_tile(even)
    sds = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=v5e)

    def all_four(src, out, w, order, pos, sizes, g_rows, g_y):
        table = pk.group_tiles(sizes, rows, tile)
        moved, gather_vjp = jax.vjp(
            lambda s: pk.gather_rows(s, order, pos, table, tile)[0], src)
        y, combine_vjp = jax.vjp(
            lambda o, w: pk.combine_rows(o, w, order, pos, table, tile),
            out, w)
        return moved, gather_vjp(g_rows), y, combine_vjp(g_y)

    assert _compile(
        all_four, sds((tokens, d)), sds((rows, d)),
        sds((tokens, top_k), jnp.float32), sds((rows,), jnp.int32),
        sds((tokens, top_k), jnp.int32), sds((groups,), jnp.int32),
        sds((rows, d)), sds((tokens, d))) == 8


# the streams of hyper-connections: (streams, C, tokens a step, dtype) --
# the seventh cell's, and its width in float32 (the tile a quarter)
HC_SHAPES = {"xing": (4, 3584, 4096, jnp.bfloat16),
             "xing_f32": (4, 3584, 1024, jnp.float32)}


@pytest.mark.parametrize("shape", sorted(HC_SHAPES))
def test_stream_passes_compile_for_v5e(v5e, shape):
    """The three passes of ``transformer._hc_junction`` / ``_hc_last``,
    forward (with and without the mix, with and without the maps' sums) and
    backward, H_pre's mix and its cotangent: eight Mosaic kernels, each
    inside the VMEM it asks for (``_GROUP_VMEM``) at the tile
    ``_hc_tile`` gives."""
    n, C, tokens, dtype = HC_SHAPES[shape]
    sds = lambda shape, dt=dtype: jax.ShapeDtypeStruct(shape, dt,
                                                       sharding=v5e)
    x, y = sds((tokens, n * C)), sds((tokens, C))
    w = sds((tokens, pk._LANES), jnp.float32)
    phi_t = sds((pk.hc_maps_width(n), n * C))
    dz = sds((tokens, pk.hc_maps_width(n)), jnp.float32)

    def passes(x, y, w, phi_t, dz):
        return (pk.hc_fwd(x, y, w, phi_t, n=n),
                pk.hc_fwd(x, phi_t=phi_t, n=n), pk.hc_fwd(x, y, w, n=n),
                pk.hc_mix(x, w, n=n), pk.hc_dpre(x, y, n=n),
                pk.hc_bwd(x, w, y, x, dz, phi_t, x, y, n=n),
                pk.hc_bwd(x, w, y, x, dz, phi_t, n=n),
                pk.hc_bwd(x, w, x=x, y=y, n=n))

    assert _compile(passes, x, y, w, phi_t, dz) == 8


def test_flash_walk_at_its_bound_compiles_for_v5e(v5e):
    """The walk's table is a scalar-prefetch operand and lives in the 1 MiB
    of SMEM for the whole call, 12 bytes a step: a row that walks exactly
    ``MAX_WALK_STEPS`` pairs (non-causal, 256 x 256 blocks of 512: T
    131,072) compiles, all three kernels; one pair more is refused by the
    shapes, before anything is built (a causal row of 362 blocks)."""
    t = 256 * 512
    assert pk.flash_walk(False, None, 512, 512, t, "k").steps \
        == pk.MAX_WALK_STEPS
    x = jax.ShapeDtypeStruct((1, t, 128), jnp.bfloat16, sharding=v5e)

    def loss(q, k, v):
        return pk.flash_attention(q, k, v).astype(jnp.float32).sum()

    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == 3
    past = jax.ShapeDtypeStruct((1, 362 * 512, 128), jnp.bfloat16,
                                sharding=v5e)
    with pytest.raises(ValueError, match="larger blocks"):
        jax.jit(lambda q: pk.flash_attention(q, q, q, causal=True)).lower(
            past)


@pytest.mark.parametrize("peephole", [True, False])
@pytest.mark.parametrize("wrt", ["fwd", "bwd"])
def test_lstm_cell_compiles_for_v5e(v5e, wrt, peephole):
    """The char-RNN width (GravesLSTM, batch 32, hidden 200 — not a
    multiple of the 128-lane tile)."""
    b, h = 32, 200
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=v5e)
    args = [sds(b, 4 * h), sds(b, h), sds(b, h), sds(h, 4 * h)]
    if peephole:
        args.append(sds(3, h))

    def loss(*a):
        hh, cc = pk.lstm_cell(*a)
        return hh.sum() + cc.sum()

    fn = pk.lstm_cell if wrt == "fwd" else jax.grad(
        loss, argnums=tuple(range(len(args))))
    assert _compile(fn, *args) == 1


def test_sharded_lm_step_compiles_for_2x2_mesh(v5e_devices, monkeypatch):
    """``TransformerLM.shard(mesh)`` with the kernel route: GSPMD refuses
    to partition a Mosaic kernel, so the step compiles for four chips only
    because the route wraps the kernel in a shard_map over the batch axis.
    Shapes only — nothing can be placed on a described device — so the
    plan is attached the way ``shard()`` does, minus the device_put."""
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    from deeplearning4j_tpu.parallel.sharding_core import (ShardingCore,
                                                           build_mesh)

    # default_backend() is the CPU here: force the route the TPU takes
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    mesh = build_mesh(devices=v5e_devices)
    lm = TransformerLM(TransformerConfig(
        vocab_size=1024, max_len=256, d_model=256, n_heads=4, n_layers=1,
        d_ff=512, compute_dtype="bfloat16", block_size=128))
    params, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
    lm.params = lm.opt_state = None
    core = lm._shard_plan = ShardingCore(mesh, level=3)

    def sds(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=core.sharding(spec))

    tokens = jax.ShapeDtypeStruct((8, 256), jnp.int32,
                                  sharding=core.data_sharding())
    compiled = lm._build_step().lower(
        jax.tree.map(lambda a: sds(a, core.param_spec(a)), params),
        jax.tree.map(lambda a: sds(a, core.updater_spec(a)), opt),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=core.sharding(P())),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=core.sharding(P())),
        tokens, tokens, None).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    assert "all-gather" in text   # level 3: params gathered just in time


def test_sharded_hyper_step_takes_xla_passes_for_2x2_mesh(v5e_devices,
                                                          monkeypatch):
    """A ``shard()``-ed model with hyper-connections at a width the
    streams' kernels take: GSPMD cannot partition them, so under the plan
    the block takes XLA's passes on the streams it holds side by side,
    and the step compiles for four chips with the flash kernels alone."""
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.models import transformer
    from deeplearning4j_tpu.parallel.sharding_core import (ShardingCore,
                                                           build_mesh)

    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    monkeypatch.setattr(transformer, "hc_supported",
                        lambda n, width: width % 128 == 0)
    mesh = build_mesh(devices=v5e_devices)
    lm = transformer.TransformerLM(transformer.TransformerConfig(
        vocab_size=1024, max_len=256, d_model=256, n_heads=4, n_layers=1,
        d_ff=512, compute_dtype="bfloat16", block_size=128,
        hyper=transformer.HyperConnections()))
    params, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
    lm.params = lm.opt_state = None
    core = lm._shard_plan = ShardingCore(mesh, level=3)

    def sds(a, spec):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=core.sharding(spec))

    tokens = jax.ShapeDtypeStruct((8, 256), jnp.int32,
                                  sharding=core.data_sharding())
    obs.reset_metrics()
    compiled = lm._build_step().lower(
        jax.tree.map(lambda a: sds(a, core.param_spec(a)), params),
        jax.tree.map(lambda a: sds(a, core.updater_spec(a)), opt),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=core.sharding(P())),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=core.sharding(P())),
        tokens, tokens, None).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3
    assert (obs.metrics.value("hc.kernel_sublayers"),
            obs.metrics.value("hc.xla_sublayers")) == (0, 2)
    obs.reset_metrics()


def test_lm_step_names_reach_the_chips_program(v5e, monkeypatch):
    """What the chip's compiler makes of the step's ``jax.named_scope``s
    (``models/transformer.SCOPES``). XLA names a custom call after the last
    piece of its name stack before the primitive, and the benchmark's
    accepted readers (``benchmark/layer_metrics/flash_*_roofline.py``, which
    a PR may not edit) tell the forward flash kernel from the backward ones
    by ``%jvp`` / ``%transpose`` at the start of that name: a scope nested
    inside ``block.attn``, a ``name=`` on the flash ``pallas_call``s or a
    scope around ``value_and_grad`` would turn both metrics silent."""
    import re

    from benchmark import trace_reduce
    from benchmark.layer_metrics import flash_bwd_roofline, flash_fwd_roofline
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)

    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    layers = 2
    lm = TransformerLM(TransformerConfig(
        vocab_size=512, max_len=128, d_model=128, n_heads=2, n_layers=layers,
        d_ff=512, compute_dtype="bfloat16", block_size=128))
    params, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
    lm.params = lm.opt_state = None
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32, sharding=v5e)
    text = lm._build_step().lower(
        jax.tree.map(sds, params), jax.tree.map(sds, opt),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e),
        tokens, tokens, None).compile().as_text()
    # a profiler's event is named by the instruction's text from its name on
    kernels = [line.strip() for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    fwd = [k for k in kernels if flash_fwd_roofline.is_flash_fwd(k)]
    bwd = [k for k in kernels if flash_bwd_roofline.is_flash_bwd(k)]
    assert (len(kernels), len(fwd), len(bwd)) == (3 * layers, layers,
                                                  2 * layers)
    _assert_statistics_cross_compact(text, {2 * 2}, 128, 128)
    stacks = {re.search(r'op_name="([^"]*)"', k).group(1) for k in kernels}
    assert stacks == {"jit(step)/jvp(block.attn)/pallas_call",
                      "jit(step)/transpose(jvp(block.attn))/pallas_call"}
    # the ledger's breakdown keeps one row for all layers' copies of a kernel
    assert len({trace_reduce.label(k) for k in fwd}) == 1
    assert len({trace_reduce.label(k) for k in bwd}) == 2   # dQ; dK, dV
    # and the other scopes are in the compiled program's metadata
    for scope in ("embed", "block.ln1", "block.mlp", "logits_loss",
                  "optimizer"):
        assert re.search(rf'op_name="jit\(step\)/[^"]*{scope}[)/]', text), scope


# Pallas calls under ``block.moe_dispatch`` in a sparse layer's step
MOVED_A_LAYER = 10


def test_mixed_lm_step_compiles_for_v5e(v5e, monkeypatch):
    """A step with a per-layer list (full and window layers with their own
    head counts, the gate, experts held 8 of 16, remat) compiles for the chip:
    three flash kernels a layer (forward, dQ, dK/dV: a rematerialised block
    keeps the forward kernel's output and logsumexp, ``transformer._remat``,
    and its backward does not run it again), the grouped products as Pallas
    calls whose names hold ``block.experts`` (``moe_experts_roofline`` finds
    them by it) and none of the compiler's own, and every scope of the
    per-layer vocabulary in the program's names. The expert layer asks the
    backend which product to take and sees the CPU here: the test answers
    for the chip. What the block keeps costs the named residuals'
    bytes and no more: the lane-replicated logsumexp, 128 times the one that
    is named, would not pass."""
    import re

    import chip_smoke
    from deeplearning4j_tpu.models import expert_layer, transformer
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    monkeypatch.setattr(expert_layer, "pallas_supported", lambda: True)
    sizes = dict(chip_smoke._MIXED_LM, seq=1024, d_model=128, d_ff=256,
                 vocab_size=512)
    rows = 2
    lm = transformer.TransformerLM(chip_smoke._mixed_config(sizes, 0))
    c = lm.conf
    params, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
    lm.params = lm.opt_state = None
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)
    tokens = jax.ShapeDtypeStruct((rows, sizes["seq"]), jnp.int32,
                                  sharding=v5e)

    def compiled():
        return lm._build_step().lower(
            jax.tree.map(sds, params), jax.tree.map(sds, opt),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e),
            tokens, tokens, None).compile()

    step = compiled()
    text = step.as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                         r'op_name="([^"]*)"', text)
    flash = [n for n in kernels if n.endswith("pallas_call")
             and "block.attn" in n]
    assert len(flash) == 3 * c.n_layers
    assert sum("block.attn_window" in n for n in flash) == 3 * 3
    assert sum("block.attn_full" in n for n in flash) == 3 * 2
    # a sparse layer's three products: forward, forward again under remat,
    # input gradient, weight gradient
    grouped = [n for n in kernels if n.endswith("pallas_call")
               and "block.experts" in n]
    # ... and the rows around them: the gather and the combine, each with
    # the pass that lays its source out as words; forward, the gather again
    # under remat (the combine's result is not needed there), and backward
    # each as the other
    moved = [n for n in kernels if n.endswith("pallas_call")
             and "block.moe_dispatch" in n]
    assert len(grouped) == 4 * 3 * 4
    assert len(moved) == 4 * MOVED_A_LAYER
    assert len(kernels) == len(flash) + len(grouped) + len(moved)
    assert "ragged-dot" not in text
    # no row is moved by XLA: under the dispatch's scope nothing as long as
    # the buffer is scattered (each assignment's row is the inverse of a
    # sort, a second sort; the walk's table is a few dozen entries), and no
    # float32 copy of the row buffer is made for a select or a sum
    import math
    buffer, d = c.experts.rows(rows * sizes["seq"]), c.d_model
    dispatch = [line for line in text.splitlines()
                if "block.moe_dispatch" in line]
    scattered = [math.prod(map(int, shape.split(",")))
                 for line in dispatch if " scatter(" in line
                 for shape in re.findall(r"= \w+\[([\d,]+)\]", line)]
    assert dispatch and max(scattered, default=0) < buffer
    # (at this toy width a row's weight, replicated a lane tile wide on its
    # way into a kernel, has a row's shape: that broadcast is not one)
    assert not [line for line in dispatch if f"f32[{buffer},{d}]" in line
                and "pallas_call" not in line and " broadcast(" not in line]
    for scope in ("attn_gate", "router", "moe_dispatch", "experts",
                  "shared_expert"):
        assert re.search(rf'op_name="jit\(step\)/[^"]*block\.{scope}[)/]',
                         text), scope

    heads = [c.layer_spec(i).n_heads for i in range(c.n_layers)]
    _assert_statistics_cross_compact(text, {rows * h for h in heads},
                                     sizes["seq"], c.block_size)

    # a layer's named residuals: the output in bfloat16, one float32 a row
    kept = rows * sizes["seq"] * sum(heads) * (c.hd * 2 + 4)
    monkeypatch.setattr(transformer, "_remat", jax.checkpoint)
    bare = compiled().memory_analysis().temp_size_in_bytes
    temp = step.memory_analysis().temp_size_in_bytes
    assert temp <= bare + kept
    # and nothing holds a logsumexp 128 times: while the forward kernel
    # wrote it lane-replicated this step compiled to 324.4 MB of temporaries
    # (PR 32's tree; 238.4 since PR 33), a window layer's copy 67.1 MB of
    # them. Since PR 37 it is 265.3: at this toy width (d 128) the expert
    # layer's rows cross to their kernels as float32 words and the rows'
    # weights and dots as [rows, 1] columns a lane tile wide, 26.9 MB in all
    # (the cells' own peaks did not move: PERF.md)
    moved = 26_900_000
    assert temp <= 324_435_456 + moved \
        - rows * sizes["seq"] * max(heads) * 128 * 4


def test_looped_lm_step_compiles_for_v5e(v5e, monkeypatch):
    """A step with a looped stack (two layers run four times over the same
    weights, heads of 128, sandwich norms, an exit gate and the loss over four
    exits, remat) compiles for the chip: three flash kernels an APPLICATION,
    none twice (a rematerialised block keeps the forward kernel's output
    through every run), the three scopes of the looped vocabulary in the
    program's names, and the exits' vocabulary-wide logits not alive
    together: doubling the runs adds four exits, and beyond what the added
    block applications keep the temporaries grow by less than two exits'
    float32 logits (read: 1.5), where four exits alive together
    would take four, their log-softmax and gradients not counted."""
    import re

    import chip_smoke
    from deeplearning4j_tpu.models import transformer
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    rows, vocab = 2, 8192
    sizes = dict(chip_smoke._LOOPED_LM, max_len=1024, vocab_size=vocab)

    def compiled(loops):
        lm = transformer.TransformerLM(transformer.TransformerConfig(
            pos_embed="rope", rope_layout="half", norm="rmsnorm", bias=False,
            ffn="swiglu", tie_embeddings=False, post_norm=True,
            exit_gate=True, exit_entropy=0.1, remat=True,
            compute_dtype="bfloat16", **dict(sizes, loops=loops)))
        params, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
        lm.params = lm.opt_state = None
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)
        tokens = jax.ShapeDtypeStruct((rows, sizes["max_len"]), jnp.int32,
                                      sharding=v5e)
        return lm.conf, lm._build_step().lower(
            jax.tree.map(sds, params), jax.tree.map(sds, opt),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e),
            tokens, tokens, None).compile()

    c, step = compiled(4)
    text = step.as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                         r'op_name="([^"]*)"', text)
    assert len(kernels) == 3 * c.applications == 24
    _assert_statistics_cross_compact(text, {rows * c.n_heads},
                                     sizes["max_len"], c.block_size)
    assert all(n.endswith("block.attn/pallas_call")
               or n.endswith("block.attn)/pallas_call") for n in kernels)
    for scope in ("block.attn_norm", "block.mlp_norm", "exit_gate",
                  "final_ln", "logits_loss"):
        assert re.search(rf'op_name="jit\(step\)/[^"]*{scope}[)/]', text), \
            scope
    one_exit = rows * sizes["max_len"] * vocab * 4
    # a rematerialised block keeps its input, the attention output and lse
    kept_a_run = rows * sizes["max_len"] * c.n_layers * (
        c.d_model * 2 + c.n_heads * (c.hd * 2 + 4))
    _, twice = compiled(8)
    grown = twice.memory_analysis().temp_size_in_bytes \
        - step.memory_analysis().temp_size_in_bytes
    assert grown < 4 * kept_a_run + 2 * one_exit


def test_latent_lm_step_compiles_for_v5e(v5e, monkeypatch):
    """The sixth cell's step at its widths (d 2048; latent ranks 768 / 512;
    20 heads of 192 + 64 rope, v 256; a dense layer of 10240 and a routed
    layer of 8 held experts of 1536 with a shared one and the selection
    bias; the multi-token-prediction module; remat), cut to two layers, 1024
    tokens and a 4096-row vocabulary, compiles for the chip: three flash
    kernels at d 256 an application of latent attention, the module's among
    them, all under ``block.attn_latent``, within the scoped VMEM; the new
    scopes in the program's names."""
    import json
    import re

    from benchmark.drivers import glm_train
    from deeplearning4j_tpu.models import expert_layer, transformer
    monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
    monkeypatch.setattr(expert_layer, "pallas_supported", lambda: True)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        config = dict(json.load(f), num_hidden_layers=2, vocab_size=4096)
    seq = 1024
    lm = transformer.TransformerLM(glm_train.program_config(config, seq, 0))
    c = lm.conf
    params, opt = jax.eval_shape(lambda: (lm.init().params, lm.opt_state))
    lm.params = lm.opt_state = None
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)
    tokens = jax.ShapeDtypeStruct((1, seq), jnp.int32, sharding=v5e)
    step = lm._build_step().lower(
        jax.tree.map(sds, params), jax.tree.map(sds, opt),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=v5e),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=v5e),
        tokens, tokens, None).compile()
    text = step.as_text()
    kernels = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*'
                         r'op_name="([^"]*)"', text)
    flash = [n for n in kernels if n.endswith("pallas_call")
             and "block.attn" in n]
    assert len(flash) == 3 * (c.n_layers + 1)
    assert all("block.attn_latent" in n for n in flash)
    _assert_statistics_cross_compact(text, {c.n_heads}, seq, c.block_size)
    for scope in ("block.q_latent", "block.kv_latent", "mtp.join",
                  "mtp.logits_loss", "block.router", "block.experts"):
        assert re.search(rf'op_name="jit\(step\)/[^"]*{re.escape(scope)}'
                         r'[)/]', text), scope


def _smoke(*args, env=None):
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_chip_smoke_rehearsal_passes_on_cpu():
    """The four LM phases at tiny size on the CPU (the ResNet rehearsal is
    `make smoke-rehearse`): every check passes and the last line names the
    CPU — it can never be read as a chip result."""
    r = _smoke("--rehearse", "--phases",
               "train_lm,train_mixed_lm,train_looped_lm,serve_lm")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    lines = [json.loads(l) for l in r.stdout.splitlines()
             if l.startswith("{")]
    assert [l["phase"] for l in lines[:-1]] == [
        "setup", "train_lm", "train_mixed_lm", "train_looped_lm", "serve_lm"]
    assert all(l["ok"] for l in lines)
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """No --rehearse, no TPU: non-zero at once, nothing on stdout — a CPU
    run can never stand in for the chip, with or without JAX_PLATFORMS."""
    for platforms in ("cpu", None):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        if platforms:
            env["JAX_PLATFORMS"] = platforms
        r = _smoke(env=env)
        assert r.returncode != 0
        assert r.stdout.strip() == ""
        assert "no TPU" in r.stderr


# ---------------------------------------------------------------------------
# no path that quietly runs on the CPU, or against a guessed peak
# ---------------------------------------------------------------------------

def test_unknown_device_kind_has_no_peak():
    from deeplearning4j_tpu import hw
    assert hw.peak_bf16_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError, match="no peak FLOP/s recorded"):
        hw.peak_bf16_flops("TPU v9000")
    with pytest.raises(KeyError, match="'cpu'"):
        hw.peak_bf16_flops()   # this test's own backend
