"""The stream passes of manifold-constrained hyper-connections as Pallas
kernels (``transformer._hc_junction`` / ``_hc_last`` over
``pallas_kernels.hc_fwd`` / ``hc_mix`` / ``hc_dpre`` / ``hc_bwd``), in
interpreter mode, against XLA's ``_hc_pre`` / ``_hc_post``: forward and
``jax.vjp`` of each pass, and ``jax.grad`` through ``jax.checkpoint``.

In float32 the two compute the same sums in another order: within 1e-5 of
the result. In bfloat16 the kernels carry a pass's cotangents in float32 and
cast once, where XLA's autodiff rounds each use's cotangent to bfloat16 before
adding them; so the bfloat16 kernels are held to the exact result of the
bfloat16 program -- XLA's passes on float32 copies of the same inputs, each
stream the program writes rounded as the program rounds it -- within one
bfloat16 ulp of each element.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.models import transformer as tr
from deeplearning4j_tpu.models.transformer import (HyperConnections,
                                                   TransformerConfig)
from deeplearning4j_tpu.ops import pallas_kernels as pk


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")


# (streams, rows, sequence, C): 200 and 144 tokens are no whole number of
# the token tile (128), C is two lane tiles
SHAPES = {"n4": (4, 2, 100, 256), "n2": (2, 1, 144, 256)}
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


def config(n, C):
    return TransformerConfig(vocab_size=64, max_len=256, d_model=C,
                             n_heads=2, hyper=HyperConnections(streams=n))


def operands(n, B, T, C, dtype, seed=0):
    """Streams, a sublayer's output, the maps it was added with, and a
    sublayer's leaves (phi at the scale that keeps the maps' logits near
    1: the kernels' ``z`` is what every map reads)."""
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    m = n * (n + 2)
    return dict(
        x=jax.random.normal(k[0], (B, T, n, C)).astype(dtype),
        y=jax.random.normal(k[1], (B, T, C)).astype(dtype),
        post=2.0 * jax.nn.sigmoid(jax.random.normal(k[2], (B, T, n))),
        res=tr._sinkhorn(jnp.exp(jax.random.normal(k[3], (B, T, n, n))), 20,
                         1e-6),
        phi=(jax.random.normal(k[4], (n * C, m)) * 0.3).astype(dtype),
        alpha=jnp.array([1.0, 0.7, 1.3], dtype),
        b=(0.1 * jax.random.normal(k[5], (m,))).astype(dtype))


def rounded(a, dtype):
    """``a`` as the program stores it in ``dtype``, the cotangent passed
    through as the program's own cast passes it."""
    return a + jax.lax.stop_gradient(a.astype(dtype).astype(a.dtype) - a)


def side_by_side(x):
    """Streams [B, T, n, C] as the kernels' route holds them, [B, T, n·C]."""
    return x.reshape(*x.shape[:2], -1)


def apart(x, n):
    return x.reshape(*x.shape[:2], n, -1)


# each pass on the kernels (the streams in and out as XLA's passes take
# them), and the same pass by XLA's functions; the XLA form rounds the
# streams it writes to ``dtype`` (a no-op where they are already in it)
def kernel_pass(name, c):
    n = c.hyper.streams

    def junction(x, y, post, res, phi, alpha, b):
        inp, x, post, res, err = tr._hc_junction(
            c, side_by_side(x), y, post, res, phi, alpha, b)
        return inp, apart(x, n), post, res, err

    if name == "pre":
        return lambda x, phi, alpha, b: junction(x, None, None, None, phi,
                                                 alpha, b)
    if name == "mid":
        return junction
    return lambda x, y, post, res: apart(
        tr._hc_last(side_by_side(x), y, post, res), n)


def xla_pass(name, c, dtype):
    def pre(x, phi, alpha, b):
        inp, post, res, err = tr._hc_pre(
            c, {"h": phi, "h_alpha": alpha, "h_b": b}, x, "h")
        return inp, x, post, res, err

    if name == "pre":
        return pre
    if name == "mid":
        def mid(x, y, post, res, phi, alpha, b):
            return pre(rounded(tr._hc_post(x, y, post, res), dtype), phi,
                       alpha, b)
        return mid
    return tr._hc_post


ARGS = {"pre": ("x", "phi", "alpha", "b"),
        "mid": ("x", "y", "post", "res", "phi", "alpha", "b"),
        "last": ("x", "y", "post", "res")}


def upcast(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def assert_close(got, want, dtype, what):
    """float32: within 1e-5 of the leaf's largest magnitude; bfloat16:
    within one bfloat16 ulp of each element, and a sixteenth of the ulp of
    the leaf's largest (an element that is a cancelling sum over the tokens,
    as alpha's and b's are, carries float32's round-off of its terms)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, what
    scale = float(np.abs(want).max())
    if dtype == jnp.float32:
        bound = 1e-5 * scale
    else:
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30)))
                      - 7)
        bound = ulp + 2.0 ** -12 * scale
    err = np.abs(got - want)
    assert (err <= bound).all(), (what, float(err.max()), scale)


def cotangents(outs, seed=1):
    """Random cotangents of a pass's outputs, none for the error (a
    statistic, never differentiated)."""
    leaves, tree = jax.tree.flatten(outs)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([
        jax.random.normal(k, a.shape).astype(a.dtype) if a.ndim
        else jnp.zeros((), a.dtype) for a, k in zip(leaves, keys)])


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("name", ["pre", "mid", "last"])
def test_each_pass_and_its_vjp_match_xla(interpret, name, shape, dtype):
    """The forward and every cotangent (dx, dy, dH_post, dH_res, dphi,
    dalpha, db) of one pass: the first sublayer's maps (``_hc_pre``), a
    sublayer's output added back and the next maps (``_hc_post`` then
    ``_hc_pre``), a block's last output added back (``_hc_post``)."""
    n, B, T, C = SHAPES[shape]
    dtype = DTYPES[dtype]
    c = config(n, C)
    ops = operands(n, B, T, C, dtype)
    args = tuple(ops[a] for a in ARGS[name])
    got, got_vjp = jax.vjp(jax.jit(kernel_pass(name, c)), *args)
    want, want_vjp = jax.vjp(jax.jit(xla_pass(name, c, dtype)),
                             *upcast(args))
    cts = cotangents(got)
    for i, (g, w) in enumerate(zip(jax.tree.leaves(got),
                                   jax.tree.leaves(want))):
        assert g.dtype == dtype or g.dtype == jnp.float32
        assert_close(g, w, dtype, f"output {i}")
    for arg, g, w in zip(ARGS[name], got_vjp(cts), want_vjp(upcast(cts))):
        assert g.dtype == ops[arg].dtype, arg
        assert_close(g, w, dtype, f"d{arg}")


def block_of_maps(c, kernels, dtype, remat=True):
    """Two sublayers' stream passes around stand-ins of the sublayers (the
    input's mean square scales the output: each input reaches the next
    maps), as ``_block_apply`` walks them, under ``jax.checkpoint`` where
    ``remat``: the gradient of a weighted sum of the block's output."""
    def sublayer(inp, y):
        return (y.astype(jnp.float32) * jnp.mean(
            jnp.square(inp.astype(jnp.float32)))).astype(dtype)

    def block(p, x, y1, y2):
        if kernels:
            inp, x, post, res, _ = tr._hc_junction(
                c, side_by_side(x), None, None, None, p["phi1"], p["alpha1"],
                p["b1"])
            inp, x, post, res, _ = tr._hc_junction(
                c, x, sublayer(inp, y1), post, res, p["phi2"], p["alpha2"],
                p["b2"])
            return apart(tr._hc_last(x, sublayer(inp, y2), post, res),
                         c.hyper.streams)
        inp, post, res, _ = tr._hc_pre(
            c, {"h": p["phi1"], "h_alpha": p["alpha1"], "h_b": p["b1"]}, x,
            "h")
        x = tr._hc_post(x, sublayer(inp, y1), post, res)
        inp, post, res, _ = tr._hc_pre(
            c, {"h": p["phi2"], "h_alpha": p["alpha2"], "h_b": p["b2"]}, x,
            "h")
        return tr._hc_post(x, sublayer(inp, y2), post, res)

    def loss(p, x, y1, y2, w):
        out = (jax.checkpoint(block) if remat else block)(p, x, y1, y2)
        return (out.astype(jnp.float32) * w).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))


def block_operands(dtype):
    n, B, T, C = SHAPES["n4"]
    ops = operands(n, B, T, C, dtype)
    p = {"phi1": ops["phi"], "phi2": ops["phi"][::-1],
         "alpha1": ops["alpha"], "alpha2": ops["alpha"][::-1],
         "b1": ops["b"], "b2": ops["b"][::-1]}
    w = jax.random.normal(jax.random.PRNGKey(7), ops["x"].shape)
    return config(n, C), (p, ops["x"], ops["y"],
                          jnp.roll(ops["y"], 1, axis=1), w)


def test_grad_through_checkpoint_matches_xla(interpret):
    """``jax.grad`` of two sublayers' passes under ``jax.checkpoint`` (the
    backward recomputes the kernels' forward), in float32: phi's, alpha's
    and b's gradients (through dH_res and dH_post), the streams' (dx) and
    both outputs' (dy)."""
    c, args = block_operands(jnp.float32)
    got = block_of_maps(c, True, jnp.float32)(*args)
    want = block_of_maps(c, False, jnp.float32)(*args)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert_close(g, w, jnp.float32, jax.tree_util.keystr(path))


def test_checkpoint_recomputes_the_same_bfloat16_gradient(interpret):
    """In bfloat16 a chain of passes rounds where the kernels round (each
    pass is held to XLA's above); under ``jax.checkpoint`` the kernels'
    recomputed forward gives the gradient they give without it, bit for
    bit."""
    c, args = block_operands(jnp.bfloat16)
    got = block_of_maps(c, True, jnp.bfloat16)(*args)
    want = block_of_maps(c, True, jnp.bfloat16, remat=False)(*args)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert g.dtype == w.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32),
                                      jax.tree_util.keystr(path))


def test_the_kernels_run_where_the_width_is_lane_tiles(monkeypatch):
    """Off the TPU without the interpreter no kernel runs; with it, a
    width of whole lane tiles takes them and any other width XLA's passes;
    so do too many streams for a token's row of maps."""
    monkeypatch.delenv("DL4J_TPU_PALLAS_INTERPRET", raising=False)
    assert not pk.hc_supported(4, 256)
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")
    assert pk.hc_supported(4, 256) and pk.hc_supported(2, 128)
    assert not pk.hc_supported(4, 64) and not pk.hc_supported(4, 200)
    assert not pk.hc_supported(11, 256)


@pytest.mark.parametrize("tokens,tile", [(4096, 128), (200, 128),
                                         (144, 128), (40, 32), (8, 8)])
def test_a_tile_is_whole_row_chunks_within_the_vmem_budget(tokens, tile):
    """The forward's tile at the seventh cell's width (n 4, C 3584, bf16):
    128 tokens, whole chunks of 16 rows, all of a shorter call; the
    backward with every input fits ``_HC_VMEM`` at half that in bfloat16
    and at a quarter in float32."""
    n, C, lanes = 4, 3584, pk._LANES
    slots = pk._hc_slots(n)[3] + 1
    # x and x', y, the maps' row, z, the sums' row; the maps laid across
    # lanes and the squares' partial sums as scratch
    row = (2 * n * C + C) * 2 + 2 * lanes * 4 + 32 * 4
    assert pk._hc_tile(tokens, row, 0, (slots + 1) * lanes * 4) == tile
    # dx' x' x dx, g y dy, two rows of maps, dz; phi and dphi (float32)
    # whole; the dz phi^T terms of a lane chunk, the maps' cotangents'
    # partial sums and the maps laid across lanes as scratch
    scratch = 4 * (n * 512 + (n * n + n + slots) * lanes)
    for itemsize, tile in ((2, 64), (4, 32)):
        row = (4 * n * C + 3 * C) * itemsize + 2 * lanes * 4 + 32 * 4
        phi = 32 * n * C * (itemsize + 4)
        assert pk._hc_tile(4096, row, phi, scratch) == tile


def test_a_block_counts_its_sublayers_by_route(interpret):
    """``hc.kernel_sublayers`` / ``hc.xla_sublayers``: two a block traced,
    by the route its width takes."""
    for width, kernels in ((128, True), (64, False)):
        lm = tr.TransformerLM(TransformerConfig(
            vocab_size=64, max_len=16, d_model=width, n_heads=2, n_layers=3,
            d_ff=64, hyper=HyperConnections(streams=4)))
        params = jax.eval_shape(lambda: lm.init().params)
        obs.reset_metrics()
        jax.eval_shape(lambda p: lm._loss(p, jnp.zeros((1, 16), jnp.int32),
                                          jnp.zeros((1, 16), jnp.int32),
                                          None), params)
        got = (obs.metrics.value("hc.kernel_sublayers"),
               obs.metrics.value("hc.xla_sublayers"))
        assert got == ((6, 0) if kernels else (0, 6)), width
    obs.reset_metrics()
