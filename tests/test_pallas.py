"""Pallas flash-attention kernel + helper-seam tests. On the CPU test mesh the
kernel runs in interpreter mode (DL4J_TPU_PALLAS_INTERPRET=1), which executes
the same kernel logic; the TPU-compiled path is exercised by bench/verify runs
(reference pattern: CuDNNGradientChecks force-injects the helper, §4.1)."""

import numpy as np
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.parallel.sequence_parallel import dense_attention


@pytest.fixture
def interpret_pallas(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_PALLAS_INTERPRET", "1")


class TestFlashKernel:
    def test_matches_dense(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(2, 3, 32, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 3, 32, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 3, 32, 8), jnp.float32)
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_causal_matches_dense(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_causal_padded_length(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 27, 8), jnp.float32)  # 27 % 8 != 0
        out = flash_attention(q, q, q, causal=True, block_q=8, block_k=8)
        ref = dense_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    def test_gradients_match_dense(self, rng, interpret_pallas):
        import jax
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 16, 4), jnp.float32)
        k = jnp.asarray(rng.randn(1, 16, 4), jnp.float32)
        v = jnp.asarray(rng.randn(1, 16, 4), jnp.float32)
        g1 = jax.grad(lambda a: flash_attention(a, k, v, block_q=8,
                                                block_k=8).sum())(q)
        g2 = jax.grad(lambda a: dense_attention(a, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)


class TestFlashBackwardKernels:
    """The pallas dQ and dK/dV kernels (FlashAttention-2-style backward,
    P recomputed from the saved logsumexp) against dense-softmax autodiff,
    over multi-block grids where the streamed accumulations matter."""

    def _grads(self, fn, q, k, v):
        import jax
        # a non-uniform cotangent exercises delta = rowsum(dO*O) properly;
        # deterministic so the two sides of a comparison share it
        cot = jnp.asarray(
            np.random.RandomState(42).randn(*q.shape), jnp.float32)

        def loss(a, b, c):
            return (fn(a, b, c) * cot).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_all_grads_match_dense_multiblock(self, rng, interpret_pallas,
                                              causal):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(2, 64, 16), jnp.float32)
        k = jnp.asarray(rng.randn(2, 64, 16), jnp.float32)
        v = jnp.asarray(rng.randn(2, 64, 16), jnp.float32)
        got = self._grads(lambda a, b, c: flash_attention(
            a, b, c, causal=causal, block_q=16, block_k=16), q, k, v)
        want = self._grads(lambda a, b, c: dense_attention(
            a, b, c, causal=causal), q, k, v)
        for g1, g2, name in zip(got, want, "qkv"):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=2e-4, err_msg=f"d{name}")

    def test_rectangular_blocks(self, rng, interpret_pallas):
        """block_q != block_k exercises the independent grid index maps of
        the two backward kernels."""
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 64, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 64, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 64, 8), jnp.float32)
        got = self._grads(lambda a, b, c: flash_attention(
            a, b, c, causal=True, block_q=32, block_k=16), q, k, v)
        want = self._grads(lambda a, b, c: dense_attention(
            a, b, c, causal=True), q, k, v)
        for g1, g2 in zip(got, want):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=2e-4)

    def test_matches_scan_escape_hatch(self, rng, interpret_pallas,
                                       monkeypatch):
        """DL4J_TPU_FLASH_BWD=scan must produce the same gradients as the
        pallas backward (they are two implementations of one math)."""
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention

        def fn(a, b, c):
            return flash_attention(a, b, c, causal=True, block_q=16,
                                   block_k=16)
        q = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
        pallas_grads = self._grads(fn, q, k, v)
        monkeypatch.setenv("DL4J_TPU_FLASH_BWD", "scan")
        scan_grads = self._grads(fn, q, k, v)
        for g1, g2 in zip(pallas_grads, scan_grads):
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=2e-4)

    def test_causal_padded_grads(self, rng, interpret_pallas):
        """T not divisible by the block: the sliced-output vjp zero-pads the
        cotangent; padded rows/keys must contribute exact zeros (the lse
        +LARGE guard), not NaNs."""
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 27, 8), jnp.float32)
        got = self._grads(lambda a, b, c: flash_attention(
            a, b, c, causal=True, block_q=8, block_k=8), q, q, q)
        want = self._grads(lambda a, b, c: dense_attention(
            a, b, c, causal=True), q, q, q)
        for g1, g2 in zip(got, want):
            assert np.isfinite(np.asarray(g1)).all()
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=2e-4)

    # (q shape, k / v heads, v width, block): latent attention's q / k of
    # 192 (128 + 64 rope) against v of 128, the cell's widths, one head a
    # row on rows of four blocks, and a K/V group of 2 folded into a step
    OWN_WIDTHS = {"latent": ((1, 2, 256, 192), 2, 128, 64),
                  "grouped": ((1, 4, 256, 192), 2, 128, 64)}

    @pytest.mark.parametrize("case", sorted(OWN_WIDTHS))
    def test_v_of_its_own_width_and_a_scale_match_dense(self, rng,
                                                        interpret_pallas,
                                                        monkeypatch, case):
        """v narrower than q and k, and a softmax scale that is not
        1 / sqrt(d) (YaRN's mscale² over sqrt(192)): the forward and dQ, dK,
        dV against ``dense_attention`` at that scale, with the pallas
        backward and with the scan escape hatch; the output and dV take v's
        width, and nothing of v is padded to q's."""
        import jax
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q_shape, kv_heads, dv, block = self.OWN_WIDTHS[case]
        B, H, T, d = q_shape
        scale = 2.00474 / np.sqrt(d)
        q = jnp.asarray(rng.randn(*q_shape), jnp.float32)
        k = jnp.asarray(rng.randn(B, kv_heads, T, d), jnp.float32)
        v = jnp.asarray(rng.randn(B, kv_heads, T, dv), jnp.float32)
        cot = jnp.asarray(np.random.RandomState(42).randn(B, H, T, dv),
                          jnp.float32)
        rep = lambda x: jnp.repeat(x, H // kv_heads, axis=1)

        def grads(fn):
            return jax.value_and_grad(lambda a, b, c: (fn(a, b, c) * cot).sum(),
                                      argnums=(0, 1, 2))(q, k, v)
        flash = lambda a, b, c: flash_attention(
            a, b, c, causal=True, block_q=block, block_k=block, scale=scale)
        assert flash(q, k, v).shape == (B, H, T, dv)
        want = grads(lambda a, b, c: dense_attention(
            a, rep(b), rep(c), causal=True, scale=scale))
        for hatch in ("pallas", "scan"):
            if hatch == "scan":
                monkeypatch.setenv("DL4J_TPU_FLASH_BWD", "scan")
            out, got = grads(flash)
            np.testing.assert_allclose(out, want[0], rtol=1e-5)
            for g1, g2, name in zip(got, want[1], "qkv"):
                assert g1.shape == g2.shape
                np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                           atol=2e-4, err_msg=f"d{name}")
        # the default scale is 1 / sqrt(d) of q's width, not of v's
        np.testing.assert_allclose(
            flash_attention(q, k, v, causal=True, block_q=block,
                            block_k=block),
            dense_attention(q, rep(k), rep(v), causal=True), atol=1e-5)

    def test_bf16_inputs_grads_finite_and_close(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 32, 8), jnp.bfloat16)
        k = jnp.asarray(rng.randn(1, 32, 8), jnp.bfloat16)
        v = jnp.asarray(rng.randn(1, 32, 8), jnp.bfloat16)
        got = self._grads(lambda a, b, c: flash_attention(
            a, b, c, causal=True, block_q=16, block_k=16), q, k, v)
        want = self._grads(lambda a, b, c: dense_attention(
            a.astype(jnp.float32), b.astype(jnp.float32),
            c.astype(jnp.float32), causal=True), q, k, v)
        for g1, g2 in zip(got, want):
            assert g1.dtype == jnp.bfloat16
            assert np.isfinite(np.asarray(g1, np.float32)).all()
            np.testing.assert_allclose(np.asarray(g1, np.float32),
                                       np.asarray(g2, np.float32),
                                       atol=0.15, rtol=0.1)


def _pallas_calls(jaxpr, found=None):
    """``{kernel function's name: inner jaxpr}`` of every pallas_call under
    ``jaxpr`` (the flash calls take no ``name=``: tests/test_aot_compile)."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            inner = eqn.params["jaxpr"]
            found[inner.debug_info.func_name] = inner
            continue
        for sub in _sub_jaxprs(eqn):
            _pallas_calls(sub, found)
    return found


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in (value if isinstance(value, (list, tuple)) else [value]):
            item = getattr(item, "jaxpr", item)
            if hasattr(item, "eqns"):
                yield item


def _eqns(jaxpr):
    """Every equation under ``jaxpr``, through calls, branches (pl.when) and
    loops (the heads of a step)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _eqns(sub)


def _dots(jaxpr):
    """Every dot_general of a kernel's jaxpr."""
    return (eqn for eqn in _eqns(jaxpr)
            if eqn.primitive.name == "dot_general")


def _assert_flash_matches_dense(q_shape, kv_shape, bq, bk, window, seed=11,
                                atol=3e-4):
    """Forward and all three gradients of the kernels (interpreter mode)
    against ``dense_attention``, float32, K/V heads repeated for the
    reference."""
    import jax
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(*q_shape), jnp.float32)
    k = jnp.asarray(rng.randn(*kv_shape), jnp.float32)
    v = jnp.asarray(rng.randn(*kv_shape), jnp.float32)
    cot = jnp.asarray(rng.randn(*q_shape), jnp.float32)
    group = q_shape[-3] // kv_shape[-3] if len(q_shape) > 3 else 1

    def flash(a, b, c):
        return flash_attention(a, b, c, causal=True, block_q=bq, block_k=bk,
                               window=window)

    def dense(a, b, c):
        if group > 1:
            b, c = (jnp.repeat(x, group, axis=-3) for x in (b, c))
        return dense_attention(a, b, c, causal=True, window=window)

    got, got_vjp = jax.vjp(flash, q, k, v)
    want, want_vjp = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               err_msg="out")
    for g, w, name in zip(got_vjp(cot), want_vjp(cot), "qkv"):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol,
                                   err_msg=f"d{name}")


class TestFlashGridStep:
    """What one grid step of the three kernels hands to the MXU, and that
    the step's branches and tiles (``flash_plan``) compute the attention
    they stand for."""

    # products a tile: forward QKᵀ, PV; dQ QKᵀ, dO Vᵀ, dS K; dK/dV K Qᵀ,
    # V dOᵀ, Pᵀ dO, dSᵀ Q
    PRODUCTS = {"_flash_kernel": 2, "_flash_dq_kernel": 3,
                "_flash_dkv_kernel": 4}

    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("kernel", sorted(PRODUCTS))
    def test_every_product_takes_the_inputs_dtype(self, interpret_pallas,
                                                  kernel, dtype):
        """bfloat16 inputs: every product of the step meets the MXU with two
        bfloat16 operands and accumulates in float32 (P and dS are cast to
        the operand they meet, dO is not upcast); float32 callers keep
        float32 products."""
        import jax
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        x = jnp.zeros((2, 512, 32), dtype)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=256,
                                   block_k=256).astype(jnp.float32).sum()
        calls = _pallas_calls(
            jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x).jaxpr)
        assert sorted(calls) == sorted(self.PRODUCTS)
        dots = list(_dots(calls[kernel]))
        # some whole number of tiles, each with all of its products
        assert dots and len(dots) % self.PRODUCTS[kernel] == 0
        for eqn in dots:
            assert [v.aval.dtype for v in eqn.invars] == [jnp.dtype(dtype)] * 2
            assert eqn.params["preferred_element_type"] == jnp.float32
            assert eqn.outvars[0].aval.dtype == jnp.float32

    # bfloat16 inputs, so the float32 reference sees the same numbers; on
    # the way to a gradient entry four values are rounded to bfloat16 once
    # each (the output O that delta is made of, P, dS, the gradient as it
    # is returned), 2**-9 relative each; twice that for what a row of up to
    # 512 such terms accumulates
    BF16_TOL = 8 * 2.0 ** -9

    # T of 3-4 blocks: a row of blocks holds a wholly live block, one the
    # diagonal (or the window's edge) crosses and a dead one at once
    CASES = {
        "square": dict(q=(2, 512, 32), bq=128, bk=128),
        "tiles_in_the_diagonal_block": dict(q=(1, 1536, 32), bq=512,
                                            bk=512),
        "window": dict(q=(2, 512, 32), bq=128, bk=128, window=200),
        "window_inside_a_block": dict(q=(1, 768, 32), bq=256, bk=256,
                                      window=100),
        "rect_q_wider": dict(q=(1, 512, 32), bq=256, bk=128),
        "rect_k_wider": dict(q=(1, 512, 32), bq=128, bk=256),
        "gqa": dict(q=(1, 4, 384, 32), kv=(1, 2, 384, 32), bq=128, bk=128),
        "padded": dict(q=(2, 450, 32), bq=128, bk=128),
        "heads_share_a_step": dict(q=(8, 256, 32), bq=256, bk=256),
        "non_causal": dict(q=(2, 384, 32), bq=128, bk=128, causal=False),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_bf16_forward_and_grads_match_dense(self, interpret_pallas, case):
        _assert_bf16_flash_matches_dense(self.CASES[case], self.BF16_TOL)


def _assert_bf16_flash_matches_dense(case, tol):
    """Forward, dQ, dK and dV of the kernels (interpreter mode) on bfloat16
    inputs against float32 ``dense_attention`` on the same numbers, each
    within ``tol`` of the reference's largest entry."""
    import jax
    from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
    c = dict(case)
    q_shape = c.pop("q")
    kv_shape = c.pop("kv", q_shape)
    bq, bk = c.pop("bq"), c.pop("bk")
    causal = c.pop("causal", True)
    window = c.pop("window", None)
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(*q_shape), jnp.bfloat16)
    k = jnp.asarray(rng.randn(*kv_shape), jnp.bfloat16)
    v = jnp.asarray(rng.randn(*kv_shape), jnp.bfloat16)
    cot = jnp.asarray(rng.randn(*q_shape), jnp.float32)
    group = q_shape[-3] // kv_shape[-3]

    def flash(a, b, c):
        return flash_attention(a, b, c, causal=causal, block_q=bq,
                               block_k=bk, window=window)

    def dense(a, b, c):
        a, b, c = (x.astype(jnp.float32) for x in (a, b, c))
        if group > 1:
            b, c = (jnp.repeat(x, group, axis=-3) for x in (b, c))
        return dense_attention(a, b, c, causal=causal, window=window)

    def grads(fn):
        return jax.grad(lambda a, b, c: (fn(a, b, c).astype(jnp.float32)
                                         * cot).sum(), (0, 1, 2))(q, k, v)
    got = (flash(q, k, v),) + grads(flash)
    want = (dense(q, k, v),) + grads(dense)
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), w, rtol=0,
            atol=tol * np.abs(w).max(), err_msg=name)


class TestFlashBoundary:
    """lse and delta cross the boundary of the three kernels one float32 a
    query row (``_stat_rows``): the forward writes, and the two backward
    kernels read, ``[n, T / block, 1, block]`` (``[n / G, T / block, 1,
    G·block]`` where a step holds a K/V group of G query heads); no
    lane-replicated ``[n, T, 128]`` copy of either is written, sliced or
    broadcast around them, in any of the shapes the callers send."""

    # q [batch, heads, T, d] (d never 128, so that a trailing 128 is a
    # replicated statistic and nothing else); n = batch x heads
    CASES = {
        "plain": dict(q=(1, 2, 512, 32), bq=128, bk=128),
        "remat": dict(q=(1, 2, 512, 32), bq=128, bk=128, remat=True),
        "window": dict(q=(1, 2, 512, 32), bq=128, bk=128, window=200),
        "gqa": dict(q=(1, 4, 384, 32), kv=(1, 2, 384, 32), bq=128, bk=128),
        "gqa_remat": dict(q=(1, 4, 384, 32), kv=(1, 2, 384, 32), bq=128,
                          bk=128, remat=True),
        "padded": dict(q=(1, 2, 450, 32), bq=128, bk=128),
        "heads_share_a_step": dict(q=(1, 8, 256, 32), bq=256, bk=256),
        "two_blocks_heads_share": dict(q=(1, 4, 512, 32), bq=256, bk=256),
        "block_under_128": dict(q=(1, 3, 64, 8), bq=16, bk=16),
        "block_under_128_remat": dict(q=(1, 3, 64, 8), bq=16, bk=16,
                                      remat=True),
    }

    @staticmethod
    def _sizes(case):
        b, h, t, _ = case["q"]
        return b * h, -(-t // case["bq"]) * case["bq"]   # n, padded T

    def _jaxpr(self, case):
        import jax
        from deeplearning4j_tpu.models.transformer import _remat
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jax.ShapeDtypeStruct(case["q"], jnp.bfloat16)
        kv = jax.ShapeDtypeStruct(case.get("kv", case["q"]), jnp.bfloat16)

        def attend(a, b, c):
            return flash_attention(a, b, c, causal=True, block_q=case["bq"],
                                   block_k=case["bk"],
                                   window=case.get("window"))
        if case.get("remat"):
            attend = _remat(attend)

        def loss(a, b, c):
            return attend(a, b, c).astype(jnp.float32).sum()
        return jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, kv).jaxpr

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_no_replicated_statistic_around_the_kernels(self,
                                                        interpret_pallas,
                                                        case):
        case = self.CASES[case]
        n, t = self._sizes(case)
        group = case["q"][1] // case.get("kv", case["q"])[1]
        stat = (n // group, t // case["bq"], 1, group * case["bq"])
        calls, named = [], []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                for var in list(eqn.invars) + list(eqn.outvars):
                    aval = getattr(var, "aval", None)
                    shape = getattr(aval, "shape", ())
                    # a float32 [n, T, 128] inside or outside a kernel's
                    # operands: the replicated lse or delta
                    assert not (aval is not None and aval.dtype == jnp.float32
                                and shape == (n, t, 128)), eqn
                if eqn.primitive.name == "broadcast_in_dim":
                    assert eqn.params["shape"][-1:] != (128,), eqn
                if eqn.primitive.name == "name":
                    named.append((eqn.params["name"], eqn.invars[0].aval))
                if eqn.primitive.name == "pallas_call":
                    calls.append(eqn)
                    continue
                for sub in _sub_jaxprs(eqn):
                    walk(sub)
        walk(self._jaxpr(case))
        assert len(calls) == 3
        # the forward's second result and the last two operands of each
        # backward kernel: one float32 a row, the same array's shape
        fwd = [c for c in calls if len(c.outvars) == 2
               and c.outvars[1].aval.shape == stat]
        assert len(fwd) == 1 and fwd[0].outvars[1].aval.dtype == jnp.float32
        for call in calls:
            if call is not fwd[0]:
                assert [(v.aval.shape, v.aval.dtype)
                        for v in call.invars[-2:]] == [(stat, jnp.float32)] * 2
        lse = [aval for name, aval in named if name == "flash_lse"]
        assert lse and all(a.size == n * t and a.dtype == jnp.float32
                           for a in lse)

    @pytest.mark.parametrize("case", sorted(
        c for c in CASES if "remat" not in c))
    def test_forward_and_grads_match_dense(self, interpret_pallas, case):
        _assert_bf16_flash_matches_dense(self.CASES[case],
                                         TestFlashGridStep.BF16_TOL)

    @pytest.mark.parametrize("case", ["plain", "gqa", "padded",
                                      "block_under_128"])
    def test_gauge_counts_four_bytes_a_row_a_statistic_a_kernel(
            self, interpret_pallas, case):
        """``flash.stat_bytes``: the forward has lse at its boundary, dQ and
        dK/dV lse and delta each: five statistics a layer, 4 n T bytes each
        (512 n T while the forward and dQ took them lane-replicated)."""
        from deeplearning4j_tpu import obs
        case = self.CASES[case]
        n, t = self._sizes(case)
        obs.reset_metrics()
        self._jaxpr(case)
        assert obs.metrics.value("flash.stat_bytes") == 5 * 4 * n * t
        obs.reset_metrics()


class TestFlashPlan:
    """``flash_plan``: heads a grid step, the compute tile and the K/V group
    a step holds, from shapes alone (no knob); and the tiles a block's kind
    leaves to compute."""

    # (n, t, d, itemsize, block_q, block_k, kv_group) -> (heads, tile_q,
    # tile_k[, group]); a group of 1 where none is given
    CASES = {
        # the benchmark's cells: 8 x 16 heads at T 1024, 32 x 16 at T 256
        "gpt2m_t1024": ((128, 1024, 64, 2, 512, 512, 1), (4, 256, 256)),
        "gpt2m_t256": ((512, 256, 64, 2, 256, 256, 1), (8, 256, 256)),
        # chip_smoke's GPT-2 small: 96 rows
        "gpt2s_t1024": ((96, 1024, 64, 2, 512, 512, 1), (4, 256, 256)),
        # float32 blocks are twice the bytes. With lse and delta one
        # float32 a row at the dQ kernel's boundary (two double-buffered
        # [block, 128] blocks a statistic before PR 33, a [block, 128]
        # scratch a statistic now) a step holds 4 float32 heads at block
        # 512 (2 before), 8 at block 256 (4), 2 at block 1024 (1), and 8
        # bfloat16 heads at block 384 (4)
        "float32": ((64, 1024, 64, 4, 512, 512, 1), (4, 256, 256)),
        "float32_block_256": ((128, 512, 64, 4, 256, 256, 1), (8, 256, 256)),
        "bfloat16_block_384": ((128, 768, 64, 2, 384, 384, 1),
                               (8, 384, 384)),
        "float32_block_1024": ((64, 1024, 64, 4, 1024, 1024, 1),
                               (2, 256, 256)),
        "d128": ((128, 1024, 128, 2, 512, 512, 1), (2, 256, 256)),
        # long rows keep one row a step; grouped-query attention takes the
        # whole group a step, its heads folded into the rows of 256-row
        # strips of tiles: the cells' groups of 6 and 8 (Laguna's full and
        # window layers, 2 x 48 and 2 x 64 query heads on 2 x 8 K/V heads)
        # and of 7 (SmallThinker's, 28 on 4)
        "gqa": ((32, 2048, 128, 2, 512, 512, 4), (1, 256, 256, 4)),
        "long_row": ((32, 4096, 64, 2, 512, 512, 1), (1, 256, 256)),
        "laguna_full": ((96, 8192, 128, 2, 512, 512, 6), (1, 256, 256, 6)),
        "laguna_window": ((128, 8192, 128, 2, 512, 512, 8),
                          (1, 256, 256, 8)),
        "smallthinker": ((28, 16384, 128, 2, 512, 512, 7), (1, 256, 256, 7)),
        # a group the VMEM of a step holds only at narrower strips
        "gqa_16_d256": ((32, 4096, 256, 2, 512, 512, 16), (1, 64, 256, 16)),
        # a short grouped row is folded as well; tiles stay powers of two
        # that divide the block
        "gqa_short_row": ((8, 256, 64, 2, 256, 256, 2), (1, 256, 256, 2)),
        "gqa_block_384": ((16, 768, 64, 2, 384, 384, 4), (1, 128, 384, 4)),
        # strips stay whole sublane tiles: 16 bfloat16 rows, 8 float32
        "gqa_block_48": ((8, 48, 64, 2, 48, 48, 4), (1, 16, 48, 4)),
        "gqa_block_24_float32": ((8, 24, 64, 4, 24, 24, 4), (1, 8, 24, 4)),
        # a group that folds into no step keeps one query head a step, one
        # tile a block side: 32 query heads a K/V head at d 128 pass the
        # VMEM of a step at any tile, a block of 300 rows cuts into tiles of
        # 4, a bfloat16 block of 24 into tiles of 8
        "gqa_32_d128": ((64, 4096, 128, 2, 512, 512, 32), (1, 256, 256)),
        "gqa_block_300": ((8, 300, 64, 2, 300, 300, 4), (1, 300, 300)),
        "gqa_block_24": ((8, 24, 64, 2, 24, 24, 4), (1, 24, 24)),
        # heads divide n
        "odd_n": ((3, 256, 64, 2, 256, 256, 1), (1, 256, 256)),
        "n_6": ((6, 32, 8, 4, 16, 16, 1), (2, 16, 16)),
        # blocks that 256 does not divide are one tile
        "block_128": ((64, 1024, 64, 2, 128, 128, 1), (1, 128, 128)),
        "block_384": ((4, 384, 32, 2, 384, 384, 1), (4, 384, 384)),
        "rect": ((2, 512, 32, 2, 256, 128, 1), (1, 256, 128)),
        "small_block": ((2, 12, 8, 4, 12, 12, 1), (2, 12, 12)),
    }

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_plan_from_shapes(self, case):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        args, want = self.CASES[case]
        plan = pk.flash_plan(*args)
        assert plan == pk.FlashPlan(*want)
        n, t, d, itemsize, bq, bk, kv_group = args
        assert n % (plan.heads * plan.group) == 0
        assert bq % plan.tile_q == 0 and bk % plan.tile_k == 0
        assert pk._flash_vmem_bytes(plan.heads, bq, bk, d,
                                    itemsize) <= pk._VMEM_BUDGET
        assert plan.group in (1, kv_group)
        if kv_group > 1:
            assert plan.heads == 1
        if plan.group > 1:
            assert pk._group_vmem_bytes(kv_group, bq, bk, plan.tile_q,
                                        plan.tile_k, d, itemsize) \
                <= pk._GROUP_VMEM
            assert plan.tile_q % (32 // itemsize) == 0
            assert bq // plan.tile_q <= pk._MAX_STRIPS

    def test_a_group_past_the_vmem_of_a_step_keeps_a_head_a_step(self):
        """From shapes alone: a group whose blocks alone pass the VMEM a
        grouped step may take runs one query head a step, as an ungrouped
        long row does; smaller blocks fold it again."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        assert pk.flash_plan(64, 4096, 256, 2, 1024, 1024, 64) \
            == pk.FlashPlan(1, 256, 256)
        assert pk.flash_plan(16, 4096, 256, 2, 256, 256, 16).group == 16

    @pytest.mark.parametrize("kind,tile,want", [
        # a 512-block in 256-tiles: the diagonal block leaves out the tile
        # above the diagonal and masks the two on it
        ("diag", 256, [(0, 0, True), (256, 0, False), (256, 256, True)]),
        ("full", 256, [(0, 0, False), (0, 256, False), (256, 0, False),
                       (256, 256, False)]),
        ("edge", 256, [(0, 0, True), (0, 256, True), (256, 0, True),
                       (256, 256, True)]),
        # one tile a block: the step the kernels took before the tiles
        ("diag", 512, [(0, 0, True)]),
        ("full", 512, [(0, 0, False)]),
    ])
    def test_tiles_of_a_block(self, kind, tile, want):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        plan = pk.FlashPlan(1, tile, tile)
        assert pk._tiles(kind, 512, 512, plan) == want
        # 128-tiles of the diagonal block: 10 of 16, 4 of them masked
        fine = pk._tiles("diag", 512, 512, pk.FlashPlan(1, 128, 128))
        assert (len(fine), sum(m for _, _, m in fine)) == (10, 4)


class TestFlashWalk:
    """``flash_walk``: the grid steps of a row of n are its live (Q block,
    K block) pairs, each once, in the order the sums are made in, from the
    statics of a call alone."""

    # T, block_q, block_k, window, causal
    CASES = {
        # the third cell's two layer kinds: rows of 16 blocks
        "laguna_full": (8192, 512, 512, None, True),
        "laguna_window": (8192, 512, 512, 512, True),
        "rows_of_8": (4096, 512, 512, None, True),
        "window_one_and_a_half_blocks": (4096, 512, 512, 768, True),
        "window_no_multiple_of_the_block": (1024, 128, 128, 200, True),
        "window_inside_a_block": (768, 256, 256, 100, True),
        "window_of_one": (64, 8, 8, 1, True),
        "rect_q_wider": (2048, 256, 128, None, True),
        "rect_k_wider_window": (2048, 128, 256, 300, True),
        "odd_block_count": (640, 128, 128, None, True),
        "non_causal": (1024, 128, 256, None, False),
        # the GPT-2 cells: two blocks a row, one block a row
        "gpt2m_t1024": (1024, 512, 512, None, True),
        "gpt2m_t256": (256, 256, 256, None, True),
    }

    @staticmethod
    def _live_by_position(t, bq, bk, window, causal):
        """The live pairs from the mask entry by entry, not from
        ``_block_live``."""
        pos = np.arange(t)
        dist = pos[:, None] - pos[None, :]
        keep = np.ones((t, t), bool)
        if causal:
            keep = dist >= 0
            if window is not None:
                keep &= dist < window
        blocks = keep.reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
        return {(int(i), int(j)) for i, j in zip(*np.nonzero(blocks))}

    @pytest.mark.parametrize("inner", ["k", "q"])
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_walks_the_live_pairs_once_in_order(self, case, inner):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        t, bq, bk, window, causal = self.CASES[case]
        walk = pk.flash_walk(causal, window, bq, bk, t, inner)
        pairs = list(zip(walk.q.tolist(), walk.k.tolist()))
        want = self._live_by_position(t, bq, bk, window, causal)
        assert len(pairs) == len(set(pairs)) == walk.steps == walk.live
        assert set(pairs) == want
        if causal:
            assert all(bool(pk._block_live(i, j, bq, bk, window)) ==
                       ((i, j) in want)
                       for i in range(t // bq) for j in range(t // bk))
        # outer block by outer block, the inner blocks ascending: the order
        # of the rectangle's live steps
        key = (lambda p: p) if inner == "k" else (lambda p: p[::-1])
        assert pairs == sorted(pairs, key=key)
        outer = walk.q if inner == "k" else walk.k
        n_outer = t // (bq if inner == "k" else bk)
        first = (walk.flags & pk._FIRST) != 0
        last = (walk.flags & pk._LAST) != 0
        # every outer block is there, with one first and one last step
        assert outer[first].tolist() == outer[last].tolist() \
            == list(range(n_outer))
        for o in range(n_outer):
            steps = np.flatnonzero(outer == o)
            assert first[steps[0]] and last[steps[-1]]
            assert (np.diff(steps) == 1).all()
        assert walk.single == (walk.steps == n_outer)

    def test_live_over_walked_at_the_cells_shapes(self):
        """At 16 blocks a row the rectangle held 256 steps: 136 live under
        the causal mask, 31 under a window of one block. The walk takes
        those and nothing else, in all three kernels."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        for case, live in (("laguna_full", 136), ("laguna_window", 31)):
            t, bq, bk, window, causal = self.CASES[case]
            for inner in "kq":
                walk = pk.flash_walk(causal, window, bq, bk, t, inner)
                assert (walk.steps, walk.live) == (live, live)
        one = pk.flash_walk(True, None, 256, 256, 256, "k")
        assert (one.steps, one.single) == (1, True)

    @pytest.mark.parametrize("case,rows,steps", [
        ("laguna_window", 128, 31), ("laguna_full", 96, 136),
        ("gpt2m_t1024", 128 // 4, 3), ("gpt2m_t256", 512 // 8, 1)])
    def test_the_grid_of_all_three_kernels_is_the_walk(self, case, rows,
                                                       steps):
        """At the cells' shapes (bfloat16; d 128 for the third cell's, 64
        for GPT-2's): every ``pallas_call`` of a traced forward + backward
        has the grid ``(rows of n / heads a step, steps of the walk)`` and
        takes the walk's table ahead of its arrays."""
        import jax
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        t, bq, bk, window, causal = self.CASES[case]
        d = 128 if case.startswith("laguna") else 64
        n = {"laguna_window": 128, "laguna_full": 96, "gpt2m_t1024": 128,
             "gpt2m_t256": 512}[case]
        x = jax.ShapeDtypeStruct((n, t, d), jnp.bfloat16)

        def loss(q, k, v):
            return pk.flash_attention(
                q, k, v, causal=causal, block_q=bq, block_k=bk,
                window=window).astype(jnp.float32).sum()
        jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, x, x).jaxpr
        grids = [(eqn.params["grid_mapping"].grid,
                  eqn.params["grid_mapping"].num_index_operands)
                 for eqn in _eqns(jaxpr) if eqn.primitive.name == "pallas_call"]
        assert grids == [((rows, steps), 1)] * 3

    def test_a_row_past_the_tables_bound_is_refused(self):
        """Decided by the shapes alone: past ``MAX_WALK_STEPS`` the call
        raises before anything is built (the bound itself compiles for the
        chip: tests/test_aot_compile.py)."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        blocks = 362                       # 65,703 causal pairs
        assert blocks * (blocks + 1) // 2 > pk.MAX_WALK_STEPS
        with pytest.raises(ValueError, match="larger blocks"):
            pk.flash_walk(True, None, 8, 8, 8 * blocks, "k")
        assert pk.flash_walk(True, None, 8, 8, 8 * 361, "q").steps \
            == 361 * 362 // 2 <= pk.MAX_WALK_STEPS

    def test_gauges_count_the_steps_of_a_traced_call(self, interpret_pallas):
        """``flash.steps_walked`` / ``flash.steps_live``: rows of n times the
        walk's steps, summed over the kernels a traced program holds."""
        import jax
        from deeplearning4j_tpu import obs
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        x = jnp.zeros((2, 64, 8), jnp.float32)

        def loss(q, k, v, window):
            return pk.flash_attention(q, k, v, causal=True, block_q=16,
                                      block_k=16, window=window).sum()
        obs.reset_metrics()
        # forward alone: 2 rows x 10 of the 16 pairs of a causal row of 4
        jax.make_jaxpr(lambda q: loss(q, q, q, None))(x)
        assert (obs.metrics.value("flash.steps_walked"),
                obs.metrics.value("flash.steps_live")) == (20, 20)
        # a window of one block, forward + dQ + dK/dV: 3 x 2 rows x 7 pairs
        obs.reset_metrics()
        jax.make_jaxpr(jax.grad(lambda q: loss(q, q, q, 16)))(x)
        assert (obs.metrics.value("flash.steps_walked"),
                obs.metrics.value("flash.steps_live")) == (42, 42)
        obs.reset_metrics()


class TestFlashGroupFold:
    """A grid step that holds a K/V group folds the group's query heads into
    the rows of each tile's products: the kernels' code, and so what a
    program spends tracing and lowering them, does not grow with the group
    (each head a copy of the step's code, as the rows of an ungrouped step
    are, traced eight copies of the body at a group of 8)."""

    @staticmethod
    def _kernel_eqns(q_shape, kv_shape, block, window):
        """Equations of each flash kernel's jaxpr in a traced forward +
        backward, through its branches (``pl.when``) and loops."""
        import jax
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
        kv = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16)

        def loss(a, b, c):
            return pk.flash_attention(
                a, b, c, causal=True, block_q=block, block_k=block,
                window=window).astype(jnp.float32).sum()
        calls = _pallas_calls(
            jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, kv).jaxpr)
        assert sorted(calls) == sorted(TestFlashGridStep.PRODUCTS)
        return {name: sum(1 for _ in _eqns(jaxpr))
                for name, jaxpr in calls.items()}

    @pytest.mark.parametrize("window", [None, 16, 40])
    def test_a_group_of_8_is_the_code_of_one_head(self, interpret_pallas,
                                                  window):
        """One head and a group of 8 on the same walk (rows of 4 blocks of
        16, one 16-row tile a block either way): at most a quarter more
        equations in each kernel, the reshapes that fold and unfold."""
        one = self._kernel_eqns((1, 1, 64, 16), (1, 1, 64, 16), 16, window)
        eight = self._kernel_eqns((1, 8, 64, 16), (1, 1, 64, 16), 16,
                                  window)
        for name, count in one.items():
            assert eight[name] <= 1.25 * count, (name, count, eight[name])

    @pytest.mark.parametrize("window", [None, 512])
    def test_the_cells_groups_trace_the_same_kernels(self, interpret_pallas,
                                                     window):
        """Laguna's groups of 6 and 8 and SmallThinker's of 7, at the cells'
        blocks (512, d 128, 256-row tiles folded): the same number of
        equations, kernel by kernel, and at most a quarter more than one
        ungrouped head's."""
        counts = [self._kernel_eqns((1, 8 * g, 2048, 128), (1, 8, 2048, 128),
                                    512, window) for g in (6, 7, 8)]
        one = self._kernel_eqns((1, 1, 2048, 128), (1, 1, 2048, 128), 512,
                                window)
        assert counts[0] == counts[1] == counts[2]
        for name, count in one.items():
            assert counts[0][name] <= 1.25 * count, (name, count)

    @staticmethod
    def _twin_gauges(name):
        """``flash.steps_walked`` and ``flash.grouped_steps`` once the
        training step of a rehearsal twin is traced."""
        import json
        import os

        import jax
        from deeplearning4j_tpu import obs
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(root, "benchmark", "rehearsal", "configs",
                               f"{name}-tiny.json")) as f:
            config = json.load(f)
        seq = {"laguna": 32, "smallthinker": 64, "gpt2": 64}[name]
        if name == "gpt2":
            conf = TransformerConfig(
                vocab_size=config["vocab_size"],
                max_len=config["n_positions"], d_model=config["n_embd"],
                n_heads=config["n_head"], n_layers=config["n_layer"],
                d_ff=4 * config["n_embd"],
                block_size=config["assumed"]["block_size"],
                compute_dtype=config["assumed"]["compute_dtype"])
        else:
            from benchmark.drivers import laguna_train, smallthinker_train
            program = {"laguna": laguna_train,
                       "smallthinker": smallthinker_train}[name]
            conf = program.program_config(config, seq, 7)
        lm = TransformerLM(conf)
        params, opt = jax.eval_shape(lambda: (lm.init().params,
                                              lm.opt_state))
        lm.params = lm.opt_state = None
        tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
        obs.reset_metrics()
        lm._build_step().trace(
            params, opt, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32), tokens, tokens, None)
        read = (obs.metrics.value("flash.steps_walked"),
                obs.metrics.value("flash.grouped_steps"))
        obs.reset_metrics()
        return read

    @pytest.mark.parametrize("name", ["laguna", "smallthinker", "gpt2"])
    def test_gauge_counts_the_steps_that_hold_a_group(self, interpret_pallas,
                                                      monkeypatch, name):
        """``flash.grouped_steps``: every walked step of the grouped twins
        (Laguna's 4 query heads on 2 K/V heads, SmallThinker's 14 on 2),
        none of GPT-2's."""
        monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
        walked, grouped = self._twin_gauges(name)
        assert walked > 0
        assert grouped == (0 if name == "gpt2" else walked)


class TestFlashRemat:
    """A rematerialised block whose policy keeps ``FLASH_RESIDUALS``
    (``transformer._remat``) runs the forward kernel once: its backward holds
    dQ and dK/dV and no second forward, and computes the same bits."""

    # (query heads, K/V heads, window): T 64 in blocks of 16, heads of 8
    CASES = {"full": (2, 2, None), "window": (2, 2, 16), "gqa": (4, 2, None)}
    T, HD, D, BLOCK, BLOCKS = 64, 8, 16, 16, 2

    def _stack(self, case, wrap):
        """(loss of a two-block stack, its weights and input); ``wrap`` is
        what each block goes through."""
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        heads, kv_heads, window = self.CASES[case]
        rng = np.random.RandomState(5)
        cols = (heads + 2 * kv_heads) * self.HD
        weights = [
            {"qkv": jnp.asarray(rng.randn(self.D, cols) * 0.3, jnp.float32),
             "proj": jnp.asarray(rng.randn(heads * self.HD, self.D) * 0.3,
                                 jnp.float32)}
            for _ in range(self.BLOCKS)]
        x = jnp.asarray(rng.randn(1, self.T, self.D), jnp.float32)

        def block(w, x):
            q, k, v = (
                a.reshape(1, self.T, -1, self.HD).transpose(0, 2, 1, 3)
                for a in jnp.split(x @ w["qkv"], [heads * self.HD,
                                                  (heads + kv_heads) * self.HD],
                                   axis=-1))
            o = flash_attention(q, k, v, causal=True, block_q=self.BLOCK,
                                block_k=self.BLOCK, window=window)
            return x + o.transpose(0, 2, 1, 3).reshape(x.shape[:2] + (-1,)) \
                @ w["proj"]

        def loss(weights, x):
            for w in weights:
                x = wrap(block)(w, x)
            return jnp.square(x).sum()
        return loss, weights, x

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_backward_holds_no_second_forward(self, interpret_pallas, case):
        import jax
        from deeplearning4j_tpu.models.transformer import _remat

        def calls(wrap):
            loss, weights, x = self._stack(case, wrap)
            jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(weights, x).jaxpr
            return sum(e.primitive.name == "pallas_call"
                       for e in _eqns(jaxpr))
        assert calls(_remat) == 3 * self.BLOCKS
        assert calls(jax.checkpoint) == 4 * self.BLOCKS
        assert calls(lambda blk: blk) == 3 * self.BLOCKS

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_gradients_are_the_bare_checkpoints_bits(self, interpret_pallas,
                                                     case):
        import jax
        from deeplearning4j_tpu.models.transformer import _remat

        def grads(wrap):
            loss, weights, x = self._stack(case, wrap)
            return jax.tree.leaves(
                jax.jit(jax.grad(loss, (0, 1)))(weights, x))
        kept = grads(_remat)
        assert all(np.abs(np.asarray(g)).max() > 0 for g in kept)
        for other in (jax.checkpoint, lambda blk: blk):
            for got, want in zip(kept, grads(other)):
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))

    def test_a_name_under_no_policy_lowers_to_nothing(self, interpret_pallas,
                                                      monkeypatch):
        """Without a policy that saves them, ``FLASH_RESIDUALS`` change no
        operation of a flash call's lowered forward and backward. (The
        numbers MLIR's symbol table hangs on a private function's name are
        left out: jax lowers each distinct equation once as a function named
        after its primitive and inlines it, and a second ``name`` equation
        moves that counter on by one.)"""
        import re

        import jax
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        x = jnp.zeros((2, 64, 8), jnp.float32)

        def lowered():
            def loss(q, k, v):
                return pk.flash_attention(q, k, v, causal=True, block_q=16,
                                          block_k=16).sum()
            text = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
                x, x, x).as_text()
            return re.sub(r"(@\w+?)_\d+\b", r"\1", text)
        named = lowered()
        seen = []
        monkeypatch.setattr(pk, "checkpoint_name",
                            lambda a, name: seen.append(name) or a)
        assert lowered() == named
        assert tuple(seen) == pk.FLASH_RESIDUALS


# (rows of the buffer, k, n, group sizes, row tile, dtype): the grouped
# products of the expert layer, ``pallas_kernels.grouped_matmul``
GROUPED_CASES = {
    # every row in a group, every group of whole tiles
    "groups_fill_the_buffer": (64, 32, 16, [8] * 8, 8, "float32"),
    # three quarters of the buffer in no group
    "tail_of_three_quarters_in_no_group":
        (128, 32, 48, [5, 9, 3, 15], 8, "float32"),
    # one expert takes most rows, several take none
    "one_full_group_and_empty_ones":
        (96, 32, 16, [70, 0, 0, 1, 0, 0, 2, 0], 16, "float32"),
    # boundaries inside a tile, three groups in one tile, rows that are no
    # multiple of the tile
    "group_boundaries_inside_a_tile": (72, 32, 16, [10, 7, 2, 3, 30], 16,
                                       "float32"),
    # Laguna's twin: d_expert narrower than the row tile, bfloat16
    "laguna_twin_bf16": (128, 64, 32, [0, 40, 11, 0, 9, 30, 2, 1], 64,
                         "bfloat16"),
    # blocks of the contraction and of the output's width: the accumulator
    # across k steps, the weight gradient's output in six blocks
    "contraction_and_width_in_blocks": (64, 256, 384, [13, 0, 20, 7], 16,
                                        "bfloat16"),
}


class TestGroupedMatmul:
    """The expert layer's grouped product over the row tiles that hold a
    group's rows, against a float32 loop over the groups."""

    @staticmethod
    def _loop(a, w, g, sizes):
        a, w, g = (np.asarray(x, np.float32) for x in (a, w, g))
        out = np.zeros((a.shape[0], w.shape[2]), np.float32)
        da, dw = np.zeros(a.shape, np.float32), np.zeros(w.shape, np.float32)
        lo = 0
        for e, size in enumerate(sizes):
            hi = lo + size
            out[lo:hi] = a[lo:hi] @ w[e]
            da[lo:hi] = g[lo:hi] @ w[e].T
            dw[e] = a[lo:hi].T @ g[lo:hi]
            lo = hi
        return out, da, dw

    @pytest.mark.parametrize("case", sorted(GROUPED_CASES))
    def test_forward_and_both_gradients_with_a_poisoned_tail(
            self, case, interpret_pallas, monkeypatch):
        """The operands' rows outside every group are NaN: result and input
        gradient are finite and right on the rows inside the groups, the
        weight gradient is finite and right, and the walk visits no tile
        without a group's row."""
        import jax
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        rows, k, n, sizes, tile, dtype = GROUPED_CASES[case]
        live, groups = sum(sizes), len(sizes)
        keys = jax.random.split(jax.random.PRNGKey(3), 3)
        a = jax.random.normal(keys[0], (rows, k), dtype)
        w = jax.random.normal(keys[1], (groups, k, n), dtype)
        g = jax.random.normal(keys[2], (rows, n), dtype)
        inside = (jnp.arange(rows) < live)[:, None]
        want = self._loop(jnp.where(inside, a, 0), w, jnp.where(inside, g, 0),
                          sizes)
        a, g = jnp.where(inside, a, jnp.nan), jnp.where(inside, g, jnp.nan)
        if case == "contraction_and_width_in_blocks":
            monkeypatch.setattr(pk, "_GROUPED_VMEM", 150_000)
            assert pk.grouped_plan(tile, k, n, 2) == ((128, 128), (128, 128))
        table = pk.group_tiles(jnp.asarray(sizes, jnp.int32), rows, tile)
        out, vjp = jax.vjp(
            lambda a, w: pk.grouped_matmul(a, w, table, tile), a, w)
        da, dw = vjp(g)
        tol = 1e-5 if dtype == "float32" else 2e-2
        for name, got, ref in (("out", out[:live], want[0][:live]),
                               ("da", da[:live], want[1][:live]),
                               ("dw", dw, want[2])):
            got = np.asarray(got, np.float32)
            assert np.isfinite(got).all(), name
            np.testing.assert_allclose(got, ref, rtol=tol,
                                       atol=tol * np.abs(ref).max(),
                                       err_msg=name)
        # a step a (tile, group) pair with a row in common, one a group
        # of no rows
        bounds = np.concatenate([[0], np.cumsum(sizes)])
        steps = sum(1 if hi == lo else (hi - 1) // tile - lo // tile + 1
                    for lo, hi in zip(bounds[:-1], bounds[1:]))
        assert int(table[-1]) == steps <= -(-live // tile) + groups
        walked = np.asarray(table[groups + 1:-1]).reshape(2, -1)[:, :steps]
        assert (np.diff(walked[0]) >= 0).all() and \
            (np.diff(walked[1]) >= 0).all()
        assert walked[1].max() <= max(live - 1, 0) // tile

    def test_row_tile_follows_the_even_group(self):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        # the fifth cell, the third, chip_smoke's mixed model, the twins
        assert [pk.grouped_row_tile(x) for x in (1536, 512, 256, 16, 3)] \
            == [256, 256, 128, 8, 8]

    def test_the_plan_fits_the_budget_at_the_cells_widths(self):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        # the fifth cell's gate / up product keeps a whole [2560, 768] weight
        # in a block; its down product and its weight gradients are halved
        assert pk.grouped_plan(256, 2560, 768, 2) == ((2560, 768), (1280, 768))
        assert pk.grouped_plan(256, 768, 2560, 2) == ((768, 1280), (768, 1280))
        for tile, k, n in ((256, 2560, 768), (256, 768, 2560),
                           (256, 2048, 512), (256, 512, 2048), (8, 64, 32)):
            (bk, bn), (ok, on) = pk.grouped_plan(tile, k, n, 2)
            assert k % bk == 0 and n % bn == 0 and k % ok == 0 and n % on == 0
            blocks = 4 * (tile * bk + bk * bn + tile * bn)
            assert blocks + 4 * tile * bn * (1 + (bk < k)) <= pk._GROUPED_VMEM
            assert 8 * ok * on + 4 * tile * (ok + on) <= pk._GROUPED_VMEM
        with pytest.raises(ValueError, match="fit"):
            pk.grouped_plan(256, 100_000, 100_000, 2)


# (tokens, experts, top_k, held, row_buffer, d, dtype, the expert no token
# chooses): the rows around the grouped products, ``gather_rows`` and
# ``combine_rows``, on the indices ``expert_layer.dispatch`` makes
MOVE_CASES = {
    # the fifth cell's twin: top 6, a buffer of every assignment of which a
    # quarter is live, bfloat16 columns packed two a word
    "top6_quarter_of_the_buffer_live": (64, 16, 6, 4, 4.0, 256, "bfloat16",
                                        None),
    # the third cell's twin: top 8, half the buffer live
    "top8_half_of_the_buffer_live": (64, 32, 8, 4, 2.0, 256, "bfloat16",
                                     None),
    # a held expert with no assignment: a group of no rows in the walk
    "an_empty_group": (48, 8, 2, 4, 2.0, 128, "float32", 2),
    # more assignments than rows: the last ones have no row (pos -1)
    "rows_over_buffer": (64, 8, 3, 4, 0.5, 128, "float32", None),
    # float32 rows of a width that is no lane tile, one word a column
    "float32_narrow_rows": (40, 8, 3, 2, 4.0, 48, "float32", None),
    # bfloat16 rows too narrow to pack go as float32 words
    "bfloat16_narrow_rows": (40, 8, 3, 2, 4.0, 64, "bfloat16", None),
    # a row tile of two chunks of 32: the kernels' chunk loop is a loop
    "row_tiles_of_two_chunks": (256, 8, 4, 4, 2.0, 256, "bfloat16", None),
}


class TestRowMovement:
    """``gather_rows`` and ``combine_rows`` against ``src[token]`` and
    ``.at[token].add`` in float32 on the rows inside the groups, forward and
    all three gradients, the buffer's tail and the dead tiles poisoned."""

    @staticmethod
    def _case(case):
        from deeplearning4j_tpu.models import expert_layer
        tokens, experts, top_k, held, buffer, d, dtype, unused = \
            MOVE_CASES[case]
        rng = np.random.RandomState(len(case))
        scores = rng.rand(tokens, experts)
        if unused is not None:
            scores[:, unused] = -1.0
        chosen = jnp.asarray(np.argsort(-scores, -1)[:, :top_k], jnp.int32)
        ex = expert_layer.Experts(experts, top_k, 8, held=(0, held),
                                  row_buffer=buffer)
        routing = expert_layer.Routing(
            jnp.asarray(rng.rand(tokens, top_k) + 0.1, jnp.float32),
            *expert_layer.dispatch(ex, chosen, tokens))
        tile = expert_layer._row_tile(ex, tokens)
        rows = routing.order.shape[0]
        src = jnp.asarray(rng.randn(tokens, d), dtype)
        out = jnp.asarray(rng.randn(rows, d), dtype)
        return routing, tile, src, out, rng

    @pytest.mark.parametrize("case", sorted(MOVE_CASES))
    def test_forward_and_three_gradients_with_poisoned_dead_rows(
            self, case, interpret_pallas):
        import jax
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        routing, tile, src, out, rng = self._case(case)
        w, order, pos, valid, sizes, stats, table = routing
        tokens, top_k = w.shape
        rows, d = out.shape
        live = int(valid.sum())
        assert table is not None and 0 < live <= rows
        token = np.asarray(order) // top_k
        sizes = np.asarray(sizes)
        walked = np.asarray(table[len(sizes) + 1:-1]).reshape(2, -1)[
            1, :int(table[-1])]
        # what the cases are for
        if case.startswith("top"):
            share = {"top6_quarter_of_the_buffer_live": 4,
                     "top8_half_of_the_buffer_live": 2}[case]
            assert 0.75 * rows < share * live < 1.25 * rows
            assert (np.bincount(walked) > 1).any()  # a tile two groups share
            assert (np.asarray(pos) < 0).all(axis=1).any()   # a token left out
        if case == "an_empty_group":
            assert sizes[2] == 0 and sizes.sum() == live
        if case == "rows_over_buffer":
            assert int(stats["rows_over_buffer"]) > 0 and live == rows
        if case == "row_tiles_of_two_chunks":
            assert tile == 2 * pk._ROW_CHUNK
        # pos is the inverse of order on the valid rows, -1 elsewhere
        flat_pos = np.asarray(pos).reshape(-1)
        np.testing.assert_array_equal(flat_pos[np.asarray(order)[:live]],
                                      np.arange(live))
        assert (flat_pos >= 0).sum() == live
        visited = (int(walked.max()) + 1) * tile
        f32 = lambda x: np.asarray(x, np.float32)
        tol = 1e-5 if out.dtype == jnp.float32 else 1e-2
        poison = lambda x: jnp.where(valid[:, None], x, jnp.nan)

        # the gather: a row's own bits inside the groups, zeros in the rest
        # of a visited tile, nothing written past the walk; two references
        # to the one array, for two readers
        (got, again), vjp = jax.vjp(
            lambda s: pk.gather_rows(s, order, pos, table, tile, 2), src)
        assert again is got
        np.testing.assert_array_equal(f32(got)[:live], f32(src)[token[:live]])
        assert (f32(got)[live:visited] == 0).all()
        assert np.isnan(f32(got)[visited:]).all()
        # its transpose: the readers' cotangents summed as their dtype sums
        # them, then a token's sum over its rows, in float32
        g = [jnp.asarray(rng.randn(rows, d), out.dtype) for _ in range(2)]
        d_src, = vjp(tuple(poison(x) for x in g))
        want = np.zeros((tokens, d), np.float32)
        np.add.at(want, token[:live], f32(g[0] + g[1])[:live])
        np.testing.assert_allclose(f32(d_src), want, rtol=tol, atol=tol)

        # the combine, the rows outside the groups NaN
        w_rows = f32(w).reshape(-1)[np.asarray(order)]
        y, vjp = jax.vjp(
            lambda o, w: pk.combine_rows(o, w, order, pos, table, tile),
            poison(out), w)
        want = np.zeros((tokens, d), np.float32)
        np.add.at(want, token[:live], w_rows[:live, None] * f32(out)[:live])
        np.testing.assert_allclose(f32(y), want, rtol=tol, atol=tol)
        dy = jnp.asarray(rng.randn(tokens, d), out.dtype)
        d_out, d_w = vjp(dy)
        np.testing.assert_allclose(
            f32(d_out)[:live], w_rows[:live, None] * f32(dy)[token[:live]],
            rtol=tol, atol=tol)
        assert (f32(d_out)[live:visited] == 0).all()
        want = np.zeros(tokens * top_k, np.float32)
        want[np.asarray(order)[:live]] = (
            f32(out)[:live] * f32(dy)[token[:live]]).sum(1)
        np.testing.assert_allclose(f32(d_w).reshape(-1), want, rtol=tol,
                                   atol=tol * np.abs(want).max())

    def test_the_token_tile_follows_top_k_and_the_width(self):
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        # the fifth cell, the third, float32 rows, the twins
        assert [pk.combine_plan(*x) for x in (
            (6, 2560, "bfloat16"), (8, 2048, "bfloat16"),
            (6, 2560, "float32"), (2, 64, "bfloat16"))] == [128, 128, 64, 256]
        for top_k, d, dtype in ((6, 2560, "bfloat16"), (8, 2048, "bfloat16")):
            tile = pk.combine_plan(top_k, d, dtype)
            assert tile * top_k * (d // 2) * 4 <= pk._COMBINE_VMEM


class TestSlidingWindow:
    """Causal sliding-window attention: the kernels mask entries more than
    window-1 positions in the past and skip fully out-of-window blocks."""

    def test_forward_matches_dense_window(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(2, 64, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 64, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 64, 8), jnp.float32)
        for w in (1, 7, 16, 40, 64, 1000):
            out = flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=16, window=w)
            ref = dense_attention(q, k, v, causal=True, window=w)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-5, err_msg=f"window={w}")

    def test_grads_match_dense_window(self, rng, interpret_pallas):
        import jax
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 64, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 64, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 64, 8), jnp.float32)
        cot = jnp.asarray(np.random.RandomState(7).randn(1, 64, 8),
                          jnp.float32)

        def gr(fn):
            return jax.grad(lambda a, b, c: (fn(a, b, c) * cot).sum(),
                            argnums=(0, 1, 2))(q, k, v)
        for w in (9, 16, 33):
            got = gr(lambda a, b, c: flash_attention(
                a, b, c, causal=True, block_q=16, block_k=16, window=w))
            want = gr(lambda a, b, c: dense_attention(
                a, b, c, causal=True, window=w))
            for g1, g2, name in zip(got, want, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(g1), np.asarray(g2), atol=2e-4,
                    err_msg=f"d{name} window={w}")

    # rows of 8 and 16 blocks (the third cell's are 16): a window of one
    # block, of one and a half, and none; the last two with rectangular
    # blocks. Every Q block but the last ends on a K block that is not the
    # row's last one, and under a window none but the first starts on
    # block 0: the accumulators are written and finalised where the walk
    # says, not at the ends of the rectangle
    @pytest.mark.parametrize("blocks", [8, 16])
    @pytest.mark.parametrize("window,bq,bk", [
        (8, 8, 8), (12, 8, 8), (None, 8, 8), (12, 16, 8), (20, 8, 16)])
    def test_long_rows_match_dense(self, interpret_pallas, blocks, window,
                                   bq, bk):
        _assert_flash_matches_dense((2, 8 * blocks, 8), (2, 8 * blocks, 8),
                                    bq, bk, window)

    def test_finalises_on_the_last_live_block(self, interpret_pallas):
        """Window 8 at block 8 over 8 blocks: Q block 3's K blocks are 2
        and 3, so its last step is neither the row's last K block (7) nor
        is its first K block 0; dK/dV's K block 3 walks Q blocks 3 and 4
        of 8."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        fwd = pk.flash_walk(True, 8, 8, 8, 64, "k")
        mine = fwd.q == 3
        assert fwd.k[mine].tolist() == [2, 3]
        assert fwd.flags[mine].tolist() == [pk._FIRST, pk._LAST]
        bwd = pk.flash_walk(True, 8, 8, 8, 64, "q")
        mine = bwd.k == 3
        assert bwd.q[mine].tolist() == [3, 4]
        assert bwd.flags[mine].tolist() == [pk._FIRST, pk._LAST]
        _assert_flash_matches_dense((1, 64, 8), (1, 64, 8), 8, 8, 8)

    def test_window_one_attends_self_only(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 32, 8), jnp.float32)
        out = flash_attention(q, q, v, causal=True, block_q=8, block_k=8,
                              window=1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(v), atol=1e-5)

    def test_window_requires_causal(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 16, 4), jnp.float32)
        with pytest.raises(ValueError):
            flash_attention(q, q, q, window=4)
        with pytest.raises(ValueError):
            flash_attention(q, q, q, causal=True, window=0)


class TestGroupedQueryAttention:
    """GQA: k/v carry fewer heads than q; a grid step holds a run of
    kv_group query heads and the one K/V head they share (no materialized
    repeat), folded into the rows of each tile, and dK/dV are summed over
    the group inside the kernel's products."""

    def _ref(self, q, k, v, g, **kw):
        return dense_attention(q, jnp.repeat(k, g, axis=-3),
                               jnp.repeat(v, g, axis=-3), **kw)

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_repeated_dense(self, rng, interpret_pallas,
                                            causal):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(2, 8, 32, 16), jnp.float32)  # B=2, Hq=8
        k = jnp.asarray(rng.randn(2, 2, 32, 16), jnp.float32)  # Hkv=2
        v = jnp.asarray(rng.randn(2, 2, 32, 16), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        ref = self._ref(q, k, v, 4, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_grads_match_repeated_dense(self, rng, interpret_pallas):
        import jax
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 4, 64, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 64, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, 64, 8), jnp.float32)
        cot = jnp.asarray(np.random.RandomState(9).randn(1, 4, 64, 8),
                          jnp.float32)

        def gr(fn):
            return jax.grad(lambda a, b, c: (fn(a, b, c) * cot).sum(),
                            argnums=(0, 1, 2))(q, k, v)
        got = gr(lambda a, b, c: flash_attention(
            a, b, c, causal=True, block_q=16, block_k=16))
        want = gr(lambda a, b, c: self._ref(a, b, c, 2, causal=True))
        for g1, g2, name in zip(got, want, "qkv"):
            assert g1.shape == g2.shape
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       atol=2e-4, err_msg=f"d{name}")

    def test_gqa_with_window(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 4, 64, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 64, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, 64, 8), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                              window=10)
        ref = self._ref(q, k, v, 2, causal=True, window=10)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    # the cells' groups, 6 and 8 query heads a K/V head (Laguna), 7
    # (SmallThinker), and 2, on rows of one block, 8 and 16 blocks, with a
    # window of one block, of two and a half, and none
    @pytest.mark.parametrize("kv_group,blocks,window", [
        (6, 16, 8), (8, 16, None), (8, 8, 8), (6, 8, None), (8, 8, 12)] + [
        (g, b, w) for g in (2, 6, 7, 8) for b in (1, 8) for w in (8, 20, None)
        if (g, b, w) not in {(8, 8, 8), (6, 8, None)}])
    def test_long_rows_in_groups_match_dense(self, interpret_pallas,
                                             kv_group, blocks, window):
        _assert_flash_matches_dense((1, kv_group, 8 * blocks, 8),
                                    (1, 1, 8 * blocks, 8), 8, 8, window)

    @pytest.mark.parametrize("window", [None, 16])
    def test_dkv_leaves_the_kernel_a_kv_head_a_row(self, interpret_pallas,
                                                   window):
        """Two batch rows of 2 K/V heads, groups of 4: the dK/dV kernel
        writes [n / G, T, d], and what it writes is the gradient: nothing
        sums the group after it."""
        import jax
        q = jax.ShapeDtypeStruct((2, 8, 64, 8), jnp.float32)
        kv = jax.ShapeDtypeStruct((2, 2, 64, 8), jnp.float32)

        def loss(a, b, c):
            from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
            return flash_attention(a, b, c, causal=True, block_q=16,
                                   block_k=16, window=window).sum()
        jaxpr = jax.make_jaxpr(jax.grad(loss, (1, 2)))(q, kv, kv)
        dkv = [eqn for eqn in _eqns(jaxpr.jaxpr)
               if eqn.primitive.name == "pallas_call"
               and eqn.params["jaxpr"].debug_info.func_name
               == "_flash_dkv_kernel"]
        assert len(dkv) == 1
        assert [v.aval.shape for v in dkv[0].outvars] == [(4, 64, 8)] * 2
        # the kernel's results are the gradients, reshaped and nothing more
        outs = {id(v) for v in dkv[0].outvars}
        assert not [eqn for eqn in _eqns(jaxpr.jaxpr)
                    if eqn.primitive.name in ("reduce_sum", "add", "add_any")
                    and any(id(v) in outs for v in eqn.invars)]
        _assert_flash_matches_dense((2, 8, 64, 8), (2, 2, 64, 8), 16, 16,
                                    window)

    # 32 query heads a K/V head at d 128 and block 512, a group that no
    # step holds, on a row of one block and of two; and a row of 300 (one
    # block of 300 rows at the default block 512, strips of 4), with and
    # without a window
    @pytest.mark.parametrize("q_shape,kv_shape,block,window", [
        ((1, 32, 512, 128), (1, 1, 512, 128), 512, None),
        ((1, 32, 1024, 128), (1, 1, 1024, 128), 512, None),
        ((1, 8, 300, 16), (1, 2, 300, 16), 512, None),
        ((1, 8, 300, 16), (1, 2, 300, 16), 512, 100)])
    def test_groups_a_step_does_not_hold_match_dense(
            self, interpret_pallas, q_shape, kv_shape, block, window):
        """Where the group does not fold (``flash_plan``), a step holds one
        query head, and dK/dV are summed to the K/V heads after the
        kernel."""
        from deeplearning4j_tpu.ops import pallas_kernels as pk
        n, t, d = q_shape[1], q_shape[2], q_shape[3]
        bq = min(block, t)
        assert pk.flash_plan(n, t, d, 4, bq, bq,
                             q_shape[1] // kv_shape[1]).group == 1
        _assert_flash_matches_dense(q_shape, kv_shape, block, block, window)

    def test_scan_escape_hatch_gqa(self, rng, interpret_pallas, monkeypatch):
        import jax
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        monkeypatch.setenv("DL4J_TPU_FLASH_BWD", "scan")
        q = jnp.asarray(rng.randn(1, 4, 32, 8), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
        got = jax.grad(lambda b: flash_attention(
            q, b, v, causal=True, block_q=16, block_k=16).sum())(k)
        want = jax.grad(lambda b: self._ref(
            q, b, v, 2, causal=True).sum())(k)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4)

    def test_indivisible_heads_raise(self, rng, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import flash_attention
        q = jnp.asarray(rng.randn(1, 3, 16, 4), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 16, 4), jnp.float32)
        with pytest.raises(ValueError):
            flash_attention(q, k, k, causal=True)


class TestTransformerAttnRoute:
    def test_pallas_route_matches_scan_route(self, interpret_pallas,
                                             monkeypatch):
        """TransformerLM with block_size: the pallas flash route must train
        identically to the lax.scan route (same loss trajectory from the
        same seed)."""
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        toks = np.random.RandomState(0).randint(0, 128, (2, 32))

        def losses(mode):
            monkeypatch.setenv("DL4J_TPU_LM_ATTN", mode)
            lm = TransformerLM(TransformerConfig(
                vocab_size=128, max_len=32, d_model=32, n_heads=2,
                n_layers=2, d_ff=64, block_size=16, seed=3)).init()
            out = []
            for _ in range(3):
                lm.fit_batch(jnp.asarray(toks))
                out.append(float(lm.score_))
            return out

        a, b = losses("pallas"), losses("scan")
        np.testing.assert_allclose(a, b, rtol=2e-4)


class TestTransformerWindow:
    def _lm(self, **kw):
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        base = dict(vocab_size=96, max_len=32, d_model=32, n_heads=2,
                    n_layers=2, d_ff=64, seed=5)
        base.update(kw)
        return TransformerLM(TransformerConfig(**base)).init()

    def test_window_geq_seq_equals_dense(self):
        toks = jnp.asarray(np.random.RandomState(1).randint(0, 96, (2, 32)))
        a, b = self._lm(), self._lm(window=32)
        np.testing.assert_allclose(np.asarray(a.output(toks)),
                                   np.asarray(b.output(toks)), atol=1e-5)

    def test_small_window_changes_logits_and_trains(self):
        toks = jnp.asarray(np.random.RandomState(1).randint(0, 96, (2, 32)))
        a, b = self._lm(), self._lm(window=4)
        assert not np.allclose(np.asarray(a.output(toks)),
                               np.asarray(b.output(toks)), atol=1e-3)
        first = last = None
        for _ in range(5):
            b.fit_batch(toks)
            last = float(b.score_)
            first = first if first is not None else last
        assert np.isfinite(last) and last < first

    def test_generate_respects_window_consistently(self):
        """Teacher-forced logits and the KV-cache decode must agree on the
        windowed attention pattern: greedy generation continued from a
        prompt equals argmax over the windowed forward logits."""
        lm = self._lm(window=6)
        prompt = np.random.RandomState(2).randint(0, 96, (1, 8))
        out = np.asarray(lm.generate(prompt, 4, temperature=0.0, seed=0))
        seq = prompt.copy()
        for _ in range(4):
            logits = np.asarray(lm.output(jnp.asarray(seq)))
            nxt = logits[:, -1].argmax(-1)[:, None]
            seq = np.concatenate([seq, nxt], axis=1)
        np.testing.assert_array_equal(out, seq)

    def test_pallas_window_route_matches_dense_fallback(self,
                                                        interpret_pallas,
                                                        monkeypatch):
        toks = jnp.asarray(np.random.RandomState(3).randint(0, 96, (2, 32)))
        monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
        a = self._lm(block_size=16, window=8)
        monkeypatch.setenv("DL4J_TPU_LM_ATTN", "scan")   # window -> dense
        b = self._lm(block_size=16, window=8)
        np.testing.assert_allclose(np.asarray(a.output(toks)),
                                   np.asarray(b.output(toks)), atol=2e-5)


class TestTransformerGQA:
    def _lm(self, **kw):
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        base = dict(vocab_size=96, max_len=32, d_model=32, n_heads=4,
                    n_layers=2, d_ff=64, seed=5)
        base.update(kw)
        return TransformerLM(TransformerConfig(**base)).init()

    def test_param_savings_and_training(self):
        full, gqa = self._lm(), self._lm(n_kv_heads=1)
        assert gqa.num_params() < full.num_params()
        toks = jnp.asarray(np.random.RandomState(1).randint(0, 96, (2, 32)))
        first = last = None
        for _ in range(5):
            gqa.fit_batch(toks)
            last = float(gqa.score_)
            first = first if first is not None else last
        assert np.isfinite(last) and last < first

    def test_generate_matches_teacher_forcing(self):
        """The grouped KV-cache decode must agree with the teacher-forced
        forward — greedy continuation equals argmax over output logits."""
        lm = self._lm(n_kv_heads=2)
        prompt = np.random.RandomState(2).randint(0, 96, (1, 8))
        out = np.asarray(lm.generate(prompt, 4, temperature=0.0, seed=0))
        seq = prompt.copy()
        for _ in range(4):
            logits = np.asarray(lm.output(jnp.asarray(seq)))
            seq = np.concatenate(
                [seq, logits[:, -1].argmax(-1)[:, None]], axis=1)
        np.testing.assert_array_equal(out, seq)

    def test_pallas_route_matches_dense_repeat(self, interpret_pallas,
                                               monkeypatch):
        toks = jnp.asarray(np.random.RandomState(3).randint(0, 96, (2, 32)))
        monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
        a = self._lm(block_size=16, n_kv_heads=2)
        monkeypatch.setenv("DL4J_TPU_LM_ATTN", "scan")   # repeat + scan
        b = self._lm(block_size=16, n_kv_heads=2)
        np.testing.assert_allclose(np.asarray(a.output(toks)),
                                   np.asarray(b.output(toks)), atol=2e-5)

    def test_invalid_kv_heads_raise(self):
        with pytest.raises(ValueError):
            self._lm(n_kv_heads=3)   # 4 % 3 != 0


class TestRope:
    def _lm(self, **kw):
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        base = dict(vocab_size=96, max_len=32, d_model=32, n_heads=4,
                    n_layers=2, d_ff=64, pos_embed="rope", seed=5)
        base.update(kw)
        return TransformerLM(TransformerConfig(**base)).init()

    def test_no_wpe_param_and_trains(self):
        lm = self._lm()
        assert "wpe" not in lm.params
        toks = jnp.asarray(np.random.RandomState(1).randint(0, 96, (2, 32)))
        first = last = None
        for _ in range(6):
            lm.fit_batch(toks)
            last = float(lm.score_)
            first = first if first is not None else last
        assert np.isfinite(last) and last < first

    def test_position_sensitivity(self):
        """RoPE must break permutation symmetry: swapping two tokens has to
        change the last-position logits."""
        lm = self._lm()
        toks = np.random.RandomState(2).randint(0, 96, (1, 16))
        swapped = toks.copy()
        swapped[0, [2, 7]] = swapped[0, [7, 2]]
        a = np.asarray(lm.output(jnp.asarray(toks)))[:, -1]
        b = np.asarray(lm.output(jnp.asarray(swapped)))[:, -1]
        assert not np.allclose(a, b, atol=1e-4)

    def test_generate_matches_teacher_forcing(self):
        """The decode path rotates at the ABSOLUTE position and caches the
        rotated keys; greedy continuation must equal argmax over the
        teacher-forced logits."""
        lm = self._lm(n_kv_heads=2)   # rope + GQA together
        prompt = np.random.RandomState(3).randint(0, 96, (1, 8))
        out = np.asarray(lm.generate(prompt, 4, temperature=0.0, seed=0))
        seq = prompt.copy()
        for _ in range(4):
            logits = np.asarray(lm.output(jnp.asarray(seq)))
            seq = np.concatenate(
                [seq, logits[:, -1].argmax(-1)[:, None]], axis=1)
        np.testing.assert_array_equal(out, seq)

    def test_rope_pallas_route_matches_fallback(self, interpret_pallas,
                                                monkeypatch):
        toks = jnp.asarray(np.random.RandomState(4).randint(0, 96, (2, 32)))
        monkeypatch.setenv("DL4J_TPU_LM_ATTN", "pallas")
        a = self._lm(block_size=16, window=8)
        monkeypatch.setenv("DL4J_TPU_LM_ATTN", "scan")
        b = self._lm(block_size=16, window=8)
        np.testing.assert_allclose(np.asarray(a.output(toks)),
                                   np.asarray(b.output(toks)), atol=2e-5)

    def test_checkpoint_roundtrip(self, tmp_path):
        """A rope model (no wpe key) must round-trip through the zip
        serializer and produce identical outputs."""
        from deeplearning4j_tpu.utils.model_serializer import (restore_model,
                                                               write_model)
        lm = self._lm()
        toks = jnp.asarray(np.random.RandomState(5).randint(0, 96, (1, 16)))
        want = np.asarray(lm.output(toks))
        path = str(tmp_path / "rope_lm.zip")
        write_model(lm, path)
        back = restore_model(path)
        np.testing.assert_allclose(np.asarray(back.output(toks)), want,
                                   atol=1e-6)

    def test_invalid_configs_raise(self):
        with pytest.raises(ValueError):
            self._lm(pos_embed="sinusoidal")
        with pytest.raises(ValueError):
            self._lm(d_model=12, n_heads=4)   # head dim 3 is odd


class TestSamplingFilters:
    def _lm(self, **kw):
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        base = dict(vocab_size=64, max_len=24, d_model=32, n_heads=2,
                    n_layers=1, d_ff=64, seed=11)
        base.update(kw)
        return TransformerLM(TransformerConfig(**base)).init()

    def test_top_k_one_is_greedy(self):
        lm = self._lm()
        prompt = np.random.RandomState(0).randint(0, 64, (2, 6))
        greedy = lm.generate(prompt, 6, temperature=0.0, seed=0)
        k1 = lm.generate(prompt, 6, temperature=1.0, top_k=1, seed=3)
        np.testing.assert_array_equal(greedy, k1)

    def test_top_p_tiny_is_greedy(self):
        lm = self._lm()
        prompt = np.random.RandomState(1).randint(0, 64, (1, 6))
        greedy = lm.generate(prompt, 5, temperature=0.0, seed=0)
        p0 = lm.generate(prompt, 5, temperature=1.0, top_p=1e-6, seed=9)
        np.testing.assert_array_equal(greedy, p0)

    def test_filters_keep_tokens_in_the_allowed_set(self):
        """With top_k=4, every sampled token must be among the 4 most
        likely given its prefix (checked against teacher-forced logits)."""
        lm = self._lm()
        prompt = np.random.RandomState(2).randint(0, 64, (1, 6))
        out = lm.generate(prompt, 5, temperature=1.2, top_k=4, seed=5)
        seq = out[:, :6]
        for t in range(5):
            logits = np.asarray(lm.output(jnp.asarray(out[:, :6 + t])))
            allowed = np.argsort(-logits[0, -1])[:4]
            assert out[0, 6 + t] in allowed

    def test_full_top_p_matches_unfiltered_distribution(self):
        lm = self._lm()
        prompt = np.random.RandomState(3).randint(0, 64, (1, 6))
        a = lm.generate(prompt, 5, temperature=1.0, seed=7)
        b = lm.generate(prompt, 5, temperature=1.0, top_p=1.0, seed=7)
        np.testing.assert_array_equal(a, b)

    def test_invalid_filters_raise(self):
        lm = self._lm()
        prompt = np.zeros((1, 4), np.int32)
        with pytest.raises(ValueError):
            lm.generate(prompt, 2, top_k=0)
        with pytest.raises(ValueError):
            lm.generate(prompt, 2, top_p=0.0)
        with pytest.raises(ValueError):
            lm.generate(prompt, 2, repetition_penalty=0.0)

    def test_repetition_penalty_breaks_greedy_loops(self):
        """An untrained model loops under greedy decoding; a strong
        penalty must strictly reduce repetition (and stay finite)."""
        lm = self._lm()
        prompt = np.random.RandomState(5).randint(0, 64, (1, 6))

        def max_run(seq):
            best = run = 1
            for a, b in zip(seq[:-1], seq[1:]):
                run = run + 1 if a == b else 1
                best = max(best, run)
            return best

        plain = lm.generate(prompt, 16, temperature=0.0)[0, 6:]
        pen = lm.generate(prompt, 16, temperature=0.0,
                          repetition_penalty=5.0)[0, 6:]
        assert len(set(pen.tolist())) > len(set(plain.tolist())) \
            or max_run(pen) < max_run(plain)

    def test_no_penalty_path_unchanged(self):
        lm = self._lm()
        prompt = np.random.RandomState(6).randint(0, 64, (2, 5))
        a = lm.generate(prompt, 6, temperature=0.7, seed=4)
        b = lm.generate(prompt, 6, temperature=0.7, seed=4,
                        repetition_penalty=1.0)
        # penalty of exactly 1.0 is mathematically the identity
        np.testing.assert_array_equal(a, b)


class TestLmTrainingKnobs:
    def _lm(self, **kw):
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        base = dict(vocab_size=64, max_len=16, d_model=32, n_heads=2,
                    n_layers=1, d_ff=64, seed=13)
        base.update(kw)
        return TransformerLM(TransformerConfig(**base)).init()

    def test_grad_clip_bounds_the_update(self):
        """With a tiny clip norm the parameter update magnitude must be
        bounded; with none it is larger for the same batch."""
        import jax
        toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (4, 16)))

        def delta(lm):
            before = jax.tree.map(np.asarray, lm.params)
            lm.fit_batch(toks)
            return max(float(np.abs(np.asarray(a) - b).max())
                       for a, b in zip(jax.tree.leaves(lm.params),
                                       jax.tree.leaves(before)))
        free = delta(self._lm(learning_rate=1.0))
        clipped = delta(self._lm(learning_rate=1.0, grad_clip_norm=1e-4))
        assert clipped < free

    def test_label_smoothing_raises_floor_not_divergence(self):
        toks = jnp.asarray(np.random.RandomState(1).randint(0, 64, (4, 16)))
        a, b = self._lm(), self._lm(label_smoothing=0.1)
        for _ in range(10):
            a.fit_batch(toks)
            b.fit_batch(toks)
        la, lb = float(a.score_), float(b.score_)
        assert np.isfinite(lb)
        # the smoothed objective cannot reach the unsmoothed minimum
        assert lb > la

    def test_z_loss_shrinks_logit_normalizer(self):
        import jax
        toks = jnp.asarray(np.random.RandomState(2).randint(0, 64, (4, 16)))
        a, b = self._lm(learning_rate=3e-3), self._lm(learning_rate=3e-3,
                                                      z_loss=1e-2)
        for _ in range(30):
            a.fit_batch(toks)
            b.fit_batch(toks)
        za = np.abs(np.asarray(jax.nn.logsumexp(
            a.output(toks[:, :-1]), axis=-1))).mean()
        zb = np.abs(np.asarray(jax.nn.logsumexp(
            b.output(toks[:, :-1]), axis=-1))).mean()
        assert zb < za


class TestEmaWeights:
    def _lm(self, **kw):
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        base = dict(vocab_size=64, max_len=16, d_model=32, n_heads=2,
                    n_layers=1, d_ff=64, learning_rate=0.01, seed=17)
        base.update(kw)
        return TransformerLM(TransformerConfig(**base)).init()

    def test_ema_lags_live_params_toward_init(self):
        import jax
        lm = self._lm(ema_decay=0.9)
        init = jax.tree.map(np.asarray, lm.params)
        toks = jnp.asarray(np.random.RandomState(0).randint(0, 64, (4, 16)))
        for _ in range(5):
            lm.fit_batch(toks)
        ema = lm.opt_state["ema"]
        # the shadow trails the live weights: closer to the init
        d_live = sum(float(np.abs(np.asarray(p) - i).sum()) for p, i in
                     zip(jax.tree.leaves(lm.params), jax.tree.leaves(init)))
        d_ema = sum(float(np.abs(np.asarray(e) - i).sum()) for e, i in
                    zip(jax.tree.leaves(ema), jax.tree.leaves(init)))
        assert 0 < d_ema < d_live

    def test_ema_model_evaluates_with_shadow_weights(self):
        import jax
        lm = self._lm(ema_decay=0.5)
        toks = jnp.asarray(np.random.RandomState(1).randint(0, 64, (4, 16)))
        for _ in range(3):
            lm.fit_batch(toks)
        shadow = lm.ema_model()
        for a, b in zip(jax.tree.leaves(shadow.params),
                        jax.tree.leaves(lm.opt_state["ema"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.isfinite(float(shadow.eval_loss(toks)))

    def test_ema_roundtrips_through_checkpoint(self, tmp_path):
        from deeplearning4j_tpu.utils.model_serializer import (restore_model,
                                                               write_model)
        lm = self._lm(ema_decay=0.8)
        toks = jnp.asarray(np.random.RandomState(2).randint(0, 64, (2, 16)))
        lm.fit_batch(toks)
        path = str(tmp_path / "ema.zip")
        write_model(lm, path)
        back = restore_model(path)
        import jax
        for a, b in zip(jax.tree.leaves(back.opt_state["ema"]),
                        jax.tree.leaves(lm.opt_state["ema"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_requires_config(self):
        with pytest.raises(ValueError):
            self._lm().ema_model()
        with pytest.raises(ValueError):
            self._lm(ema_decay=1.5)


class TestBeamSearch:
    def _lm(self, **kw):
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        base = dict(vocab_size=48, max_len=24, d_model=32, n_heads=2,
                    n_layers=2, d_ff=64, seed=21)
        base.update(kw)
        return TransformerLM(TransformerConfig(**base)).init()

    @staticmethod
    def _joint_logp(lm, seq, P):
        """Sum of next-token log-probs over the continuation."""
        import jax
        logits = np.asarray(lm.output(jnp.asarray(seq[:, :-1])))
        logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
        tot = 0.0
        for t in range(P - 1, seq.shape[1] - 1):
            tot += logp[0, t, seq[0, t + 1]]
        return tot

    def test_single_beam_is_greedy(self):
        lm = self._lm()
        prompt = np.random.RandomState(0).randint(0, 48, (2, 6))
        greedy = lm.generate(prompt, 6, temperature=0.0)
        beam1 = lm.beam_search(prompt, 6, beams=1)
        np.testing.assert_array_equal(greedy, beam1)

    def test_beam_score_at_least_greedy(self):
        """The 4-beam result's joint continuation log-probability can
        never be below greedy's (greedy is in the searched space)."""
        lm = self._lm()
        prompt = np.random.RandomState(1).randint(0, 48, (1, 6))
        greedy = lm.generate(prompt, 8, temperature=0.0)
        beam = lm.beam_search(prompt, 8, beams=4)
        assert (self._joint_logp(lm, beam, 6)
                >= self._joint_logp(lm, greedy, 6) - 1e-4)

    def test_batched_shapes_and_determinism(self):
        lm = self._lm()
        prompt = np.random.RandomState(2).randint(0, 48, (3, 5))
        a = lm.beam_search(prompt, 7, beams=3)
        b = lm.beam_search(prompt, 7, beams=3)
        assert a.shape == (3, 12)
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a[:, :5], prompt)

    def test_bf16_decode_uses_half_size_cache(self):
        """A bf16-trained model must decode with bf16 KV caches (half the
        HBM) and still produce sane tokens."""
        lm = self._lm(compute_dtype="bfloat16")
        assert lm._cache_dtype() == "bfloat16"
        prompt = np.random.RandomState(3).randint(0, 48, (1, 6))
        out = lm.generate(prompt, 6, temperature=0.0)
        assert out.shape == (1, 12) and (out >= 0).all()
        beam = lm.beam_search(prompt, 6, beams=2)
        assert beam.shape == (1, 12)

    def test_invalid_beams_raise(self):
        lm = self._lm()
        prompt = np.zeros((1, 4), np.int32)
        with pytest.raises(ValueError):
            lm.beam_search(prompt, 2, beams=0)
        with pytest.raises(ValueError):
            lm.beam_search(prompt, 100)   # exceeds max_len


class TestHelperSeam:
    def test_registry_and_disable_env(self, monkeypatch):
        from deeplearning4j_tpu.nn import helpers
        from deeplearning4j_tpu.nn.layers import SelfAttentionLayer
        layer = SelfAttentionLayer(n_in=4, n_out=4)
        assert helpers.get_helper(layer) is not None
        monkeypatch.setenv("DL4J_TPU_DISABLE_HELPERS", "1")
        assert helpers.get_helper(layer) is None

    def test_helper_declines_on_mask(self, interpret_pallas):
        from deeplearning4j_tpu.nn import helpers
        from deeplearning4j_tpu.nn.layers import SelfAttentionLayer
        layer = SelfAttentionLayer(n_in=4, n_out=4)
        helper = helpers.get_helper(layer)
        assert helper.supports(layer, mask=None)
        assert not helper.supports(layer, mask=jnp.ones((1, 4)))

    def test_layer_uses_helper_and_matches_builtin(self, rng, interpret_pallas,
                                                   monkeypatch):
        from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.layers import RnnOutputLayer, SelfAttentionLayer

        def conf():
            return (NeuralNetConfiguration.Builder().seed(3).list()
                    .layer(SelfAttentionLayer(n_in=6, n_out=6, n_heads=2,
                                              causal=True, block_size=8))
                    .layer(RnnOutputLayer(n_in=6, n_out=3, activation="softmax",
                                          loss="mcxent"))
                    .build())

        x = rng.randn(2, 16, 6).astype(np.float32)
        net_helper = MultiLayerNetwork(conf()).init()
        out_helper = np.asarray(net_helper.output(x))

        monkeypatch.setenv("DL4J_TPU_DISABLE_HELPERS", "1")
        net_plain = MultiLayerNetwork(conf()).init()
        net_plain.set_params(np.asarray(net_helper.params()))
        out_plain = np.asarray(net_plain.output(x))
        np.testing.assert_allclose(out_helper, out_plain, atol=1e-5)

    def test_broken_helper_propagates(self, rng):
        """Declining in ``supports()`` is the way back to the built-in
        path; a helper that accepted the call and then raised reaches the
        caller through the layer's whole forward (PR 21 took the swallowing
        fall-back out of the seam: on the chip it hid a kernel the compiler
        had refused; tests/test_helpers_seam.py holds the seam itself)."""
        from deeplearning4j_tpu.nn import helpers
        from deeplearning4j_tpu.nn.layers import SelfAttentionLayer

        class Broken(helpers.LayerHelper):
            def supports(self, layer, **ctx):
                return True

            def attention(self, *a, **kw):
                raise RuntimeError("boom")

        layer = SelfAttentionLayer(n_in=4, n_out=4).apply_global_defaults({})
        helpers.register_helper("SelfAttentionLayer", Broken())
        try:
            import jax
            params = layer.init_params(jax.random.PRNGKey(0))
            x = jnp.asarray(rng.randn(1, 8, 4), jnp.float32)
            with pytest.raises(RuntimeError, match="boom"):
                layer.forward(params, x, {})
        finally:
            helpers.register_helper("SelfAttentionLayer",
                                    helpers.FlashAttentionHelper())


class TestFusedLstmCell:
    """ISSUE 10 tentpole (b): the fused LSTM cell kernel
    (ops/pallas_kernels.lstm_cell) vs the built-in scan's per-step gate
    math — fwd + bwd in interpret mode, plain and peephole (Graves)
    formulations, and the layer-level wiring behind
    DL4J_TPU_LSTM_KERNEL=pallas including the bidirectional reverse
    pass."""

    @staticmethod
    def _ref_cell(zx, h, c, rw, p=None):
        import jax
        z = zx + h @ rw
        i, f, g, o = jnp.split(z, 4, axis=1)
        if p is not None:
            i = i + c * p[0:1]
            f = f + c * p[1:2]
        i = jax.nn.sigmoid(i)
        f = jax.nn.sigmoid(f)
        g = jnp.tanh(g)
        c2 = f * c + i * g
        if p is not None:
            o = o + c2 * p[2:3]
        o = jax.nn.sigmoid(o)
        return o * jnp.tanh(c2), c2

    def _args(self, rng, peep):
        B, H = 4, 8
        zx = jnp.asarray(rng.randn(B, 4 * H), jnp.float32)
        h0 = jnp.asarray(rng.randn(B, H), jnp.float32)
        c0 = jnp.asarray(rng.randn(B, H), jnp.float32)
        rw = jnp.asarray(rng.randn(H, 4 * H) * 0.1, jnp.float32)
        p = (jnp.asarray(rng.randn(3, H) * 0.1, jnp.float32)
             if peep else None)
        return zx, h0, c0, rw, p

    @pytest.mark.parametrize("peep", [False, True])
    def test_forward_matches_gate_math(self, rng, interpret_pallas, peep):
        from deeplearning4j_tpu.ops.pallas_kernels import lstm_cell
        zx, h0, c0, rw, p = self._args(rng, peep)
        h, c = lstm_cell(zx, h0, c0, rw, p)
        hr, cr = self._ref_cell(zx, h0, c0, rw, p)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(c), np.asarray(cr), atol=1e-6)

    @pytest.mark.parametrize("peep", [False, True])
    def test_backward_matches_autodiff(self, rng, interpret_pallas, peep):
        """The hand-fused backward kernel (custom_vjp) vs jax autodiff of
        the reference gate math — every input's gradient, incl. the
        peephole rows."""
        import jax
        from deeplearning4j_tpu.ops.pallas_kernels import lstm_cell
        zx, h0, c0, rw, p = self._args(rng, peep)
        args = (zx, h0, c0, rw) + ((p,) if peep else ())

        def loss(fn):
            def go(a):
                h, c = fn(*a)
                return jnp.sum(h * 1.3) + jnp.sum(c * 0.7)
            return go

        gk = jax.grad(loss(lstm_cell))(args)
        gr = jax.grad(loss(lambda *a: self._ref_cell(
            a[0], a[1], a[2], a[3], a[4] if peep else None)))(args)
        for got, want in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5)

    def test_supported_predicate(self, interpret_pallas):
        from deeplearning4j_tpu.ops.pallas_kernels import lstm_cell_supported
        assert lstm_cell_supported("sigmoid", "tanh")
        assert lstm_cell_supported("sigmoid", None)     # default cell act
        assert not lstm_cell_supported("hardsigmoid", "tanh")
        assert not lstm_cell_supported("sigmoid", "relu")

    def _lstm_net(self, layer_cls, seed=7):
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.models.multi_layer_network import (
            MultiLayerNetwork)
        from deeplearning4j_tpu.nn.layers import RnnOutputLayer
        conf = (NeuralNetConfiguration.Builder().seed(seed)
                .learning_rate(0.05).updater("sgd").list()
                .layer(layer_cls(n_in=6, n_out=8, activation="tanh"))
                .layer(RnnOutputLayer(n_in=8, n_out=6, activation="softmax",
                                      loss="mcxent")).build())
        return MultiLayerNetwork(conf).init()

    def _seq(self, rng, b=4, t=10, v=6):
        ids = (rng.rand(b, t) * v).astype(int)
        x = np.eye(v, dtype=np.float32)[ids]
        y = np.eye(v, dtype=np.float32)[np.roll(ids, -1, 1)]
        return x, y

    def test_layer_fit_parity_all_lstm_variants(self, rng, interpret_pallas,
                                                monkeypatch):
        """fit_batch through the kernel-backed scan vs the built-in scan:
        LSTM, GravesLSTM (peepholes) and GravesBidirectionalLSTM (the
        reverse pass shares the kernel) — fwd + bwd through a real
        update."""
        from deeplearning4j_tpu.nn.layers import (GravesBidirectionalLSTM,
                                                  GravesLSTM, LSTM)
        x, y = self._seq(rng)
        for cls in (LSTM, GravesLSTM, GravesBidirectionalLSTM):
            monkeypatch.setenv("DL4J_TPU_LSTM_KERNEL", "builtin")
            a = self._lstm_net(cls)
            a.fit_batch(x, y)
            monkeypatch.setenv("DL4J_TPU_LSTM_KERNEL", "pallas")
            b = self._lstm_net(cls)
            b.fit_batch(x, y)
            d = max(float(np.max(np.abs(np.asarray(p) - np.asarray(q))))
                    for p, q in zip(a.params(), b.params()))
            assert d < 1e-6, (cls.__name__, d)
            assert abs(float(a.score_) - float(b.score_)) < 1e-6, cls.__name__

    def test_mask_semantics_match_builtin(self, rng, interpret_pallas,
                                          monkeypatch):
        """Hold/zero mask handling is applied around the kernel exactly
        as in the built-in scan."""
        from deeplearning4j_tpu.nn.layers import GravesLSTM
        x, y = self._seq(rng)
        fm = np.ones((4, 10), np.float32)
        fm[:, -3:] = 0.0
        monkeypatch.setenv("DL4J_TPU_LSTM_KERNEL", "builtin")
        a = self._lstm_net(GravesLSTM)
        a.fit_batch(x, y, fmask=fm, lmask=fm)
        monkeypatch.setenv("DL4J_TPU_LSTM_KERNEL", "pallas")
        b = self._lstm_net(GravesLSTM)
        b.fit_batch(x, y, fmask=fm, lmask=fm)
        d = max(float(np.max(np.abs(np.asarray(p) - np.asarray(q))))
                for p, q in zip(a.params(), b.params()))
        assert d < 1e-6

    def test_exotic_activation_falls_back_to_builtin(self, rng,
                                                     interpret_pallas,
                                                     monkeypatch):
        """A cell activation outside the kernel's sigmoid/tanh contract
        falls back to the built-in scan silently — same params either
        way because it IS the same path."""
        from deeplearning4j_tpu import NeuralNetConfiguration
        from deeplearning4j_tpu.models.multi_layer_network import (
            MultiLayerNetwork)
        from deeplearning4j_tpu.nn.layers import LSTM, RnnOutputLayer

        def net():
            conf = (NeuralNetConfiguration.Builder().seed(3)
                    .learning_rate(0.05).updater("sgd").list()
                    .layer(LSTM(n_in=6, n_out=8, activation="softsign"))
                    .layer(RnnOutputLayer(n_in=8, n_out=6,
                                          activation="softmax",
                                          loss="mcxent")).build())
            return MultiLayerNetwork(conf).init()

        x, y = self._seq(rng)
        monkeypatch.setenv("DL4J_TPU_LSTM_KERNEL", "pallas")
        a = net()
        a.fit_batch(x, y)
        monkeypatch.setenv("DL4J_TPU_LSTM_KERNEL", "builtin")
        b = net()
        b.fit_batch(x, y)
        np.testing.assert_array_equal(a.params(), b.params())
