"""Long-context TransformerLM: blockwise (flash) vs dense attention on TPU.

The long-context story (SURVEY §5.7 — tBPTT is the reference's only answer;
ring/Ulysses/blockwise attention are this build's) needs a silicon number:
tokens/sec + MFU for the SAME d512/L8 model at long sequence lengths, dense
O(T²) vs the blockwise flash recurrence (``block_size``), both with remat.

Every line is tagged with the platform so CPU-fallback output can't be
mistaken for chip results. One TPU process at a time.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

import _bootstrap  # noqa: F401  (repo root onto sys.path)

from deeplearning4j_tpu.hw import (TRAIN_FLOPS_MULTIPLIER,
                                   peak_bf16_flops,
                                   transformer_fwd_flops_per_token)
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)

PLATFORM = jax.devices()[0].platform
if PLATFORM == "cpu":
    print("WARNING: running on CPU — numbers are NOT chip results")
# MFU only against a recorded peak: off the TPU the column reads nan
PEAK = peak_bf16_flops() if PLATFORM == "tpu" else float("nan")

D, L, H, FF, V = 512, 8, 8, 2048, 32_768


def flops_fwd_per_token(T):
    return transformer_fwd_flops_per_token(T, D, L, FF, V)


def measure(T, B, block_size, warm=2, meas=10, attn=None, window=None):
    if attn:          # force the block-attention route (pallas|scan);
        os.environ["DL4J_TPU_LM_ATTN"] = attn   # read at trace time
    else:
        os.environ.pop("DL4J_TPU_LM_ATTN", None)
    lm = TransformerLM(TransformerConfig(
        vocab_size=V, max_len=T, d_model=D, n_heads=H, n_layers=L,
        d_ff=FF, compute_dtype="bfloat16", remat=True,
        block_size=block_size, window=window, seed=0)).init()
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, V, (B, T)), jnp.int32)
    jax.block_until_ready(toks)
    t0 = time.perf_counter()
    for _ in range(warm):
        lm.fit_batch(toks)
    float(jnp.float32(lm.score_))
    compile_t = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(meas):
        lm.fit_batch(toks)
    float(jnp.float32(lm.score_))
    dt = time.perf_counter() - t0
    toks_s = meas * B * (T - 1) / dt
    mfu = toks_s * TRAIN_FLOPS_MULTIPLIER * flops_fwd_per_token(T) / PEAK
    kind = f"block{block_size}" if block_size else "dense"
    if window:
        kind += f"+win{window}"   # MFU column keeps the dense-equivalent
    if attn:                      # FLOP basis: it reads as speedup-vs-dense
        kind += f"/{attn}"
    print(f"[{PLATFORM}] T={T} B={B} {kind:14s}: {toks_s:,.0f} tok/s, "
          f"MFU {mfu:.3f} (compile+{warm}-step warmup {compile_t:.0f}s)",
          flush=True)
    return toks_s


def measure_generate(B=8, prompt=32, n_new=480, reps=3):
    """KV-cache sampling throughput: one compiled lax.scan per config."""
    lm = TransformerLM(TransformerConfig(
        vocab_size=V, max_len=prompt + n_new, d_model=D, n_heads=H,
        n_layers=L, d_ff=FF, compute_dtype="bfloat16", seed=0)).init()
    p = np.random.default_rng(0).integers(0, V, (B, prompt))
    t0 = time.perf_counter()
    lm.generate(p, n_new, temperature=1.0, seed=0)    # compile + warm
    compile_t = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(reps):
        lm.generate(p, n_new, temperature=1.0, seed=i + 1)
    dt = time.perf_counter() - t0
    rate = reps * B * n_new / dt
    print(f"[{PLATFORM}] generate B={B} prompt={prompt} new={n_new}: "
          f"{rate:,.0f} tok/s sampled (compile {compile_t:.0f}s)",
          flush=True)
    return rate


if __name__ == "__main__":
    import os
    from deeplearning4j_tpu.config import env_flag
    if env_flag("DL4J_TPU_AB_SMOKE"):
        # tiny CPU smoke of the whole harness; numbers are meaningless.
        # interpret mode lets the pallas arm execute off-TPU.
        if "DL4J_TPU_PALLAS_INTERPRET" not in os.environ:
            os.environ["DL4J_TPU_PALLAS_INTERPRET"] = "1"
        D, L, H, FF, V = 64, 2, 2, 128, 512
        grid = ((256, 2, (None, 64)),)
    else:
        # same token budget (64k) per config so HBM stays bounded as T grows
        grid = ((2048, 32, (None, 512)), (4096, 16, (None, 512)),
                (8192, 8, (None, 512)))
    for T, B, blocks in grid:
        for block in blocks:
            # the block arm runs twice — pallas kernel vs lax.scan — so the
            # chip decides which route the auto default should trust
            for attn in ((None,) if block is None else ("pallas", "scan")):
                try:
                    measure(T, B, block, attn=attn)
                except Exception as e:
                    kind = f"block{block}/{attn}" if block else "dense"
                    print(f"[{PLATFORM}] T={T} B={B} {kind}: FAILED "
                          f"{str(e)[-160:]}", flush=True)
    # sliding-window arm at the longest T: O(T*W) vs the O(T^2/2) arms above
    T, B, blk, W = ((256, 2, 64, 64) if env_flag("DL4J_TPU_AB_SMOKE")
                    else (8192, 8, 512, 1024))
    try:
        measure(T, B, blk, attn="pallas", window=W)
    except Exception as e:
        print(f"[{PLATFORM}] window arm: FAILED {str(e)[-160:]}", flush=True)
    finally:
        os.environ.pop("DL4J_TPU_LM_ATTN", None)
    try:
        if env_flag("DL4J_TPU_AB_SMOKE"):
            measure_generate(B=2, prompt=8, n_new=24, reps=1)
        else:
            measure_generate()
    except Exception as e:
        print(f"[{PLATFORM}] generate: FAILED {str(e)[-160:]}", flush=True)
