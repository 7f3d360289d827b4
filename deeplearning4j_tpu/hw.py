"""Hardware constants shared by the benchmarks and perf tools.

Single source for the MFU basis so bench.py and tools/ can never diverge.
"""

# Peak dense bf16 matmul FLOP/s of ONE chip, keyed by the ``device_kind``
# JAX reports for it — the MFU denominator. A device that is not listed
# has no peak here: ``peak_bf16_flops`` raises rather than assume one.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    # (16 GB HBM at 819 GB/s)
    "TPU v5 lite": 197e12,
}

# MFU numerator convention: train step FLOPs = 3x forward (fwd + ~2x bwd)
TRAIN_FLOPS_MULTIPLIER = 3


def peak_bf16_flops(device_kind=None):
    """The table's peak for ``device_kind`` (default: the first device JAX
    reports). Unknown kinds are an error — an MFU against a guessed peak
    is not a measurement."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s recorded for device_kind {device_kind!r} "
            f"(known: {sorted(PEAK_BF16_FLOPS)}); add it to "
            "deeplearning4j_tpu/hw.py with its source before reporting "
            "MFU on it") from None


def transformer_fwd_flops_per_token(T, d_model, n_layers, d_ff, vocab):
    """Matmul FLOPs per token, forward pass, decoder block stack with tied
    logits (2 flop per MAC): qkv + output projections, QK^T/AV against T
    keys/values, MLP up+down, final logits. Shared by bench.py's
    transformer_lm line and tools/transformer_longseq.py so the two can
    never report diverging MFU for the same model."""
    per_layer = (2 * d_model * 3 * d_model     # qkv projection
                 + 2 * d_model * d_model       # attention output projection
                 + 4 * T * d_model             # QK^T + AV
                 + 2 * d_model * d_ff * 2)     # MLP up + down
    return n_layers * per_layer + 2 * d_model * vocab
