"""A/B the ns_scan kernel: scatter strategy x batch size x table dtype.

Phase 1 sweeps SCATTER_IMPL in {fused, sorted, two} (exact-equivalent —
proven in tests/test_nlp.py::test_scatter_impls_are_equivalent) and B in
{8192, 16384, 32768, 65536} with f32 tables. Phase 2 re-runs the winning
impl's batch column with bfloat16 tables (kernel math stays f32; close-
equivalent — tests/test_nlp.py::test_bf16_tables_match_f32_within_tolerance)
— the gather/scatter phases are HBM-bandwidth-bound, so bf16 halves their
bytes. Every line is tagged with the actual platform so CPU numbers can
never be mistaken for chip results (see PERF.md). One TPU process at a
time.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

import _bootstrap  # noqa: F401  (repo root onto sys.path)

from deeplearning4j_tpu.nlp import lookup as L

PLATFORM = jax.devices()[0].platform
if PLATFORM == "cpu":
    print("WARNING: running on CPU — numbers are NOT chip results")

from deeplearning4j_tpu.config import env_flag
if env_flag("DL4J_TPU_AB_SMOKE"):
    # tiny CPU smoke of the full sweep machinery (catches runtime drift
    # without burning a chip claim); numbers are meaningless
    V, D, K, S = 2_000, 16, 2, 4
    BATCHES = (256, 512)
else:
    V, D, K, S = 30_000, 100, 5, 64
    BATCHES = (8192, 16384, 32768, 65536)
rng = np.random.RandomState(0)
syn0 = rng.rand(V, D).astype(np.float32)
syn1 = rng.rand(V, D).astype(np.float32)
table = jnp.asarray(rng.randint(0, V, 100_000).astype(np.int32))
zipf = 1.0 / np.arange(1, V + 1)
zipf /= zipf.sum()

_data = {}
def batch_data(B):
    if B not in _data:
        _data[B] = (
            jnp.asarray(rng.choice(V, (S, B), p=zipf).astype(np.int32)),
            jnp.asarray(rng.choice(V, (S, B), p=zipf).astype(np.int32)),
            jnp.ones((S, B), bool), jnp.full((S,), 0.025, jnp.float32))
    return _data[B]


def measure(impl, B, dtype):
    L.set_scatter_impl(impl)          # also clears compiled kernels
    centers, pos, valid, lrs = batch_data(B)
    key = jax.random.PRNGKey(0)
    s0 = jnp.asarray(syn0, dtype)
    s1 = jnp.asarray(syn1, dtype)
    t0 = time.perf_counter()
    key, sub = jax.random.split(key)
    s0, s1 = L.ns_scan_devneg(s0, s1, table, centers, pos, valid, lrs, K,
                              sub)
    float(jnp.float32(s0[0, 0]))
    compile_t = time.perf_counter() - t0
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        key, sub = jax.random.split(key)
        s0, s1 = L.ns_scan_devneg(s0, s1, table, centers, pos, valid, lrs,
                                  K, sub)
    float(jnp.float32(s0[0, 0]))
    dt = (time.perf_counter() - t0) / reps
    rate = S * B / dt / 1e6
    dname = jnp.dtype(dtype).name
    print(f"[{PLATFORM}] impl={impl:6s} B={B} dtype={dname}: "
          f"{dt/S*1e3:.2f} ms/step, {rate:.2f} M pairs/s "
          f"(compile {compile_t:.1f}s)", flush=True)
    return rate


best = None
for impl in ("fused", "sorted", "two"):
    for B in BATCHES:
        rate = measure(impl, B, jnp.float32)
        if best is None or rate > best[0]:
            best = (rate, impl, B, "float32")

for B in BATCHES:                     # phase 2: bf16 column of the winner
    rate = measure(best[1], B, jnp.bfloat16)
    if rate > best[0]:
        best = (rate, best[1], B, "bfloat16")

print(f"BEST: impl={best[1]} B={best[2]} dtype={best[3]} "
      f"({best[0]:.2f} M pairs/s) — set DL4J_TPU_W2V_SCATTER={best[1]} "
      f"DL4J_TPU_W2V_BATCH={best[2]} DL4J_TPU_W2V_DTYPE={best[3]}")
