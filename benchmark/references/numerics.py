"""What the plain references share: a PRNG key from any seed, and the rounding
that puts a reference into the stated precision or the one below it.

``precision`` of a product (matmul, einsum, convolution):

* ``"float32"``  -- float32 at ``jax.default_matmul_precision("highest")``: THE
  reference.
* ``"bfloat16"`` -- operands and result rounded to bfloat16, float32
  accumulation: what the configurations state (``compute_dtype``).
* ``"fp8"``, ``"int8"`` -- as ``"bfloat16"``, but the operands rounded to
  float8_e4m3fn (per-tensor scale amax / 448) or to symmetric int8 (per-tensor
  scale amax / 127): the controls, the nearest precision below the stated one,
  the step that would tempt a later PR (the v5e's MXU runs int8 at twice its
  bfloat16 rate).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0   # largest finite float8_e4m3fn


def seed_key(seed, stream=0):
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def round_operand(x, precision):
    """Round one matmul operand to ``precision`` (straight-through gradient)."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    elif precision == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
        q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)


def round_result(y, precision):
    """Below float32 a product's result is kept in bfloat16."""
    if precision == "float32":
        return y
    return y.astype(jnp.bfloat16).astype(jnp.float32)


def matmul(a, b, precision, spec=None):
    """``a @ b`` (or ``einsum(spec, a, b)``) with both operands rounded to
    ``precision``, accumulated in float32 at the highest matmul precision."""
    a, b = round_operand(a, precision), round_operand(b, precision)
    with jax.default_matmul_precision("highest"):
        return round_result(jnp.einsum(spec, a, b) if spec else a @ b,
                            precision)

