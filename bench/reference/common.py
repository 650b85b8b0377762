"""Pieces every plain reference shares: the matmul at a stated precision
and the random weights a configuration is served with.

The references import nothing of the system under test. They define the
model's mathematics in straightforward ``jax.numpy`` at float32, with
every matrix product at ``Precision.HIGHEST``, and they also make the
weights from the seed, in the layout the system reads them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(a, b):
    """float32 matrix product at full precision (the reference)."""
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(x, axis):
    """Round ``x`` to float8_e4m3fn with an absmax scale over ``axis``
    (per row for activations, per matrix for weights)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def mm_fp8(a, b):
    """The control's matrix product: both operands rounded to fp8
    (e4m3, absmax-scaled), products accumulated in float32."""
    a = _fp8(a.astype(jnp.float32), axis=-1)
    b = _fp8(b.astype(jnp.float32), axis=None)
    return jnp.matmul(a, b, precision=HIGHEST)


MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


def normal(key, shape, std):
    return jax.random.normal(key, shape, jnp.float32) * std


def uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)
