"""Numerics shared by the plain references: float32 matrix products at the
highest precision, or, for the control, the same products with both
operands rounded to float8 (e4m3, one scale per tensor), the precision
below the bfloat16 that the configurations serve in."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = float(jnp.finfo(jnp.float8_e4m3fn).max)
MODES = ("f32", "fp8")


def to_fp8(x):
    """Round ``x`` to e4m3 with one absmax scale for the whole tensor and
    return it in float32."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, F8_MAX / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def einsum(mode: str, spec: str, a, b):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        a, b = to_fp8(a), to_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def layer_slice(layers: dict, i: int) -> dict:
    return jax.tree_util.tree_map(lambda x: x[i], layers)
