"""Pure-jnp oracle for the fused two-tier state push (Faasm §4.2).

A push moves `delta = local - base` from the local tier to the global tier.
The compressed variant quantises the delta to int8 with one f32 scale per
128-lane row — what actually crosses the pod interconnect.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.state_push import QMAX


def quantize_delta_ref(local, base):
    """local/base: (R, 128) f32.  Returns (q int8 (R,128), scales f32 (R, 1))."""
    delta = local.astype(jnp.float32) - base.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(delta), axis=1, keepdims=True)
    scale = jnp.maximum(absmax / QMAX, 1e-12)
    q = jnp.clip(jnp.round(delta / scale), -QMAX, QMAX).astype(jnp.int8)
    return q, scale


def apply_delta_ref(global_val, q, scale):
    """global_val: (R, 128); q: (R,128) int8; scale: (R,1).  Returns new global."""
    return (global_val.astype(jnp.float32)
            + q.astype(jnp.float32) * scale).astype(global_val.dtype)
