"""Jitted decode-attention wrapper with backend dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import (NEG_INF, pad_axis_to, resolve_backend,
                                  round_up)
from repro.kernels.decode_attention.kernel import decode_attention_pallas


def decode_attention(q, k, v, lengths, *, scale: float | None = None,
                     backend: str | None = None, block_k: int = 512):
    """q: (B, H, D); k/v: (B, S, K, D); lengths: (B,) -> (B, H, D)."""
    b = resolve_backend(backend)
    if b == "xla":
        return _decode_xla(q, k, v, lengths, scale=scale)
    return _decode_pallas(q, k, v, lengths, scale=scale, block_k=block_k,
                          interpret=(b == "pallas_interpret"))


def _decode_xla(q, k, v, lengths, *, scale):
    """bf16 inputs stay bf16 (no materialised f32 KV copies); the score matmul
    accumulates in f32 via preferred_element_type — decode is HBM-bound, so
    the KV bytes read per token are the whole roofline."""
    B, H, D = q.shape
    _, S, K, _ = k.shape
    G = H // K
    if scale is None:
        scale = D ** -0.5
    qg = ((q.astype(jnp.float32) * scale).astype(q.dtype)).reshape(B, K, G, D)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(q.dtype),
                        preferred_element_type=jnp.float32)
    mask = jnp.arange(S)[None, :] >= lengths[:, None]
    logits = jnp.where(mask[:, None, None], NEG_INF, logits)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    denom = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(q.dtype), v.astype(q.dtype),
                     preferred_element_type=jnp.float32)
    out = out / denom
    return out.reshape(B, H, D).astype(q.dtype)


def _decode_pallas(q, k, v, lengths, *, scale, block_k, interpret):
    B, H, D = q.shape
    _, S, K, _ = k.shape
    G = H // K
    g_pad = max(8, round_up(G, 8))                       # sublane alignment
    qg = q.reshape(B, K, G, D)
    qg = pad_axis_to(qg, 2, g_pad)
    S_p = round_up(S, min(block_k, round_up(S, 8)))
    block_k = min(block_k, S_p)
    S_p = round_up(S_p, block_k)
    # the kernel streams a heads-major cache: (B, S, K, D) -> (B, K, S, D)
    kp = pad_axis_to(k, 1, S_p).swapaxes(1, 2)
    vp = pad_axis_to(v, 1, S_p).swapaxes(1, 2)
    out = decode_attention_pallas(qg, kp, vp, lengths.astype(jnp.int32),
                                  scale=scale, block_k=block_k,
                                  interpret=interpret)
    return out[:, :, :G, :].reshape(B, H, D)
