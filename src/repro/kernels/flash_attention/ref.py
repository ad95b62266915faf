"""Pure-jnp oracle for flash attention (GQA, causal, query offset).

Materialises the full (Sq, Sk) score matrix — only usable at test scale; the
Pallas kernel and the blocked XLA path in ``ops.py`` are validated against this.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.common import NEG_INF


def attention_ref(q, k, v, *, causal: bool = True, scale: float | None = None,
                  q_offset: int = 0, kv_len=None):
    """Reference attention.

    Args:
      q: (B, Sq, H, D)
      k: (B, Sk, K, D) with H % K == 0 (GQA)
      v: (B, Sk, K, Dv); Dv may differ from D (latent attention)
      causal: lower-triangular masking in absolute positions
      scale: logit scale (default 1/sqrt(D))
      q_offset: absolute position of q[0] (decode: cache length)
      kv_len: optional (B,) valid KV lengths (positions >= kv_len are masked)

    Returns: (B, Sq, H, Dv) in q.dtype.
    """
    B, Sq, H, D = q.shape
    Bk, Sk, K, Dk = k.shape
    assert (B, D) == (Bk, Dk) and H % K == 0, (q.shape, k.shape)
    G = H // K
    if scale is None:
        scale = D ** -0.5

    qf = q.astype(jnp.float32) * scale
    qg = qf.reshape(B, Sq, K, G, D)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))

    q_pos = q_offset + jnp.arange(Sq)[:, None]          # (Sq, 1)
    k_pos = jnp.arange(Sk)[None, :]                      # (1, Sk)
    mask = jnp.zeros((Sq, Sk), dtype=bool)
    if causal:
        mask = mask | (k_pos > q_pos)
    if kv_len is not None:
        mask = mask[None] | (k_pos[None] >= kv_len[:, None, None])   # (B, Sq, Sk)
        logits = jnp.where(mask[:, None, None], NEG_INF, logits)
    else:
        logits = jnp.where(mask[None, None, None], NEG_INF, logits)

    p = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)
