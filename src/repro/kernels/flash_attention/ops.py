"""Jitted flash-attention wrapper with backend dispatch and a flash backward.

``backend="xla"`` is a blocked online-softmax implementation in pure jnp
(a ``lax.scan`` over KV tiles) with a **custom VJP**: the backward pass
recomputes each tile's probabilities from the saved softmax stats (m, l)
instead of letting JAX stack per-tile residuals — peak memory stays
O(Sq·block_k) in both directions (the FlashAttention-2 backward).  This is
what the dry-run lowers, so the roofline's memory term reflects it.

``backend="pallas"`` calls the TPU kernel (forward; training uses the xla
path's VJP); ``"pallas_interpret"`` runs the kernel body on CPU for tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import NEG_INF, resolve_backend, round_up, pad_axis_to
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_offset: int = 0, backend: str | None = None,
                    block_q: int = 128, block_k: int = 512):
    """Memory-bounded attention.  Shapes as in ``ref.attention_ref``; v may
    have fewer channels than q and k, and the output has v's."""
    b = resolve_backend(backend)
    if b == "xla":
        if scale is None:
            scale = q.shape[-1] ** -0.5
        return _flash_xla(q, k, v, causal, float(scale), q_offset,
                          min(block_k, k.shape[1]))
    return _flash_pallas_padded(q, k, v, causal=causal, scale=scale,
                                q_offset=q_offset, block_q=block_q,
                                block_k=min(block_k, 128),
                                interpret=(b == "pallas_interpret"))


def _flash_pallas_padded(q, k, v, *, causal, scale, q_offset, block_q, block_k,
                         interpret):
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Sq_p = round_up(Sq, min(block_q, round_up(Sq, 8)))
    block_q = min(block_q, Sq_p)
    Sq_p = round_up(Sq, block_q)
    Sk_p = round_up(Sk, block_k) if Sk >= block_k else round_up(Sk, 8)
    block_k = min(block_k, Sk_p)
    Sk_p = round_up(Sk_p, block_k)
    # the kernel is heads-major: (B, S, H, D) -> (B, H, S, D) and back
    qp = pad_axis_to(q, 1, Sq_p).swapaxes(1, 2)
    kp = pad_axis_to(k, 1, Sk_p).swapaxes(1, 2)
    vp = pad_axis_to(v, 1, Sk_p).swapaxes(1, 2)
    out = flash_attention_pallas(
        qp, kp, vp, causal=causal, scale=scale, q_offset=q_offset,
        kv_len=Sk, block_q=block_q, block_k=block_k, interpret=interpret)
    return out.swapaxes(1, 2)[:, :Sq]


# ---------------------------------------------------------------------------
# XLA path with flash backward (custom VJP)
# ---------------------------------------------------------------------------

def _kv_tiles(k, block_k):
    """(B, Sk_p, K, D) -> (n, B, bk, K, D) f32 tiles."""
    B, Sk_p, K, D = k.shape
    n = Sk_p // block_k
    return jnp.moveaxis(k.reshape(B, n, block_k, K, D), 1, 0)


def _mask_for(block_start, block_k, Sk, q_pos, causal):
    k_pos = block_start + jnp.arange(block_k)
    mask = k_pos[None, :] >= Sk                              # padding
    if causal:
        mask = mask | (k_pos[None, :] > q_pos[:, None])      # (Sq, bk)
    return mask


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_xla(q, k, v, causal, scale, q_offset, block_k):
    out, _, _ = _flash_xla_fwd_impl(q, k, v, causal, scale, q_offset, block_k)
    return out


def _flash_xla_fwd_impl(q, k, v, causal, scale, q_offset, block_k):
    # K/V tiles stay in the input dtype (no materialised f32 cache copies);
    # score/accumulator matmuls accumulate in f32 via preferred_element_type.
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    Dv = v.shape[-1]
    G = H // K
    cdt = q.dtype
    Sk_p = round_up(Sk, block_k)
    kp = pad_axis_to(k, 1, Sk_p).astype(cdt)
    vp = pad_axis_to(v, 1, Sk_p).astype(cdt)
    qg = ((q.astype(jnp.float32) * scale).astype(cdt)).reshape(B, Sq, K, G, D)
    q_pos = q_offset + jnp.arange(Sq)
    kb, vb = _kv_tiles(kp, block_k), _kv_tiles(vp, block_k)
    starts = jnp.arange(Sk_p // block_k) * block_k

    def body(carry, xs):
        m, l, acc = carry
        kt, vt, start = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kt,
                       preferred_element_type=jnp.float32)
        mask = _mask_for(start, block_k, Sk, q_pos, causal)
        s = jnp.where(mask[None, None, None], NEG_INF, s)
        m_cur = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l * corr + p.sum(axis=-1)
        acc_cur = acc * corr[..., None] + jnp.einsum(
            "bkgqs,bskd->bkgqd", p.astype(cdt), vt,
            preferred_element_type=jnp.float32)
        return (m_cur, l_cur, acc_cur), None

    m0 = jnp.full((B, K, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    acc0 = jnp.zeros((B, K, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (kb, vb, starts))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    outg = acc / l_safe[..., None]                            # (B,K,G,Sq,Dv) f32
    out = jnp.moveaxis(outg, 3, 1).reshape(B, Sq, H, Dv).astype(q.dtype)
    return out, m, l_safe


def _flash_xla_fwd(q, k, v, causal, scale, q_offset, block_k):
    out, m, l = _flash_xla_fwd_impl(q, k, v, causal, scale, q_offset, block_k)
    return out, (q, k, v, out, m, l)


def _flash_xla_bwd(causal, scale, q_offset, block_k, res, dout):
    q, k, v, out, m, l = res
    B, Sq, H, D = q.shape
    _, Sk, K, _ = k.shape
    Dv = v.shape[-1]
    G = H // K
    cdt = q.dtype
    Sk_p = round_up(Sk, block_k)
    kp = pad_axis_to(k, 1, Sk_p).astype(cdt)
    vp = pad_axis_to(v, 1, Sk_p).astype(cdt)
    qg = ((q.astype(jnp.float32) * scale).astype(cdt)).reshape(B, Sq, K, G, D)
    outg = jnp.moveaxis(out.reshape(B, Sq, K, G, Dv), 1, 3)
    dog = jnp.moveaxis(dout.astype(cdt).reshape(B, Sq, K, G, Dv), 1, 3)
    Di = jnp.einsum("bkgqd,bkgqd->bkgq", outg.astype(cdt), dog,
                    preferred_element_type=jnp.float32)       # (B,K,G,Sq)
    q_pos = q_offset + jnp.arange(Sq)
    kb, vb = _kv_tiles(kp, block_k), _kv_tiles(vp, block_k)
    starts = jnp.arange(Sk_p // block_k) * block_k

    def body(dq_acc, xs):
        kt, vt, start = xs
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kt,
                       preferred_element_type=jnp.float32)
        mask = _mask_for(start, block_k, Sk, q_pos, causal)
        s = jnp.where(mask[None, None, None], NEG_INF, s)
        p = jnp.exp(s - m[..., None]) / l[..., None]          # exact softmax
        pc = p.astype(cdt)
        dv_t = jnp.einsum("bkgqs,bkgqd->bskd", pc, dog,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bkgqd,bskd->bkgqs", dog, vt,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - Di[..., None])).astype(cdt)           # (B,K,G,Sq,bk)
        dq_acc = dq_acc + scale * jnp.einsum(
            "bkgqs,bskd->bqkgd", ds, kt, preferred_element_type=jnp.float32)
        # qg already carries `scale`, so dk = dsᵀ·(q·scale) = dsᵀ·qg
        dk_t = jnp.einsum("bkgqs,bqkgd->bskd", ds, qg,
                          preferred_element_type=jnp.float32)
        return dq_acc, (dk_t, dv_t)

    dq0 = jnp.zeros((B, Sq, K, G, D), jnp.float32)
    dq, (dk_t, dv_t) = jax.lax.scan(body, dq0, (kb, vb, starts))
    dk = jnp.moveaxis(dk_t, 0, 1).reshape(B, Sk_p, K, D)[:, :Sk]
    dv = jnp.moveaxis(dv_t, 0, 1).reshape(B, Sk_p, K, Dv)[:, :Sk]
    dq = dq.reshape(B, Sq, H, D)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash_xla.defvjp(_flash_xla_fwd, _flash_xla_bwd)
