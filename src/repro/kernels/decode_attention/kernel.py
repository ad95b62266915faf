"""Pallas TPU flash-decode kernel: one query token vs. a long KV cache.

Decode attention is purely memory-bound (arithmetic intensity ~1 FLOP/byte), so
the kernel is organised around streaming the KV cache through VMEM exactly once:

  * grid = (batch, kv_heads, kv_splits); the split dimension is sequential and
    carries online-softmax stats in VMEM scratch (flash-decode reduction).
  * all G = H/K query heads of one KV head are processed together as a (G, D)
    tile, so each streamed KV tile is reused G times from VMEM (the GQA
    arithmetic-intensity win: bytes/token divided by G).
  * per-sequence cache lengths arrive via scalar prefetch (SMEM) and mask the
    tail tile; whole splits past the length are elided with ``pl.when``.
  * k/v are heads-major, (B, K, S, D), so each streamed tile's last two dims
    are (block_k, head_dim) as Mosaic requires; ``ops.py`` transposes the
    model's (B, S, K, D) cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, cdiv, tpu_compiler_params

_MIN_LANES = 128


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                   scale: float, block_k: int, n_splits: int, g_pad: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)
    length = len_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik * block_k < length)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale            # (Gp, D)
        k = k_ref[...].astype(jnp.float32)                     # (bk, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (Gp, bk)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (g_pad, block_k), 1)
        s = jnp.where(k_pos >= length, NEG_INF, s)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ik == n_splits - 1)
    def _finalize():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, lengths, *, scale: float | None = None,
                            block_k: int = 512, interpret: bool = False):
    """q: (B, K, Gp, D) grouped+padded queries; k/v: (B, K, S, D); lengths: (B,)."""
    B, K, Gp, D = q.shape
    S = k.shape[2]
    if scale is None:
        scale = D ** -0.5
    block_k = min(block_k, S)
    assert S % block_k == 0, (S, block_k)
    n_splits = S // block_k

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                               n_splits=n_splits, g_pad=Gp)
    q_spec = pl.BlockSpec((None, None, Gp, D), lambda b, h, ik, lens: (b, h, 0, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, D),
                           lambda b, h, ik, lens: (b, h, ik, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, K, n_splits),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Gp, D), jnp.float32),
            pltpu.VMEM((Gp, _MIN_LANES), jnp.float32),
            pltpu.VMEM((Gp, _MIN_LANES), jnp.float32),
        ],
    )
    compiler_params = tpu_compiler_params(("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=compiler_params,
        interpret=interpret,
    )(lengths, q, k, v)
