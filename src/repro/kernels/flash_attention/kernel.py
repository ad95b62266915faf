"""Pallas TPU flash-attention kernel (causal, GQA) with VMEM tiling.

Design (TPU-native, not a CUDA port):
  * grid = (batch, q_heads, q_blocks, kv_blocks); the kv dimension is
    ``arbitrary`` (sequential) so the online-softmax accumulators live in VMEM
    scratch across kv steps — HBM sees each q/k/v tile exactly once.
  * operands are heads-major, (B, H, S, D): each block's last two dims are a
    (block, head_dim) tile, which Mosaic needs as (8k or full, 128k or full).
    ``ops.py`` transposes to and from the model's (B, S, H, D).
  * q tile (block_q, head_dim) stays resident; k/v tiles stream through VMEM.
    v (and the output) may be narrower than q and k (latent attention's
    192-channel q/k against 128-channel v): each operand has its own spec.
    block sizes default to 128 to align with the 128×128 MXU and 8×128 VREG lanes.
  * causal blocks strictly above the diagonal are skipped via ``pl.when``
    (grid-level work elision, the TPU analogue of warp-level early exit).
  * GQA: the k/v index map folds the query head onto its kv group
    (h -> h // group), so no repeated-KV materialisation in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import NEG_INF, cdiv, tpu_compiler_params

# TPU VREG minor dimension; accumulators are padded to this many lanes.
_MIN_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  n_kv_blocks: int, q_offset: int, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Last absolute query position covered by this q tile.
    q_last = q_offset + (iq + 1) * block_q - 1
    needed = (ik * block_k <= q_last) if causal else (ik >= 0)

    @pl.when(needed)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[...].astype(jnp.float32)                  # (bk, D)
        v = v_ref[...].astype(jnp.float32)                  # (bk, Dv)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                    # (bq, bk)

        q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos >= kv_len                                      # tail padding
        if causal:
            mask = mask | (k_pos > q_pos)
        s = jnp.where(mask, NEG_INF, s)

        m_prev = m_ref[:, 0]                                        # (bq,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])                             # (bq, bk)
        l_cur = l_prev * corr + p.sum(axis=-1)

        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)                             # fully-masked rows
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           scale: float | None = None, q_offset: int = 0,
                           kv_len: int | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """(B, H, Sq, D) x (B, K, Sk, D) x (B, K, Sk, Dv) -> (B, H, Sq, Dv).
    Sq/Sk padded by ops.py."""
    B, H, Sq, D = q.shape
    _, K, Sk, _ = k.shape
    Dv = v.shape[-1]
    assert H % K == 0
    group = H // K
    if scale is None:
        scale = D ** -0.5
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    n_q, n_k = Sq // block_q, Sk // block_k
    kv_len = Sk if kv_len is None else kv_len

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_kv_blocks=n_k, q_offset=q_offset, kv_len=kv_len)

    grid = (B, H, n_q, n_k)

    def q_spec(width):
        return pl.BlockSpec((None, None, block_q, width),
                            lambda b, h, iq, ik: (b, h, iq, 0))

    def kv_spec(width):
        return pl.BlockSpec((None, None, block_k, width),
                            lambda b, h, iq, ik: (b, h // group, ik, 0))

    compiler_params = tpu_compiler_params(
        ("parallel", "parallel", "parallel", "arbitrary"))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec(D), kv_spec(D), kv_spec(Dv)],
        out_specs=q_spec(Dv),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),         # acc
            pltpu.VMEM((block_q, _MIN_LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _MIN_LANES), jnp.float32),  # running denom
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v)
