"""Pallas TPU kernel for the chunked Mamba2 SSD scan.

TPU-native layout of the SSD algorithm (Dao & Gu, 2024, §6):

  * operands are heads-major, (B, H, S, ·), so every block's last two dims
    are a (chunk, width) tile as Mosaic requires; ``ops.py`` transposes.
  * grid = (batch, heads, chunks); the chunk dimension is sequential
    (``arbitrary``) and the inter-chunk recurrent state (P, N) lives in VMEM
    scratch across chunk steps — HBM traffic is one read of x/dt/B/C and one
    write of y, with no state round-trips.
  * the intra-chunk quadratic term (C·Bᵀ ⊙ L) and the chunk-state update are
    (Q×N)·(N×Q) and (P×Q)·(Q×N) matmuls — MXU work, with Q (chunk length),
    N (state) and P (head dim) chosen as multiples of the 128 MXU tile where
    the model config allows.
  * all decays are exp of non-positive cumulative sums (A < 0, dt > 0), so the
    kernel is overflow-free in f32 scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import tpu_compiler_params


def _ssd_kernel(A_ref, D_ref, x_ref, dtc_ref, dtr_ref, B_ref, C_ref, init_ref,
                y_ref, final_ref, state_ref, *, chunk: int, n_chunks: int):
    h = pl.program_id(1)
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = init_ref[...].astype(jnp.float32)

    x = x_ref[...].astype(jnp.float32)                       # (Q, P)
    dt_col = dtc_ref[...].astype(jnp.float32)                # (Q, 1)
    dt_row = dtr_ref[...].astype(jnp.float32)                # (1, Q)
    Bm = B_ref[...].astype(jnp.float32)                      # (Q, N)
    Cm = C_ref[...].astype(jnp.float32)                      # (Q, N)
    A_h = A_ref[h]
    D_h = D_ref[h]

    # inclusive cumsum of dA <= 0 as a column and as a row, by masked
    # reductions over the (Q, Q) lower triangle
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col
    cs_col = jnp.sum(jnp.where(tri, dt_row * A_h, 0.0), axis=1,
                     keepdims=True)                          # (Q, 1)
    cs_row = jnp.sum(jnp.where(row <= col, dt_col * A_h, 0.0), axis=0,
                     keepdims=True)                          # (1, Q)
    cs_last = jnp.sum(dt_row * A_h, axis=1, keepdims=True)   # (1, 1)
    # mask BEFORE exp: upper-triangular seg is positive and would overflow
    L = jnp.exp(jnp.where(tri, cs_col - cs_row, -jnp.inf))   # (Q, Q)

    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, Q)
    dtx = x * dt_col                                               # (Q, P)
    y = jax.lax.dot_general(CB * L, dtx, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)    # (Q, P)

    state = state_ref[...]                                         # (P, N)
    y = y + jnp.exp(cs_col) * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                        # (Q,N)x(P,N)->(Q,P)
    y = y + D_h * x
    y_ref[...] = y.astype(y_ref.dtype)

    decay_out = jnp.exp(cs_last - cs_col)                          # (Q, 1)
    new_state = jnp.exp(cs_last) * state + jax.lax.dot_general(
        dtx * decay_out, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                        # (P, N)
    state_ref[...] = new_state

    @pl.when(c == n_chunks - 1)
    def _final():
        final_ref[...] = new_state.astype(final_ref.dtype)


def ssd_pallas(x, dt, A, B, C, D_skip, initial_state, *, chunk: int,
               interpret: bool = False):
    """Chunked SSD over heads-major operands; S must be a multiple of
    ``chunk`` (ops.py pads and transposes).

    x: (Bt, H, S, P); dt: (Bt, H, S); B/C: (Bt, G, S, N);
    initial_state: (Bt, H, P, N).  Returns y (Bt, H, S, P) and the final
    state (Bt, H, P, N) f32.
    """
    Bt, H, S, P = x.shape
    G, N = B.shape[1], B.shape[3]
    rep = H // G
    assert S % chunk == 0
    n_chunks = S // chunk
    # dt enters twice, per chunk as a column and as a row, so that both
    # blocks are whole trailing dims
    dt_col = dt.reshape(Bt, H, n_chunks, chunk, 1)
    dt_row = dt.reshape(Bt, H, n_chunks, 1, chunk)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    state_spec = pl.BlockSpec((None, None, P, N),
                              lambda b, h, c, A, D: (b, h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bt, H, n_chunks),
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c, A, D: (b, h, c, 0)),
            pl.BlockSpec((None, None, None, chunk, 1),
                         lambda b, h, c, A, D: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, None, 1, chunk),
                         lambda b, h, c, A, D: (b, h, c, 0, 0)),
            pl.BlockSpec((None, None, chunk, N),
                         lambda b, h, c, A, D: (b, h // rep, c, 0)),
            pl.BlockSpec((None, None, chunk, N),
                         lambda b, h, c, A, D: (b, h // rep, c, 0)),
            state_spec,
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c, A, D: (b, h, c, 0)),
            state_spec,
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
    )
    compiler_params = tpu_compiler_params(("parallel", "parallel", "arbitrary"))
    y, final_state = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((Bt, H, P, N), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(A.astype(jnp.float32), D_skip.astype(jnp.float32), x, dt_col, dt_row,
      B, C, initial_state)
    return y, final_state
