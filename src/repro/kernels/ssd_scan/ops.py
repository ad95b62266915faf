"""Jitted SSD wrapper: chunked XLA path, Pallas dispatch, and the decode step.

The XLA path is the same chunked algorithm as the kernel, expressed as a
``lax.scan`` over chunks so peak memory stays O(chunk²·H) — this is what the
dry-run lowers for the SSM archs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_axis_to, resolve_backend, round_up
from repro.kernels.ssd_scan.kernel import ssd_pallas


def ssd(x, dt, A, B, C, D_skip, *, chunk: int = 256, initial_state=None,
        backend: str | None = None):
    """Chunked SSD scan.  Shapes as in ``ref.ssd_ref``; S is padded internally.

    Padding note: padded steps use dt=0 → decay exp(0·A)=1 and zero input, so
    the recurrent state is unchanged and padded outputs are discarded.
    """
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    if initial_state is None:
        initial_state = jnp.zeros((Bt, H, P, N), jnp.float32)

    b = resolve_backend(backend)
    chunk = min(chunk, max(16, 1 << (S - 1).bit_length()))   # don't over-chunk tiny S
    S_p = round_up(S, chunk)
    xp = pad_axis_to(x, 1, S_p)
    dtp = pad_axis_to(dt, 1, S_p)
    Bp = pad_axis_to(B, 1, S_p)
    Cp = pad_axis_to(C, 1, S_p)

    if b == "xla":
        y, final = _ssd_xla(xp, dtp, A, Bp, Cp, D_skip, initial_state, chunk)
    else:
        # the kernel is heads-major: (Bt, S, H, ·) -> (Bt, H, S, ·) and back
        y, final = ssd_pallas(xp.swapaxes(1, 2), dtp.swapaxes(1, 2), A,
                              Bp.swapaxes(1, 2), Cp.swapaxes(1, 2), D_skip,
                              initial_state, chunk=chunk,
                              interpret=(b == "pallas_interpret"))
        y = y.swapaxes(1, 2)
    return y[:, :S], final


@functools.partial(jax.jit, static_argnames=("chunk",))
def _ssd_xla(x, dt, A, B, C, D_skip, initial_state, chunk):
    Bt, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    rep = H // G
    nc = S // chunk
    Q = chunk

    xf = x.astype(jnp.float32).reshape(Bt, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(Bt, nc, Q, H)
    Bf = B.astype(jnp.float32).reshape(Bt, nc, Q, G, N)
    Cf = C.astype(jnp.float32).reshape(Bt, nc, Q, G, N)
    Af = A.astype(jnp.float32)

    tri = jnp.tril(jnp.ones((Q, Q), bool))

    def chunk_step(state, inp):
        xc, dtc, Bc, Cc = inp              # (Bt,Q,H,P) (Bt,Q,H) (Bt,Q,G,N) x2
        dA = dtc * Af                       # (Bt,Q,H)
        cs = jnp.cumsum(dA, axis=1)         # inclusive
        seg = cs[:, :, None, :] - cs[:, None, :, :]            # (Bt,Q,Q,H)
        # mask BEFORE exp: upper-triangular seg is positive and would overflow
        L = jnp.exp(jnp.where(tri[None, :, :, None], seg, -jnp.inf))
        CB = jnp.einsum("bign,bjgn->bijg", Cc, Bc)               # (Bt,Q,Q,G)
        CBh = jnp.repeat(CB, rep, axis=3)                       # (Bt,Q,Q,H)
        scores = CBh * L
        dtx = xc * dtc[..., None]                                # (Bt,Q,H,P)
        y = jnp.einsum("bijh,bjhp->bihp", scores, dtx)
        # contribution of the incoming state
        Ch = jnp.repeat(Cc, rep, axis=2)                         # (Bt,Q,H,N)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum("bihn,bhpn->bihp", Ch, state)
        # state update
        decay_out = jnp.exp(cs[:, -1:, :] - cs)                  # (Bt,Q,H)
        Bh = jnp.repeat(Bc, rep, axis=2)                         # (Bt,Q,H,N)
        new_state = jnp.exp(cs[:, -1, :])[..., None, None] * state + \
            jnp.einsum("bjhp,bjhn->bhpn", dtx * decay_out[..., None], Bh)
        return new_state, y

    xs = (jnp.moveaxis(xf, 1, 0), jnp.moveaxis(dtf, 1, 0),
          jnp.moveaxis(Bf, 1, 0), jnp.moveaxis(Cf, 1, 0))
    final, ys = jax.lax.scan(chunk_step, initial_state.astype(jnp.float32), xs)
    y = jnp.moveaxis(ys, 0, 1).reshape(Bt, S, H, P)
    y = y + D_skip.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), final


def ssd_step(state, x_t, dt_t, A, B_t, C_t, D_skip):
    """Single decode step of the SSD recurrence (pure jnp — O(H·P·N)).

    state: (Bt, H, P, N) f32; x_t: (Bt, H, P); dt_t: (Bt, H);
    B_t/C_t: (Bt, G, N).  Returns (y_t (Bt,H,P), new_state).
    """
    Bt, H, P, N = state.shape
    G = B_t.shape[1]
    rep = H // G
    xf = x_t.astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    Bh = jnp.repeat(B_t.astype(jnp.float32), rep, axis=1)     # (Bt,H,N)
    Ch = jnp.repeat(C_t.astype(jnp.float32), rep, axis=1)
    decay = jnp.exp(dtf * A.astype(jnp.float32))[..., None, None]
    new_state = decay * state + (dtf[..., None] * xf)[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    y = y + D_skip.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x_t.dtype), new_state
