"""Jitted wrappers for the fused state push, handling arbitrary shapes.

Arrays are flattened and padded to (rows, 128); the pad region quantises to
zero-delta so applying a padded push is a no-op on the pad.

Two encode paths, chosen per call by where the operands live
(:func:`host_resident`), whatever the backend:

* **host-native** (``hostcodec``): both operands are plain numpy.  The
  math is a handful of cache-resident numpy passes, so a device round trip
  — two copies in, a kernel queued behind whatever else the device runs,
  and a copy out — is pure overhead and is skipped entirely.
* **device**: anything holding a device array goes through **one** fused
  jitted executable per backend: on ``xla`` flatten + pad + quantise +
  residual (``_encode_fused``), on Pallas flatten + pad + the quantise
  kernel + the f32 delta rows (``_encode_pallas``).  jax compiles each once
  per ``(shape, dtype)`` and caches it.  On ``xla`` large values are
  encoded in row chunks whose copy-out is pipelined with the next chunk's
  dispatch — async dispatch means chunk N quantises on device while chunk
  N−1's payload is crossing to the host.

The backend only chooses the kernel for device operands.

Every Pallas dispatch here runs inside ``jax.jit`` with the kernel and its
static parameters as static arguments.  An eager ``pallas_call`` builds a
fresh wrapper on every call, so jax's cache never hits and each call pays
a backend compile.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.common import resolve_backend, round_up
from repro.kernels.state_push import hostcodec
from repro.kernels.state_push import ref as _ref
from repro.kernels.state_push.kernel import (LANES, apply_delta_pallas,
                                             quantize_delta_pallas)

# the xla path is the hot CPU-host wire codec (LocalTier.push_delta calls it
# per push): jit once, jax caches the executable per shape
_quantize_ref = jax.jit(_ref.quantize_delta_ref)
_apply_ref = jax.jit(_ref.apply_delta_ref)

# rows a device-side encode processes per dispatch when chunking: 2 MB of f32
# keeps enough compute in flight to hide each chunk's host copy-out
DEVICE_CHUNK_ROWS = 4096


def _to_rows(x):
    flat = jnp.ravel(x).astype(jnp.float32)
    n = flat.shape[0]
    rows = max(1, round_up(n, LANES) // LANES)
    padded = jnp.pad(flat, (0, rows * LANES - n))
    return padded.reshape(rows, LANES), n


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("interpret",))
def _pallas_rows(kernel, *operands, interpret: bool):
    """Run a row-streaming state-push kernel over (R, ·) operands.

    Blocks are (8k, 128) tiles of at most 256 rows, as Mosaic requires, so
    the rows are zero-padded to a whole number of blocks.  Zero rows
    quantise to zero codes and apply as a no-op; every output is trimmed
    back to R rows.  jax compiles it once per kernel and operand shapes."""
    rows = operands[0].shape[0]
    blk = min(256, round_up(rows, 8))
    pad = round_up(rows, blk) - rows
    out = kernel(*(jnp.pad(x, ((0, pad), (0, 0))) for x in operands),
                 block_rows=blk, interpret=interpret)
    if isinstance(out, (list, tuple)):
        return tuple(o[:rows] for o in out)
    return out[:rows]


@functools.partial(jax.jit, static_argnames=("with_residual",))
def _encode_fused(local, base, with_residual):
    """Single-dispatch device encode: flatten/pad/quantise (+ residual) in one
    executable.  jax caches the compiled program per (shape, dtype)."""
    lr, _ = _to_rows(local)
    br, _ = _to_rows(base)
    q, s = _ref.quantize_delta_ref(lr, br)
    if not with_residual:
        return q, s
    resid = (lr - br) - q.astype(jnp.float32) * s
    return q, s, resid


@functools.partial(jax.jit, static_argnames=("interpret", "with_residual"))
def _encode_pallas(local, base, interpret, with_residual):
    """Pallas twin of :func:`_encode_fused`: flatten/pad and the quantise
    kernel in one executable, returning the codes, the scales and (with
    ``with_residual``) the f32 delta rows ``lr − br``.  The residual itself
    is formed on the host from those rows, so it rounds as a plain
    multiply-subtract."""
    lr, _ = _to_rows(local)
    br, _ = _to_rows(base)
    q, s = _pallas_rows(quantize_delta_pallas, lr, br, interpret=interpret)
    if not with_residual:
        return q, s
    return q, s, lr - br


def _device_encode(eff, base, *, b, with_residual):
    """Device-path encode returning host numpy wire buffers.

    Each backend takes one cached executable per shape: ``_encode_pallas``
    on Pallas, ``_encode_fused`` on ``xla``.  On ``xla``, values above
    ``DEVICE_CHUNK_ROWS`` rows are encoded chunk by chunk: every chunk's
    kernel is dispatched before any copy-out blocks, so the device
    quantises chunk N while chunk N−1 streams to the host.  Scales are
    per-row and chunks split on row boundaries, so the result is bitwise
    identical to a single-shot encode."""
    n = int(np.prod(np.shape(eff))) if np.shape(eff) else 1
    rows = hostcodec.rows_for(n)
    if b != "xla":
        out = _encode_pallas(eff, base, b == "pallas_interpret",
                             with_residual)
        qn, sn = np.asarray(out[0]), np.asarray(out[1])
        if not with_residual:
            return qn, sn, n, None
        deltar = np.asarray(out[2])
        resid = deltar - qn.astype(np.float32) * sn
        return qn, sn, n, resid.reshape(-1)[:n]
    if rows <= DEVICE_CHUNK_ROWS:
        out = _encode_fused(eff, base, with_residual)
        if with_residual:
            q, s, resid = out
            return (np.asarray(q), np.asarray(s), n,
                    np.asarray(resid).reshape(-1)[:n])
        q, s = out
        return np.asarray(q), np.asarray(s), n, None
    # chunked: dispatch everything (async), then copy out in order
    lr, _ = _to_rows(eff)
    br, _ = _to_rows(base)
    parts = []
    for r0 in range(0, rows, DEVICE_CHUNK_ROWS):
        r1 = min(r0 + DEVICE_CHUNK_ROWS, rows)
        parts.append((r0, r1,
                      _encode_fused(lr[r0:r1], br[r0:r1], with_residual)))
    qn = np.empty((rows, LANES), np.int8)
    sn = np.empty((rows, 1), np.float32)
    resid = np.empty(rows * LANES, np.float32) if with_residual else None
    for r0, r1, out in parts:
        if with_residual:
            qc, sc, rc = out
            resid[r0 * LANES: r1 * LANES] = np.asarray(rc).reshape(-1)
        else:
            qc, sc = out
        qn[r0:r1] = np.asarray(qc)
        sn[r0:r1] = np.asarray(sc)
    return qn, sn, n, (resid[:n] if with_residual else None)


def host_resident(eff, base) -> bool:
    """The encode-path rule: True when ``eff`` is a plain numpy array and
    ``base`` is ``None`` or one too, so :func:`encode_quant` runs the host
    codec.  One home for the rule, shared by the dispatch and the callers
    that tag or count which path an encode took."""
    return isinstance(eff, np.ndarray) and (
        base is None or isinstance(base, np.ndarray))


def encode_quant(eff, base, *, backend: str | None = None,
                 with_residual: bool = True):
    """Fused int8 wire encode: quantise ``eff − base`` to codes in
    ``[-QMAX, QMAX]`` and (optionally) the error-feedback residual, in one
    pass.  Returns host numpy
    ``(q int8 (R,128), scales f32 (R,1), numel, residual f32 (numel,) | None)``.

    Host-resident operands (:func:`host_resident`) take the host codec
    (:mod:`.hostcodec`) on every backend and dispatch nothing to JAX; device
    operands take one fused cached executable, and ``backend`` chooses its
    kernel: ``_encode_pallas`` on Pallas, ``_encode_fused`` (with
    chunk-pipelined copy-out) on ``xla``."""
    b = resolve_backend(backend)
    if host_resident(eff, base):
        q, s, n, resid = hostcodec.encode_quant(eff, base)
        return q, s, n, (resid if with_residual else None)
    if base is None:
        base = jnp.zeros_like(jnp.ravel(eff))
    return _device_encode(eff, base, b=b, with_residual=with_residual)


def quantize_delta(local, base, *, backend: str | None = None):
    """Any-shape fused delta quantisation.  Returns (q (R,128) int8, scales (R,1),
    original_numel) — the wire format of a compressed push."""
    b = resolve_backend(backend)
    if b == "xla" and hostcodec.usable(local, base):
        q, s, n, _ = hostcodec.encode_quant(local, base)
        return q, s, n
    lr, n = _to_rows(local)
    br, _ = _to_rows(base)
    if b == "xla":
        q, s = _quantize_ref(lr, br)
    else:
        q, s = _pallas_rows(quantize_delta_pallas, lr, br,
                            interpret=(b == "pallas_interpret"))
    return q, s, n


def dequantize(q, scales, numel: int):
    """Decode a wire tuple back to the flat f32 delta of length ``numel``.

    The pad region (rows*128 − numel) quantises to zero-delta, so the trim
    here drops only zeros."""
    if isinstance(q, np.ndarray) and isinstance(scales, np.ndarray):
        return hostcodec.decode_rows(q, scales, numel)
    return (q.astype(jnp.float32) * scales).reshape(-1)[:numel]


def wire_nbytes(q, scales) -> int:
    """Bytes the compressed push actually moves: int8 payload + f32 scales."""
    return int(q.size) + int(scales.size) * 4


def _apply_wire(value, q, scales, backend: str | None):
    """Shared decode/apply: ``value += q·scale`` (any shape), one fused pass.

    The single home of the wire-apply dispatch for both directions —
    :func:`apply_delta` (push: global buffer) and :func:`apply_pull`
    (pull/broadcast: replica or device value)."""
    b = resolve_backend(backend)
    shape, dtype = value.shape, value.dtype
    gr, n = _to_rows(value)
    if b == "xla":
        out = _apply_ref(gr, q, scales)
    else:
        out = _pallas_rows(apply_delta_pallas, gr, q, scales,
                           interpret=(b == "pallas_interpret"))
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


def apply_delta(global_val, q, scales, *, backend: str | None = None):
    """Apply a compressed push to a value of any shape."""
    return _apply_wire(global_val, q, scales, backend)


def encode_pull(new, base, *, backend: str | None = None):
    """Pull-direction encode: quantise ``new − base`` (the delta a warm
    replica at ``base`` needs to catch up to ``new``) with the same fused
    quantise kernel the push wire uses.  Returns the ``(q, scales, numel)``
    wire tuple — the symmetric twin of :func:`quantize_delta`."""
    return quantize_delta(new, base, backend=backend)


def apply_pull(value, q, scales, *, backend: str | None = None):
    """Pull-direction decode/apply: ``replica += q·scale`` (any shape).

    Applies a pulled (or peer-broadcast) wire tuple onto a replica value —
    host- or device-resident — in one fused pass; the pad region quantises
    to zero-delta so the trim is a no-op beyond ``numel``.  Same kernel as
    :func:`apply_delta`, dispatched from the opposite side of the tier
    boundary."""
    return _apply_wire(value, q, scales, backend)

