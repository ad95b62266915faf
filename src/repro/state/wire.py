"""Symmetric wire fabric for two-tier state movement (Faasm §4.2).

Every byte that crosses the tier boundary — delta **pushes** (replica →
global), delta **pulls** (global → warm replica refresh) and **peer
broadcast** (global → every subscribed replica) — travels as one
:class:`WireFrame`, encoded by one of two codecs (:func:`get_codec`):
:class:`ExactCodec` or :class:`Int8Codec`.  Both are direction-agnostic:
the int8 encode is the fused ``kernels/state_push`` quantise kernel
whichever side runs it, and the decode/apply is the same
``q·scale`` accumulate whether it lands in the global buffer (push), a host
replica (pull/broadcast) or a JAX device replica (``ops.apply_pull``).

Wire tuple layout (the protocol, see ROADMAP "Wire protocol"):

  ``(wire, numel, payload, scales, prev_version → version)``

  * ``wire="exact"`` — ``payload`` is the flat f32 delta itself, ``scales``
    is ``None``; wire bytes = ``4·numel``.
  * ``wire="int8"``  — ``payload`` is the ``(rows, 128)`` int8 quantised
    delta, ``scales`` the per-row f32 absmax scales; wire bytes ≈ ``numel``.
  * ``prev_version``/``version`` stamp the key's global write version the
    frame moved between — a receiver applies a frame only when its replica
    sits exactly at ``prev_version`` (anything else is repaired by the next
    delta pull, which re-bases on the receiver's actual version).

Error-feedback **residual ownership**: quantisation debt always lives with
the party whose value is behind by it.  A push residual belongs to the
pushing replica (host- or device-side, as before); a pull residual belongs
to the pulling replica and is threaded through
:meth:`GlobalTier.pull_wire`, so repeated int8 refreshes converge instead of
random-walking.  Broadcast frames carry no residual: the broadcast payload
is byte-identical to the delta the global tier itself applied, so applying
it is exact replication.

:class:`WirePolicy` serves ``wire="auto"`` (an explicit ``wire=`` string
overrides it): per key, it picks int8 vs exact from the observed delta
magnitude/density and the error-feedback residual norm, with flip-flop
damping (a switch needs ``damping`` consecutive contrary observations).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro import faults
# numpy-only (jax-free) host codec: exact delta + int8 row decode
from repro.kernels.state_push import hostcodec

WIRES = ("exact", "int8")

# repro.analysis.sanitizer installs its hook state here (enable()); None
# compiles every check in this module down to one pointer compare
_SAN = None

# Values smaller than this stay on the exact wire even when int8 is
# requested: the per-row scales + dispatch overhead eat the 4x payload
# saving on tiny values.  (Historic home: repro.state.local, re-exported
# there for compatibility.)
INT8_WIRE_MIN_BYTES = 4096


@dataclass
class WireFrame:
    """One unit of tier traffic: a flat f32 delta in encoded form."""

    wire: str                           # codec name, one of WIRES
    numel: int                          # flat f32 elements the delta covers
    payload: np.ndarray                 # exact: f32[numel]; int8: (R,128) i8
    scales: Optional[np.ndarray] = None  # int8: (R,1) f32 absmax scales
    dtype: np.dtype = np.dtype(np.float32)  # value dtype the delta applies to
    prev_version: int = -1              # key version the frame applies on top of
    version: int = -1                   # key version the frame produces
    origin: Optional[str] = None        # pushing host (stamped by apply_wire):
    # a replica pulling through the window must skip its own frames — its
    # buffer already holds those deltas in un-quantised form

    @property
    def nbytes(self) -> int:
        """Bytes this frame moves across a tier boundary."""
        n = int(self.payload.nbytes)
        if self.scales is not None:
            n += int(self.scales.nbytes)
        return n

    def decode(self) -> np.ndarray:
        """The flat f32 delta of length ``numel`` (pure numpy — safe to call
        under a stripe lock; kernel-side decode is ``ops.apply_pull``)."""
        if self.wire == "exact":
            return self.payload.reshape(-1)[:self.numel]
        return hostcodec.decode_rows(self.payload, self.scales, self.numel)

    def codes(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """The kernel-applyable ``(q, scales)`` row pair for int8 frames,
        ``None`` for exact — the device fast path (``ops.apply_pull``)
        consumes this so a ``DeviceReplica`` value never round-trips
        through a host decode."""
        if self.wire == "exact":
            return None
        return self.payload, self.scales


class ExactCodec:
    """Identity wire: the frame payload is the f32 delta itself.

    ``encode`` still flushes any error-feedback residual handed to it (the
    exact wire pays quantisation debt in full), so a replica switching wires
    mid-stream never strands debt."""

    name = "exact"

    def encode(self, eff, base, *,
               backend: Optional[str] = None) -> Tuple[WireFrame, Any]:
        """Encode ``eff − base`` as an exact frame.  ``eff``/``base`` are
        flat f32 (numpy or jax; jax inputs are synced).  Returns
        ``(frame, residual)`` with residual ``None`` — the exact wire drops
        nothing."""
        if hostcodec.usable(eff, base):
            # chunked host path: each completed chunk of the payload is
            # final wire bytes while later chunks are still encoding
            delta = hostcodec.encode_exact(eff, base)
        else:
            delta = np.asarray(eff, np.float32) - np.asarray(base, np.float32)
            delta = np.ascontiguousarray(delta.reshape(-1))
        return WireFrame(wire=self.name, numel=delta.size,
                         payload=delta), None

    def encode_delta(self, delta: np.ndarray, *,
                     backend: Optional[str] = None) -> WireFrame:
        """Encode an already-computed flat f32 delta (pull direction)."""
        delta = np.ascontiguousarray(np.asarray(delta, np.float32).reshape(-1))
        return WireFrame(wire=self.name, numel=delta.size, payload=delta)


class Int8Codec:
    """Quantised wire: the fused ``kernels/state_push`` int8 codec.

    The encode runs the fused quantise path — host-native numpy for
    host-resident buffers, one cached jitted executable with chunk-pipelined
    copy-out for device arrays — and returns the error-feedback residual:
    what quantisation dropped, to be carried by the owning replica into its
    next encode."""

    name = "int8"

    def encode(self, eff, base, *,
               backend: Optional[str] = None) -> Tuple[WireFrame, Any]:
        from repro.kernels.state_push import ops

        faults.point("codec-error")
        q, s, n, residual = ops.encode_quant(eff, base, backend=backend)
        if _SAN is not None:
            # recompute the dequantised carry from the codes themselves so
            # the conservation check is independent of the fused residual
            deq = ops.dequantize(np.asarray(q), np.asarray(s), int(n))
            true_delta = (np.asarray(eff, np.float32).reshape(-1)[:int(n)]
                          - np.asarray(base, np.float32).reshape(-1)[:int(n)])
            _SAN.check_residual(true_delta, np.asarray(deq), residual)
        return WireFrame(wire=self.name, numel=int(n), payload=np.asarray(q),
                         scales=np.asarray(s, np.float32)), residual

    def encode_delta(self, delta: np.ndarray, *,
                     backend: Optional[str] = None) -> WireFrame:
        """Encode an already-computed flat f32 delta (pull direction) —
        same fused quantise path, zero base (no zeros materialised)."""
        from repro.kernels.state_push import ops

        delta = np.asarray(delta, np.float32).reshape(-1)
        q, s, n, _ = ops.encode_quant(delta, None, backend=backend,
                                      with_residual=False)
        return WireFrame(wire=self.name, numel=int(n), payload=np.asarray(q),
                         scales=np.asarray(s, np.float32))


_CODECS: Dict[str, Any] = {"exact": ExactCodec(), "int8": Int8Codec()}


def get_codec(wire: str):
    try:
        return _CODECS[wire]
    except KeyError:
        raise ValueError(f"wire {wire!r} not in {WIRES}") from None


class WirePolicy:
    """Per-key adaptive wire selection with flip-flop damping.

    Non-float dtypes and values under ``min_bytes`` always ride the exact
    wire.  Past those structural fallbacks the policy holds one choice,
    int8 or exact, and ``observe`` feeds back what the last encode saw:

      * ``residual_ratio`` — mean |residual| over mean |carried delta|.
        Near zero for well-conditioned deltas; grows past ``residual_cap``
        when per-row outliers make the absmax scale coarse (quantisation is
        dropping real signal) → prefer exact.  ``None`` means the push rode
        the exact wire and produced **no quantisation evidence** — such
        observations never vote for int8 (that would guarantee a permanent
        exact↔int8 thrash on keys int8 genuinely mishandles); instead they
        count toward a periodic **re-probe**: after ``probe_after`` dense
        exact pushes, ``select`` routes a single push back onto int8 so its
        residual can re-qualify (or re-disqualify) the cheap wire.
      * ``density`` — nonzero fraction of the encoded delta.  Below
        ``min_density`` the delta is a handful of spot writes; per-row
        scales carry almost no information → prefer exact.

    A switch requires ``damping`` consecutive observations preferring the
    other wire; any confirming observation resets the streak, so an
    alternating workload doesn't thrash the wire (flip-flop damping)."""

    def __init__(self, *, min_bytes: int = INT8_WIRE_MIN_BYTES,
                 residual_cap: float = 0.25, min_density: float = 1.0 / 256,
                 damping: int = 3, probe_after: int = 8):
        self.min_bytes = min_bytes
        self.residual_cap = residual_cap
        self.min_density = min_density
        self.damping = max(1, damping)
        self.probe_after = max(1, probe_after)
        self._wire = "int8"
        self._streak = 0
        self._exact_obs = 0              # dense exact pushes since last probe
        self.flips = 0                   # damped wire switches (telemetry)

    @property
    def wire(self) -> str:
        """The adaptive choice for values past the structural fallbacks."""
        return self._wire

    def select(self, nbytes: int, dtype, *, probe: bool = True) -> str:
        """The wire to use now.  ``probe=False`` (pull-side selection) reads
        the current choice without consuming the int8 re-probe — a pull's
        encode produces no ``observe`` feedback, so spending the probe on it
        would starve the push wire's re-qualification."""
        if np.dtype(dtype).kind != "f" or nbytes < self.min_bytes:
            return "exact"
        if (probe and self._wire == "exact"
                and self._exact_obs >= self.probe_after):
            self._exact_obs = 0
            return "int8"                # one probe push; observe() decides
        return self._wire

    def observe(self, *, delta_absmax: float, density: float,
                residual_ratio: Optional[float] = None) -> None:
        if delta_absmax == 0.0:
            return                       # a no-op push teaches nothing
        if residual_ratio is None:
            # exact-wire push: quantisation quality unknown.  Sparse deltas
            # still vote exact; dense ones only advance the re-probe clock.
            if density < self.min_density:
                self._vote("exact")
            elif self._wire == "exact":
                self._exact_obs += 1
            return
        prefer_exact = (residual_ratio > self.residual_cap
                        or density < self.min_density)
        self._vote("exact" if prefer_exact else "int8")

    def _vote(self, want: str) -> None:
        if want == self._wire:
            self._streak = 0
            return
        self._streak += 1
        if self._streak >= self.damping:
            self._wire = want
            self._streak = 0
            self._exact_obs = 0
            self.flips += 1
