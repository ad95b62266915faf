"""Shared helpers for the Pallas kernels.

All kernels target TPU (pl.pallas_call + BlockSpec VMEM tiling) and are validated
on CPU in ``interpret=True`` mode, which executes the kernel body with the pure-JAX
interpreter.  ``default_backend()`` picks the dispatch used by the model code:

  * ``"xla"``              — pure-jnp blocked implementation (lowers everywhere;
                             used by the dry-run so cost_analysis sees real HLO)
  * ``"pallas"``           — compiled Pallas kernel (TPU)
  * ``"pallas_interpret"`` — Pallas interpreter (CPU correctness tests)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro import cancellation

BACKENDS = ("xla", "pallas", "pallas_interpret")

NEG_INF = float(-1e30)   # large-negative instead of -inf: keeps bf16 softmax NaN-free


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def resolve_backend(backend: str | None) -> str:
    # every kernel dispatch wrapper passes through here, making it the
    # time-sliced cancellation checkpoint for long pure-compute loops that
    # never touch a host-interface call (cost: one thread-local read)
    cancellation.checkpoint()
    b = backend or "auto"
    if b == "auto":
        return default_backend()
    if b not in BACKENDS:
        raise ValueError(f"backend {b!r} not in {BACKENDS}")
    return b


def interpret_mode(backend: str) -> bool:
    return backend == "pallas_interpret"


def tpu_compiler_params(dimension_semantics):
    """Pallas TPU compiler params carrying the grid's dimension semantics."""
    return pltpu.CompilerParams(dimension_semantics=tuple(dimension_semantics))


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pad_axis_to(x: jnp.ndarray, axis: int, size: int, value=0.0) -> jnp.ndarray:
    """Pad ``axis`` of ``x`` up to ``size`` (no-op if already there)."""
    cur = x.shape[axis]
    if cur == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, size - cur)
    return jnp.pad(x, pads, constant_values=value)
