"""Fused two-tier state push kernels.

Re-exports are lazy (PEP 562): ``ops``/``ref`` import jax at module scope,
but ``hostcodec`` — the numpy-only host wire codec — must stay importable
without jax (``state/wire.py`` imports it at module scope and
``scripts/check_jax_pin.py`` exercises it before touching jax).
"""

# the int8 wire's largest code: every encode quantises to [-QMAX, QMAX]
QMAX = 127.0

_OPS = ("apply_delta", "apply_pull", "dequantize", "encode_pull",
        "encode_quant", "quantize_delta", "wire_nbytes")
_REF = ("apply_delta_ref", "quantize_delta_ref")

__all__ = ["QMAX"] + list(_OPS) + list(_REF)


def __getattr__(name):
    if name in _OPS:
        from repro.kernels.state_push import ops
        return getattr(ops, name)
    if name in _REF:
        from repro.kernels.state_push import ref
        return getattr(ref, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
