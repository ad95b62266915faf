"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode runs a kernel's body but not Mosaic's checks (block tiling,
VMEM limits), so each kernel is also compiled here for one chip of a
described ``v5e:2x2`` topology — no chip needed — and the compiled HLO must
hold the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import base64
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import gmm
from repro.kernels.ssd_scan import ssd
from repro.kernels.state_push import ops as state_push

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
SERVE_STATS_NUMEL = 151_936          # serve/stats: one f32 per qwen1.5 token id
FOUR_MB_NUMEL = (4 << 20) // 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _attn(arch):
    cfg = get_config(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.head_dim


def _flash(arch, S):
    H, K, D = _attn(arch)
    return (lambda q, k, v: flash_attention(q, k, v, backend="pallas"),
            [((1, S, H, D), BF16), ((1, S, K, D), BF16), ((1, S, K, D), BF16)])


def _mla_flash(arch, S):
    """Latent attention's prefill: q and k of nope + rope channels, v of
    v_head_dim."""
    cfg = get_config(arch)
    H, D, Dv = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    return (lambda q, k, v: flash_attention(q, k, v, backend="pallas",
                                            scale=D ** -0.5),
            [((1, S, H, D), BF16), ((1, S, H, D), BF16), ((1, S, H, Dv), BF16)])


def _decode(arch, B, S):
    H, K, D = _attn(arch)
    return (lambda q, k, v, n: decode_attention(q, k, v, n, backend="pallas"),
            [((B, H, D), BF16), ((B, S, K, D), BF16), ((B, S, K, D), BF16),
             ((B,), I32)])


def _ssd(arch, S):
    cfg = get_config(arch)
    H, P = cfg.ssm_nheads, cfg.ssm_headdim
    G, N = cfg.ssm_ngroups, cfg.ssm_state
    return (lambda x, dt, A, B, C, D: ssd(x, dt, A, B, C, D,
                                         chunk=cfg.ssm_chunk,
                                         backend="pallas"),
            [((1, S, H, P), BF16), ((1, S, H), F32), ((H,), F32),
             ((1, S, G, N), BF16), ((1, S, G, N), BF16), ((H,), F32)])


def _gmm(arch, rows):
    cfg = get_config(arch)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    return (lambda x, w, sizes: gmm(x, w, sizes, backend="pallas"),
            [((rows, d), BF16), ((E, d, f), BF16), ((E,), I32)])


def _gmm_held(arch, S):
    """The served forward's grouped matmul: every routed slot of an
    S-token prompt, over the experts this chip holds."""
    cfg = get_config(arch)
    E, d, f = cfg.experts_held, cfg.d_model, cfg.moe_d_ff
    return (lambda x, w, sizes: gmm(x, w, sizes, backend="pallas"),
            [((S * cfg.experts_per_token, d), BF16), ((E, d, f), BF16),
             ((E,), I32)])


def _quantize(n):
    return (lambda a, b: state_push.quantize_delta(a, b, backend="pallas"),
            [((n,), F32), ((n,), F32)])


def _encode(n):
    """The served push's encode: one executable, delta rows included."""
    return (lambda a, b: state_push._encode_pallas(
                a, b, interpret=False, with_residual=True),
            [((n,), F32), ((n,), F32)])


def _apply(n):
    rows = -(-n // 128)
    return (lambda g, q, s: state_push.apply_delta(g, q, s, backend="pallas"),
            [((n,), F32), ((rows, 128), jnp.int8), ((rows, 1), F32)])


CASES = {
    "flash-qwen1.5-0.5b-S512": lambda: _flash("qwen1.5-0.5b", 512),
    "flash-qwen3-4b-S1024": lambda: _flash("qwen3-4b", 1024),
    "flash-mla-deepseek-v2-lite-S2048": lambda: _mla_flash(
        "deepseek-v2-lite", 2048),
    "gmm-deepseek-v2-lite-S2048": lambda: _gmm_held("deepseek-v2-lite", 2048),
    "decode-qwen1.5-0.5b-B8-S2048": lambda: _decode("qwen1.5-0.5b", 8, 2048),
    "decode-qwen3-4b-B8-S2048": lambda: _decode("qwen3-4b", 8, 2048),
    "ssd-mamba2-130m-S512": lambda: _ssd("mamba2-130m", 512),
    "gmm-deepseek-moe-16b": lambda: _gmm("deepseek-moe-16b", 1024),
    "state_push-quantize-serve_stats": lambda: _quantize(SERVE_STATS_NUMEL),
    "state_push-apply-serve_stats": lambda: _apply(SERVE_STATS_NUMEL),
    "state_push-encode-serve_stats": lambda: _encode(SERVE_STATS_NUMEL),
    "state_push-quantize-4MB": lambda: _quantize(FOUR_MB_NUMEL),
    "state_push-apply-4MB": lambda: _apply(FOUR_MB_NUMEL),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, case


def _without_locations(hlo: str) -> str:
    """``hlo`` with each Mosaic kernel's serialized module replaced by its
    text without source locations, which name files and lines of the
    checkout and so differ between two copies of one program."""
    from jax._src.lib.mlir import ir

    def text(m):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            mod = ir.Module.parse(base64.b64decode(m.group(1)))
            return mod.operation.get_asm(enable_debug_info=False)

    return re.sub(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)", text, hlo)


# sha256 of ``_without_locations`` of the lowered flash_attention call at
# qwen1.5-0.5b's served shape (1, 384, 16 heads, 64 channels), taken at the
# commit before v got a width of its own (JAX 0.9.0)
QWEN_FLASH_HLO_SHA256 = (
    "70d0f605177f83302b1c31a4cd14a138024eb3d7f4b8377e66325d394f1900cf")


def test_equal_width_flash_attention_lowers_as_before(one_chip):
    """With q, k and v of one width the kernel and the HLO around it are
    the ones qwen1.5-0.5b's cell measured before latent attention."""
    args = [jax.ShapeDtypeStruct((1, 384, 16, 64), BF16, sharding=one_chip)
            ] * 3
    hlo = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, backend="pallas")).lower(*args).as_text()
    assert "tpu_custom_call" in hlo
    got = hashlib.sha256(_without_locations(hlo).encode()).hexdigest()
    assert got == QWEN_FLASH_HLO_SHA256


# sha256 of ``_without_locations`` of the device-replica int8 encode
# (``_encode_pallas`` over 151,936 f32, serve/stats' size), taken when the
# codec still carried a qmax argument and an fp8 branch (JAX 0.9.0)
SERVE_STATS_ENCODE_HLO_SHA256 = (
    "2861271b195f0c9054ee9e71a512f09a07f3ca4de39379465637663df30dc904")


def test_served_encode_lowers_as_before(one_chip):
    """The int8 encode of a device replica (host replicas, such as the
    served ``serve/stats``, encode on the host) is the same program, kernel
    and HLO around it, as before the codec lost its other tiers."""
    fn, shapes = _encode(SERVE_STATS_NUMEL)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).as_text()
    assert "tpu_custom_call" in hlo
    got = hashlib.sha256(_without_locations(hlo).encode()).hexdigest()
    assert got == SERVE_STATS_ENCODE_HLO_SHA256
