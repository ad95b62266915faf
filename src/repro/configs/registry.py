"""Architecture registry: ``--arch <id>`` lookup, smoke-config reduction.

``get_config(arch_id)`` returns the full published config; ``smoke_config(arch_id)``
returns a reduced config of the same family (small widths, few experts, tiny vocab)
used by the CPU smoke tests.  Full configs are only ever *lowered* (ShapeDtypeStruct,
no allocation) via the dry-run.
"""
from __future__ import annotations

from typing import Dict, List

from repro.configs.base import ModelConfig, ShapeConfig, SHAPES, shape_applicable

from repro.configs.qwen15_05b import CONFIG as _QWEN15
from repro.configs.starcoder2_7b import CONFIG as _STARCODER2
from repro.configs.granite3_8b import CONFIG as _GRANITE3
from repro.configs.qwen3_4b import CONFIG as _QWEN3
from repro.configs.zamba2_12b import CONFIG as _ZAMBA2
from repro.configs.whisper_tiny import CONFIG as _WHISPER
from repro.configs.deepseek_moe_16b import CONFIG as _DSMOE
from repro.configs.deepseek_v2_lite import CONFIG as _DSV2LITE
from repro.configs.kimi_k2 import CONFIG as _KIMI
from repro.configs.mamba2_130m import CONFIG as _MAMBA2
from repro.configs.internvl2_2b import CONFIG as _INTERNVL

ARCHS: Dict[str, ModelConfig] = {
    c.name: c
    for c in (
        _QWEN15, _STARCODER2, _GRANITE3, _QWEN3, _ZAMBA2,
        _WHISPER, _DSMOE, _KIMI, _MAMBA2, _INTERNVL, _DSV2LITE,
    )
}


def arch_ids() -> List[str]:
    return list(ARCHS.keys())


def get_config(arch_id: str) -> ModelConfig:
    try:
        return ARCHS[arch_id]
    except KeyError:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {', '.join(ARCHS)}") from None


def get_shape(shape_id: str) -> ShapeConfig:
    try:
        return SHAPES[shape_id]
    except KeyError:
        raise KeyError(
            f"unknown shape {shape_id!r}; available: {', '.join(SHAPES)}") from None


def cells(include_skipped: bool = False):
    """Yield every assigned (arch, shape) cell, with applicability."""
    for arch_id, cfg in ARCHS.items():
        for shape_id, shape in SHAPES.items():
            ok, reason = shape_applicable(cfg, shape)
            if ok or include_skipped:
                yield arch_id, shape_id, ok, reason


def smoke_config(arch_id: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests (one fwd/train step)."""
    cfg = get_config(arch_id)
    kw = dict(
        name=f"{cfg.name}-smoke",
        n_layers=min(cfg.n_layers, 3),
        d_model=64,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=257,
        max_seq_len=1 << 12,
    )
    if cfg.n_heads:
        kw.update(
            n_heads=4,
            n_kv_heads=max(1, 4 * cfg.n_kv_heads // cfg.n_heads),
            head_dim=16,
        )
    if cfg.n_experts:
        kw.update(n_experts=8, experts_per_token=2,
                  n_shared_experts=min(cfg.n_shared_experts, 1),
                  moe_d_ff=32, d_ff=32, dense_d_ff=96,
                  first_k_dense=min(cfg.first_k_dense, 1),
                  capacity_factor=8.0,   # effectively dropless at smoke scale
                  expert_shards=min(cfg.expert_shards, 2))
    if cfg.kv_lora_rank:
        kw.update(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                  v_head_dim=16)
    if cfg.ssm_state:
        kw.update(ssm_state=16, ssm_headdim=16, ssm_chunk=16)
    if cfg.family == "hybrid":
        kw.update(attn_every=2, n_layers=4)
    if cfg.family == "encdec":
        kw.update(n_enc_layers=2, n_frames=8, n_layers=2)
    if cfg.family == "vlm":
        kw.update(n_image_tokens=4)
    return cfg.with_overrides(**kw)


def smoke_shape(kind: str = "train") -> ShapeConfig:
    """Tiny shape for smoke tests."""
    if kind == "train":
        return ShapeConfig("smoke_train", "train", 32, 2)
    if kind == "prefill":
        return ShapeConfig("smoke_prefill", "prefill", 32, 2)
    return ShapeConfig("smoke_decode", "decode", 32, 2)
