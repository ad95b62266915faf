"""Execution configuration: knobs that change *how* a model runs, not *what*.

These are the hillclimb levers — kernel backend, remat policy, MoE dispatch
implementation, loss chunking, microbatching — kept separate from ModelConfig
so the same architecture can be lowered under different execution plans and
compared in the roofline table.
"""
from __future__ import annotations

from dataclasses import dataclass, replace


@dataclass(frozen=True)
class ExecConfig:
    backend: str = "auto"            # kernel dispatch: auto|xla|pallas|pallas_interpret
    remat: str = "full"              # "none" | "full" | "dots"
    scan_layers: bool = True         # lax.scan over stacked layer params
    moe_impl: str = "einsum"         # training: "einsum" (GShard dense
                                     # dispatch) | "sorted" (dropless gmm);
                                     # passes that do not train are dropless
    moe_group_size: int = 1024       # GShard dispatch group size (tokens)
    loss_chunk: int = 512            # seq chunk for fused unembed+xent (0 = off)
    attn_block_k: int = 512          # xla flash attention KV tile
    attn_buckets: int = 1            # causal q-bucketing: bucket i attends its
                                     # prefix only (4 -> 0.625x attention work)
    microbatches: int = 1            # gradient accumulation steps
    logits_f32: bool = True
    flash_for_prefill: bool = True   # blocked attention (vs naive ref) in prefill
    shard_activations: bool = True   # SP: residual stream seq-sharded over model
    accum_dtype: str = "float32"     # grad-accumulator dtype (bf16 for 1T cfg)

    def with_overrides(self, **kw) -> "ExecConfig":
        return replace(self, **kw)


DEFAULT_EXEC = ExecConfig()
