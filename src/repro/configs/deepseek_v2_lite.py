"""deepseek-v2-lite — latent attention (MLA) + fine-grained MoE, one chip's
share of an expert-parallel deployment.

Published (huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json): 27L
d2048, 16 heads of MLA (kv_lora 512, q/k 128 nope + 64 rope, v 128, no
q-LoRA), YaRN rope (factor 40 over 4096 positions), layer 0 dense (d_ff
10944), then 64 routed experts top-6 (softmax, greedy, gates not
renormalised) + 2 shared, per-expert d_ff 1408, vocab 102400, untied.

The cut: 8 chips share each MoE layer and this one holds experts 0-7 of
the 64 (the router keeps its 64 outputs and top-6); attention is
data-parallel, so every head is here at full width; the vocabulary is split
8 ways (12,800 rows of embedding and head); depth is the dense layer and 4
MoE layers, the other 22 being further pipeline stages.
"""
from repro.configs.base import ModelConfig, YarnScaling

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=5,                 # published 27
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    d_ff=10_944,
    dense_d_ff=10_944,
    moe_d_ff=1408,
    vocab_size=12_800,          # published 102400, split over 8 chips
    n_experts=64,
    experts_per_token=6,
    n_shared_experts=2,
    first_k_dense=1,
    norm_topk_prob=False,
    expert_shards=8,            # this chip holds experts 0-7
    rope_theta=10_000.0,
    yarn=YarnScaling(factor=40, original_max_position_embeddings=4096,
                     beta_fast=32, beta_slow=1, mscale=0.707,
                     mscale_all_dim=0.707),
    norm_type="rmsnorm",
    norm_eps=1e-6,
    mlp_act="silu",
    max_seq_len=163_840,
    source="huggingface.co/deepseek-ai/DeepSeek-V2-Lite (config.json), "
           "expert-parallel share of 8 chips",
)
