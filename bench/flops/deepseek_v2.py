"""Operations and bytes that DeepSeek-V2's served work needs, computed
from shapes, as in ``bench/flops``: latent attention's prefill in the
``flash_attention`` kernel, and the forward pass of one served request."""
from __future__ import annotations


def mla_flash(S: int, heads: int, qk_dim: int, v_dim: int, batch: int = 1,
              dtype_bytes: int = 2):
    """Causal attention over ``S`` positions with q and k of ``qk_dim``
    channels and v and the output of ``v_dim``: ``(flops, bytes)``.

    Scores take 2 operations per query-key pair per q/k channel, the
    weighted sum of values 2 per pair per v channel; HBM traffic is one
    read of q, k and v and one write of the output, every head of each."""
    pairs = S * (S + 1) // 2
    flops = 2 * batch * heads * pairs * (qk_dim + v_dim)
    elems = batch * S * heads * (2 * qk_dim + 2 * v_dim)
    return flops, elems * dtype_bytes


def forward(cfg: dict, S: int) -> int:
    """Operations of one served request: the forward pass over the
    ``S``-token prompt plus the unembedding of the last position.  Each MoE
    layer's routed experts count the slots routed to the experts held here,
    ``S * top_k * n_routed_experts / router_experts`` rows."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, r = cfg["v_head_dim"], cfg["kv_lora_rank"]
    f = cfg["moe_intermediate_size"]
    proj = 2 * S * (d * H * (nope + rope) + d * (r + rope)
                    + r * H * (nope + dv) + H * dv * d)
    attn = mla_flash(S, H, nope + rope, dv)[0]
    dense = 2 * S * d * cfg["intermediate_size"] * 3
    rows = S * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["router_experts"]
    moe = (2 * S * d * cfg["router_experts"]
           + 2 * S * d * f * cfg["n_shared_experts"] * 3
           + 2 * rows * d * f * 3)
    L, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return int(L * (proj + attn) + n_dense * dense + (L - n_dense) * moe
               + 2 * d * cfg["vocab_size"])
