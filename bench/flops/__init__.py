"""Operations and bytes that the work needs, computed from shapes.

These are the counts the algorithm requires, not what an implementation
happens to execute: causal attention counts only the query-key pairs at or
below the diagonal, and a served forward pass counts the unembedding of the
last position only (the one token a call returns).  Each kernel's count is
per call of that kernel; each model's count is per served request.
"""
from __future__ import annotations


def flash_attention(S: int, heads: int, kv_heads: int, head_dim: int,
                    batch: int = 1, dtype_bytes: int = 2):
    """Causal self-attention over ``S`` positions: ``(flops, bytes)``.

    Scores and the weighted sum of values each take 2 operations per
    query-key pair per channel; HBM traffic is one read of q, k, v and one
    write of the output."""
    pairs = S * (S + 1) // 2
    flops = 4 * batch * heads * head_dim * pairs
    elems = batch * S * head_dim * (2 * heads + 2 * kv_heads)
    return flops, elems * dtype_bytes


def qwen2_forward(cfg: dict, S: int) -> int:
    """Operations of one served Qwen2-family request: the forward pass over
    the ``S``-token prompt plus the unembedding of the last position."""
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    K = cfg["num_key_value_heads"]
    D = d // H
    f = cfg["intermediate_size"]
    proj = 2 * S * d * (H * D + 2 * K * D) + 2 * S * H * D * d
    attn = flash_attention(S, H, K, D)[0]
    mlp = 2 * S * d * f * 3
    return cfg["num_hidden_layers"] * (proj + attn + mlp) \
        + 2 * d * cfg["vocab_size"]


FORWARD = {"qwen2": qwen2_forward}


def forward(cfg: dict, S: int) -> int:
    return FORWARD[cfg["reference"]](cfg, S)


# How each kernel's device operations read in the trace.  Pallas kernels
# appear as ``tpu_custom_call`` operations named by their HLO text, without
# the kernel's name, so each is told apart by its signature:
# flash_attention returns one 4-d bf16 array from three (q, k, v).
_ARR = r"bf16\[\d+,\d+,\d+,\d+\]\{[^}]*\}"
_CALL = r'custom_call_target="tpu_custom_call"'
KERNEL_OPS = {
    "flash_attention": (rf"^%[\w.\-]+ = {_ARR} custom-call\({_ARR} %[\w.\-]+, "
                        rf"{_ARR} %[\w.\-]+, {_ARR} %[\w.\-]+\), {_CALL}"),
}
