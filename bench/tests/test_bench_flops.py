"""Operation and byte counts of ``bench/flops`` against hand counts."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops  # noqa: E402


def test_flash_attention_hand_count():
    # S=4 causal: 10 query-key pairs; H=2, D=8: scores and values 2 ops each
    f, b = flops.flash_attention(4, heads=2, kv_heads=1, head_dim=8)
    assert f == 4 * 2 * 8 * 10
    # q and o: 4*2*8 each, k and v: 4*1*8 each, bf16
    assert b == 2 * (2 * 64 + 2 * 32)


def test_qwen2_forward_hand_count():
    cfg = {"reference": "qwen2", "hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 1, "intermediate_size": 16,
           "num_hidden_layers": 3, "vocab_size": 11}
    S = 5
    proj = 2 * S * 8 * (8 + 2 * 4) + 2 * S * 8 * 8
    attn = 4 * 2 * 4 * 15
    mlp = 2 * S * 8 * 16 * 3
    assert flops.forward(cfg, S) == 3 * (proj + attn + mlp) + 2 * 8 * 11


def test_unembedding_counts_one_position():
    cfg = {"reference": "qwen2", "hidden_size": 8, "num_attention_heads": 2,
           "num_key_value_heads": 2, "intermediate_size": 16,
           "num_hidden_layers": 1, "vocab_size": 1000}
    grow = flops.forward(cfg, 2) - flops.forward(cfg, 1)
    assert grow < 2 * 8 * 1000
