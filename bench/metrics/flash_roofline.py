"""Kernel ``flash_attention``: share of its roofline over its device time
in the traced window (``bench/roofline.py``; causal work from
``bench/flops``)."""
from bench import flops, roofline


def read(run):
    kv = run.config["num_key_value_heads"]

    def work(shape):
        B, H, S, D = shape
        return flops.flash_attention(S, H, kv, D, batch=B)

    return roofline.share(run, "flash_attention", work)
