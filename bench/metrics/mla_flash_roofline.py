"""Kernel ``flash_attention`` under latent attention: share of its roofline
over its device time in the traced window (``bench/roofline.py``).  Each
event's work comes from ``bench/flops/deepseek_v2.py`` with the q/k and v
widths of the configuration file (nope + rope, and v_head_dim), not from
the event's shape, so channels padded in the kernel count as waste."""
from bench import roofline
from bench.flops import deepseek_v2


def read(run):
    cfg = run.config
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]

    def work(shape):
        B, H, S, _ = shape
        return deepseek_v2.mla_flash(S, H, qk, cfg["v_head_dim"], batch=B)

    return roofline.share(run, "flash_attention", work)
