"""Whole served forward pass of DeepSeek-V2: operations the calls settled
in the traced window required (``bench/flops/deepseek_v2.py``: the routed
experts at the slots routed to the experts held here, the unembedding of
the last position only), over the window times the chip's bf16 peak."""
from bench.flops import deepseek_v2


def read(run):
    if not run.trace or not run.peaks:
        return None
    lo, hi = run.trace["t0"], run.trace["t1"]
    work = sum(deepseek_v2.forward(run.config, c.length) for c in run.calls
               if c.rc == 0 and lo <= c.end <= hi)
    if work == 0:
        return None
    return 100.0 * work / ((hi - lo) * run.peaks["bf16_flops_per_s"])
