"""Whole served forward pass: operations the calls settled in the traced
window required (the forward over each prompt plus the unembedding of its
last position; ``bench/flops``), over the window times the chip's bf16
peak.  The unembedding of every other position, which ``infer`` computes
and drops, counts as waste."""
from bench import flops


def read(run):
    if not run.trace or not run.peaks:
        return None
    lo, hi = run.trace["t0"], run.trace["t1"]
    work = sum(flops.forward(run.config, c.length) for c in run.calls
               if c.rc == 0 and lo <= c.end <= hi)
    if work == 0:
        return None
    return 100.0 * work / ((hi - lo) * run.peaks["bf16_flops_per_s"])
