"""A kernel's share of its roofline over the traced window.

For each device event of the kernel, the shape is read from the event's
HLO text (the kernel's first 4-d bfloat16 result, heads-major
``(batch, heads, positions, channels)``), the operations and
bytes the work needs come from ``bench/flops``, and the least time the
chip could take is the larger of operations over peak FLOP/s and bytes
over peak bandwidth.  The share is that least time, summed, over the
events' measured time, summed."""
from __future__ import annotations

import re

SHAPE = re.compile(r"bf16\[(\d+),(\d+),(\d+),(\d+)\]")


def event_shape(name: str):
    m = SHAPE.search(name)
    return tuple(int(x) for x in m.groups()) if m else None


def share(run, kernel: str, work) -> float | None:
    """``work(shape) -> (flops, bytes)`` for one event; ``None`` where the
    trace holds no event of ``kernel`` or none with a readable shape."""
    if not run.trace or not run.peaks:
        return None
    need = spent = 0.0
    for _start, dur, name in run.trace["kernels"].get(kernel, []):
        shape = event_shape(name)
        if shape is None or dur <= 0:
            continue
        f, b = work(shape)
        need += max(f / run.peaks["bf16_flops_per_s"],
                    b / run.peaks["hbm_bytes_per_s"])
        spent += dur
    if spent == 0:
        return None
    return 100.0 * need / spent
