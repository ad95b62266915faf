"""Reduce a profiler trace of the window to device busy time, per-kernel
device events and a breakdown of where device time and idle gaps went.

The trace comes from ``jax.profiler`` (``*.xplane.pb``), read with
``jax.profiler.ProfileData``.  Device operations are the events of the
``XLA Ops`` line of each ``/device:TPU:<n>`` plane, named by their HLO
text.  Control-flow operations (a layer scan's ``while``) enclose the
operations they run, so busy time is the union of all events and each
operation's own time is its duration less the events nested in it.  The
profiler's clock and the runtime's (``time.perf_counter``) are tied
together by one marker annotation that the harness records at a known
``perf_counter_ns``.
"""
from __future__ import annotations

import glob
import re
from pathlib import Path

from bench import stats

MARK = "bench.window_mark"
OPS_LINE = "XLA Ops"
TOP = 10
_LAYOUT = re.compile(r"\{[^{}]*\}")
_HEAD = re.compile(r"^%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def load(trace_dir):
    import jax
    pbs = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                           recursive=True))
    if not pbs:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(pbs[-1])


def mark_ns(pd) -> float:
    for p in pd.planes:
        for line in p.lines:
            for e in line.events:
                if e.name == MARK:
                    return e.start_ns
    raise ValueError(f"no {MARK} event in the trace")


def device_ops(plane) -> list:
    """``(name, start_ns, end_ns)`` of every device operation."""
    for line in plane.lines:
        if line.name == OPS_LINE:
            return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return []


def label(name: str, kernels: dict) -> str:
    """A short name for an operation: the kernel it belongs to, or its
    opcode, with its result type and without layouts."""
    for k, pat in kernels.items():
        if re.search(pat, name):
            m = _HEAD.match(name)
            return f"{k} {_LAYOUT.sub('', m.group(2)) if m else ''}".strip()
    m = _HEAD.match(name)
    if not m:
        return name[:120]
    return f"{m.group(3)} {_LAYOUT.sub('', m.group(2))}"[:120]


def self_times(ops: list) -> list:
    """Each operation's duration less the operations nested inside it."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [e - s for _, s, e in ops]
    stack: list = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def reduce(trace_dir, mark_perf_ns: int, t0: float, t1: float, run=None,
           kernels=None) -> dict:
    """Busy and window seconds (busy averaged over the chips traced),
    device self time by operation, each kernel's events, and the longest
    idle gaps named by what the runtime's calls were doing in them.

    ``t0``/``t1`` bound the window on the ``perf_counter`` clock; each
    entry of ``kernels`` maps a kernel's name to a pattern of its
    operations' HLO text (``bench.flops.KERNEL_OPS`` by default)."""
    from bench import flops
    kernels = flops.KERNEL_OPS if kernels is None else kernels
    pd = load(trace_dir)
    off = mark_ns(pd) - mark_perf_ns            # trace ns = perf ns + off
    lo, hi = t0 * 1e9 + off, t1 * 1e9 + off
    planes = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy, by_label, gaps = 0.0, {}, []
    found = {k: [] for k in kernels}
    for plane in planes:
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in device_ops(plane)
               if min(e, hi) > max(s, lo)]
        merged = stats.merge((s, e) for _, s, e in ops)
        busy += sum(e - s for s, e in merged)
        for (n, s, e), own in zip(ops, self_times(ops)):
            lab = label(n, kernels)
            by_label[lab] = by_label.get(lab, 0.0) + own / 1e9
            for k, pat in kernels.items():
                if re.search(pat, n):
                    found[k].append(((s - off) / 1e9, (e - s) / 1e9, n))
        edges = [lo] + [x for se in merged for x in se] + [hi]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    n = len(planes)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "t0": t0, "t1": t1,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "kernels": found,
        "device_ops": [[k, v / n] for k, v in
                       sorted(by_label.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[owner(run, (a - off) / 1e9, (b - off) / 1e9),
                       (b - a) / 1e9] for a, b in gaps[:TOP]],
    }


def owner(run, a: float, b: float) -> str:
    """What the runtime's calls spent most of ``[a, b]`` (perf clock) in:
    the innermost of the program's spans that covers at least half the gap
    on some call, else the span with the largest overlap, else
    ``no call running``."""
    per: dict = {}
    for spans in (run.spans or {}).values() if run is not None else ():
        for s in spans:
            ov = min(s.t1, b) - max(s.t0, a)
            if ov > 0:
                per[s.name] = max(per.get(s.name, 0.0), ov)
    if not per:
        return "no call running"
    covering = [n for n, t in per.items() if t >= 0.5 * (b - a)]
    if covering:
        return min(covering, key=_depth)
    return max(per, key=per.get)


_INNER_FIRST = ("wire.", "call.restore", "call.reset", "call.exec")


def _depth(name: str) -> int:
    return next((i for i, p in enumerate(_INNER_FIRST) if name.startswith(p)),
                len(_INNER_FIRST))
