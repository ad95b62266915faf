"""The program's own spans in a trace recorded on the chip.

``data/qwen05b-fanout-spans/`` holds the profiler trace of a three-second
traced run of the ``qwen05b-fanout`` cell (384-token prompts) on one TPU v5
lite chip.  The armed tracer mirrors its interval spans into the trace as
host annotations of the same name carrying ``call=<id>``, so the trace
alone names what the host was doing in each idle gap of the device.
Reading it needs only ``jax.profiler.ProfileData``."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import trace_reduce  # noqa: E402

TRACE = Path(__file__).resolve().parent / "data" / "qwen05b-fanout-spans"
MIRRORED = ("call.exec", "serve.weights", "serve.forward", "wire.push",
            "wire.pull", "wire.encode", "state.lock")
NEW = ("serve.", "wire.encode", "state.lock")
# the program's span names, innermost first
INNER_FIRST = ("wire.encode", "wire.", "state.lock", "serve.weights",
               "serve.forward", "call.restore", "call.reset", "call.exec")


@pytest.fixture(scope="module")
def recorded():
    """The marker's time and every mirrored host event as ``(name, start,
    end, call)`` in trace nanoseconds."""
    pd = trace_reduce.load(TRACE)
    events = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in MIRRORED:
                    call = dict(e.stats).get("call")
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns, call))
    return trace_reduce.mark_ns(pd), events


def test_mirrored_spans_carry_their_call(recorded):
    _, events = recorded
    names = {name for name, _, _, _ in events}
    assert {"serve.weights", "serve.forward", "wire.push"} <= names, names
    assert all(call is not None for _, _, _, call in events)
    by_call = {}
    for name, s, e, call in events:
        by_call.setdefault(call, {}).setdefault(name, []).append((s, e))
    whole = [c for c in by_call.values()
             if "serve.weights" in c and "serve.forward" in c]
    assert whole
    for c in whole:         # one weights enqueue, then one forward
        (w0, w1), = c["serve.weights"]
        (f0, f1), = c["serve.forward"]
        assert w0 < w1 <= f0 < f1


def test_idle_gaps_are_owned_by_the_programs_spans(recorded, monkeypatch):
    """Spans rebuilt from the trace's host events, on the marker's clock,
    name the device's idle gaps once the new names rank inside
    ``call.exec``."""
    mark, events = recorded
    spans = {}
    for name, s, e, call in events:
        spans.setdefault(call, []).append(SimpleNamespace(
            name=name, t0=(s - mark) / 1e9, t1=(e - mark) / 1e9))
    monkeypatch.setattr(trace_reduce, "_INNER_FIRST", INNER_FIRST)
    r = trace_reduce.reduce(TRACE, 0, 0.0, 1e3, SimpleNamespace(spans=spans))
    owners = [owner for owner, _ in r["idle_gaps"]]
    assert any(o.startswith(NEW) for o in owners), owners
    assert 0 < r["busy_s"] < r["window_s"]
