"""Readers of the program's own spans: ``weights_ms.fanout``,
``forward_ms.fanout``, ``compile_ms.fanout`` and ``lock_ms.fanout`` on
synthetic runs."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import reader  # noqa: E402


def span(name, t0, t1, call):
    return SimpleNamespace(name=name, t0=t0, t1=t1, call=call)


def call(cid, rc):
    return SimpleNamespace(cid=cid, rc=rc)


def traced(spans, calls):
    by_call = {}
    for s in spans:
        by_call.setdefault(s.call, []).append(s)
    return SimpleNamespace(spans=by_call, calls=calls)


def body(cid, t, compiles=()):
    """One call's body: weights 0.1 s, forward 0.3 s, two lock waits of
    0.05 and 0.15 s, compiles as given."""
    return [span("call.exec", t, t + 1.0, cid),
            span("serve.weights", t, t + 0.1, cid),
            span("serve.forward", t + 0.1, t + 0.4, cid),
            span("state.lock", t + 0.4, t + 0.45, cid),
            span("state.lock", t + 0.45, t + 0.6, cid)] + [
        span("jax.compile", t + a, t + b, cid) for a, b in compiles]


RUN = traced(body(1, 0.0, [(0.5, 0.6)]) + body(2, 1.0, [(0.5, 0.55)])
             + body(3, 2.0, [(0.5, 0.9)])       # call 3 failed
             + [span("jax.compile", 5.0, 6.0, None)],   # outside any call
             [call(1, 0), call(2, 0), call(3, 1), call(4, None)])


@pytest.mark.parametrize("name,want", [
    ("weights_ms.fanout", 100.0),
    ("forward_ms.fanout", 300.0),
    # 100 ms + 50 ms over the two served calls; failed and callless
    # compiles are left out
    ("compile_ms.fanout", 75.0),
    ("lock_ms.fanout", 200.0),
])
def test_mean_per_served_call(name, want):
    assert reader(name)(RUN) == pytest.approx(want)


def test_a_served_call_without_the_span_counts_as_zero():
    run = traced(body(1, 0.0), [call(1, 0), call(2, 0)])
    assert reader("weights_ms.fanout")(run) == pytest.approx(50.0)


def test_instrumented_program_that_compiles_nothing_reads_zero():
    run = traced(body(1, 0.0) + body(2, 1.0), [call(1, 0), call(2, 0)])
    assert reader("compile_ms.fanout")(run) == 0.0


@pytest.mark.parametrize("name", [
    "weights_ms.fanout", "forward_ms.fanout", "compile_ms.fanout",
    "lock_ms.fanout"])
def test_silent_where_the_program_records_no_such_span(name):
    # a program without the function-body spans (compiles unrecorded too)
    older = traced([span("call.exec", 0.0, 1.0, 1),
                    span("wire.push", 0.5, 0.9, 1)], [call(1, 0)])
    assert reader(name)(older) is None
    assert reader(name)(SimpleNamespace(spans=None, calls=[])) is None
    # nothing served
    assert reader(name)(traced(body(1, 0.0), [call(1, 1)])) is None
