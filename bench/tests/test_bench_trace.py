"""The trace reduction, on a small trace recorded on the chip.

``data/qwen05b-fanout/`` holds the profiler trace of a one-second traced
run of the ``qwen05b-fanout`` cell on one TPU v5 lite chip, recorded under
an earlier mix of 32-, 128- and 512-token prompts.  Reading it
needs only ``jax.profiler.ProfileData``; nothing loads the TPU library."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import flops, roofline, stats, trace_reduce  # noqa: E402

TRACE = Path(__file__).resolve().parent / "data" / "qwen05b-fanout"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def recorded():
    pd = trace_reduce.load(TRACE)
    mark = trace_reduce.mark_ns(pd)
    # window: from the marker to the end of the trace, on the marker's clock
    reduced = trace_reduce.reduce(TRACE, 0, 0.0, 1e3)
    return pd, mark, reduced


def test_busy_is_the_union_of_device_operations(recorded):
    pd, mark, r = recorded
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = [(s, e) for _, s, e in trace_reduce.device_ops(plane)
           if e > mark]
    want = stats.union_length(stats.clip(ops, mark, mark + 1e12)) / 1e9
    assert r["busy_s"] == pytest.approx(want)
    assert 0 < r["busy_s"] < r["window_s"]


def test_self_times_add_up_to_busy(recorded):
    pd, mark, r = recorded
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = [(n, max(s, mark), e) for n, s, e in trace_reduce.device_ops(plane)
           if e > mark]
    assert sum(trace_reduce.self_times(ops)) / 1e9 == pytest.approx(
        r["busy_s"], rel=1e-6)
    labels = [name for name, _ in r["device_ops"]]
    assert any(name.startswith("flash_attention bf16[1,16,") for name in labels)


def test_flash_events_come_24_to_a_forward_pass(recorded):
    _, _, r = recorded
    events = r["kernels"]["flash_attention"]
    assert events and len(events) % 24 == 0
    lengths = {roofline.event_shape(name)[2] for _, _, name in events}
    assert lengths <= {32, 128, 512}


def test_roofline_share_is_a_share(recorded):
    _, _, r = recorded
    run = SimpleNamespace(trace=r, peaks=PEAKS)
    share = roofline.share(run, "flash_attention", lambda s: flops.flash_attention(
        s[2], s[1], 16, s[3], batch=s[0]))
    assert 0 < share < 100


def test_roofline_of_a_kernel_not_in_the_trace_is_silent(recorded):
    _, _, r = recorded
    run = SimpleNamespace(trace=dict(r, kernels={"flash_attention": []}),
                          peaks=PEAKS)
    assert roofline.share(run, "flash_attention", lambda s: (1, 1)) is None


def test_self_times_of_nested_operations():
    ops = [("loop", 0, 10), ("a", 1, 3), ("b", 4, 9), ("c", 5, 6), ("d", 12, 13)]
    assert trace_reduce.self_times(ops) == [3, 2, 4, 1, 1]


def test_idle_gap_goes_to_the_innermost_span():
    span = lambda name, t0, t1: SimpleNamespace(name=name, t0=t0, t1=t1)
    run = SimpleNamespace(spans={
        1: [span("call.queue", 0, 5), span("call.exec", 5, 9)],
        2: [span("call.exec", 0, 10), span("wire.push", 2, 6)],
    })
    assert trace_reduce.owner(run, 2.5, 5.5) == "wire.push"
    assert trace_reduce.owner(run, 6.5, 8.5) == "call.exec"
    assert trace_reduce.owner(run, 11, 12) == "no call running"
