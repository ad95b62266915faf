"""Exact rate and interval arithmetic of the benchmark."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import stats  # noqa: E402


def test_rate():
    assert stats.rate(90, 45.0) == 2.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_intervals():
    iv = [(0, 1), (0.5, 2), (3, 4), (3.5, 3.7)]
    assert stats.union_length(iv) == pytest.approx(3.0)
    assert stats.merge(iv) == [(0, 2), (3, 4)]
    assert stats.clip(iv, 1.5, 3.6) == [(1.5, 2), (3, 3.6), (3.5, 3.6)]
    assert stats.union_length([]) == 0.0


def test_rps_counts_served_calls_settled_inside_the_window():
    from types import SimpleNamespace
    from bench.harness import reader
    call = lambda rc, end: SimpleNamespace(rc=rc, end=end)
    run = SimpleNamespace(t0=10.0, t1=14.0, seconds=4.0, calls=[
        call(0, 10.5), call(0, 14.0), call(1, 12.0), call(0, 14.2),
        call(None, 0.0), call(0, 9.9)])
    assert reader("rps")(run) == 2 / 4.0
