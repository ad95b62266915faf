"""Calls settled with return code 0 inside the window, per second of it."""
from bench import stats


def read(run):
    n = sum(1 for c in run.calls if c.rc == 0 and run.t0 <= c.end <= run.t1)
    return stats.rate(n, run.seconds)
