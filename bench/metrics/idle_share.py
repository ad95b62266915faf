"""Device: share of the traced window, in percent, in which no operation
ran on the chip (1 - busy / window), from the profiler trace."""


def read(run):
    if not run.trace or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
