"""State tier (state/ddo.py, local.py, kv.py, wire.py): mean time per
served call inside the program's ``state.lock`` spans, the waits for the
``serve/stats`` replica's write lock in its pulls and base snapshots,
behind pushes that hold it through their encode."""
from bench import program_spans


def read(run):
    return program_spans.served_mean_ms(run, "state.lock")
