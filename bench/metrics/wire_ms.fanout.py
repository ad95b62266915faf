"""State tier (state/ddo.py, local.py, kv.py, wire.py): mean time per
served call inside the program's ``wire.*`` spans (the ``serve/stats``
pull and int8 push), as the union of each call's spans."""
from bench import stats


def read(run):
    if run.spans is None:
        return None
    per_call = [stats.union_length((s.t0, s.t1) for s in run.spans.get(c.cid, [])
                                   if s.name.startswith("wire."))
                for c in run.calls if c.rc == 0]
    return 1e3 * sum(per_call) / len(per_call) if per_call else None
