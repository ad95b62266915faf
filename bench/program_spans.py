"""Per-call readings of the program's own spans (``repro.telemetry.spans``)
in a traced run: ``run.spans`` maps each call id to the spans recorded on
its thread while it ran."""
from __future__ import annotations


def served_mean_ms(run, name: str, witness: str = None):
    """Time inside the spans named ``name`` per call settled with return
    code 0, in ms: their summed length over every served call, divided by
    the number of served calls.  ``None`` when the run recorded no span
    named ``witness`` (``name`` by default), as in a program without that
    instrumentation, or served no call."""
    if run.spans is None:
        return None
    witness = witness or name
    if not any(s.name == witness for spans in run.spans.values()
               for s in spans):
        return None
    served = [c.cid for c in run.calls if c.rc == 0]
    if not served:
        return None
    total = sum(s.t1 - s.t0 for cid in served
                for s in run.spans.get(cid, ()) if s.name == name)
    return 1e3 * total / len(served)
