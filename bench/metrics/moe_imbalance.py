"""Expert layer (models/moe.py): per served call, the slots routed to its
busiest held expert in any MoE layer over the mean slots per held expert
and layer, read from the ``moe_rows`` and ``moe_rows_max`` the program
puts on the call's ``serve.forward`` span; the mean over served calls.
1.0 is even routing.  Silent for a program whose spans carry no routing."""


def read(run):
    if run.spans is None:
        return None
    ratios = []
    for c in run.calls:
        if c.rc != 0:
            continue
        for s in run.spans.get(c.cid, ()):
            if s.name != "serve.forward" or not s.tags \
                    or "moe_rows" not in s.tags:
                continue
            rows = [n for layer in s.tags["moe_rows"] for n in layer]
            mean = sum(rows) / len(rows)
            if mean > 0:
                ratios.append(s.tags["moe_rows_max"] / mean)
    return sum(ratios) / len(ratios) if ratios else None
