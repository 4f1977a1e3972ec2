"""How unevenly the replicas hold live table rows: the largest replica's
live rows over the mean replica's, minus 1, each summed over the ticks
of the traced window (the ``replica_live_rows`` the program's ``tick``
span carries on a mesh: the live rows of each replica's slot block)."""


def read(ctx):
    ticks = [s["replica_live_rows"] for s in ctx.spans
             if s["span"] == "tick" and "replica_live_rows" in s]
    if not ticks:
        return None
    per = [sum(col) for col in zip(*ticks)]
    mean = sum(per) / len(per)
    return max(per) / mean - 1.0 if mean else None
