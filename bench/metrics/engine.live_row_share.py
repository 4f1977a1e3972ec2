"""Share of the tables' rows that hold live state: the live rows of
every level and L0 table after expiry over the rows allocated, summed
over the slots and the ticks of the traced window (the counters the
program's ``tick`` span carries, from ``repro.core.engine.TickLoad``)."""


def read(ctx):
    ticks = [s for s in ctx.spans
             if s["span"] == "tick" and "capacity_rows" in s]
    cap = sum(s["capacity_rows"] for s in ticks)
    if not cap:
        return None
    return sum(s["live_rows"] for s in ticks) / cap
