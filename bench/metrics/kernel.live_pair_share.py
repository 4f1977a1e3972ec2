"""Share of the row pairs the join kernels visit that can match: over
every join call of every slot, live rows on side A times live rows on
side B, over the rows each side sweeps, summed over the ticks of the
traced window (the counters the program's ``tick`` span carries)."""


def read(ctx):
    ticks = [s for s in ctx.spans
             if s["span"] == "tick" and "capacity_pairs" in s]
    cap = sum(s["capacity_pairs"] for s in ticks)
    if not cap:
        return None
    return sum(s["live_pairs"] for s in ticks) / cap
