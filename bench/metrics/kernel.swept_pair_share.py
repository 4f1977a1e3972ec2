"""Share of the row pairs a whole sweep would visit that the join
kernels sweep: over every join call of every slot, the rows below side
A's live extent times those below side B's, over the rows each side
holds, summed over the ticks of the traced window (the counters the
program's ``tick`` span carries)."""


def read(ctx):
    ticks = [s for s in ctx.spans
             if s["span"] == "tick" and "swept_pairs" in s]
    cap = sum(s["capacity_pairs"] for s in ticks)
    if not cap:
        return None
    return sum(s["swept_pairs"] for s in ticks) / cap
