"""Host time of match readback and delivery per tick: the program's
``tick.deliver`` span over the ticks of the traced window."""


def read(ctx):
    ms = [s["ms"] for s in ctx.spans if s["span"] == "tick.deliver"]
    if not ms or not ctx.n_ticks:
        return None
    return sum(ms) / ctx.n_ticks
