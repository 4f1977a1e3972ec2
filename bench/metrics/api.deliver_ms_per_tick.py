"""Host time in the match callbacks per tick, where the api layer builds
and delivers its ``Match`` records: the program's ``tick.callbacks``
spans over the ticks of the traced window."""


def read(ctx):
    ms = [s["ms"] for s in ctx.spans if s["span"] == "tick.callbacks"]
    if not ms or not ctx.n_ticks:
        return None
    return sum(ms) / ctx.n_ticks
