"""Host time reading each slot group's tick result back from the device
per tick: the program's ``tick.readback`` spans over the ticks of the
traced window."""


def read(ctx):
    ms = [s["ms"] for s in ctx.spans if s["span"] == "tick.readback"]
    if not ms or not ctx.n_ticks:
        return None
    return sum(ms) / ctx.n_ticks
