"""Device time of every XLA op other than the Pallas kernels per tick:
the slot tick's append, compaction, expiry and label-scan fusions, over
the ticks the traced window holds, the mean over the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.n_ticks:
        return None
    ns = sum(v for k, v in t["by_opcode_ns"].items() if k != "custom-call")
    return ns / 1e6 / ctx.n_ticks if ns else None
