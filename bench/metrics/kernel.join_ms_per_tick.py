"""Device time of the Pallas join kernels per tick: the ``custom-call``
ops of the trace (``compat_join_pairs`` / ``compat_mask``), over the
ticks the traced window holds, the mean over the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.n_ticks:
        return None
    ns = t["by_opcode_ns"].get("custom-call")
    if not ns:
        return None
    return ns / 1e6 / ctx.n_ticks
