"""Device time of the cross-replica collectives per tick: the
all-reduce, all-gather, reduce-scatter, all-to-all and
collective-permute ops of the trace, in their synchronous and their
asynchronous ``-start``/``-done`` forms, over the ticks the traced
window holds, the mean over the cell's chips."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
OPCODES = frozenset(f"{op}{form}" for op in COLLECTIVES
                    for form in ("", "-start", "-done"))


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.n_ticks:
        return None
    ns = sum(v for k, v in t["by_opcode_ns"].items() if k in OPCODES)
    return ns / 1e6 / ctx.n_ticks if ns else None
