"""Share of the measured window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, the mean over the
cell's chips (``bench.devtrace``)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t["window_ns"]:
        return None
    return 1.0 - t["busy_ns"] / t["window_ns"]
