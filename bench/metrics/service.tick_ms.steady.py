"""Median tick time as the program reports it (``tick.latency_ms``: from
the dispatch of a tick to its barrier), over the ticks completed inside
the window."""

import statistics


def read(ctx):
    if not ctx.tick_ms:
        return None
    return statistics.median(ctx.tick_ms)
