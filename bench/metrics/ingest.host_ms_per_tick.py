"""Host time of the ingest frontier per tick: the program's
``ingest.pump`` and ``ingest.release`` spans (poll, dedup, merge,
watermark release) over the ticks of the traced window."""


def read(ctx):
    ms = [s["ms"] for s in ctx.spans
          if s["span"] in ("ingest.pump", "ingest.release")]
    if not ms or not ctx.n_ticks:
        return None
    return sum(ms) / ctx.n_ticks
