"""Nearest-rank p99 of the time the records released in the traced
window were held in the ingest frontier's reorder buffer, from the poll
that took each in to its release (the ``hold_ms`` of the program's
``ingest.release`` spans)."""

from bench import stats


def read(ctx):
    holds = [h for s in ctx.spans if s["span"] == "ingest.release"
             for h in s.get("hold_ms", ())]
    if not holds:
        return None
    return stats.percentile(holds, 0.99)
