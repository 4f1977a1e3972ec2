"""Subprocess helper: the live-row counters on 4 virtual devices.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4 (the parent
test, tests/test_tick_trace.py, sets it and asserts the LOAD-MESH-OK
sentinel).  A ``ShardedSearchService`` of 4 replicas x 2 slots and a
single-device service of one 8-slot group serve the same tenants over
the same stream: every tick's ``ServeInfo`` counters are equal, and the
mesh tick's psum'd ``MeshTickStats`` rows equal the host's sums.
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

from repro.core.join import JoinBackend  # noqa: E402
from repro.core.multi import SlotTickCache  # noqa: E402
from repro.runtime import (  # noqa: E402
    ContinuousSearchService,
    ShardedSearchService,
)

from test_tick_trace import BATCH, CAP, chain2, split_query, stream  # noqa: E402

FIELDS = ("live_rows", "capacity_rows", "live_pairs", "capacity_pairs",
          "swept_pairs")


def serve(svc, edges, on_tick=None):
    for q in (split_query(), split_query(), chain2(), chain2()):
        svc.register(q, 24)
    seen = []

    def tick(info):
        seen.append(tuple(getattr(info, f) for f in FIELDS))
        if on_tick is not None:
            on_tick(info)

    svc.serve_stream(edges, on_tick=tick, **BATCH)
    return seen


def main():
    edges = stream(160, seed=5)
    tc = SlotTickCache()
    single = serve(ContinuousSearchService(
        slots_per_group=8, backend=JoinBackend.REF, tick_cache=tc, **CAP),
        edges)
    mesh = ShardedSearchService(n_replicas=4, slots_per_replica=2,
                                backend=JoinBackend.REF, tick_cache=tc,
                                **CAP)

    def psum_agrees(info):
        stats = mesh.last_mesh_stats().values()
        assert sum(s["live_rows"] for s in stats) == info.live_rows
        assert sum(s["capacity_rows"] for s in stats) == info.capacity_rows

    sharded = serve(mesh, edges, psum_agrees)
    assert len(mesh._iter_groups()) == 2          # two structures
    assert sharded == single, (sharded, single)
    assert max(s[0] for s in single) > 0 and max(s[2] for s in single) > 0, single
    print("LOAD-MESH-OK", len(single))


if __name__ == "__main__":
    main()
