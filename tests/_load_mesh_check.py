"""Subprocess helper: the live-row counters on 4 virtual devices.

Run with XLA_FLAGS=--xla_force_host_platform_device_count=4 (the parent
test, tests/test_tick_trace.py, sets it and asserts the LOAD-MESH-OK
sentinel).  A ``ShardedSearchService`` of 4 replicas x 2 slots and a
single-device service of one 8-slot group serve the same tenants over
the same stream: every tick's ``ServeInfo`` counters are equal, and the
mesh tick's psum'd ``MeshTickStats`` rows equal the host's sums.  With a
tracer on the mesh, each ``tick`` span's ``mesh_matches`` and
``mesh_live_rows``, and the sums of its ``mesh.collectives`` events,
equal the host's counts, and its ``replica_live_rows`` /
``replica_capacity_rows`` equal the rows of each replica's slot block
counted on the host from the slot states.
"""

import json
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402

from repro.core.join import JoinBackend  # noqa: E402
from repro.core.multi import SlotTickCache  # noqa: E402
from repro.obs import memory_tracer  # noqa: E402
from repro.runtime import (  # noqa: E402
    ContinuousSearchService,
    ShardedSearchService,
)

from test_tick_trace import BATCH, CAP, chain2, split_query, stream  # noqa: E402

FIELDS = ("live_rows", "capacity_rows", "live_pairs", "capacity_pairs",
          "swept_pairs")
STATS = {"gid", "n_matches", "n_overflow", "t_clock", "live_rows",
         "capacity_rows"}


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

    mesh.tracer, buf = memory_tracer()
    per_replica = []

    def psum_agrees(info):
        stats = mesh.last_mesh_stats().values()
        assert sum(s["live_rows"] for s in stats) == info.live_rows
        assert sum(s["capacity_rows"] for s in stats) == info.capacity_rows
        per_replica.append(replica_rows(mesh))

    sharded = serve(mesh, edges, psum_agrees)
    assert len(mesh._iter_groups()) == 2          # two structures
    assert sharded == single, (sharded, single)
    assert max(s[0] for s in single) > 0 and max(s[2] for s in single) > 0, single
    mesh.tracer.flush()
    check_tick_spans([json.loads(x) for x in buf.getvalue().splitlines()],
                     per_replica)
    print("LOAD-MESH-OK", len(single))


def replica_rows(svc) -> tuple[list[int], list[int]]:
    """Live and allocated table rows of each replica's slot block, counted
    on the host from the slot states."""
    live = np.zeros(svc.n_replicas, np.int64)
    cap = np.zeros(svc.n_replicas, np.int64)
    for g in svc._iter_groups():
        eng = g.sstate.engines
        for t in [t for sub in eng.levels for t in sub] + list(eng.l0):
            v = np.asarray(t.valid).reshape(svc.n_replicas, -1)
            live += v.sum(axis=1)
            cap += v.shape[1]
    return live.tolist(), cap.tolist()


def check_tick_spans(spans, per_replica):
    ticks = [s for s in spans if s["span"] == "tick"]
    assert len(ticks) == len(per_replica) > 0
    for tick, (live, cap) in zip(ticks, per_replica):
        kids = [s for s in spans if s["parent"] == tick["id"]]
        deliver = next(s for s in kids if s["span"] == "tick.deliver")
        events = [s for s in kids if s["span"] == "mesh.collectives"]
        assert len(events) == 2 and all(STATS <= set(e) for e in events)
        assert tick["mesh_matches"] == deliver["n_matches"] \
            == sum(e["n_matches"] for e in events)
        assert tick["mesh_live_rows"] == tick["live_rows"] \
            == sum(e["live_rows"] for e in events)
        assert sum(e["capacity_rows"] for e in events) \
            == tick["capacity_rows"]
        assert tick["replica_live_rows"] == live, (tick, live)
        assert tick["replica_capacity_rows"] == cap, (tick, cap)
        assert sum(live) == tick["live_rows"]
        assert sum(cap) == tick["capacity_rows"]
    assert any(t["mesh_matches"] for t in ticks)
    # replicas 0-1 hold the split query, 2-3 the chain: uneven rows
    assert any(len(set(t["replica_live_rows"])) > 1 for t in ticks)


if __name__ == "__main__":
    main()
