"""Crash/restore differential test for the multi-tenant service.

The paper's timing-order semantics demand that a restarted server misses
nothing still inside the window.  Proof by differential execution:

* run A: a multi-tenant ``ContinuousSearchService`` serves a synthetic
  stream to completion, checkpointing as it goes, and every reported
  match is logged with the edge offset of the tick that produced it;
* run B: an identical service crashes mid-stream (``SimulatedFailure``
  injected from the ``on_tick`` hook), is restored from the newest
  usable checkpoint, and replays the remaining edges.

A consumer that rolls back reports newer than the last durable
checkpoint (standard at-least-once -> exactly-once downgrade) must see
EXACTLY run A's match multiset: nothing within the window missed,
nothing duplicated.  Run A itself is cross-checked against the
brute-force oracle's incremental match union, and the restore must hit
the process-wide compiled-tick cache: zero recompiles, zero retraces
for previously-seen structures.
"""

from collections import Counter

import numpy as np
import pytest

from repro.checkpoint import checkpoint_steps
from repro.core import compile_plan
from repro.core.join import JoinBackend
from repro.core.multi import SlotTickCache
from repro.core.oracle import OracleEngine
from repro.core.query import QueryGraph
from repro.launch.stream_serve import StreamServer
from repro.runtime.fault import SimulatedFailure
from repro.runtime.service import ContinuousSearchService

from test_engine_oracle import small_stream, star_query, tri_query

CAP = dict(level_capacity=512, l0_capacity=512, max_new=256)
# pinned chunk size: deterministic tick/checkpoint boundaries and a
# single trace shape per compiled tick (the no-retrace assertions)
SERVE = dict(batch_size=16, min_batch=16, max_batch=16)


def chain_query():
    return QueryGraph(3, (0, 1, 2), ((0, 1), (1, 2)), prec=frozenset({(0, 1)}))


def chain_query_relabeled():
    return QueryGraph(3, (1, 2, 0), ((0, 1), (1, 2)), prec=frozenset({(0, 1)}))


def event_key(plan, bindings_row, ets_row):
    """One reported match -> the canonical frozenset of
    ``(query_edge_id, (src, dst, ts))`` used by ``current_matches`` and
    the oracle."""
    q = plan.query
    vslot = {v: s for s, v in enumerate(plan.final_vertex_layout)}
    epos = {e: s for s, e in enumerate(plan.final_edge_layout)}
    return frozenset(
        (eid, (int(bindings_row[vslot[q.edges[eid][0]]]),
               int(bindings_row[vslot[q.edges[eid][1]]]),
               int(ets_row[epos[eid]])))
        for eid in range(q.n_edges))


class EventLog:
    """Log (qid, match) events tagged with the END offset of their tick,
    optionally injecting a crash at a given tick."""

    def __init__(self, svc, crash_at_tick=None):
        self.svc = svc
        self.crash_at_tick = crash_at_tick
        self.events = []      # (qid, match_key, end_of_tick_edge_offset)
        self._pending = []

    def on_match(self, qid, bindings, ets):
        plan = self.svc.registry.get(qid).plan
        for b, t in zip(bindings, ets):
            self._pending.append((qid, event_key(plan, b, t)))

    def on_tick(self, info):
        self.events += [(qid, k, info.n_edges_ingested)
                        for qid, k in self._pending]
        self._pending.clear()
        if self.crash_at_tick is not None and info.tick == self.crash_at_tick:
            raise SimulatedFailure(f"injected at tick {info.tick}")


def oracle_reported(query, window, stream):
    """Every match the engine must report over ``stream``: the union of
    the oracle's window contents after each edge insertion."""
    oracle = OracleEngine(query, window)
    seen = set()
    for e in stream:
        oracle.insert(e)
        seen |= oracle.matches()
    return seen, oracle.matches()


QUERIES = [(chain_query(), 20), (chain_query_relabeled(), 30),
           (tri_query(), 25)]


def _fresh(ckpt_dir, backend, tc):
    svc = ContinuousSearchService(
        slots_per_group=2, backend=backend, tick_cache=tc,
        ckpt_dir=str(ckpt_dir), **CAP)
    qids = [svc.register(q, w) for q, w in QUERIES]
    return svc, qids


@pytest.mark.parametrize(
    "backend", [JoinBackend.REF, JoinBackend.PALLAS_INTERPRET])
def test_crash_restore_differential(tmp_path, backend):
    tc = SlotTickCache()
    stream = small_stream(160, n_vertices=9, seed=41)

    # ---- run A: uninterrupted reference --------------------------------
    svc_a, qids = _fresh(tmp_path / "a", backend, tc)
    log_a = EventLog(svc_a)
    svc_a.serve_stream(stream, on_match=log_a.on_match,
                       on_tick=log_a.on_tick, ckpt_every=3, **SERVE)
    assert svc_a.n_edges_ingested == len(stream)
    builds_a = tc.n_builds
    assert builds_a == 2            # two structural signatures, ever
    trace_sizes_a = [t._cache_size() for t in tc.ticks()]
    assert trace_sizes_a == [1, 1]  # one chunk shape -> one trace each

    # run A is oracle-exact, per qid, and reports each match exactly once
    count_a = Counter((qid, k) for qid, k, _ in log_a.events)
    assert count_a and max(count_a.values()) == 1
    for qid, (q, w) in zip(qids, QUERIES):
        want_reported, want_window = oracle_reported(q, w, stream)
        got = {k for (qq, k, _) in log_a.events if qq == qid}
        assert got == want_reported
        assert svc_a.matches(qid) == want_window

    # ---- run B: crash at tick 5, past the tick-3 checkpoint ------------
    svc_b, qids_b = _fresh(tmp_path / "b", backend, tc)
    assert qids_b == qids
    assert svc_b.n_compiles == 0    # structures already cached by run A
    log_b = EventLog(svc_b, crash_at_tick=5)
    with pytest.raises(SimulatedFailure):
        svc_b.serve_stream(stream, on_match=log_b.on_match,
                           on_tick=log_b.on_tick, ckpt_every=3, **SERVE)
    svc_b.ckpt.wait()               # flush in-flight async writes

    # ---- restore: same tenants, same slots, zero recompiles ------------
    # the backend is the restoring process's choice, not the manifest's:
    # a test that pins one passes it again
    svc_r = ContinuousSearchService.restore(str(tmp_path / "b"),
                                            tick_cache=tc, backend=backend)
    assert svc_r.n_compiles == 0
    assert tc.n_builds == builds_a
    assert svc_r.registry.qids() == qids
    assert svc_r.n_ticks == 3                       # newest durable ckpt
    assert svc_r.n_edges_ingested == 3 * 16
    for qid, (q, w) in zip(qids, QUERIES):
        assert svc_r.registry.get(qid).query == q
        assert svc_r.registry.get(qid).window == w

    # exactly-once consumer: roll back reports newer than the checkpoint
    kept = [(qid, k, off) for qid, k, off in log_b.events
            if off <= svc_r.n_edges_ingested]

    # ---- replay the tail on the restored server ------------------------
    log_r = EventLog(svc_r)
    svc_r.serve_stream(stream[svc_r.n_edges_ingested:],
                       on_match=log_r.on_match, on_tick=log_r.on_tick,
                       ckpt_every=3, **SERVE)
    assert svc_r.n_edges_ingested == len(stream)

    # the shared jitted ticks saw no new shapes: zero retraces end-to-end
    assert tc.n_builds == builds_a
    assert [t._cache_size() for t in tc.ticks()] == trace_sizes_a

    # ---- differential: crash+restore == uninterrupted, exactly once ----
    count_b = Counter((qid, k) for qid, k, _ in kept + log_r.events)
    assert count_b == count_a
    for qid in qids:
        assert svc_r.matches(qid) == svc_a.matches(qid)
        assert int(svc_r.stats(qid).n_matches_total) == \
            int(svc_a.stats(qid).n_matches_total)


def test_restore_with_cold_tick_cache(tmp_path):
    """Correctness does not depend on the warm process cache: a restore
    into a fresh SlotTickCache (≈ a new process) rebuilds each structure
    once and reproduces the same final state."""
    tc = SlotTickCache()
    stream = small_stream(160, n_vertices=9, seed=42)
    svc, qids = _fresh(tmp_path, JoinBackend.REF, tc)
    svc.serve_stream(stream, ckpt_every=4, **SERVE)
    cold = SlotTickCache()
    svc2 = ContinuousSearchService.restore(str(tmp_path), tick_cache=cold)
    assert svc2.n_compiles == cold.n_builds == 2
    for qid in qids:
        assert svc2.matches(qid) == svc.matches(qid)


def test_restore_skips_torn_checkpoint(tmp_path):
    """Truncating the newest checkpoint (a torn write) must roll restore
    back to the previous one, and replaying from there still converges to
    the uninterrupted final state."""
    tc = SlotTickCache()
    stream = small_stream(160, n_vertices=9, seed=43)
    svc, qids = _fresh(tmp_path, JoinBackend.REF, tc)
    svc.serve_stream(stream, ckpt_every=2, **SERVE)   # ckpts at 2,4,6,8,10
    steps = checkpoint_steps(str(tmp_path))
    assert steps[-1] == 10
    npz = tmp_path / f"step_{steps[-1]}.npz"
    npz.write_bytes(npz.read_bytes()[:128])           # tear it

    svc2 = ContinuousSearchService.restore(str(tmp_path), tick_cache=tc)
    assert svc2.n_ticks == 8                          # fell back one step
    assert svc2.n_edges_ingested == 8 * 16
    svc2.serve_stream(stream[svc2.n_edges_ingested:], **SERVE)
    for qid in qids:
        assert svc2.matches(qid) == svc.matches(qid)
        assert int(svc2.stats(qid).n_matches_total) == \
            int(svc.stats(qid).n_matches_total)


def test_stream_server_is_a_service_wrapper(tmp_path):
    """StreamServer owns no tick machinery: it restores and serves purely
    through ContinuousSearchService, and a restarted server resumes from
    the checkpointed offset with the same window state."""
    tc = SlotTickCache()
    stream = small_stream(160, n_vertices=9, seed=44)
    plan = compile_plan(chain_query(), 20, **CAP)

    hits = []
    s1 = StreamServer(plan, ckpt_dir=str(tmp_path), tick_cache=tc)
    assert isinstance(s1.service, ContinuousSearchService)
    for attr in ("tick", "state_"):
        assert not hasattr(s1, attr)   # no tick-building logic of its own
    total = s1.ingest(stream[:80], on_match=lambda b, t: hits.append(len(b)),
                      ckpt_every=2, batch_size=16)
    aimd = s1._coalescer
    assert aimd is not None
    total += s1.ingest(stream[80:], on_match=lambda b, t: hits.append(len(b)),
                       ckpt_every=2, batch_size=16)
    assert s1._coalescer is aimd       # AIMD state persists across ingests
    assert total == sum(hits) > 0
    assert s1.resume_offset == len(stream)

    s2 = StreamServer(plan, ckpt_dir=str(tmp_path), tick_cache=tc)
    assert s2.ticks == s1.ticks
    assert s2.resume_offset == len(stream)            # nothing left to replay
    assert s2.matches() == s1.matches()
    assert s2.service.n_compiles == 0                 # warm cache restore

    # a different query cannot hijack the checkpoint
    other = compile_plan(tri_query(), 25, **CAP)
    with pytest.raises(ValueError, match="different query"):
        StreamServer(other, ckpt_dir=str(tmp_path), tick_cache=tc)


def test_custom_decomposition_plan_round_trips(tmp_path):
    """A caller-supplied plan (custom decomposition) must be served
    exactly as given AND survive checkpoint/restore — not be silently
    replaced by the decomposition heuristics."""
    from repro.core.decompose import TCSubquery
    from repro.core.registry import plan_decomposition

    q = tri_query()
    # the heuristic compiles this ≺-chain triangle to ONE TC-subquery;
    # force the all-singletons decomposition instead
    custom = [TCSubquery(frozenset({e}), (e,)) for e in range(3)]
    plan = compile_plan(q, 25, decomposition=custom, **CAP)
    assert plan_decomposition(plan) == [(0,), (1,), (2,)]
    assert plan_decomposition(compile_plan(q, 25, **CAP)) != \
        plan_decomposition(plan)

    stream = small_stream(160, n_vertices=9, seed=45)
    svc = ContinuousSearchService(slots_per_group=2,
                                  ckpt_dir=str(tmp_path), **CAP)
    qid = svc.register(q, 25, plan=plan)
    assert plan_decomposition(svc.registry.get(qid).plan) == \
        [(0,), (1,), (2,)]
    svc.serve_stream(stream[:96], ckpt_every=2, **SERVE)

    svc2 = ContinuousSearchService.restore(str(tmp_path))
    assert plan_decomposition(svc2.registry.get(qid).plan) == \
        [(0,), (1,), (2,)]
    svc2.serve_stream(stream[96:], **SERVE)
    svc.serve_stream(stream[96:], **SERVE)     # uninterrupted reference
    assert svc2.matches(qid) == svc.matches(qid)


def test_plan_with_divergent_capacities_rejected():
    """A caller plan whose capacities differ from the registry's would
    checkpoint fine but could NEVER restore (restore recompiles with the
    registry's capacities -> shape mismatch), so registration must
    reject it up front — including the case where the plan's l0 joins
    use the level capacity while the registry's l0_capacity differs."""
    q = star_query()                 # 3 singleton subqueries -> l0 joins
    plan = compile_plan(q, 15, level_capacity=512, l0_capacity=512,
                        max_new=256)
    svc = ContinuousSearchService(level_capacity=512, l0_capacity=1024,
                                  max_new=256)
    with pytest.raises(ValueError, match="capacities"):
        svc.register(q, 15, plan=plan)
    # matching capacities are accepted
    ok = ContinuousSearchService(level_capacity=512, l0_capacity=512,
                                 max_new=256)
    ok.register(q, 15, plan=plan)


def test_restore_overrides_serving_knobs(tmp_path):
    """backend / extract_matches are serving-behavior knobs: a restart
    may override the checkpointed values (e.g. re-enable match
    extraction) instead of being silently stuck with them."""
    stream = small_stream(96, n_vertices=9, seed=46)
    svc = ContinuousSearchService(slots_per_group=2, extract_matches=False,
                                  ckpt_dir=str(tmp_path), **CAP)
    qid = svc.register(chain_query(), 20)
    svc.serve_stream(stream, ckpt_every=2, **SERVE)

    svc2 = ContinuousSearchService.restore(str(tmp_path))
    assert svc2.extract_matches is False              # default: keep config
    svc3 = ContinuousSearchService.restore(
        str(tmp_path), extract_matches=True,
        backend=JoinBackend.PALLAS_INTERPRET)
    assert svc3.extract_matches is True
    assert svc3.backend == JoinBackend.PALLAS_INTERPRET
    assert svc3.registry.qids() == [qid]


def test_serve_stream_honors_small_batch_bounds():
    """batch_size below the coalescer's default min_batch must be served
    as requested (not silently clamped to 32), and a lone max_batch below
    the defaults must not crash."""
    stream = small_stream(64, n_vertices=9, seed=47)
    svc = ContinuousSearchService(slots_per_group=2, **CAP)
    svc.register(chain_query(), 20)
    chunks = []
    svc.serve_stream(stream[:32], on_tick=lambda i: chunks.append(i.chunk),
                     batch_size=8)
    assert chunks[0] == 8
    chunks.clear()
    svc.serve_stream(stream[32:], on_tick=lambda i: chunks.append(i.chunk),
                     batch_size=64, max_batch=16)    # self-consistent args
    assert chunks[0] == 16

    # an on_match that could never fire must fail loudly, not silently
    svc_nx = ContinuousSearchService(extract_matches=False, **CAP)
    svc_nx.register(chain_query(), 20)
    with pytest.raises(ValueError, match="extract_matches"):
        svc_nx.serve_stream(stream, on_match=lambda q, b, t: None)


def test_checkpoint_retention_and_loud_misconfig(tmp_path):
    """keep-last-K retention bounds ckpt_dir growth (restore still works
    from the newest kept step), and ckpt_every without ckpt_dir fails
    loudly instead of silently skipping fault tolerance."""
    stream = small_stream(160, n_vertices=9, seed=49)
    svc = ContinuousSearchService(slots_per_group=2, ckpt_dir=str(tmp_path),
                                  keep_checkpoints=3, **CAP)
    qid = svc.register(chain_query(), 20)
    svc.serve_stream(stream, ckpt_every=1, **SERVE)     # 10 ticks, 10 saves
    steps = checkpoint_steps(str(tmp_path))
    assert len(steps) == 3 and steps[-1] == 10
    svc2 = ContinuousSearchService.restore(str(tmp_path))
    assert svc2.n_edges_ingested == len(stream)
    assert svc2.matches(qid) == svc.matches(qid)

    bare = ContinuousSearchService(slots_per_group=2, **CAP)
    bare.register(chain_query(), 20)
    with pytest.raises(ValueError, match="ckpt_dir"):
        bare.serve_stream(stream, ckpt_every=5)


def test_stream_server_rejects_plan_capacity_drift(tmp_path):
    """Restarting over a checkpoint with a recompiled (bigger-capacity)
    plan must fail loudly — the restore serves the checkpointed plan, so
    silently keeping the old tables would hide the operator's fix."""
    plan = compile_plan(chain_query(), 20, **CAP)
    s1 = StreamServer(plan, ckpt_dir=str(tmp_path))
    s1.ingest(small_stream(64, n_vertices=9, seed=50), ckpt_every=1,
              batch_size=16)
    bigger = compile_plan(chain_query(), 20, level_capacity=2048,
                          l0_capacity=2048, max_new=1024)
    with pytest.raises(ValueError, match="capacities or decomposition"):
        StreamServer(bigger, ckpt_dir=str(tmp_path))


def test_stream_server_rejects_foreign_checkpoints(tmp_path):
    """A ckpt_dir holding non-service checkpoints (legacy or foreign
    writer) must fail loudly at startup, not crash obscurely or silently
    start fresh (which would break the miss-nothing guarantee)."""
    import jax.numpy as jnp
    from repro.checkpoint import save_checkpoint

    save_checkpoint(str(tmp_path), 1, {"x": jnp.ones(2)})
    plan = compile_plan(chain_query(), 20, **CAP)
    with pytest.raises(ValueError, match="service manifest"):
        StreamServer(plan, ckpt_dir=str(tmp_path))


def test_stream_server_refuses_all_torn_dir(tmp_path):
    """Checkpoints exist but every one is torn: restarting must raise,
    not silently start fresh at offset 0."""
    from repro.checkpoint import CheckpointError

    plan = compile_plan(chain_query(), 20, **CAP)
    s1 = StreamServer(plan, ckpt_dir=str(tmp_path))
    s1.ingest(small_stream(64, n_vertices=9, seed=48), ckpt_every=1,
              batch_size=16)
    assert checkpoint_steps(str(tmp_path))
    for s in checkpoint_steps(str(tmp_path)):
        p = tmp_path / f"step_{s}.npz"
        p.write_bytes(p.read_bytes()[:16])
    with pytest.raises(CheckpointError, match="none are usable"):
        StreamServer(plan, ckpt_dir=str(tmp_path))
