"""Cross-tenant prefix sharing (repro.core.share): differential proofs.

The SharedPrefixForest must be INVISIBLE in results and very visible in
cost:

* per-tenant match multisets with sharing enabled are exactly equal to
  sharing-disabled runs and to the brute-force oracle (REF and
  PALLAS_INTERPRET), including across unregister-then-reregister churn
  (epoch semantics: a mid-stream tenant gets fresh nodes, never
  inherited history) and crash/restore;
* K tenants sharing one prefix build the prefix tables ONCE — one
  forest node chain, leaf refcount K — and partial overlap (a 3-chain
  tenant over a 2-chain tenant's pattern) shares the common nodes and
  diverges after;
* register/unregister storms leave no orphaned prefix tables and no
  orphaned slot groups;
* checkpoints snapshot the forest (tables + refcounts + signatures) and
  restore resumes sharing with zero warm recompiles.
"""

from collections import Counter

import pytest

from repro.api import Pattern, StreamSession
from repro.core.join import JoinBackend
from repro.core.multi import SlotTickCache
from repro.core.query import QueryGraph
from repro.core.share import prefix_chain
from repro.runtime.fault import SimulatedFailure
from repro.runtime.service import ContinuousSearchService

from test_engine_oracle import small_stream
from test_service_restore import EventLog, oracle_reported

CAP = dict(level_capacity=512, l0_capacity=512, max_new=256)
SERVE = dict(batch_size=16, min_batch=16, max_batch=16)
W = 50          # one window for all patterns: the prefix signature
                # includes the window span, so sharing requires equality


def chain3():
    """3-chain whose first two edges are exactly ``chain2()``."""
    return QueryGraph(4, (0, 1, 2, 0), ((0, 1), (1, 2), (2, 3)),
                      prec=frozenset({(0, 1), (1, 2)}))


def chain2():
    return QueryGraph(3, (0, 1, 2), ((0, 1), (1, 2)),
                      prec=frozenset({(0, 1)}))


def chain2_other_labels():
    return QueryGraph(3, (1, 2, 0), ((0, 1), (1, 2)),
                      prec=frozenset({(0, 1)}))


def fork():
    """Two TC-subqueries (fork with e1 ≺ e0): exercises the
    L0-delta-join path downstream of a shared prefix."""
    return QueryGraph(3, (0, 1, 2), ((0, 1), (0, 2)),
                      prec=frozenset({(1, 0)}))


def tri():
    """Timing-chained triangle: the depth-3 node's edge binds BOTH
    endpoints to already-known prefix vertices (no new columns)."""
    return QueryGraph(3, (0, 1, 2), ((0, 1), (1, 2), (2, 0)),
                      prec=frozenset({(0, 1), (1, 2)}))


def stream160(seed=5):
    return small_stream(160, n_vertices=8, n_vertex_labels=3, seed=seed)


def svc_pair(tc, backend=JoinBackend.REF, **kw):
    """(sharing-enabled, sharing-disabled) twin services."""
    mk = lambda share: ContinuousSearchService(
        slots_per_group=4, tick_cache=tc, backend=backend,
        enable_sharing=share, **CAP, **kw)
    return mk(True), mk(False)


def reported(svc, stream, **serve):
    """serve the stream, returning the Counter of (qid, match-key)
    reports plus per-tick ServeInfo records."""
    from test_service_restore import event_key
    events, infos = [], []

    def on_match(qid, bindings, ets):
        plan = svc.registry.get(qid).plan
        for b, t in zip(bindings, ets):
            events.append((qid, event_key(plan, b, t)))

    svc.serve_stream(stream, on_match=on_match, on_tick=infos.append,
                     **SERVE, **serve)
    return Counter(events), infos


# --------------------------------------------------------------------- #
# differential: shared == unshared == oracle
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "backend", [JoinBackend.REF, JoinBackend.PALLAS_INTERPRET])
def test_sharing_differential_oracle(backend):
    tc = SlotTickCache()
    stream = stream160()
    shared, plain = svc_pair(tc, backend)
    queries = [chain3(), chain2(), chain2(), chain2_other_labels(), fork(),
               tri()]
    qs = [shared.register(q, W) for q in queries]
    qp = [plain.register(q, W) for q in queries]
    assert qs == qp

    # trie shape: chain3 shares depth-1/2 with both chain2 tenants and
    # owns depth 3; the triangle shares depth-1/2 with them too (its
    # first two chain edges ARE a 2-chain) and owns its closing depth-3
    # node; the relabeled chain2 and the fork get their own chains
    # (labels are part of the prefix signature)
    fs = shared.forest_stats()
    assert fs.n_tenants == 6
    leaf2 = shared.shared_prefix(qs[1])
    assert leaf2.depth == 2                  # chain3 + 2x chain2 + tri
    assert leaf2.n_tenants == 4
    assert shared.shared_prefix(qs[0]).depth == 3
    assert shared.shared_prefix(qs[0]).n_tenants == 1
    assert shared.shared_prefix(qs[5]).depth == 3
    assert shared.shared_prefix(qs[5]).n_tenants == 1
    assert plain.forest_stats() is None
    assert plain.shared_prefix(qp[0]) is None

    count_s, infos_s = reported(shared, stream)
    count_p, infos_p = reported(plain, stream)
    assert count_s and count_s == count_p      # exact multiset equality
    assert all(i.n_shared_prefix_ticks == len(shared.forest)
               for i in infos_s)
    assert all(i.n_shared_prefix_ticks == 0 for i in infos_p)

    for qid, q in zip(qs, queries):
        want_reported, want_window = oracle_reported(q, W, stream)
        got = {k for (qq, k) in count_s if qq == qid}
        assert got == want_reported
        assert shared.matches(qid) == want_window == plain.matches(qid)
        assert shared.tenant_overflow(qid) == 0
    # non-vacuous: the window and the reports both carry matches
    assert sum(count_s.values()) > 50
    assert any(shared.matches(qid) for qid in qs)


def test_sharing_differential_under_overflow():
    """Saturated tables drop appends deterministically, and a shared
    node drops exactly the appends each aliasing tenant's own table
    would have dropped — reports stay multiset-identical even past
    capacity, and the pressure is visible through the tenant's
    overflow counters either way."""
    tiny = dict(level_capacity=16, l0_capacity=16, max_new=4)
    tc = SlotTickCache()
    mk = lambda share: ContinuousSearchService(
        slots_per_group=4, tick_cache=tc, enable_sharing=share, **tiny)
    shared, plain = mk(True), mk(False)
    queries = [chain3(), chain2(), chain2()]
    qs = [shared.register(q, W) for q in queries]
    qp = [plain.register(q, W) for q in queries]

    stream = stream160()
    count_s, infos_s = reported(shared, stream)
    count_p, infos_p = reported(plain, stream)
    assert count_s == count_p
    assert sum(shared.tenant_overflow(q) for q in qs) > 0
    for q_s, q_p in zip(qs, qp):
        assert shared.matches(q_s) == plain.matches(q_p)
        assert shared.tenant_overflow(q_s) == plain.tenant_overflow(q_p) > 0
    # per-tenant attribution of shared-node drops makes the serve loop's
    # overflow trace IDENTICAL to the unshared run's, tick by tick
    assert [i.n_overflow for i in infos_s] == \
        [i.n_overflow for i in infos_p]
    assert any(i.n_overflow > 0 for i in infos_s)


# --------------------------------------------------------------------- #
# scale: K tenants sharing one prefix build its tables once
# --------------------------------------------------------------------- #
def test_k_tenants_one_prefix_chain():
    K = 12
    tc = SlotTickCache()
    svc = ContinuousSearchService(slots_per_group=16, tick_cache=tc,
                                  enable_sharing=True, **CAP)
    qids = [svc.register(chain2(), W) for _ in range(K)]
    fs = svc.forest_stats()
    assert fs.n_nodes == 2                    # depth-1 + depth-2, ONCE
    assert fs.n_shared_nodes == 2
    assert fs.n_tenants == K
    leaves = {svc.shared_prefix(q) for q in qids}
    assert len(leaves) == 1                   # every tenant: same leaf
    assert leaves.pop().n_tenants == K        # refcount K
    # one slot group, one suffix tick build, two node-tick builds
    assert len(svc._iter_groups()) == 1
    assert svc.n_compiles == 1
    assert tc.n_builds == 3

    # adding a chain3 tenant reuses the chain, adds ONE node + one group
    q3 = svc.register(chain3(), W)
    fs = svc.forest_stats()
    assert fs.n_nodes == 3 and fs.n_tenants == K + 1
    assert svc.shared_prefix(q3).depth == 3
    assert svc.shared_prefix(qids[0]).n_tenants == K + 1

    # serving works and the tables really are shared: every chain2
    # tenant reports identical per-tick results
    from repro.stream.generator import to_batches
    for b in to_batches(stream160(), 16):
        out = svc.ingest(b)
        assert len({int(out[q].n_new_matches) for q in qids}) == 1


# --------------------------------------------------------------------- #
# churn: epochs isolate history; storms leave no orphans
# --------------------------------------------------------------------- #
def test_churn_epochs_match_unshared_and_oracle():
    tc = SlotTickCache()
    stream = stream160(seed=5)
    half = 80
    shared, plain = svc_pair(tc)
    a_s, a_p = shared.register(chain3(), W), plain.register(chain3(), W)
    b_s, b_p = shared.register(chain2(), W), plain.register(chain2(), W)

    count1_s, _ = reported(shared, stream[:half])
    count1_p, _ = reported(plain, stream[:half])
    assert count1_s == count1_p

    # B leaves; a NEW chain2 tenant arrives mid-stream.  Its prefix is
    # signature-equal to A's depth-2 node but epoch-separated: sharing
    # A's table would hand it pre-registration history.
    shared.unregister(b_s)
    plain.unregister(b_p)
    c_s, c_p = shared.register(chain2(), W), plain.register(chain2(), W)
    assert shared.shared_prefix(c_s).epoch == half
    assert shared.shared_prefix(c_s).n_tenants == 1
    assert shared.forest_stats().n_nodes == 5      # A's 3 + C's fresh 2

    count2_s, _ = reported(shared, stream[half:])
    count2_p, _ = reported(plain, stream[half:])
    assert count2_s == count2_p
    assert shared.matches(a_s) == plain.matches(a_p)
    assert shared.matches(c_s) == plain.matches(c_p)

    # C is oracle-exact over exactly the suffix it was registered for
    want_reported, want_window = oracle_reported(chain2(), W, stream[half:])
    assert {k for (q, k) in count2_s if q == c_s} == want_reported
    assert shared.matches(c_s) == want_window

    # full storm: everyone leaves -> no orphaned tables, no orphan groups
    shared.unregister(a_s)
    shared.unregister(c_s)
    assert len(shared.forest) == 0
    assert shared.forest_stats() == (0, 0, 0, 0)
    assert not shared._groups


def test_failed_registration_rolls_back_chain_and_qid():
    """A failure after chain acquisition (e.g. the suffix tick compile)
    must leave NO trace: no half-registered qid, no phantom forest
    handle, no empty group entry — and a clean retry must work."""
    tc = SlotTickCache()
    svc = ContinuousSearchService(slots_per_group=2, tick_cache=tc,
                                  enable_sharing=True, **CAP)
    q0 = svc.register(chain2(), W)
    orig = svc._new_group
    svc._new_group = lambda template, leaf=None: (_ for _ in ()).throw(
        RuntimeError("injected compile failure"))
    with pytest.raises(RuntimeError, match="injected"):
        svc.register(tri(), W)
    svc._new_group = orig
    assert svc.n_active == 1 and len(svc.registry) == 1
    assert svc.forest_stats().n_tenants == 1
    assert len(svc.forest) == 2           # only q0's chain survives
    assert len(svc._groups) == 1          # no empty group-key entry
    qt = svc.register(tri(), W)           # clean retry
    assert svc.n_active == 2
    svc.unregister(q0)
    svc.unregister(qt)
    assert len(svc.forest) == 0 and not svc._groups


def test_register_unregister_storm_no_orphans():
    tc = SlotTickCache()
    svc = ContinuousSearchService(slots_per_group=2, tick_cache=tc,
                                  enable_sharing=True, **CAP)
    queries = [chain3(), chain2(), chain2_other_labels(), fork()]
    live = {}
    from repro.stream.generator import to_batches
    batches = list(to_batches(stream160(seed=9), 16))
    for i in range(30):
        q = queries[i % len(queries)]
        qid = svc.register(q, W)
        live[qid] = q
        if i % 3 == 2:                      # drop the oldest two
            for drop in sorted(live)[:2]:
                svc.unregister(drop)
                del live[drop]
        if i % 5 == 4:
            svc.ingest(batches[(i // 5) % len(batches)])
    # refcount bookkeeping exact: tenants in == handles held, and a
    # leaf's co-tenant count never exceeds the live population
    assert svc.forest_stats().n_tenants == len(live)
    for qid in live:
        info = svc.shared_prefix(qid)
        assert 1 <= info.n_tenants <= len(live)
    for qid in list(live):
        svc.unregister(qid)
    assert len(svc.forest) == 0 and not svc._groups
    assert svc.forest_stats().n_tenants == 0


# --------------------------------------------------------------------- #
# crash/restore: the differential harness with sharing enabled
# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "backend", [JoinBackend.REF, JoinBackend.PALLAS_INTERPRET])
def test_crash_restore_differential_with_sharing(tmp_path, backend):
    tc = SlotTickCache()
    stream = stream160(seed=5)
    queries = [chain3(), chain2(), fork()]

    def fresh(d):
        svc = ContinuousSearchService(
            slots_per_group=2, backend=backend, tick_cache=tc,
            enable_sharing=True, ckpt_dir=str(d), **CAP)
        return svc, [svc.register(q, W) for q in queries]

    # run A: uninterrupted reference (itself oracle-exact per tenant)
    svc_a, qids = fresh(tmp_path / "a")
    log_a = EventLog(svc_a)
    svc_a.serve_stream(stream, on_match=log_a.on_match,
                       on_tick=log_a.on_tick, ckpt_every=3, **SERVE)
    # NOTE: the stream may contain duplicate identical edges, so one
    # match KEY can be reported by several distinct row instances —
    # the differential below is on the full multiset either way
    count_a = Counter((qid, k) for qid, k, _ in log_a.events)
    assert count_a
    for qid, q in zip(qids, queries):
        want_reported, want_window = oracle_reported(q, W, stream)
        assert {k for qq, k, _ in log_a.events if qq == qid} == want_reported
        assert svc_a.matches(qid) == want_window
    builds_a = tc.n_builds

    # run B: crash at tick 5 (newest durable checkpoint: tick 3)
    svc_b, qids_b = fresh(tmp_path / "b")
    assert qids_b == qids
    assert svc_b.n_compiles == 0          # warm cache from run A
    log_b = EventLog(svc_b, crash_at_tick=5)
    with pytest.raises(SimulatedFailure):
        svc_b.serve_stream(stream, on_match=log_b.on_match,
                           on_tick=log_b.on_tick, ckpt_every=3, **SERVE)
    svc_b.ckpt.wait()

    svc_r = ContinuousSearchService.restore(str(tmp_path / "b"),
                                            tick_cache=tc, backend=backend)
    assert tc.n_builds == builds_a        # zero warm recompiles
    assert svc_r.forest is not None
    assert svc_r.forest_stats() == svc_b.forest_stats()
    assert [(n.pid, n.depth, n.epoch, n.refcount)
            for n in svc_r.forest.nodes()] == \
        [(n.pid, n.depth, n.epoch, n.refcount)
         for n in svc_b.forest.nodes()]
    assert svc_r.n_ticks == 3

    kept = [(qid, k, off) for qid, k, off in log_b.events
            if off <= svc_r.n_edges_ingested]
    log_r = EventLog(svc_r)
    svc_r.serve_stream(stream[svc_r.n_edges_ingested:],
                       on_match=log_r.on_match, on_tick=log_r.on_tick,
                       ckpt_every=3, **SERVE)
    count_b = Counter((qid, k) for qid, k, _ in kept + log_r.events)
    assert count_b == count_a             # exactly-once, nothing missed
    for qid in qids:
        assert svc_r.matches(qid) == svc_a.matches(qid)


def test_restore_into_cold_cache_rebuilds_forest(tmp_path):
    """A restore in a fresh process (cold SlotTickCache) rebuilds node
    and suffix ticks once each and reproduces the same state."""
    tc = SlotTickCache()
    svc = ContinuousSearchService(slots_per_group=2, tick_cache=tc,
                                  enable_sharing=True,
                                  ckpt_dir=str(tmp_path), **CAP)
    qids = [svc.register(q, W) for q in (chain3(), chain2())]
    svc.serve_stream(stream160(), ckpt_every=4, **SERVE)

    cold = SlotTickCache()
    svc2 = ContinuousSearchService.restore(str(tmp_path), tick_cache=cold)
    assert cold.n_builds > 0
    assert svc2.forest_stats() == svc.forest_stats()
    for qid in qids:
        assert svc2.matches(qid) == svc.matches(qid)


# --------------------------------------------------------------------- #
# api surface: share_prefixes sessions
# --------------------------------------------------------------------- #
def overlapping_patterns():
    """Two DSL patterns (differently authored) whose canonical plans
    share a 2-edge prefix chain."""
    p3 = (Pattern("exfil")
          .vertex("a", label=0).vertex("b", label=1)
          .vertex("c", label=2).vertex("d", label=0)
          .edge("a", "b").edge("b", "c").edge("c", "d")
          .before(0, 1).before(1, 2).window(W))
    p2 = (Pattern("staging")
          .vertex("x", label=0).vertex("y", label=1).vertex("z", label=2)
          .edge("y", "z", name="hop2").edge("x", "y", name="hop1")
          .before("hop1", "hop2").window(W))
    return p3, p2


def test_api_session_shares_prefixes_and_reports_stats(tmp_path):
    tc = SlotTickCache()
    sess = StreamSession(tick_cache=tc, share_prefixes=True,
                         ckpt_dir=str(tmp_path), **CAP)
    plain = StreamSession(tick_cache=tc, **CAP)
    p3, p2 = overlapping_patterns()
    s3, s2 = sess.register(p3), sess.register(p2)
    u3, u2 = plain.register(p3), plain.register(p2)

    assert s2.shared_prefix.depth == 2
    assert s2.shared_prefix.n_tenants == 2     # p3 aliases p2's chain
    assert s3.shared_prefix.depth == 3
    assert u3.shared_prefix is None

    stream = stream160()
    infos = []
    sess.serve(stream, on_tick=infos.append, **SERVE)
    plain.serve(stream, **SERVE)
    assert infos and all(i.n_shared_prefix_ticks == 3 for i in infos)

    for shared_sub, plain_sub in ((s3, u3), (s2, u2)):
        got = Counter(shared_sub.drain())
        want = Counter(plain_sub.drain())
        assert got == want and want               # typed-match multisets
        assert shared_sub.matches() == plain_sub.matches()

    # sharing survives session checkpoint/restore with original handles
    sess.checkpoint()
    sess.close()
    sess2 = StreamSession.restore(str(tmp_path), tick_cache=tc)
    assert sess2.service.forest is not None
    subs = {s.name: s for s in sess2.subscriptions()}
    assert subs["staging"].shared_prefix.n_tenants == 2
    assert subs["exfil"].matches() == s3.matches()


def test_prefix_chain_is_relabeling_invariant():
    """The prefix signature must dedup label-renamed / vertex-relabeled
    tenants: differently-authored isomorphic plans produce identical
    chain signatures (the canonical_key contract on prefix slices)."""
    from repro.core.registry import QueryRegistry

    reg = QueryRegistry(**CAP)
    a = reg.compile(chain2(), W)
    # same chain authored with permuted vertex ids and reversed edges
    b_query = QueryGraph(3, (2, 0, 1), ((1, 2), (2, 0)),
                         prec=frozenset({(0, 1)}))
    b = reg.compile(b_query, W)
    assert prefix_chain(a).sigs == prefix_chain(b).sigs
    # different labels -> different signatures at every depth
    c = reg.compile(chain2_other_labels(), W)
    assert prefix_chain(a).sigs[0] != prefix_chain(c).sigs[0]
    # different window -> different signatures (expiry is part of the
    # shared table's semantics)
    d = reg.compile(chain2(), W + 1)
    assert prefix_chain(a).sigs != prefix_chain(d).sigs
