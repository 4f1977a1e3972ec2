"""End-to-end tests for the public ``repro.api`` surface.

The acceptance bar for the API redesign:

* a DSL-authored session reports the EXACT oracle match multiset (each
  in-window match exactly once) on both REF and PALLAS_INTERPRET;
* two relabeled-isomorphic patterns provably share one compiled slot
  tick — one ``SlotTickCache`` build, one slot group, ONE XLA trace;
* overflow surfaces as API-level status and gates admission;
* ``StreamSession`` checkpoints carry the api state (vocab + pattern
  plans) and ``restore`` rebuilds the typed surface.
"""

from collections import Counter

import numpy as np
import pytest

from repro.api import (
    ACTIVE,
    AdmissionError,
    DEGRADED,
    Event,
    EventBuffer,
    LabelVocab,
    Pattern,
    PatternError,
    StreamSession,
    to_data_edge,
)
from repro.core.join import JoinBackend
from repro.core.multi import SlotTickCache
from repro.core.oracle import OracleEngine

CAP = dict(level_capacity=512, l0_capacity=512, max_new=256)


# --------------------------------------------------------------------- #
# fixtures: patterns + streams
# --------------------------------------------------------------------- #
def chain_pattern(name="lateral"):
    return (Pattern(name)
            .edge("a", "b", label="login")
            .edge("b", "c", label="xfer")
            .before(0, 1)
            .window(24))


def chain_pattern_reauthored():
    """Same abstract structure as ``chain_pattern`` — edges stated in the
    opposite order, different vertex names, named edges."""
    return (Pattern("lateral-b")
            .edge("y", "z", label="xfer", name="second")
            .edge("x", "y", label="login", name="first")
            .before("first", "second")
            .window(24))


def triangle_pattern():
    return (Pattern("beacon")
            .edge("u", "v")
            .edge("v", "w")
            .edge("w", "u")
            .before(0, 1).before(1, 2)
            .window(30))


def traffic(n_events, seed, n_hosts=9, labels=("login", "xfer", "probe")):
    rng = np.random.default_rng(seed)
    t, out, seen = 0, [], set()
    while len(out) < n_events:
        t += int(rng.integers(0, 3))
        s = int(rng.integers(0, n_hosts))
        d = int(rng.integers(0, n_hosts))
        if s == d:
            d = (d + 1) % n_hosts
        if (s, d, t) in seen:       # duplicate edge instances would make
            continue                # the exactly-once multiset ambiguous
        seen.add((s, d, t))
        out.append(Event(s, d, t, label=labels[int(rng.integers(0, 3))]))
    return out


def match_key(sub, m):
    """Lower a typed ``Match`` back to the canonical frozenset form the
    oracle and ``current_matches`` speak."""
    plan = sub.plan
    bind, when = m.bindings, m.times
    name_of = {c: n for n, c in zip(plan.vertex_names, plan.vertex_map)}
    out = []
    for j, ename in enumerate(plan.edge_names):
        ceid = plan.edge_map[j]
        u, v = plan.query.edges[ceid]
        out.append((ceid, (bind[name_of[u]], bind[name_of[v]], when[ename])))
    return frozenset(out)


def oracle_run(query, window, stream):
    """(every match ever reported, final window matches)."""
    oracle = OracleEngine(query, window)
    seen = set()
    for e in stream:
        oracle.insert(e)
        seen |= oracle.matches()
    return seen, oracle.matches()


# --------------------------------------------------------------------- #
@pytest.mark.parametrize(
    "backend", [JoinBackend.REF, JoinBackend.PALLAS_INTERPRET])
def test_dsl_session_matches_oracle_multiset(backend):
    """DSL-authored sessions are oracle-exact: the delivered Match
    multiset equals the oracle's reported set (each match exactly once),
    and the two isomorphic chain authorings share ONE compiled tick with
    ONE trace."""
    tc = SlotTickCache()
    sess = StreamSession(slots_per_group=4, backend=backend,
                         tick_cache=tc, **CAP)
    subs = [sess.register(p) for p in
            (chain_pattern(), chain_pattern_reauthored(), triangle_pattern())]
    # chain authored two ways -> one structure; triangle -> another
    assert tc.n_builds == 2
    assert sess.service.n_compiles == 2

    events = traffic(240, seed=3)
    delivered = sess.ingest(events, batch_size=16)
    assert delivered > 0

    stream = [to_data_edge(e, sess.vocab) for e in events]
    for sub in subs:
        want_reported, want_window = oracle_run(sub.query, sub.window, stream)
        got = Counter(match_key(sub, m) for m in sub.drain())
        assert got and max(got.values()) == 1       # exactly once
        assert set(got) == want_reported
        assert {match_key(sub, m) for m in sub.matches()} == want_window
        assert sub.status == ACTIVE and sub.n_overflow == 0

    # zero extra XLA traces: every batch was 16 wide -> one trace per tick
    assert [t._cache_size() for t in tc.ticks()] == [1, 1]


def test_isomorphic_patterns_share_one_group_and_tick():
    """Registration of a re-authored isomorphic pattern is a pure data
    write: same slot group, no new build, no new trace."""
    tc = SlotTickCache()
    sess = StreamSession(slots_per_group=4, tick_cache=tc, **CAP)
    s1 = sess.register(chain_pattern())
    sess.ingest(traffic(64, seed=5), batch_size=16)   # compile + trace
    builds, traces = tc.n_builds, [t._cache_size() for t in tc.ticks()]
    assert builds == 1 and traces == [1]

    s2 = sess.register(chain_pattern_reauthored())    # mid-stream arrival
    sess.ingest(traffic(64, seed=6), batch_size=16)
    assert tc.n_builds == builds
    assert [t._cache_size() for t in tc.ticks()] == traces
    assert len(sess.service._iter_groups()) == 1      # one padded group
    g, _ = sess.service._location[s1.qid]
    g2, _ = sess.service._location[s2.qid]
    assert g is g2


def test_match_translation_names_and_times():
    """Bindings come back under the pattern's own vertex/edge names, in
    authoring order, with per-edge timestamps honoring the timing order."""
    sess = StreamSession(**CAP)
    sub = sess.register(chain_pattern())
    sess.ingest([
        Event(src=7, dst=3, ts=10, label="login"),
        Event(src=3, dst=5, ts=12, label="xfer"),
    ])
    (m,) = sub.drain()
    assert m.bindings == {"a": 7, "b": 3, "c": 5}
    assert m.times == {"e0": 10, "e1": 12}
    assert m.ts == 12
    assert [n for n, _ in m.vertices] == ["a", "b", "c"]
    # timing order violated -> no match
    sub2 = sess.register(chain_pattern_reauthored())
    sess.ingest([
        Event(src=1, dst=2, ts=40, label="xfer"),
        Event(src=0, dst=1, ts=44, label="login"),   # login AFTER xfer
    ])
    assert sub2.drain() == []


def test_callbacks_and_serve_loop():
    """``serve`` (the production loop) dispatches through callbacks and
    returns per-subscription totals keyed by the handles."""
    sess = StreamSession(**CAP)
    hits = []
    sub = sess.register(chain_pattern(), on_match=hits.append)
    totals = sess.serve(traffic(200, seed=9), batch_size=16)
    assert totals.get(sub, 0) == len(hits) == sub.n_delivered
    assert hits and sub.drain() == []     # callback mode: queue stays empty
    assert all(set(m.bindings) == {"a", "b", "c"} for m in hits)


def test_overflow_degrades_status_and_gates_admission():
    """Tiny capacities + a dense stream -> engine overflow.  The api
    layer must surface it (DEGRADED status, session.status) and refuse
    to admit more tenants of that structure unless forced."""
    sess = StreamSession(slots_per_group=4, level_capacity=8,
                         l0_capacity=8, max_new=4)
    wild = (Pattern("wild")
            .edge("a", "b").edge("b", "c").before(0, 1).window(60))
    sub = sess.register(wild)
    overflow_ticks = []
    sess.serve(traffic(256, seed=11, n_hosts=5), batch_size=32,
               min_batch=32, max_batch=32,
               on_tick=lambda i: overflow_ticks.append(i.n_overflow))
    assert sub.n_overflow > 0
    assert sub.status == DEGRADED
    assert sess.status().degraded == (sub.qid,)
    assert sum(overflow_ticks) > 0        # ServeInfo surfaces it per tick

    # same structure: admission refused (would silently lose matches)
    with pytest.raises(AdmissionError, match="capacity pressure"):
        sess.register(chain_pattern())
    # explicit override and unrelated structures still admit
    forced = sess.register(chain_pattern(), force=True)
    assert forced.status == ACTIVE
    tri = sess.register(triangle_pattern())
    assert tri.status == ACTIVE


def test_admission_reads_no_counters_before_the_first_tick(monkeypatch):
    """Counters move only in ticks, so registering tenants ahead of the
    stream reads nothing back from the device; once a tick has run,
    admission reads the structure's counters again."""
    import repro.runtime.service as service_mod

    reads = []

    class CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, *args, **kw):
            reads.append(args[0])
            return np.asarray(*args, **kw)

    sess = StreamSession(slots_per_group=2, **CAP)
    monkeypatch.setattr(service_mod, "np", CountingNumpy())
    subs = [sess.register(chain_pattern()) for _ in range(3)]
    assert reads == [] and sess.service.overflow_pressure() == 0
    sess.serve(traffic(32, seed=3), batch_size=32, min_batch=32,
               max_batch=32)
    reads.clear()
    subs.append(sess.register(chain_pattern()))
    assert len(reads) == 2              # one read per live group
    assert all(s.status == ACTIVE for s in subs)


def test_session_checkpoint_restore_roundtrip(tmp_path):
    """Crash/restore on the api surface: original qids, same vocab ids,
    same pattern plans, window matches identical; replaying the tail
    converges with the uninterrupted session."""
    tc = SlotTickCache()
    events = traffic(192, seed=13)
    serve = dict(batch_size=16, min_batch=16, max_batch=16)

    sess_a = StreamSession(ckpt_dir=str(tmp_path / "a"), tick_cache=tc, **CAP)
    subs_a = [sess_a.register(p) for p in
              (chain_pattern(), chain_pattern_reauthored())]
    sess_a.serve(events, ckpt_every=3, **serve)
    sess_a.close()

    sess_b = StreamSession(ckpt_dir=str(tmp_path / "b"), tick_cache=tc, **CAP)
    subs_b = [sess_b.register(p) for p in
              (chain_pattern(), chain_pattern_reauthored())]
    sess_b.serve(events[:96], ckpt_every=3, **serve)
    sess_b.checkpoint()
    sess_b.close()
    del sess_b                                   # crash

    sess_r = StreamSession.restore(str(tmp_path / "b"), tick_cache=tc)
    assert sess_r.service.n_compiles == 0        # warm process cache
    assert [s.qid for s in sess_r.subscriptions()] == \
        [s.qid for s in subs_b]
    assert sess_r.vocab.to_json() == sess_a.vocab.to_json()
    for s in sess_r.subscriptions():
        assert s.plan.vertex_names in (("a", "b", "c"), ("y", "z", "x"))
    sess_r.serve(events[sess_r.resume_offset:], **serve)

    for sa, sr in zip(subs_a, sess_r.subscriptions()):
        assert sa.plan == sr.plan
        assert sr.matches() == sa.matches()


def test_mesh_session_matches_plain_session(tmp_path):
    """``StreamSession(mesh=...)`` serves through the replica-sharded
    service: same delivered multiset as the plain session, and a sharded
    checkpoint restores back onto the mesh path with the full typed
    surface intact."""
    from repro.runtime.mesh import ShardedSearchService

    events = traffic(160, seed=21)
    serve = dict(batch_size=16)

    plain = StreamSession(slots_per_group=4, tick_cache=SlotTickCache(),
                          **CAP)
    sub_p = plain.register(chain_pattern())
    plain.ingest(events, **serve)
    want = Counter(match_key(sub_p, m) for m in sub_p.drain())

    tc = SlotTickCache()
    sess = StreamSession(mesh={"n_replicas": 1, "slots_per_replica": 4},
                         ckpt_dir=str(tmp_path), tick_cache=tc, **CAP)
    assert isinstance(sess.service, ShardedSearchService)
    sub = sess.register(chain_pattern())
    sess.ingest(events, **serve)
    got = Counter(match_key(sub, m) for m in sub.drain())
    assert got == want and want
    sess.checkpoint()
    sess.close()
    del sess                                     # crash

    sess_r = StreamSession.restore(str(tmp_path), tick_cache=tc)
    assert isinstance(sess_r.service, ShardedSearchService)
    assert sess_r.service.n_replicas == 1
    (sub_r,) = sess_r.subscriptions()
    assert sub_r.plan == sub.plan
    assert sub_r.matches() == sub_p.matches()

    # the shorthand: an int is the replica count
    sess_i = StreamSession(mesh=1, tick_cache=SlotTickCache(), **CAP)
    assert isinstance(sess_i.service, ShardedSearchService)


def test_restore_refuses_non_session_checkpoints(tmp_path):
    """A raw service checkpoint (no api state) must not silently restore
    as an untyped session."""
    from repro.checkpoint import CheckpointError
    from repro.runtime.service import ContinuousSearchService
    from repro.core.query import QueryGraph

    svc = ContinuousSearchService(ckpt_dir=str(tmp_path), **CAP)
    svc.register(QueryGraph(3, (0, 1, 2), ((0, 1), (1, 2)),
                            prec=frozenset({(0, 1)})), 20)
    svc.checkpoint()
    svc.ckpt.wait()
    with pytest.raises(CheckpointError, match="StreamSession"):
        StreamSession.restore(str(tmp_path))


# --------------------------------------------------------------------- #
# DSL validation + event buffer
# --------------------------------------------------------------------- #
def test_pattern_validation_is_loud():
    with pytest.raises(PatternError, match="self-loop"):
        Pattern().edge("a", "a")
    with pytest.raises(PatternError, match="duplicate parallel"):
        Pattern().edge("a", "b").edge("a", "b")
    with pytest.raises(PatternError, match="unknown edge name"):
        Pattern().edge("a", "b").before("nope", 0)
    with pytest.raises(PatternError, match="out of range"):
        Pattern().edge("a", "b").before(0, 3)
    with pytest.raises(PatternError, match="relabelled"):
        Pattern().vertex("a", label="x").vertex("a", label="y")
    with pytest.raises(PatternError, match="no window"):
        Pattern().edge("a", "b").build()
    with pytest.raises(PatternError, match="no edges"):
        Pattern().window(10).build()
    # a before-cycle is not a strict partial order
    with pytest.raises(PatternError, match="strict partial order"):
        (Pattern().edge("a", "b").edge("b", "c")
         .before(0, 1).before(1, 0).window(10).build())


def test_event_buffer_pads_pow2():
    vocab = LabelVocab()
    buf = EventBuffer(vocab, batch_size=6)
    out = []
    for i in range(8):
        b = buf.push(Event(i, i + 1, i, label="x"))
        if b is not None:
            out.append(b)
    tail = buf.flush()
    assert len(out) == 1 and tail is not None
    assert out[0]["src"].shape == (8,)           # 6 -> pow2 pad to 8
    assert out[0]["valid"].sum() == 6
    assert tail["src"].shape == (8,)             # pow2 floor is 8
    assert tail["valid"].sum() == 2
    assert buf.flush() is None
    # label space is the session vocab's
    assert out[0]["edge_label"][0] == vocab.intern("x")


def test_label_vocab_roundtrip_and_type_guard():
    from repro.api.events import STR_BASE

    v = LabelVocab()
    assert v.intern("login") == v.intern("login") == STR_BASE
    assert v.intern("xfer") == STR_BASE + 1
    # int tokens are identity-mapped: raw DataEdge streams (already in
    # engine label space) stay aligned with int-labeled patterns no
    # matter what order labels are declared in
    assert v.intern(7) == 7 and v.intern(0) == 0
    assert v.token(7) == 7 and v.token(STR_BASE) == "login"
    assert LabelVocab.from_json(v.to_json()).to_json() == v.to_json()
    with pytest.raises(TypeError, match="str or int"):
        v.intern(("tuple",))
    with pytest.raises(TypeError, match="str or int"):
        v.intern(True)
    with pytest.raises(ValueError, match="int label tokens"):
        v.intern(-1)


def test_int_labels_align_with_raw_data_edges():
    """The declaration-order trap: a pattern declaring int labels out of
    order must still match raw DataEdges carrying those exact engine
    label ids (identity interning — without it label=2 could intern to
    id 0 and silently match nothing)."""
    from repro.core.oracle import DataEdge

    sess = StreamSession(**CAP)
    p = (Pattern("desc-order")
         .vertex("a", label=2).vertex("b", label=0).vertex("c", label=1)
         .edge("a", "b").edge("b", "c").before(0, 1).window(20))
    sub = sess.register(p)
    sess.ingest([
        DataEdge(src=5, dst=6, ts=1, src_label=2, dst_label=0, edge_label=0),
        DataEdge(src=6, dst=7, ts=2, src_label=0, dst_label=1, edge_label=0),
    ])
    (m,) = sub.drain()
    assert m.bindings == {"a": 5, "b": 6, "c": 7}


def test_subscription_queue_is_bounded():
    """An un-drained queue-mode subscription drops its OLDEST matches
    past MAX_PENDING (counted in n_dropped) instead of growing forever."""
    sess = StreamSession(**CAP)
    sub = sess.register(chain_pattern())
    sub.MAX_PENDING = 4                     # shrink the bound for the test
    sub._pending = __import__("collections").deque(maxlen=4)
    for k in range(7):
        sess.ingest([
            Event(src=10 + k, dst=50, ts=100 * k, label="login"),
            Event(src=50, dst=20 + k, ts=100 * k + 1, label="xfer"),
        ])
    assert sub.n_delivered == 7
    assert sub.n_dropped == 3
    kept = sub.drain()
    assert len(kept) == 4
    assert kept[-1].bindings == {"a": 16, "b": 50, "c": 26}   # newest kept
