"""The slot tick traced from inside the program.

* ``Span``: start, end and parent on one monotonic clock; nested spans
  nest; the JSONL still goes through ``summarize_trace``.
* Under a ``jax.profiler`` trace a tiny ``serve_frontier`` run leaves
  the ``repro.*`` annotations in the profile: one ``serve.round`` per
  round, with ``tick`` -> ``tick.dispatch`` / ``tick.barrier`` /
  ``tick.deliver`` -> ``tick.readback`` / ``tick.callbacks`` inside.
* ``TickLoad``: the live-row, live-pair and extent counters equal
  counts taken on the host from the slot states before and after each
  tick (REF backend, small tables), every tick sweeps at least its live
  pairs, and ``ServeInfo`` carries their totals; on 4
  virtual devices ``ShardedSearchService`` reports the same totals, and
  its psum'd ``MeshTickStats`` and per-replica rows agree (subprocess);
  on one device a mesh ``tick`` span carries them and a plain one not.
* ``ingest.hold_ms``: exact holds on a ``ScriptedSource`` with a
  scripted clock.
* The lowered slot tick carries every ``engine.*`` scope.
"""

from __future__ import annotations

import glob
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.engine import TickLoad
from repro.core.join import JoinBackend
from repro.core.multi import SlotTickCache, build_slot_tick, init_slot_state
from repro.core.oracle import DataEdge
from repro.core.plan import compile_plan
from repro.core.query import QueryGraph
from repro.core.state import make_batch
from repro.obs import MetricsRegistry, memory_tracer, summarize_trace
from repro.runtime.mesh import ShardedSearchService
from repro.runtime.service import ContinuousSearchService
from repro.runtime.straggler import quantize_pow2
from repro.stream.generator import StreamConfig, synth_traffic_stream, to_batches
from repro.stream.ingest import IngestFrontier, ListSource, ScriptedSource

CAP = dict(level_capacity=64, l0_capacity=64, max_new=32)
BATCH = dict(batch_size=16, min_batch=16, max_batch=16)
ENGINE_SCOPES = ("engine.label_scan", "engine.level_append",
                 "engine.level_join", "engine.level_recon",
                 "engine.l0_compact", "engine.l0_join", "engine.l0_append",
                 "engine.emit", "engine.expire")


def split_query():
    """Subquery {e0 ≺ e1} (a level join) and {e2} joined at L0."""
    return QueryGraph(4, (0, 1, 2, 0), ((0, 1), (1, 2), (2, 3)),
                      prec=frozenset({(0, 1)}))


def chain2():
    return QueryGraph(3, (0, 1, 2), ((0, 1), (1, 2)),
                      prec=frozenset({(0, 1)}))


def stream(n=160, seed=3):
    return synth_traffic_stream(StreamConfig(
        n_edges=n, n_vertices=10, n_vertex_labels=3, n_edge_labels=2,
        seed=seed, ts_step_max=2))


# ------------------------------------------------------------------ #
# spans
# ------------------------------------------------------------------ #
def test_span_start_end_parent_and_nesting():
    tr, sink = memory_tracer()
    tr.next_tick()
    with tr.span("outer") as outer:
        with tr.span("inner", k=2) as inner:
            pass
        tr.event("mark")
    tr.flush()
    recs = {r["span"]: r for r in map(json.loads,
                                      sink.getvalue().splitlines())}
    o, i, m = recs["outer"], recs["inner"], recs["mark"]
    assert o["parent"] is None and i["parent"] == o["id"] == outer.id
    assert m["parent"] == o["id"] and m["ms"] == 0
    assert o["start_ns"] <= i["start_ns"] <= i["end_ns"] <= o["end_ns"]
    assert i["k"] == 2 and inner.ms == i["ms"]
    assert all(r["tick"] == 1 for r in recs.values())
    # t0 is the real start on the wall clock: inner starts after outer
    assert o["t0"] <= i["t0"]
    s = summarize_trace(sink.getvalue().splitlines())
    assert s["n_spans"] == 3 and s["n_bad_lines"] == 0


def _profile_host_events(path):
    from jax.profiler import ProfileData

    (pb,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def _inside(child, parents):
    return any(a <= child[1] and child[2] <= b for _, a, b in parents)


def test_profile_holds_the_span_tree(tmp_path):
    edges = stream(96)
    tc = SlotTickCache()

    def serve(tracer=None):
        svc = ContinuousSearchService(slots_per_group=2, tick_cache=tc,
                                      backend=JoinBackend.REF,
                                      tracer=tracer, **CAP)
        svc.register(chain2(), 20)
        fr = IngestFrontier([ListSource("s", edges)], allowed_lateness=0,
                            sleep=lambda d: None)
        seen = []
        svc.serve_frontier(fr, on_match=lambda *a: None,
                           on_tick=seen.append, pump_size=16, **BATCH)
        return seen

    serve()                                    # compile outside the trace
    tracer, sink = memory_tracer()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    infos = serve(tracer)
    jax.profiler.stop_trace()
    spans = [json.loads(x) for x in sink.getvalue().splitlines()]
    ev = _profile_host_events(str(tmp_path))
    by = lambda n: [e for e in ev if e[0] == n]

    rounds = [s for s in spans if s["span"] == "serve.round"]
    assert len(by("repro.serve.round")) == len(rounds) >= len(infos) > 0
    assert len(by("repro.tick")) == len(infos)
    assert all(_inside(e, by("repro.serve.round")) for e in by("repro.tick"))
    for child in ("tick.dispatch", "tick.barrier", "tick.deliver"):
        assert len(by(f"repro.{child}")) == len(infos)
        assert all(_inside(e, by("repro.tick"))
                   for e in by(f"repro.{child}"))
    for child in ("tick.readback", "tick.callbacks"):
        assert by(f"repro.{child}")
        assert all(_inside(e, by("repro.tick.deliver"))
                   for e in by(f"repro.{child}"))
    assert not by("repro.tick.forest")         # no forest, no span
    # the JSONL holds the same tree, by parent ids
    ids = {s["id"]: s for s in spans}
    parent = lambda s: ids[s["parent"]]["span"]
    for s in spans:
        want = {"tick": "serve.round", "ingest.pump": "serve.round",
                "ingest.release": "serve.round",
                "serve.on_tick": "serve.round",
                "tick.dispatch": "tick", "tick.barrier": "tick",
                "tick.deliver": "tick", "tick.readback": "tick.deliver",
                "tick.callbacks": "tick.deliver"}.get(s["span"])
        if want:
            assert parent(s) == want, s
    # every tick span carries the live-row counters ServeInfo reports
    ticks = [s for s in spans if s["span"] == "tick"]
    assert [s["live_rows"] for s in ticks] == [i.live_rows for i in infos]
    assert [s["live_pairs"] for s in ticks] == [i.live_pairs for i in infos]
    assert [s["swept_pairs"] for s in ticks] == [i.swept_pairs for i in infos]


# ------------------------------------------------------------------ #
# live-row counters
# ------------------------------------------------------------------ #
def _label_mask(batch, params, k):
    """Host ``[n_qedges, B]`` label match of slot ``k``."""
    ok = batch["valid"] & (batch["src"] != batch["dst"])
    esl, edl, eel = (np.asarray(x)[k] for x in
                     (params.esl, params.edl, params.eel))
    return (ok[None, :]
            & (batch["src_label"][None, :] == esl[:, None])
            & (batch["dst_label"][None, :] == edl[:, None])
            & ((eel[:, None] < 0)
               | (batch["edge_label"][None, :] == eel[:, None])))


def host_load(plan, pre, post, batch, k):
    """Slot ``k``'s counters from the states before and after a tick.
    A table's rows before expiry are those valid before the tick plus
    those appended in it (its ``fresh`` rows afterwards); a compacted
    delta holds its rows first.  Each join is ``(live_a, live_b, ext_a,
    ext_b, cap_a, cap_b)``, an extent being the last live row + 1."""
    row = lambda x: np.asarray(x)[k]
    n = lambda x: int(np.sum(row(x)))
    cap = lambda t: np.asarray(t.valid).shape[1]
    ext = lambda m: int(np.flatnonzero(m)[-1]) + 1 if np.any(m) else 0
    held = lambda a, b: row(a.valid) | row(b.fresh)    # before expiry
    em = _label_mask(batch, pre.params, k)
    lv_pre, lv_post = pre.engines.levels, post.engines.levels
    tables = [t for sub in lv_post for t in sub]
    joins = []
    for si, s in enumerate(plan.subqueries):
        for li in range(1, len(s.levels)):
            a = held(lv_pre[si][li - 1], lv_post[si][li - 1])
            b = em[s.levels[li].qedge]
            joins.append((int(a.sum()), int(b.sum()), ext(a), ext(b),
                          cap(lv_post[si][li - 1]), em.shape[1]))
    a_pre, a_post = lv_pre[0][-1], lv_post[0][-1]
    for gi, js in enumerate(plan.l0_joins):
        b_pre, b_post = lv_pre[gi + 1][-1], lv_post[gi + 1][-1]
        d = js.max_new
        da, db = min(n(a_post.fresh), d), min(n(b_post.fresh), d)
        b = held(b_pre, b_post)
        joins.append((da, int(b.sum()), da, ext(b), d, cap(b_post)))
        a = row(a_pre.valid)
        joins.append((int(a.sum()), db, ext(a), db, cap(a_post), d))
        a_pre, a_post = pre.engines.l0[gi], post.engines.l0[gi]
    live = lambda ts: sum(n(t.valid) for t in ts)
    return ((live(tables), sum(cap(t) for t in tables),
             live(post.engines.l0), sum(cap(t) for t in post.engines.l0)),
            joins)


def test_tick_load_equals_host_counts():
    edges = stream(192, seed=5)
    svc = ContinuousSearchService(slots_per_group=3, donate=False,
                                  backend=JoinBackend.REF,
                                  tick_cache=SlotTickCache(), **CAP)
    svc.register(split_query(), 24)
    svc.register(QueryGraph(4, (1, 2, 0, 1), ((0, 1), (1, 2), (2, 3)),
                            prec=frozenset({(0, 1)})), 24)
    (g,) = svc._iter_groups()
    plan = g.template
    assert len(plan.l0_joins) == 1 and len(plan.subqueries[0].levels) == 2
    infos = []
    state = {"pre": jax.device_get(g.sstate), "i": 0}

    def on_tick(info):
        post = jax.device_get(g.sstate)
        chunk = edges[state["i"]:info.n_edges_ingested]
        state["i"] = info.n_edges_ingested
        (batch,) = to_batches(chunk, quantize_pow2(len(chunk), lo=16))
        want = [0, 0, 0, 0, 0]
        for k in range(svc.slots_per_group):
            tables, joins = host_load(plan, state["pre"], post, batch, k)
            want[0] += tables[0] + tables[2]
            want[1] += tables[1] + tables[3]
            want[2] += sum(j[0] * j[1] for j in joins)
            want[3] += sum(j[4] * j[5] for j in joins)
            want[4] += sum(j[2] * j[3] for j in joins)
        got = [info.live_rows, info.capacity_rows, info.live_pairs,
               info.capacity_pairs, info.swept_pairs]
        assert got == want, (info.tick, got, want)
        infos.append(info)
        state["pre"] = post

    svc.serve_stream(edges, on_tick=on_tick, **BATCH)
    assert len(infos) == len(edges) // 16
    assert max(i.live_rows for i in infos) > 0
    assert max(i.live_pairs for i in infos) > 0
    assert all(0 <= i.live_rows <= i.capacity_rows for i in infos)
    assert all(0 <= i.live_pairs <= i.capacity_pairs for i in infos)
    assert all(i.live_pairs <= i.swept_pairs <= i.capacity_pairs
               for i in infos)
    # expiry leaves holes below the extents: the kernel sweeps them too
    assert any(i.swept_pairs > i.live_pairs for i in infos)
    # per slot, the fixed-batch ingest path returns the same counters
    pre = jax.device_get(g.sstate)
    (batch,) = to_batches(stream(16, seed=9), 16)
    out = svc.ingest(batch)
    post = jax.device_get(g.sstate)
    for k, qid in enumerate(g.qids):
        if qid is None:
            continue
        tables, joins = host_load(plan, pre, post, batch, k)
        load = TickLoad.unpack(jax.device_get(out[qid].load))
        assert (int(load.level_live), int(load.level_cap),
                int(load.l0_live), int(load.l0_cap)) == tables
        assert list(zip(*(x.tolist() for x in (
            load.join_live_a, load.join_live_b, load.join_ext_a,
            load.join_ext_b, load.join_cap_a, load.join_cap_b)))) == joins


def test_tick_load_gauges():
    obs = MetricsRegistry()
    svc = ContinuousSearchService(slots_per_group=2, obs=obs,
                                  backend=JoinBackend.REF,
                                  tick_cache=SlotTickCache(), **CAP)
    svc.register(chain2(), 20)
    infos = []
    svc.serve_stream(stream(64), on_tick=infos.append, **BATCH)
    snap = obs.snapshot()
    assert snap["tick.live_rows"] == infos[-1].live_rows > 0
    assert snap["tick.capacity_rows"] == infos[-1].capacity_rows \
        == 2 * 2 * CAP["level_capacity"]


def test_tick_load_agrees_on_a_mesh():
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src"), str(root / "tests")])
    proc = subprocess.run(
        [sys.executable, str(root / "tests" / "_load_mesh_check.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "LOAD-MESH-OK" in proc.stdout


def test_mesh_tick_span_carries_collectives_and_replica_rows():
    svc = ShardedSearchService(n_replicas=1, slots_per_replica=2,
                               backend=JoinBackend.REF,
                               tick_cache=SlotTickCache(), **CAP)
    plain = ContinuousSearchService(slots_per_group=2,
                                    backend=JoinBackend.REF,
                                    tick_cache=SlotTickCache(), **CAP)
    spans = {}
    for name, s in (("mesh", svc), ("plain", plain)):
        s.tracer, buf = memory_tracer()
        s.register(chain2(), 20)
        s.register(split_query(), 20)
        s.serve_stream(stream(96), **BATCH)
        s.tracer.flush()
        spans[name] = [json.loads(x) for x in buf.getvalue().splitlines()]
    ticks = [s for s in spans["mesh"] if s["span"] == "tick"]
    assert ticks
    for tick in ticks:
        kids = [s for s in spans["mesh"] if s["parent"] == tick["id"]]
        deliver = next(s for s in kids if s["span"] == "tick.deliver")
        events = [s for s in kids if s["span"] == "mesh.collectives"]
        assert sorted(e["gid"] for e in events) == [0, 1]
        assert tick["mesh_matches"] == deliver["n_matches"] \
            == sum(e["n_matches"] for e in events)
        assert tick["mesh_live_rows"] == tick["live_rows"]
        assert tick["replica_live_rows"] == [tick["live_rows"]]
        assert tick["replica_capacity_rows"] == [tick["capacity_rows"]]
    assert any(t["mesh_matches"] for t in ticks)
    assert not [s for s in spans["plain"]
                if "replica_live_rows" in s or s["span"] == "mesh.collectives"]


# ------------------------------------------------------------------ #
# ingest hold time
# ------------------------------------------------------------------ #
def test_ingest_hold_ms_on_scripted_rounds():
    """Two sources, two pump rounds 10 ms apart.  Round 1 takes in a
    record from each (ts 1 and 5); the watermark (min of the sources'
    highs) releases ts 1 only.  Round 2 takes in ts 7 and 9 and releases
    ts 5 and 7.  With clock reads pump, take, pump, take at 0, 0.004,
    0.010 and 0.013 s: holds 4 ms, then 13 and 3 ms."""
    e = lambda ts: DataEdge(0, 1, ts, 0, 1, 0)
    a = ScriptedSource("a", [(0, e(1)), (1, e(7)), (2, e(20))])
    b = ScriptedSource("b", [(0, e(5)), (1, e(9)), (2, e(30))])
    times = iter([0.0, 0.004, 0.010, 0.013])
    fr = IngestFrontier([a, b], allowed_lateness=0, sleep=lambda d: None,
                        clock=lambda: next(times))
    obs = MetricsRegistry()
    fr.pump(1)
    assert [x.ts for x in fr.take_ready()] == [1]
    assert fr.last_holds_ms == pytest.approx([4.0])
    fr.publish_obs(obs)
    fr.pump(1)
    assert [x.ts for x in fr.take_ready()] == [5, 7]
    assert fr.last_holds_ms == pytest.approx([13.0, 3.0])
    fr.publish_obs(obs)
    fr.publish_obs(obs)                        # holds are published once
    h = obs.histogram("ingest.hold_ms")
    assert h.count == 3 and h.exact
    assert h.quantile(0.99) == pytest.approx(13.0)
    assert h.quantile(0.5) == pytest.approx(4.0)


# ------------------------------------------------------------------ #
# engine scopes
# ------------------------------------------------------------------ #
def test_slot_tick_hlo_carries_every_engine_scope():
    plan = compile_plan(split_query(), 50, **CAP)
    tick = build_slot_tick(plan, backend=JoinBackend.REF)
    z = np.zeros(16, np.int32)
    batch = make_batch(src=z, dst=z, ts=z, src_label=z, dst_label=z,
                       edge_label=z, valid=np.zeros(16, bool))
    text = jax.jit(tick).lower(init_slot_state(plan, 2), batch,
                               jnp.int32(0)).as_text(debug_info=True)
    missing = [s for s in ENGINE_SCOPES if s not in text]
    assert not missing
