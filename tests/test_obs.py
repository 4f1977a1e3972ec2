"""The observability layer's contract, proved differentially.

Four claims, each load-bearing for the tentpole:

1. ``repro.obs.percentile`` is byte-identical to the nearest-rank
   formulas the benches used inline before the layer existed — the
   dedupe (bench_ingest/bench_mesh now import it) changed no numbers.
2. ``Histogram`` percentiles are EXACT while the ring holds every
   sample, and degrade to one-bucket-bound estimates after a
   manifest-only restore — never silently wrong.
3. Instrumentation is free when off and inert when on: serving the same
   stream with and without obs+tracer yields identical match multisets
   and ZERO additional jit builds or per-tick compile-cache entries —
   metrics never reach traced code (the TRC107 lint proves the static
   side; this proves the dynamic side).
4. The trace JSONL round-trips through the ``python -m repro.obs``
   summarize CLI, and drop-driven DEGRADED session health survives
   checkpoint/restore via the registry's counter history.
"""

from __future__ import annotations

import json
from collections import Counter as MultiSet

import numpy as np
import pytest

from repro.core.join import JoinBackend
from repro.core.multi import SlotTickCache
from repro.core.oracle import DataEdge
from repro.core.query import QueryGraph
from repro.obs import (
    DEFAULT_LATENCY_BUCKETS_MS, NULL_SPAN, Histogram, MetricsRegistry,
    Tracer, maybe_span, memory_tracer, percentile, summarize_trace,
    to_prometheus)
from repro.obs.summarize import main as summarize_main
from repro.runtime.service import ContinuousSearchService
from repro.stream.generator import StreamConfig, synth_traffic_stream

CAP = dict(level_capacity=256, l0_capacity=256, max_new=64)


def _chain():
    return QueryGraph(3, (0, 1, 2), ((0, 1), (1, 2)),
                      prec=frozenset({(0, 1)}))


def _stream(n=400, seed=11):
    return synth_traffic_stream(StreamConfig(
        n_edges=n, n_vertices=50, n_vertex_labels=3, n_edge_labels=4,
        seed=seed, ts_step_max=2))


# ------------------------------------------------------------------ #
# 1. the shared percentile formula IS the old inline bench math
# ------------------------------------------------------------------ #
def test_percentile_matches_inline_bench_formulas():
    rng = np.random.default_rng(5)
    for n in (1, 2, 3, 10, 101, 256):
        lat = rng.exponential(10.0, n).tolist()
        srt = sorted(lat)
        for q in (0.0, 0.5, 0.9, 0.99, 1.0):
            # bench_ingest's inline pick() before the dedupe
            assert percentile(lat, q) == float(srt[min(n - 1, int(q * n))])
        # bench_mesh's inline median before the dedupe
        assert percentile(lat, 0.5) == float(srt[n // 2])
    assert percentile([], 0.5) == 0.0
    with pytest.raises(ValueError):
        percentile([1.0], 1.5)


# ------------------------------------------------------------------ #
# 2. histogram: exact while ring-complete, bounded after restore
# ------------------------------------------------------------------ #
def test_histogram_exact_then_bucket_fallback_after_restore():
    rng = np.random.default_rng(9)
    lats = rng.exponential(8.0, 500).tolist()
    reg = MetricsRegistry()
    h = reg.histogram("tick.latency_ms")
    for v in lats:
        h.observe(v)
    assert h.exact and h.count == len(lats)
    for q in (0.5, 0.9, 0.99):
        assert h.quantile(q) == percentile(lats, q)
    assert h.mean == pytest.approx(sum(lats) / len(lats))

    # manifest round-trip: counts/buckets survive, raw samples do not —
    # quantiles become bucket UPPER bounds, within one bucket step
    # (10^(1/4) ~ 1.79x) above the exact value
    reg2 = MetricsRegistry()
    reg2.load_manifest(reg.to_manifest())
    h2 = reg2.histogram("tick.latency_ms")
    assert h2.count == len(lats) and not h2.exact
    assert np.array_equal(h2.counts, h.counts)
    step = 10 ** 0.25
    for q in (0.5, 0.9, 0.99):
        exact = percentile(lats, q)
        est = h2.quantile(q)
        assert exact <= est <= exact * step * 1.001

    # counters restore monotonically (set_total never double-counts)
    reg.counter("ingest.n_late_dropped").inc(7)
    reg2.load_manifest(reg.to_manifest())
    reg2.load_manifest(reg.to_manifest())
    assert reg2.counter("ingest.n_late_dropped").value == 7


def test_histogram_ring_eviction_flips_to_estimate():
    h = Histogram("x", ring_size=8)
    for v in range(20):
        h.observe(float(v) + 0.5)
    assert not h.exact and h.count == 20
    # estimate is a valid bucket upper bound for the true p50 (9.5)
    est = h.quantile(0.5)
    assert est in DEFAULT_LATENCY_BUCKETS_MS and est >= 9.5


# ------------------------------------------------------------------ #
# 3. the on/off differential: same matches, zero extra compiles
# ------------------------------------------------------------------ #
def _serve(tc, obs=None, tracer=None):
    svc = ContinuousSearchService(
        slots_per_group=2, backend=JoinBackend.REF, tick_cache=tc,
        obs=obs, tracer=tracer, **CAP)
    svc.register(_chain(), 20)
    svc.register(_chain(), 20)
    matches = MultiSet()

    def on_match(qid, bindings, ets):
        for row, et in zip(np.asarray(bindings), np.asarray(ets)):
            matches[(qid, tuple(int(b) for b in row),
                     tuple(int(t) for t in et))] += 1

    svc.serve_stream(_stream(), on_match=on_match, batch_size=32,
                     min_batch=32, max_batch=32)
    return svc, matches


def test_instrumentation_differential_on_vs_off():
    tc = SlotTickCache()
    _serve(tc)                                   # compile + warm
    builds_warm = tc.n_builds
    cache_sizes_warm = [t._cache_size() for t in tc.ticks()]

    _, matches_off = _serve(tc)                  # bare, fully warm
    obs = MetricsRegistry()
    tracer, sink = memory_tracer()
    svc_on, matches_on = _serve(tc, obs=obs, tracer=tracer)
    tracer.flush()

    # oracle identity: instrumentation changed no match, no multiplicity
    assert matches_on == matches_off and sum(matches_on.values()) > 0
    # zero additional XLA work: no new builds, no new per-tick
    # compile-cache entries anywhere in the shared cache
    assert tc.n_builds == builds_warm
    assert [t._cache_size() for t in tc.ticks()] == cache_sizes_warm

    # the histogram saw exactly the served ticks, and its percentiles
    # are the exact nearest-rank numbers
    h = obs.histogram("tick.latency_ms")
    assert h.count == svc_on.n_ticks > 0 and h.exact
    assert h.quantile(0.5) == percentile(h.samples().tolist(), 0.5)
    snap = obs.snapshot()
    assert snap["tick.n_ticks"] == svc_on.n_ticks
    assert snap["tick.n_edges"] == svc_on.n_edges_ingested
    assert snap["tick.n_matches"] == sum(matches_on.values())

    # every span carries a tick correlation id covering all ticks; one
    # dispatch span per tick, and no forest span without a forest
    lines = [json.loads(ln) for ln in sink.getvalue().splitlines()]
    names = {ln["span"] for ln in lines}
    assert names >= {
        "serve.round", "tick", "tick.dispatch", "tick.barrier",
        "tick.deliver", "tick.readback", "tick.callbacks",
        "coalescer.decision"}
    assert not names & {"tick.forest", "tick.slot_dispatch"}
    assert sum(ln["span"] == "tick.dispatch" for ln in lines) \
        == svc_on.n_ticks
    assert max(ln["tick"] for ln in lines) == svc_on.n_ticks


# ------------------------------------------------------------------ #
# 4a. trace JSONL -> summarize CLI round-trip
# ------------------------------------------------------------------ #
def test_trace_summarize_cli_roundtrip(tmp_path, capsys):
    path = tmp_path / "trace.jsonl"
    with Tracer(str(path)) as tr:
        for _ in range(3):
            tr.next_tick()
            with tr.span("tick"):
                with tr.span("tick.barrier", n_groups=2):
                    pass
        tr.event("mesh.collectives", gid=0)

    s = summarize_trace(str(path))
    assert s["n_ticks"] == 3 and s["n_bad_lines"] == 0
    assert s["spans"]["tick.barrier"]["count"] == 3
    assert s["spans"]["tick"]["count"] == 3
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert s["spans"]["tick.barrier"]["p50_ms"] == round(percentile(
        [r["ms"] for r in recs if r["span"] == "tick.barrier"], 0.5), 4)

    assert summarize_main(["summarize", str(path)]) == 0
    out = capsys.readouterr().out
    assert "tick.barrier" in out
    assert f"{s['n_spans']} spans over 3 ticks" in out
    assert summarize_main([]) == 2          # usage error is loud


def test_tracer_off_costs_nothing_and_memory_sink():
    # off: one shared no-op span, whatever the name
    assert maybe_span(None, "a") is maybe_span(None, "b") is NULL_SPAN
    with maybe_span(None, "a") as s:
        assert s is NULL_SPAN
    # on: spans stay in memory until flush
    tr, sink = memory_tracer()
    with tr.span("a", k=1):
        pass
    assert sink.getvalue() == ""
    tr.close()
    (line,) = sink.getvalue().splitlines()
    d = json.loads(line)
    assert d["span"] == "a" and d["k"] == 1 and d["parent"] is None
    assert d["ms"] == (d["end_ns"] - d["start_ns"]) / 1e6 >= 0


# ------------------------------------------------------------------ #
# 4b. prometheus exposition smoke
# ------------------------------------------------------------------ #
def test_prometheus_export_shapes():
    reg = MetricsRegistry()
    reg.counter("tick.n_ticks").inc(4)
    reg.gauge("ingest.watermark").set(17)
    reg.register_gauge("share.n_nodes", lambda: 3)
    h = reg.histogram("tick.latency_ms")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    text = to_prometheus(reg)
    assert "repro_tick_n_ticks 4" in text
    assert "repro_ingest_watermark 17" in text
    assert "repro_share_n_nodes 3" in text
    assert 'repro_tick_latency_ms_bucket{le="+Inf"} 3' in text
    assert "repro_tick_latency_ms_count 3" in text
    assert 'repro_tick_latency_ms{quantile="0.5"} 2.0' in text


# ------------------------------------------------------------------ #
# 4c. DEGRADED health attribution survives checkpoint/restore
# ------------------------------------------------------------------ #
def test_session_degraded_health_survives_restore(tmp_path):
    from repro.api.session import ACTIVE, DEGRADED, StreamSession

    tc = SlotTickCache()
    sess = StreamSession(backend=JoinBackend.REF, tick_cache=tc,
                         ckpt_dir=str(tmp_path), **CAP)
    sess.register_query(_chain(), window=20)
    assert sess.status().health == ACTIVE

    # a script longer than one 64-event poll whose last event is ancient:
    # it surfaces on the SECOND pump round, after the merged emit floor
    # passed it — a guaranteed late drop under zero allowed lateness
    from repro.stream.ingest import ScriptedSource
    script = [(i, DataEdge(i % 7, i % 7 + 1, 10 + i, 0, 1, 0))
              for i in range(64)] + [(64, DataEdge(0, 1, 1, 0, 1, 0))]
    fr = sess.sources({"s": ScriptedSource("s", script)},
                      allowed_lateness=0, sleep=lambda d: None)
    sess.serve_frontier(fr, batch_size=8)
    st = sess.status()
    assert st.n_late_dropped >= 1 and st.health == DEGRADED

    sess.checkpoint()
    sess.close()

    restored = StreamSession.restore(str(tmp_path), tick_cache=tc)
    st2 = restored.status()
    # no frontier is bound yet the restored registry still attributes
    # the drops — health must NOT reset to ACTIVE
    assert st2.n_late_dropped == st.n_late_dropped
    assert st2.health == DEGRADED
    assert restored.metrics()["ingest.n_late_dropped"] >= 1
    assert "repro_ingest_n_late_dropped" in restored.prometheus()
