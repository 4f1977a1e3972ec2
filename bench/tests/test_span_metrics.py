"""The per-layer metrics read from the program's own spans and counters
(``tick`` live-row counters, ``tick.readback``, ``tick.callbacks``,
``ingest.release`` holds): known answers on hand-made spans, nothing on
spans that lack them (a program without these counters), and sound
values on the spans of a tiny run on the CPU."""

import io
import json

import pytest

from bench import harness, spec, stats
from bench.harness import MetricContext

NEW = ("engine.live_row_share", "kernel.live_pair_share",
       "service.readback_ms_per_tick", "api.deliver_ms_per_tick",
       "ingest.hold_ms.p99")


def reader(name):
    return spec.metric_reader(name)


def test_known_answers():
    spans = [
        {"span": "tick", "ms": 9.0, "live_rows": 10, "capacity_rows": 40,
         "live_pairs": 3, "capacity_pairs": 300},
        {"span": "tick", "ms": 9.0, "live_rows": 30, "capacity_rows": 40,
         "live_pairs": 27, "capacity_pairs": 300},
        {"span": "tick.readback", "ms": 2.0},
        {"span": "tick.readback", "ms": 4.0},
        {"span": "tick.callbacks", "ms": 1.0},
        {"span": "ingest.release", "ms": 0.1, "hold_ms": [1.0, 5.0]},
        {"span": "ingest.release", "ms": 0.1, "hold_ms": []},
        {"span": "ingest.release", "ms": 0.1, "hold_ms": [3.0]},
    ]
    ctx = MetricContext(trace=None, spans=spans, n_ticks=2, tick_ms=[])
    assert reader("engine.live_row_share")(ctx) == 0.5
    assert reader("kernel.live_pair_share")(ctx) == 0.05
    assert reader("service.readback_ms_per_tick")(ctx) == 3.0
    assert reader("api.deliver_ms_per_tick")(ctx) == 0.5
    assert reader("ingest.hold_ms.p99")(ctx) == 5.0


def test_nothing_to_read_is_none():
    """Spans as a program without the counters writes them."""
    old = [{"tick": 1, "span": "tick.deliver", "ms": 5.0, "t0": 1.0},
           {"tick": 1, "span": "ingest.release", "ms": 1.0,
            "n_released": 4},
           {"tick": 1, "span": "tick.slot_dispatch", "ms": 1.0, "gid": 0}]
    for spans in (old, []):
        ctx = MetricContext(trace=None, spans=spans, n_ticks=1, tick_ms=[])
        assert all(reader(m)(ctx) is None for m in NEW)


@pytest.mark.parametrize("mix", ["saturate", "steady"])
def test_program_spans_of_a_tiny_run(tiny_cell, mix):
    from repro.obs import Tracer

    cell = tiny_cell("netflow-w1", mix)
    rates = None
    if "schedule" in cell.mix:
        knee = float(cell.config["knee_edges_per_s"])
        rates = [(f * knee, s) for f, s in cell.mix["schedule"]]
    run = harness.Run(cell, 11, rates=rates, require_tpu=False)
    if run.loop is not None:
        run.loop.start()
    buf = io.StringIO()
    run.svc.tracer = Tracer(buf)
    infos = []

    def on_tick(info):
        infos.append(info)
        if len(infos) == 4:
            raise harness.StopRun

    run.serve(on_tick)
    spans = [json.loads(x) for x in buf.getvalue().splitlines()]
    ctx = MetricContext(trace=None, spans=spans, n_ticks=len(infos),
                        tick_ms=[])
    live = reader("engine.live_row_share")(ctx)
    assert live == sum(i.live_rows for i in infos) / sum(
        i.capacity_rows for i in infos)
    assert 0 < live < 1
    assert 0 <= reader("kernel.live_pair_share")(ctx) < 1
    assert reader("service.readback_ms_per_tick")(ctx) > 0
    assert reader("api.deliver_ms_per_tick")(ctx) >= 0
    holds = [h for i in infos for h in i.hold_ms]
    assert len(holds) == sum(i.chunk for i in infos)
    assert reader("ingest.hold_ms.p99")(ctx) == pytest.approx(
        stats.percentile(holds, 0.99), abs=1e-3)
