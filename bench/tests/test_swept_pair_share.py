"""The ``kernel.swept_pair_share`` reader, beside the other readers of
the program's ``tick`` counters (``test_span_metrics.py``): a known
answer on hand-made spans, nothing on spans of a program without the
``swept_pairs`` counter, and on the spans of a tiny run on the CPU the
window's Σ swept / Σ capacity pairs, at least the live pair share."""

import io
import json

from bench import harness, spec
from bench.harness import MetricContext

read = spec.metric_reader("kernel.swept_pair_share")


def test_known_answer():
    spans = [
        {"span": "tick", "live_pairs": 3, "capacity_pairs": 300,
         "swept_pairs": 12},
        {"span": "tick", "live_pairs": 27, "capacity_pairs": 300,
         "swept_pairs": 48},
        {"span": "tick.readback", "ms": 2.0},
    ]
    ctx = MetricContext(trace=None, spans=spans, n_ticks=2, tick_ms=[])
    assert read(ctx) == 0.1


def test_a_program_without_the_counter_reads_nothing():
    old = [{"span": "tick", "live_pairs": 3, "capacity_pairs": 300},
           {"span": "tick.deliver", "ms": 5.0}]
    for spans in (old, []):
        ctx = MetricContext(trace=None, spans=spans, n_ticks=1, tick_ms=[])
        assert read(ctx) is None


def test_spans_of_a_tiny_run(tiny_cell):
    from repro.obs import Tracer

    run = harness.Run(tiny_cell("netflow-w1", "saturate"), 11,
                      require_tpu=False)
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
    cap = sum(i.capacity_pairs for i in infos)
    swept = read(ctx)
    assert swept == sum(i.swept_pairs for i in infos) / cap
    live = spec.metric_reader("kernel.live_pair_share")(ctx)
    assert 0 < live <= swept < 1
