"""The reduction from a device trace to metrics, on a hand-made record
with known answers and on a slice of a recorded chip trace
(``data/trace_small.json``) against an independent sweep."""

import json
import os
from collections import defaultdict

import pytest

from bench import devtrace, spec
from bench.harness import MetricContext

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


def reader(name):
    return spec.metric_reader(name)


def test_hand_made_record():
    rec = {"devices": {"0": [[0, 10, "fusion", "%f.1 = s32[] fusion()"],
                             [5, 10, "custom-call", "%k.2 = s32[] custom-call()"],
                             [20, 10, "fusion", "%f.1 = s32[] fusion()"],
                             [45, 5, "fusion", "%f.1 = s32[] fusion()"]]},
           "host": [[0, 40, "bench.window"], [16, 3, "bench.poll"],
                    [19, 2, "bench.on_match"]]}
    s = devtrace.summarize(rec)
    assert s["window_ns"] == 40 and s["busy_ns"] == 25
    assert s["by_opcode_ns"] == {"fusion": 20, "custom-call": 10}
    assert s["device_ops"] == [["fusion:%f.1", 20e-9],
                               ["custom-call:%k.2", 10e-9]]
    assert s["idle_gaps"] == [["service (not annotated)", 10e-9],
                              ["poll (60% of the gap)", 5e-9]]
    ctx = MetricContext(trace=s, spans=[], n_ticks=2, tick_ms=[])
    assert reader("device.idle_share")(ctx) == pytest.approx(15 / 40)
    assert reader("kernel.join_ms_per_tick")(ctx) == pytest.approx(5e-6)
    assert reader("engine.table_ms_per_tick")(ctx) == pytest.approx(1e-5)


def test_opcode_from_hlo_text():
    assert devtrace.opcode_of(
        "%copy-start.81 = (pred[64,16384]{1,0:T(8,128)(4,1)S(1)}, "
        "u32[]{:S(2)}) copy-start(pred[64,16384]{1,0:T(8,128)(4,1)} %x)"
    ) == "copy-start"
    assert devtrace.opcode_of(
        "%copy.111 = s32[8,128,8,128]{3,1,2,0:T(8,128)S(1)} copy(s32[8] %b)"
    ) == "copy"
    assert devtrace.opcode_of("%vmap__.3 = (s32[64,16,128]{2,1,0}, "
                              "s32[64,1,128]{2,1,0}) custom-call(s32[64] "
                              "%c), custom_call_target=\"tpu_custom_call\"") \
        == "custom-call"


def sweep_busy(intervals):
    """Busy time by a +1/-1 event sweep: a second way to the union."""
    events = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    busy, depth, since = 0, 0, None
    for t, d in events:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_slice():
    with open(DATA) as f:
        rec = json.load(f)
    lo, hi = devtrace.window_of(rec)
    clipped = [c for s, d, _, _ in rec["devices"]["0"]
               if (c := devtrace.clip(s, s + d, lo, hi))]
    by_opcode = defaultdict(int)
    for s, d, _, name in rec["devices"]["0"]:
        if c := devtrace.clip(s, s + d, lo, hi):
            by_opcode[devtrace.opcode_of(name)] += c[1] - c[0]
    s = devtrace.summarize(rec)
    assert s["busy_ns"] == sweep_busy(clipped)
    assert 0 < s["busy_ns"] < s["window_ns"] == hi - lo
    assert s["by_opcode_ns"] == dict(by_opcode)
    assert by_opcode["custom-call"] > 0 and by_opcode["fusion"] > 0
    # the longest gap is the host delivering matches after the tick
    assert s["idle_gaps"][0][0].startswith("on_match (")
    assert s["idle_gaps"][0][1] > 0.01
    ctx = MetricContext(trace=s, spans=[], n_ticks=3, tick_ms=[])
    assert reader("kernel.join_ms_per_tick")(ctx) == pytest.approx(
        by_opcode["custom-call"] / 1e6 / 3)
    assert reader("engine.table_ms_per_tick")(ctx) == pytest.approx(
        (sum(by_opcode.values()) - by_opcode["custom-call"]) / 1e6 / 3)


def test_span_readers():
    spans = [{"span": "ingest.pump", "ms": 2.0},
             {"span": "ingest.release", "ms": 1.0},
             {"span": "tick.deliver", "ms": 5.0},
             {"span": "tick.barrier", "ms": 100.0},
             {"span": "ingest.pump", "ms": 3.0}]
    ctx = MetricContext(trace=None, spans=spans, n_ticks=2,
                        tick_ms=[30.0, 10.0, 20.0])
    assert reader("ingest.host_ms_per_tick")(ctx) == 3.0
    assert reader("service.deliver_ms_per_tick")(ctx) == 2.5
    assert reader("service.tick_ms.steady")(ctx) == 20.0
    assert reader("device.idle_share")(ctx) is None
    empty = MetricContext(trace=None, spans=[], n_ticks=0, tick_ms=[])
    assert all(reader(m)(empty) is None for m in (
        "ingest.host_ms_per_tick", "service.deliver_ms_per_tick",
        "service.tick_ms.steady", "kernel.join_ms_per_tick"))
