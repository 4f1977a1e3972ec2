"""The four-chip cell ``netflow-w1-x4.saturate`` and the metrics of its
``mesh`` layer.

On four virtual CPU devices, in a process of its own (the device count
is fixed when JAX starts), a cell cut to a test's size from
``bench/configs/netflow-w1-x4.json`` itself runs through
``harness.run_cell`` on ``ShardedSearchService`` and is correct; traced
ticks of the same cell, with one tenant of each walk on each replica,
read ``mesh.replica_row_imbalance`` 0.  The readers also get known
answers from hand-made trace summaries and spans, and nothing from a
program without the mesh's counters.
"""

import json
import os
import subprocess
import sys

import pytest

from bench import spec
from bench.harness import MetricContext

SCRIPT = r"""
import io, json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/bench/tests"]
from conftest import tiny
from bench import harness, spec
from repro.obs import Tracer


def cell():
    # one tenant of each walk on each of the 4 replicas (round robin)
    c = tiny("netflow-w1-x4", "saturate")
    c.chips = c.config["service"]["replicas"]
    c.config["patterns"] = [dict(p, tenants=4) for p in c.config["patterns"]]
    c.config["n_tenants"] = 4 * len(c.config["patterns"])
    return c


r = harness.run_cell(cell(), 2147483659, 2.0, False, time.perf_counter(),
                     require_tpu=False)
run = harness.Run(cell(), 23, require_tpu=False)
buf = io.StringIO()
run.svc.tracer = Tracer(buf)
infos = []


def on_tick(info):
    infos.append(info)
    if len(infos) == 4:
        raise harness.StopRun


run.serve(on_tick)
run.svc.tracer.flush()
spans = [json.loads(x) for x in buf.getvalue().splitlines()]
ctx = harness.MetricContext(trace=None, spans=spans, n_ticks=len(infos),
                            tick_ms=[])
ticks = [s for s in spans if s["span"] == "tick"]
print(json.dumps({
    "correct": r["correct"], "checks": r["checks"],
    "count": r["device"]["count"],
    "service": type(run.svc).__name__,
    "replicas": run.svc.n_replicas,
    "imbalance": spec.metric_reader("mesh.replica_row_imbalance")(ctx),
    "replica_live_rows": [t["replica_live_rows"] for t in ticks],
    "live_rows": [i.live_rows for i in infos]}))
"""


@pytest.fixture(scope="module")
def x4_run():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", SCRIPT, spec.ROOT],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_x4_cell_runs_sharded_and_correct(x4_run):
    assert x4_run["correct"], x4_run["checks"]
    assert x4_run["count"] == 4 == x4_run["replicas"]
    assert x4_run["service"] == "ShardedSearchService"


def test_x4_replicas_hold_equal_rows(x4_run):
    rows = x4_run["replica_live_rows"]
    assert len(rows) == len(x4_run["live_rows"]) == 4
    assert [sum(r) for r in rows] == x4_run["live_rows"]
    assert all(len(set(r)) == 1 for r in rows) and rows[-1][0] > 0
    assert x4_run["imbalance"] == 0.0


def test_x4_config_is_netflow_w1_on_four_chips():
    """Every size but the tenant count and the service's replicas is
    ``netflow-w1``'s, and the guarantees are the same."""
    load = lambda n: spec.load_json(
        os.path.join(spec.ROOT, "bench", "configs", f"{n}.json"))
    one, four = load("netflow-w1"), load("netflow-w1-x4")
    same = ("window", "traffic", "queries", "sources", "frontier",
            "guarantees")
    assert all(one[k] == four[k] for k in same)
    assert four["service"] == dict(one["service"], replicas=4)
    assert four["n_tenants"] == 4 * one["n_tenants"] == 4096
    assert [dict(p, tenants=64) for p in four["patterns"]] == one["patterns"]
    assert all(p["tenants"] == 256 for p in four["patterns"])
    assert list(four["reduced"]) == ["n_tenants"]
    cell = spec.resolve("netflow-w1-x4.saturate")
    assert cell.chips == 4 and cell.mix == spec.resolve(
        "netflow-w1.saturate").mix
    assert [m["name"] for m in cell.end_to_end] == ["edges_per_s",
                                                    "setup_s"]


def collective_ms(trace, n_ticks=2):
    ctx = MetricContext(trace=trace, spans=[], n_ticks=n_ticks, tick_ms=[])
    return spec.metric_reader("mesh.collective_ms_per_tick")(ctx)


def test_collective_ms_known_answers():
    by_opcode = {"all-reduce": 1e6, "all-reduce-start": 2e6,
                 "all-reduce-done": 3e6, "all-gather-start": 4e6,
                 "all-gather-done": 5e6, "collective-permute": 6e6,
                 "reduce-scatter": 7e6, "all-to-all": 8e6,
                 "fusion": 1e9, "custom-call": 1e9, "copy-start": 1e9,
                 "all-reduce-scatter-fusion": 1e9}
    assert collective_ms({"by_opcode_ns": by_opcode}) == 36 / 2
    assert collective_ms({"by_opcode_ns": {"all-reduce-done": 5e5}},
                         n_ticks=1) == 0.5


@pytest.mark.parametrize("trace", [None, {"by_opcode_ns": {}},
                                   {"by_opcode_ns": {"fusion": 1e9}}],
                         ids=["no_trace", "no_ops", "no_collectives"])
def test_collective_ms_nothing_to_read(trace):
    assert collective_ms(trace) is None


def imbalance(spans):
    ctx = MetricContext(trace=None, spans=spans, n_ticks=len(spans),
                        tick_ms=[])
    return spec.metric_reader("mesh.replica_row_imbalance")(ctx)


def tick(rows, **kw):
    return {"span": "tick", "ms": 1.0, "live_rows": sum(rows),
            "replica_live_rows": rows, **kw}


def test_replica_row_imbalance_known_answers():
    even = [tick([10, 10, 10, 10]), tick([5, 5, 5, 5])]
    assert imbalance(even) == 0.0
    # per replica over the ticks: 40, 20, 20, 20 against a mean of 25
    uneven = [tick([30, 10, 10, 10]), tick([10, 10, 10, 10]),
              {"span": "tick.deliver", "ms": 1.0, "n_matches": 3}]
    assert imbalance(uneven) == pytest.approx(0.6)
    # a replica short in one tick and long in another evens out
    assert imbalance([tick([4, 2]), tick([2, 4])]) == 0.0


def test_replica_row_imbalance_nothing_to_read():
    one_chip = [{"span": "tick", "ms": 1.0, "live_rows": 7,
                 "capacity_rows": 9}]
    assert imbalance(one_chip) is None
    assert imbalance([]) is None
    assert imbalance([tick([0, 0, 0, 0])]) is None
