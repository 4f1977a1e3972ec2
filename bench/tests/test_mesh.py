"""A configuration with ``service.replicas`` runs on the replica-sharded
service: the four-chip path, rehearsed on four virtual CPU devices in a
process of its own (the device count is fixed when JAX starts)."""

import json
import os
import subprocess
import sys

from bench import spec

SCRIPT = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/bench/tests"]
from conftest import tiny
from bench import harness
cell = tiny("netflow-w1", "saturate")
cell.chips = 4
cell.config["service"].update(replicas=4, slots=1)
r = harness.run_cell(cell, 19, 2.0, False, time.perf_counter(),
                     require_tpu=False)
print(json.dumps({"correct": r["correct"], "count": r["device"]["count"],
                  "replicas": type(harness.build_session(cell.config)
                                   .service).__name__}))
"""


def test_replicas_run_sharded_and_correct():
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    p = subprocess.run([sys.executable, "-c", SCRIPT, spec.ROOT],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"correct": True, "count": 4,
                   "replicas": "ShardedSearchService"}
