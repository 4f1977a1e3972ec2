"""Every mix at a test's size on the CPU, through the harness's
internals; and the command itself refuses a host without a TPU."""

import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness, spec


@pytest.mark.parametrize("mix", ["saturate", "steady"])
def test_tiny_run_is_correct(tiny_cell, mix):
    cell = tiny_cell("netflow-w1", mix)
    r = harness.run_cell(cell, 2 ** 31 + 7, 4.0, False, time.perf_counter(),
                         require_tpu=False)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"


def test_kernels_in_interpret_mode_are_correct(tiny_cell):
    from repro.core.join import JoinBackend

    cell = tiny_cell("netflow-w1", "saturate")
    cell.config["service"].update(level_capacity=2048, l0_capacity=2048)
    r = harness.run_cell(cell, 5, 2.0, False, time.perf_counter(),
                         require_tpu=False,
                         backend=JoinBackend.PALLAS_INTERPRET)
    assert r["correct"], r["checks"]


def test_command_refuses_a_host_without_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "netflow-w1.saturate",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=spec.ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
