"""The control (``bench/control.py``) at a test's size: the zero-overflow
guarantee broken, the comparison says not correct."""

import time

from bench import harness
from bench.control import broken


def test_control_is_not_correct(tiny_cell):
    r = harness.run_cell(broken(tiny_cell("netflow-w1", "saturate")),
                         2 ** 32 + 1, 3.0, False, time.perf_counter(),
                         require_tpu=False)
    assert not r["correct"]
    assert r["checks"]["dropped_or_overflowed_edges"]["value"] > 0
    assert r["checks"]["missing_matches"]["value"] > 0
