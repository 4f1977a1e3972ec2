"""Tests of the benchmark itself, run by path: ``pytest bench/tests``.
They run on the CPU: the harness's look for a chip is skipped by calling
its internals, and tables and tenants are cut to a test's size."""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import spec  # noqa: E402


def tiny(config: str, mix: str, *, knee: float = 400.0) -> spec.Cell:
    """A cell of the named configuration and mix at a test's size: two
    tenants on each of a few patterns, 2-slot groups, small tables and
    256-edge ticks, 4 capture points."""
    cfg = copy.deepcopy(spec.load_json(
        os.path.join(ROOT, "bench", "configs", f"{config}.json")))
    cfg["patterns"] = [dict(p, tenants=2) for p in cfg["patterns"][::5]]
    cfg["n_tenants"] = 2 * len(cfg["patterns"])
    cfg["service"].update(slots=2, level_capacity=4096, l0_capacity=4096,
                          max_new=1024, batch=256)
    cfg["sources"].update(n_sources=4)
    cfg["knee_edges_per_s"] = knee
    m = spec.load_json(os.path.join(ROOT, "bench", "mixes", f"{mix}.json"))
    e2e = [{"name": "setup_s", "unit": "s"}]
    e2e += ([{"name": "edges_per_s", "unit": "edges/s"}]
            if "schedule" not in m else
            [{"name": "match_latency_p50_ms", "unit": "ms"},
             {"name": "match_latency_p99_ms", "unit": "ms"}])
    return spec.Cell(f"{config}.{mix}", 1, cfg, m, e2e, [])


@pytest.fixture
def tiny_cell():
    return tiny
