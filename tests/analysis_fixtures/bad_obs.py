"""Linter fixture: repro.obs emission inside traced scope (TRC107).

Never imported — only parsed by ``tests/test_analysis.py`` to pin the
golden findings of ``repro.analysis.ast_lint``.
"""

import jax

from repro.obs import MetricsRegistry, Tracer

REG = MetricsRegistry()
TR = Tracer("/dev/null")


@jax.jit
def bad_obs_emit(state, x):
    REG.counter("tick.n_ticks").inc()       # TRC107: host cb in jit
    return state + x


def ok_obs_host(reg: MetricsRegistry, lat_ms: float):
    # untraced host code: emission is exactly where it belongs
    reg.histogram("tick.latency_ms").observe(lat_ms)
    TR.event("tick.barrier", ms=lat_ms)
