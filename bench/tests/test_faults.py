"""The whole run, with the timed path broken underneath, comes out not
correct: once per fault a one-chip cell can have.  (The exchange between
chips belongs to a four-chip cell, and none is in the benchmark yet.)"""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro.api.session import Subscription
from repro.runtime.service import ContinuousSearchService as Service


def stuck_state(monkeypatch):
    """A tick that returns its state unchanged."""
    tick = Service._advance_group

    def advance(self, g, batch, *args, **kw):
        keep = jax.tree.map(jnp.copy, g.sstate)
        res = tick(self, g, batch, *args, **kw)
        g.sstate = keep
        return res
    monkeypatch.setattr(Service, "_advance_group", advance)


def half_batch(monkeypatch):
    """Half of each tick's batch left out."""
    tick = Service._tick_chunk

    def tick_chunk(self, chunk, *args, **kw):
        return tick(self, chunk[:len(chunk) // 2], *args, **kw)
    monkeypatch.setattr(Service, "_tick_chunk", tick_chunk)


def altered_answer(monkeypatch):
    """One bound vertex of each delivered batch altered where the match
    rows are turned into matches."""
    deliver = Subscription._deliver_rows

    def deliver_rows(self, bindings, ets):
        bindings = bindings.copy()
        bindings[0, 0] += 1
        return deliver(self, bindings, ets)
    monkeypatch.setattr(Subscription, "_deliver_rows", deliver_rows)


@pytest.mark.parametrize("fault", [stuck_state, half_batch, altered_answer])
def test_fault_is_not_correct(tiny_cell, monkeypatch, fault):
    fault(monkeypatch)
    r = harness.run_cell(tiny_cell("netflow-w1", "saturate"), 77, 3.0,
                         False, time.perf_counter(), require_tpu=False)
    assert not r["correct"]
    assert r["checks"]["missing_matches"]["value"] > 0
