"""The open-loop arrivals: records displaced across the end of the
prefill still reach their capture point with a creation stamp, a record
reaches its queue only once it is due, and arrivals follow the rate
schedule, repeated in a cycle."""

import os
import time

import pytest

from bench import spec
from bench.feed import OpenLoop, Scatter
from bench.traffic import make


def loop_of(frac=0.3, schedule=((20000.0, 60.0),), seed=4):
    cfg = spec.load_json(os.path.join(spec.BENCH, "configs",
                                      "netflow-w1.json"))
    sources = dict(cfg["sources"], disorder_frac=frac)
    return OpenLoop(Scatter(make(cfg["traffic"], seed), sources, seed),
                    list(schedule), seed)


def test_prefill_then_open_loop_delivers_everything_stamped():
    loop = loop_of()
    loop.prefill(300)
    n_prefill = loop.queued
    assert n_prefill > 0
    loop.start()
    time.sleep(0.5)
    loop.release()
    got = [e for q in loop.queues for _, e in q]
    assert len(got) > n_prefill + 1000
    assert all(e[:3] in loop.created for e in got)
    for q in loop.queues:
        seqs = [seq for seq, _ in q]
        assert len(set(seqs)) == len(seqs)


def test_release_takes_only_what_is_due():
    loop = loop_of(schedule=[(1000.0, 60.0)])
    loop.start()
    t0 = loop.t0
    time.sleep(0.2)
    before = time.perf_counter()
    loop.release()
    after = time.perf_counter()
    got = [e for q in loop.queues for _, e in q]
    assert 100 < len(got) < 400
    assert all(loop.created[e[:3]] <= after for e in got)
    assert all(due > before for due, *_ in loop._plan)
    assert min(loop.created[e[:3]] for e in got) >= t0


def test_arrivals_step_through_the_schedule_in_a_cycle():
    loop = loop_of(schedule=[(100.0, 10.0), (1000.0, 10.0), (0.0, 5.0)])
    at = loop._arrivals(0.0, 24000)
    assert all(b > a for a, b in zip(at, at[1:]))
    assert 850 < sum(at < 10.0) < 1150
    assert 9500 < sum((at >= 10.0) & (at < 20.0)) < 10500
    assert sum((at >= 20.0) & (at < 25.0)) == 0
    assert 850 < sum((at >= 25.0) & (at < 35.0)) < 1150
    assert 9500 < sum((at >= 35.0) & (at < 45.0)) < 10500
    assert sum((at >= 45.0) & (at < 50.0)) == 0


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 99])
def test_same_seed_same_arrivals(seed):
    a = loop_of(seed=seed)._arrivals(0.0, 500)
    b = loop_of(seed=seed)._arrivals(0.0, 500)
    assert (a == b).all()


def test_steady_mix_offers_every_seed_the_same_arrivals():
    """The steady mix draws its arrival times from ``arrival_seed``, so two
    run seeds create every record at the same time after the start, and
    differ only in which capture point delivers it, and when."""
    mix = spec.load_json(os.path.join(spec.BENCH, "mixes", "steady.json"))
    cfg = spec.load_json(os.path.join(spec.BENCH, "configs",
                                      "netflow-w1.json"))
    made, points = [], []
    for seed in (3, 2 ** 31 + 99):
        loop = OpenLoop(Scatter(make(cfg["traffic"], mix["content_seed"]),
                                cfg["sources"], seed),
                        [(104.0, 60.0)], mix["arrival_seed"])
        loop.start()
        for _ in range(3):
            loop._plan_block()
        made.append({e[:3]: t - loop.t0 for *_, e, t in loop._plan})
        points.append({e[:3]: s for _, s, _, e, _ in loop._plan})
    common = made[0].keys() & made[1].keys()
    assert len(common) > 3000
    # equal but for the rounding of adding and taking away the start
    assert all(abs(made[0][k] - made[1][k]) < 1e-6 for k in common)
    assert sum(points[0][k] != points[1][k] for k in common) > 1000
