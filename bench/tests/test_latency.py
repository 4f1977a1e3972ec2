"""Event-to-match latency: the completing edge is the match's newest
(among equal timestamps the latest created), and the percentiles are
exact nearest-rank values over every match delivered in the window."""

from collections import namedtuple

import pytest

from bench import harness, stats

Match = namedtuple("Match", "vertices edges")
CHAIN = {"name": "c", "vertices": {"a": 0, "b": 0, "c": 0},
         "edges": [["a", "b", 1], ["b", "c", 2]], "before": [[0, 1]],
         "window": 100}
FREE = {"name": "f", "vertices": {"a": 0, "b": 0, "c": 0},
        "edges": [["a", "b", 1], ["a", "c", 2]], "before": [],
        "window": 100}


def m(a, b, c, t0, t1):
    return Match((("a", a), ("b", b), ("c", c)), (("e0", t0), ("e1", t1)))


def test_completing_edge_and_window():
    created = {(1, 2, 5): 10.0, (2, 3, 9): 11.0,
               (1, 2, 20): 12.0, (1, 4, 20): 12.5}
    tenants = [
        (0, [(11.5, m(1, 2, 3, 5, 9))]),          # newest edge (2, 3, 9)
        (1, [(13.0, m(1, 2, 4, 20, 20)),          # tie at 20: latest made
             (99.0, m(1, 2, 4, 20, 20))]),        # after the window
    ]
    lat = harness.latencies_ms([CHAIN, FREE], tenants, created, 11.0, 20.0)
    assert lat == pytest.approx([500.0, 500.0])


def test_percentiles_are_nearest_rank_over_all():
    xs = [float(x) for x in range(1, 201)]
    assert stats.percentile(xs, 0.5) == 101.0
    assert stats.percentile(xs, 0.99) == 199.0
    assert stats.percentile([7.0], 0.99) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_edges_per_s_is_the_window_ticks_over_their_time():
    win = harness.Window(t_open=100.0)
    win.ticks = [(102.0, 1000, 0), (104.0, 1000, 0), (106.0, 1000, 0),
                 (110.0, 800, 0)]
    # the window closed at the end of the tick that passed its length
    assert harness.edges_per_s(win) == pytest.approx(3800 / 10.0)

