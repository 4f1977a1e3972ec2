"""The reference matcher against brute force on small random streams."""

import itertools
import random

import pytest

from bench.reference import Edge, find_matches, match_key

PATTERNS = [
    {"name": "chain3", "vertices": {"a": 0, "b": 1, "c": 0, "d": 1},
     "edges": [["a", "b", 0], ["b", "c", 1], ["c", "d", 0]],
     "before": [[0, 1], [1, 2]], "window": 12},
    {"name": "triangle", "vertices": {"u": 0, "v": 1, "w": 0},
     "edges": [["u", "v", 0], ["v", "w", 1], ["w", "u", 0]],
     "before": [[0, 2]], "window": 20},
    {"name": "star", "vertices": {"h": 0, "x": 1, "y": 1, "z": 0},
     "edges": [["h", "x", 0], ["h", "y", 1], ["z", "h", 0]],
     "before": [], "window": 8},
]


def brute(p, edges):
    names = list(p["vertices"])
    out = set()
    cands = [[e for e in edges if e.label == lbl and e.src != e.dst
              and e.src_label == p["vertices"][u]
              and e.dst_label == p["vertices"][v]]
             for u, v, lbl in p["edges"]]
    for pick in itertools.product(*cands):
        bind = {}
        ok = True
        for (u, v, _), e in zip(p["edges"], pick):
            for n, x in ((u, e.src), (v, e.dst)):
                if bind.setdefault(n, x) != x:
                    ok = False
        if not ok or len(set(bind.values())) != len(names):
            continue
        ts = [e.ts for e in pick]
        if max(ts) - min(ts) >= p["window"]:
            continue
        if any(not ts[i] < ts[j] for i, j in p["before"]):
            continue
        out.add(tuple((e.src, e.dst, e.ts) for e in pick))
    return out


def stream(seed):
    r = random.Random(seed)
    label = {v: r.randrange(2) for v in range(7)}
    edges, seen = [], set()
    for t in range(60):
        s, d = r.randrange(7), r.randrange(7)
        ts = t // 2
        if (s, d, ts) not in seen:
            seen.add((s, d, ts))
            edges.append(Edge(s, d, ts, label[s], label[d], r.randrange(2)))
    return edges


@pytest.mark.parametrize("p", PATTERNS, ids=lambda p: p["name"])
def test_reference_equals_brute_force(p):
    found = 0
    for seed in range(12):
        edges = stream(seed)
        want = brute(p, edges)
        assert find_matches(p, edges) == want, seed
        found += len(want)
    assert found >= 5


def test_match_key_reads_delivered_names():
    p = PATTERNS[0]
    key = match_key(p, (("a", 5), ("b", 6), ("c", 7), ("d", 8)),
                    (("e0", 1), ("e1", 2), ("e2", 3)))
    assert key == ((5, 6, 1), (6, 7, 2), (7, 8, 3))
