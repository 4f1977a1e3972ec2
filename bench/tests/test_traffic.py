"""The generator and capture points: the same seed gives the same
stream, no (src, dst, ts) repeats, time never goes back, the stream has
the source's shape, the configuration's queries are the §6.2 walks it
names, and each block reaches every capture point in equal shares with
contiguous sequence numbers."""

import collections
import os

from bench import spec
from bench.feed import Scatter
from bench.traffic import make, walks


def config():
    return spec.load_json(os.path.join(spec.BENCH, "configs",
                                       "netflow-w1.json"))


def test_stream_is_seeded_unique_and_ordered():
    t = config()["traffic"]
    seed = 2 ** 31 + 12345
    a = make(t, seed).block(9000)
    g = make(t, seed)
    b = g.block(4000) + g.block(5000)
    assert a == b
    assert a != make(t, seed + 1).block(9000)
    assert len({e[:3] for e in a}) == len(a)
    assert all(x.ts <= y.ts for x, y in zip(a, a[1:]))
    assert all(e.src != e.dst for e in a)


def test_netflow_has_the_source_shape():
    """One host label, and the six commonest ports carry more than half
    of the records (arXiv:1801.09240 section 6.1)."""
    edges = make(config()["traffic"], 5).block(50000)
    assert {e.src_label for e in edges} | {e.dst_label for e in edges} \
        == {0}
    ports = collections.Counter(e.label for e in edges)
    top6 = sum(n for _, n in ports.most_common(6)) / len(edges)
    assert 0.5 < top6 < 0.6


def test_walk_queries_are_the_configured_ones():
    """The configuration's patterns are what the §6.2 walk draws with
    the parameters it states."""
    cfg = config()
    q = cfg["queries"]
    drawn = walks.random_walk_queries(cfg["traffic"], q["seed"], q["sizes"],
                                      cfg["window"], q["prefix"])
    assert [{k: w[k] for k in ("name", "vertices", "edges", "before")}
            for w in drawn] == [{k: p[k] for k in ("name", "vertices",
                                                   "edges", "before")}
                                for p in cfg["patterns"]]


def test_scatter_spreads_and_displaces():
    """Each block goes to the points in equal shares; a point delivers
    every record once, in sequence order but for the displaced ones, each
    behind at most max_delay later records, some across block ends."""
    cfg = config()
    sc = Scatter(make(cfg["traffic"], 9), cfg["sources"], 9)
    got = [[] for _ in range(sc.n)]
    n_blocks = 40
    for b in range(n_blocks):
        edges, deliveries = sc.block()
        assert len(edges) == sc.n * sc.per
        for s, ds in enumerate(deliveries):
            got[s] += [(seq, idx, b) for seq, _, idx in ds]
    for s, ds in enumerate(got):
        seqs = [seq for seq, _, _ in ds]
        assert sorted(seqs + [x[1] for x in sc.carry[s]]) \
            == list(range(n_blocks * sc.per))
        for k, seq in enumerate(seqs):
            assert sum(x > seq for x in seqs[:k]) <= sc.max_delay
    assert sum(seq != k for ds in got for k, (seq, _, _) in enumerate(ds))
    block = sc.n * sc.per
    assert sum(b != idx // block for ds in got for _, idx, b in ds) > 0
