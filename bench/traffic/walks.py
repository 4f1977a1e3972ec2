"""The paper's §6.2 queries (arXiv:1801.09240): a random walk over
adjacent edges of a stream prefix, inside one window, with the walked
edges' labels and the timing order they had in the data.  A walk's
structure and timing order are those of a real embedding, so every
query has at least one match in the stream it was drawn from."""

from __future__ import annotations

import numpy as np

from bench.reference import Edge
from bench.traffic import make, rng


def random_walk_query(stream: list[Edge], n_edges: int, window: int,
                      r: np.random.Generator) -> list[Edge] | None:
    """One walk: ``n_edges`` adjacent edges inside one window span, no
    repeated directed vertex pair, or None when the walk gets stuck."""
    t0 = stream[int(r.integers(0, len(stream)))].ts
    pool = [e for e in stream if t0 <= e.ts < t0 + window]
    walked = [pool[int(r.integers(0, len(pool)))]]
    touched = {walked[0].src, walked[0].dst}
    pairs = {(walked[0].src, walked[0].dst)}
    while len(walked) < n_edges:
        cands = [e for e in pool
                 if (e.src in touched or e.dst in touched)
                 and (e.src, e.dst) not in pairs and e.src != e.dst]
        if not cands:
            return None
        e = cands[int(r.integers(0, len(cands)))]
        walked.append(e)
        touched |= {e.src, e.dst}
        pairs.add((e.src, e.dst))
    return walked


def as_pattern(name: str, walked: list[Edge]) -> dict:
    """A walk as a configuration pattern entry: vertices named by first
    visit, edges in walk order, and ``before`` wherever a walked edge is
    strictly older than a later one."""
    names: dict[int, str] = {}
    vertices: dict[str, int] = {}
    edges = []
    for e in walked:
        for x, lbl in ((e.src, e.src_label), (e.dst, e.dst_label)):
            if x not in names:
                names[x] = f"v{len(names)}"
                vertices[names[x]] = lbl
        edges.append([names[e.src], names[e.dst], e.label])
    before = [[i, j] for i in range(len(walked)) for j in range(len(walked))
              if i < j and walked[i].ts < walked[j].ts]
    return {"name": name, "vertices": vertices, "edges": edges,
            "before": before}


def random_walk_queries(traffic: dict, seed: int, sizes: list[int],
                        window: int, prefix: int) -> list[dict]:
    """One distinct walk per entry of ``sizes`` (its number of edges),
    over the first ``prefix`` records of the stream of ``seed``.  A walk
    that gets stuck, or repeats an earlier walk's labelled shape, is drawn
    again; none is dropped for what it would cost to serve."""
    stream = make(traffic, seed).block(prefix)
    r = rng(seed, 3)
    out: list[dict] = []
    shapes: set = set()
    for n_edges in sizes:
        while True:
            walked = random_walk_query(stream, n_edges, window, r)
            if walked is None:
                continue
            pat = as_pattern(f"walk{len(out)}.{n_edges}e", walked)
            shape = (tuple(sorted(pat["vertices"].items())),
                     tuple(map(tuple, pat["edges"])),
                     tuple(map(tuple, pat["before"])))
            if shape not in shapes:
                shapes.add(shape)
                out.append(pat)
                break
    return out
