"""Plain reference of the served semantics: every timing-constrained match
of one pattern over a list of edges.

A match maps each pattern vertex to a distinct data vertex of the same
label, and each pattern edge ``u -> v`` with label ``l`` to a data edge
``src -> dst`` with label ``l``, ``src != dst``, on those vertices.  For
each ``before`` pair ``(i, j)`` edge ``i``'s timestamp is strictly below
edge ``j``'s, and the newest timestamp of a match minus its oldest is
below the pattern's window.  A match is keyed by the ``(src, dst, ts)``
of each pattern edge, in authoring order.

This is a backtracking search over edge indexes: straightforward, and
independent of the program under test (it imports nothing from it).
"""

from __future__ import annotations

from collections import defaultdict
from typing import Iterable, NamedTuple


class Edge(NamedTuple):
    src: int
    dst: int
    ts: int
    src_label: int
    dst_label: int
    label: int


def edge_order(pattern: dict) -> list[int]:
    """Pattern edges in a connected order: each edge after the first
    shares a vertex with an earlier one, preferring edges whose both
    endpoints are already placed."""
    edges = pattern["edges"]
    order, seen = [0], set(edges[0][:2])
    while len(order) < len(edges):
        rest = [i for i in range(len(edges)) if i not in order]
        scored = [(-(edges[i][0] in seen) - (edges[i][1] in seen), i)
                  for i in rest
                  if edges[i][0] in seen or edges[i][1] in seen]
        if not scored:
            raise ValueError(f"pattern {pattern['name']!r} is not connected")
        i = min(scored)[1]
        order.append(i)
        seen.update(edges[i][:2])
    return order


def find_matches(pattern: dict, edges: Iterable[Edge]) -> set[tuple]:
    """All matches of ``pattern`` (a configuration's pattern entry, with
    its ``window``) over ``edges``, as a set of per-edge ``(src, dst, ts)``
    tuples in authoring order."""
    vlabel = pattern["vertices"]
    pedges = [tuple(e) for e in pattern["edges"]]
    window = pattern["window"]
    labels = {lbl for _, _, lbl in pedges}
    by_label = defaultdict(list)
    out_of = defaultdict(list)
    into = defaultdict(list)
    for e in edges:
        if e.label not in labels or e.src == e.dst:
            continue
        by_label[(e.label, e.src_label, e.dst_label)].append(e)
        out_of[(e.label, e.src)].append(e)
        into[(e.label, e.dst)].append(e)

    order = edge_order(pattern)
    before = [tuple(p) for p in pattern["before"]]
    n = len(pedges)
    bind: dict[str, int] = {}
    used: set[int] = set()
    chosen: list[Edge | None] = [None] * n
    found: set[tuple] = set()

    def fits(k: int, e: Edge, lo: int, hi: int) -> bool:
        u, v, lbl = pedges[k]
        if e.src_label != vlabel[u] or e.dst_label != vlabel[v]:
            return False
        if max(hi, e.ts) - min(lo, e.ts) >= window:
            return False
        for i, j in before:
            if i == k and chosen[j] is not None and not e.ts < chosen[j].ts:
                return False
            if j == k and chosen[i] is not None and not chosen[i].ts < e.ts:
                return False
        for name, x in ((u, e.src), (v, e.dst)):
            if name in bind:
                if bind[name] != x:
                    return False
            elif x in used:
                return False
        return True

    def rec(depth: int, lo: int, hi: int) -> None:
        if depth == n:
            found.add(tuple((c.src, c.dst, c.ts) for c in chosen))
            return
        k = order[depth]
        u, v, lbl = pedges[k]
        if u in bind:
            cands = out_of.get((lbl, bind[u]), ())
        elif v in bind:
            cands = into.get((lbl, bind[v]), ())
        else:
            cands = by_label.get((lbl, vlabel[u], vlabel[v]), ())
        for e in cands:
            if not fits(k, e, lo, hi):
                continue
            new = [name for name, x in ((u, e.src), (v, e.dst))
                   if name not in bind]
            for name, x in ((u, e.src), (v, e.dst)):
                if name not in bind:
                    bind[name] = x
                    used.add(x)
            chosen[k] = e
            rec(depth + 1, min(lo, e.ts), max(hi, e.ts))
            chosen[k] = None
            for name in new:
                used.discard(bind.pop(name))

    big = 1 << 62
    rec(0, big, -big)
    return found


def match_key(pattern: dict, vertices, edge_times) -> tuple:
    """A delivered match (``(vertex_name, id)`` pairs and ``(edge_name,
    ts)`` pairs, edges named ``e0, e1, ...`` in authoring order) in the
    key form of ``find_matches``."""
    bind = dict(vertices)
    when = dict(edge_times)
    return tuple((bind[u], bind[v], when[f"e{k}"])
                 for k, (u, v, _) in enumerate(pattern["edges"]))
