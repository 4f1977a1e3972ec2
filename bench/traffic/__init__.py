"""Traffic generators, found by the ``generator`` name a configuration's
``traffic`` gives: module ``bench.traffic.<generator>`` with a
``Generator(params, seed)`` class whose ``block(n)`` returns the next
``n`` records of an endless stream as ``bench.reference.Edge`` tuples, in
timestamp order, with no ``(src, dst, ts)`` repeated."""

from __future__ import annotations

import importlib

import numpy as np


def make(traffic: dict, seed: int):
    mod = importlib.import_module(f"bench.traffic.{traffic['generator']}")
    return mod.Generator(traffic, seed)


def rng(seed: int, salt: int) -> np.random.Generator:
    """An independent stream per (seed, salt); any whole seed works."""
    return np.random.default_rng([seed % (1 << 64), salt])


def zipf_p(n: int, a: float) -> np.ndarray:
    """Probabilities of ranks ``1..n`` proportional to ``rank ** -a``."""
    p = np.arange(1, n + 1, dtype=np.float64) ** (-a)
    return p / p.sum()


class Dedup:
    """Drops repeated ``(src, dst, ts)`` records across blocks: a repeat
    is one flow seen twice, and would make a match's delivery ambiguous."""

    def __init__(self):
        self.ts = None
        self.seen: set = set()

    def keep(self, src: int, dst: int, ts: int) -> bool:
        if ts != self.ts:
            self.ts, self.seen = ts, set()
        if (src, dst) in self.seen:
            return False
        self.seen.add((src, dst))
        return True
