"""Network-flow records shaped like the paper's CAIDA traces (§6.1): one
vertex label for every host, Zipf-popular endpoints, destination ports
as edge labels with a few dominant ones, timestamps advancing by
``U{0..ts_step_max}`` per record.  The model of the program's
``synth_traffic_stream``, copied here so that the benchmark owns it, and
generated in blocks so that the stream never ends."""

from __future__ import annotations

import numpy as np

from bench.reference import Edge
from bench.traffic import Dedup, rng, zipf_p

HOST = 0


class Generator:
    def __init__(self, params: dict, seed: int):
        self.n = int(params["n_vertices"])
        self.hosts = zipf_p(self.n, float(params["host_zipf_a"]))
        self.ports = zipf_p(int(params["n_ports"]), float(params["port_zipf_a"]))
        self.step = int(params["ts_step_max"])
        self.rng = rng(seed, 1)
        self.ts = 0
        self.dedup = Dedup()
        self.pending: list[Edge] = []

    def _draw(self, k: int) -> list[Edge]:
        r = self.rng
        src = r.choice(self.n, size=k, p=self.hosts)
        dst = r.choice(self.n, size=k, p=self.hosts)
        port = r.choice(len(self.ports), size=k, p=self.ports)
        ts = self.ts + np.cumsum(r.integers(0, self.step + 1, k))
        self.ts = int(ts[-1])
        dst = np.where(src == dst, (dst + 1) % self.n, dst)
        out = []
        for s, d, t, p in zip(src.tolist(), dst.tolist(), ts.tolist(),
                              port.tolist()):
            if self.dedup.keep(s, d, t):
                out.append(Edge(s, d, t, HOST, HOST, p))
        return out

    def block(self, n: int) -> list[Edge]:
        while len(self.pending) < n:
            self.pending.extend(self._draw(4096))
        out, self.pending = self.pending[:n], self.pending[n:]
        return out
