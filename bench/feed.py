"""Capture points: how the generated stream reaches the program.

``Scatter`` splits the generator's stream over the configuration's
capture points.  Each block of ``n_sources * per_source`` records is
spread ``per_source`` to each point at random (load-balanced taps, so
the points advance through event time together), and a
``disorder_frac`` share of each point's deliveries is displaced by
``1..max_delay`` of its positions.

Two feeds turn that into ``repro.stream.ingest.Source`` capture points:

* ``ClosedFeed`` (closed input): every point always has records queued,
  generated in blocks as the points run low, so the frontier never waits.
* ``OpenLoop`` (open loop): records arrive on the clock with Poisson
  arrivals at rates set by a schedule, each stamped at its scheduled
  creation time; a poll takes what is due by then.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from repro.core.oracle import DataEdge
from repro.stream.ingest import Source, SourceEvent

from bench.traffic import rng


class Scatter:
    def __init__(self, gen, sources: dict, seed: int):
        self.gen = gen
        self.n = int(sources["n_sources"])
        self.per = int(sources["per_source"])
        self.frac = float(sources["disorder_frac"])
        self.max_delay = int(sources["max_delay"])
        self.rng = rng(seed, 4)
        self.seq = [0] * self.n
        self.n_made = 0
        # per point, records displaced past the end of the last block
        self.carry: list[list[tuple]] = [[] for _ in range(self.n)]

    def block(self):
        """The next block: ``(edges, deliveries)``.  ``deliveries[s]``
        lists what point ``s`` delivers next, in order, as ``(seq, edge,
        index)``: ``index`` counts records from the start of the stream
        (``edges`` holds this block's, from ``n_made`` before the call).
        A displaced record may come in a later block's deliveries."""
        edges = self.gen.block(self.n * self.per)
        first = self.n_made
        self.n_made += len(edges)
        owner = self.rng.permutation(np.repeat(np.arange(self.n), self.per))
        parts: list[list[int]] = [[] for _ in range(self.n)]
        for i, o in enumerate(owner.tolist()):
            parts[o].append(i)
        out = []
        for s, part in enumerate(parts):
            k = len(part)
            late = self.rng.random(k) < self.frac
            delay = np.where(late, self.rng.integers(1, self.max_delay + 1, k),
                             0).tolist()
            base = self.seq[s]
            self.seq[s] += k
            # a point delivers its records by position plus delay; what
            # may still be overtaken by the next block's records waits
            pend = self.carry[s] + [(base + j + d, base + j, edges[i],
                                     first + i)
                                    for j, (i, d) in enumerate(zip(part,
                                                                   delay))]
            pend.sort(key=lambda x: (x[0], x[1]))
            last = base + k - 1
            out.append([(seq, e, idx) for key, seq, e, idx in pend
                        if key <= last])
            self.carry[s] = [x for x in pend if x[0] > last]
        return edges, out


class QueueSource(Source):
    """One capture point reading its queue.  ``refill`` is called when the
    queue holds fewer records than a poll asks for (closed input).
    ``release`` moves what is due into the queue before each look (open
    loop); with ``wait_s`` an empty poll waits that long once, so that
    rounds with nothing to take are paced by time.  Each poll first calls
    ``alive``, which raises to end a run that has hung."""

    def __init__(self, name: str, queue: collections.deque, refill=None,
                 release=None, wait_s: float = 0.0, annotate=None,
                 alive=lambda: None):
        self.name = name
        self.queue = queue
        self.refill = refill
        self.release = release
        self.wait_s = wait_s
        self.annotate = annotate
        self.alive = alive
        self.n_polled = 0

    def connect(self, resume_from: int = 0) -> None:
        pass

    def poll(self, max_events: int = 64) -> list[SourceEvent]:
        if self.annotate is None:
            return self._poll(max_events)
        with self.annotate("bench.poll"):
            return self._poll(max_events)

    def _poll(self, max_events: int) -> list[SourceEvent]:
        self.alive()
        q = self.queue
        if self.refill is not None:
            while len(q) < max_events:
                self.refill()
        elif self.release is not None:
            self.release()
            if not q and self.wait_s:
                time.sleep(self.wait_s)
                self.release()
        out = []
        while q and len(out) < max_events:
            seq, e = q.popleft()
            out.append(SourceEvent(edge=DataEdge(*e), seq=seq))
        self.n_polled += len(out)
        return out


class ClosedFeed:
    def __init__(self, scatter: Scatter):
        self.scatter = scatter
        self.queues = [collections.deque() for _ in range(scatter.n)]

    def refill(self) -> None:
        _, deliveries = self.scatter.block()
        for q, ds in zip(self.queues, deliveries):
            q.extend((seq, e) for seq, e, _ in ds)


class OpenLoop:
    """Poisson arrivals on the benchmark's clock, at rates that follow
    ``schedule``: a list of ``(records/s, seconds)`` steps, repeated in a
    cycle from ``start``.  The arrival times are drawn from ``seed``
    ahead of time (the scatter has a seed of its own), and a record
    reaches its capture point's queue at the first look at the clock (a
    poll, or ``queued``) after it is due, so the arrivals never wait on
    the program.  ``created`` maps each
    record's ``(src, dst, ts)`` to its scheduled creation time
    (``time.perf_counter``)."""

    def __init__(self, scatter: Scatter, schedule: list, seed: int):
        self.scatter = scatter
        self.schedule = [(float(r), float(s)) for r, s in schedule]
        self.period = sum(s for _, s in self.schedule)
        self.t0: float | None = None
        self.rng = rng(seed, 5)
        self.queues = [collections.deque() for _ in range(scatter.n)]
        self.created: dict[tuple, float] = {}
        self.last_ts: int | None = None
        self._made: dict[int, float] = {}   # record index -> creation time
        self._pushed = [0.0] * scatter.n
        self._base = 0.0                    # last arrival, s after t0
        self._plan: collections.deque = collections.deque()

    def prefill(self, until_ts: int) -> None:
        """Queue whole blocks at once, up to the first record at or past
        ``until_ts``: the backlog that fills the tables before the clock
        starts."""
        while self.last_ts is None or self.last_ts < until_ts:
            now = time.perf_counter()
            first = self.scatter.n_made
            edges, deliveries = self.scatter.block()
            for q, ds in zip(self.queues, deliveries):
                q.extend((seq, e) for seq, e, idx in ds)
                for _, _, idx in ds:
                    self._made.pop(idx, None)
            for i, e in enumerate(edges):
                self.created[e[:3]] = now
                self._made[first + i] = now
            self.last_ts = edges[-1].ts

    @property
    def queued(self) -> int:
        """Records due and not yet taken by the frontier."""
        self.release()
        return sum(len(q) for q in self.queues)

    def start(self) -> None:
        self.t0 = time.perf_counter()
        self._pushed = [self.t0] * self.scatter.n

    def _step(self, t: float) -> tuple[float, float]:
        """The rate at ``t`` s after the start, and when its step ends."""
        cycle = self.period * (t // self.period)
        end = cycle
        for rate, secs in self.schedule:
            end += secs
            if t < end:
                return rate, end
        return self.schedule[0][0], end + self.schedule[0][1]

    def _arrivals(self, base: float, n: int) -> np.ndarray:
        """``n`` Poisson arrival times after ``base``: each unit-mean
        exponential gap is spent at the rate of the step it falls in."""
        out = np.empty(n)
        t = base
        for i, gap in enumerate(self.rng.exponential(1.0, n).tolist()):
            while True:
                rate, end = self._step(t)
                if rate > 0 and t + gap / rate <= end:
                    t += gap / rate
                    break
                gap -= rate * (end - t)
                t = end
            out[i] = t
        return out

    def _plan_block(self) -> None:
        first = self.scatter.n_made
        edges, deliveries = self.scatter.block()
        at = self._arrivals(self._base, len(edges)).tolist()
        self._base = at[-1]
        made = self._made
        made.update((first + i, self.t0 + t) for i, t in enumerate(at))
        plan = []
        for s, ds in enumerate(deliveries):
            # first in, first out: a record leaves its capture point once
            # made, and not before those delivered ahead of it
            for seq, e, idx in ds:
                self._pushed[s] = max(self._pushed[s], made[idx])
                plan.append((self._pushed[s], s, seq, e, made.pop(idx)))
        plan.sort(key=lambda x: x[:3])
        self._plan.extend(plan)

    def release(self) -> None:
        """Move every record due by now into its capture point's queue."""
        if self.t0 is None:
            return
        now = time.perf_counter()
        plan = self._plan
        while True:
            if not plan:
                self._plan_block()
            if plan[0][0] > now:
                return
            _, s, seq, e, t_made = plan.popleft()
            self.created[e[:3]] = t_made
            self.queues[s].append((seq, e))
