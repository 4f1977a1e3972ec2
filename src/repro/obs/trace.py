"""Structured host-side tracing: nested spans, kept in memory, written
as JSONL on ``flush``.

A :class:`Tracer` times serve-loop stages with nested spans::

    with tracer.span("tick"):
        with tracer.span("tick.barrier"):
            ...

Each span records its name, its start and end in ns on one monotonic
clock (``time.perf_counter_ns``), its parent span's id and the tick id.
Finished spans stay in memory until :meth:`Tracer.flush` (or ``close``)
writes them, one JSON object per line::

    {"tick": 17, "span": "tick.barrier", "ms": 7412.3, "id": 412,
     "parent": 409, "start_ns": ..., "end_ns": ..., "t0": 1723190400.12}

``ms`` is ``(end_ns - start_ns) / 1e6`` and ``t0`` the start on the wall
clock in seconds (anchored once per tracer, no extra clock read).
``tick`` is the per-tick correlation id that :meth:`Tracer.next_tick`
advances, so ``python -m repro.obs summarize`` can group spans by tick.

While a span is open it also holds a ``jax.profiler.TraceAnnotation``
named ``repro.<span>``: under a ``jax.profiler`` trace the span sits in
the same profile, on the same clock, as the device's ops.

Tracing is OFF by default.  Call sites use :func:`maybe_span`, which
returns the shared no-op :data:`NULL_SPAN` when the tracer is None: an
identity check, no allocation and no clock read.  Everything here runs
OUTSIDE traced/jitted code (the AST linter's TRC107 rule proves it).
"""

from __future__ import annotations

import io
import json
import time
from typing import IO

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "Span", "NULL_SPAN", "maybe_span", "memory_tracer"]


class Span:
    """One timed stage, opened with ``with tracer.span(name): ...``."""

    __slots__ = ("tracer", "name", "fields", "id", "parent", "tick",
                 "start_ns", "end_ns", "_note")

    def __init__(self, tracer: "Tracer", name: str, fields: dict):
        self.tracer = tracer
        self.name = name
        self.fields = fields
        self.id = 0
        self.parent: int | None = None
        self.tick = 0
        self.start_ns = 0
        self.end_ns = 0
        self._note = None

    def set(self, **fields) -> None:
        """Attach fields known only while the span runs (counts)."""
        self.fields.update(fields)

    @property
    def ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    def __enter__(self) -> "Span":
        tr = self.tracer
        tr._n_ids += 1
        self.id = tr._n_ids
        self.parent = tr._open[-1].id if tr._open else None
        self.tick = tr.tick
        tr._open.append(self)
        self._note = TraceAnnotation(f"repro.{self.name}")
        self._note.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._note.__exit__(*exc)
        self._note = None
        tr = self.tracer
        tr._open.remove(self)
        tr._done.append(self)


class _NullSpan:
    """The span of a disabled tracer: does nothing, allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


NULL_SPAN = _NullSpan()


def maybe_span(tracer: "Tracer | None", name: str):
    """``tracer.span(name)``, or :data:`NULL_SPAN` when ``tracer`` is
    None (set fields under ``if tracer is not None``, so the off path
    builds no keyword dict either)."""
    return NULL_SPAN if tracer is None else Span(tracer, name, {})


class Tracer:
    """Nested span timer with per-tick correlation ids.

    ``sink`` is a path or an open text file.  Finished spans are held in
    memory and written on :meth:`flush`/:meth:`close` (the service
    flushes once per serve round and on checkpoint).
    """

    def __init__(self, sink: str | IO[str]):
        if isinstance(sink, (str, bytes)):
            self._fh: IO[str] = open(sink, "w")
            self._owns = True
        else:
            self._fh = sink
            self._owns = False
        self.tick = 0
        self.n_spans = 0
        self._n_ids = 0
        self._open: list[Span] = []
        self._done: list[Span] = []
        # wall-clock anchor of the monotonic clock, for the ``t0`` field
        self._wall_ns = time.time_ns() - time.perf_counter_ns()

    # ----------------------------------------------------------- #
    def next_tick(self) -> int:
        """Advance the correlation id; returns the new tick id."""
        self.tick += 1
        return self.tick

    def span(self, name: str, **fields) -> Span:
        return Span(self, name, fields)

    def event(self, name: str, **fields) -> None:
        """Zero-duration marker (e.g. ``coalescer.decision``), a child
        of the innermost open span."""
        s = Span(self, name, fields)
        self._n_ids += 1
        s.id = self._n_ids
        s.parent = self._open[-1].id if self._open else None
        s.tick = self.tick
        s.start_ns = s.end_ns = time.perf_counter_ns()
        self._done.append(s)

    def _record(self, s: Span) -> dict:
        rec = {"tick": s.tick, "span": s.name, "ms": round(s.ms, 6),
               "id": s.id, "parent": s.parent,
               "start_ns": s.start_ns, "end_ns": s.end_ns,
               "t0": round((self._wall_ns + s.start_ns) / 1e9, 6)}
        if s.fields:
            rec.update(s.fields)
        return rec

    # ----------------------------------------------------------- #
    def flush(self) -> None:
        """Write every finished span, then flush the sink."""
        done, self._done = self._done, []
        if done:
            self._fh.write("".join(json.dumps(self._record(s)) + "\n"
                                   for s in done))
            self.n_spans += len(done)
        self._fh.flush()

    def close(self) -> None:
        self.flush()
        if self._owns:
            self._fh.close()

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def memory_tracer() -> tuple[Tracer, io.StringIO]:
    """In-memory tracer for tests: (tracer, its StringIO buffer)."""
    buf = io.StringIO()
    return Tracer(buf), buf
