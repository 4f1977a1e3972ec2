"""One run of one cell: set-up, the measured window, the check, the
result line.

Set-up builds the traffic from the seed, registers every tenant through
``repro.api.StreamSession``, and serves one window of event time through
``StreamSession.serve_frontier`` (the prefill, which also compiles or
loads the cell's one tick shape).  The window then runs in the same
``serve_frontier`` call for ``--seconds``, and ends at the first tick
that completes after it.  Every tenant's delivered matches are then
compared with ``bench.reference`` over exactly the edges the frontier
released.
"""

from __future__ import annotations

import collections
import io
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from bench import devtrace, spec, stats
from bench.reference import Edge, find_matches, match_key
from bench.traffic import make as make_traffic

CACHE = os.path.join(spec.ROOT, ".bench_cache")
TRACE_DIR = os.path.join(CACHE, "trace")
# a run that has not finished by then has hung: it fails rather than
# holding the chip (the first run in a checkout compiles, and may take
# up to 1,200 s in all)
HUNG_S = 1100.0
clock = time.perf_counter


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class StopRun(Exception):
    """Raised from the tick callback to end ``serve_frontier``."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def setup_jax(chips: int, require_tpu: bool = True):
    """JAX with its compile cache at a fixed path in the checkout, and the
    devices the cell runs on."""
    import jax

    cache = os.path.join(CACHE, "jax")
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no size limit, so no eviction: with it, one entry that lost its
    # access-time file makes every later write fail, and runs recompile
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has {len(devices)}")
    return jax, devices[:chips]


class CompileCounter:
    """XLA backend compiles in this process."""

    def __init__(self):
        from jax import monitoring

        self.n = 0

        def on_event(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        monitoring.register_event_duration_secs_listener(on_event)


def patterns(config: dict) -> list[dict]:
    return [dict(p, window=config["window"]) for p in config["patterns"]]


def to_pattern(p: dict):
    from repro.api import Pattern

    pat = Pattern(p["name"])
    for u, v, lbl in p["edges"]:
        pat.edge(u, v, label=lbl, src_label=p["vertices"][u],
                 dst_label=p["vertices"][v])
    for i, j in p["before"]:
        pat.before(i, j)
    return pat.window(p["window"])


def build_session(config: dict, backend=None):
    from repro.api import StreamSession

    svc = config["service"]
    kw = dict(level_capacity=svc["level_capacity"],
              l0_capacity=svc["l0_capacity"], max_new=svc["max_new"],
              backend=backend)
    if svc.get("replicas", 1) > 1:
        return StreamSession(mesh={"n_replicas": svc["replicas"],
                                   "slots_per_replica": svc["slots"]}, **kw)
    return StreamSession(slots_per_group=svc["slots"], **kw)


class Run:
    """A cell's deployment, registered and wired to its capture points.

    ``rates`` makes the capture points open-loop (``bench.feed.OpenLoop``
    with that schedule of ``(edges/s, seconds)`` steps); without it they
    are closed.  ``trace`` puts the benchmark's own ``jax.profiler``
    annotations on polls, match delivery and the tick callback."""

    def __init__(self, cell: spec.Cell, seed: int, *, trace: bool = False,
                 rates: list | None = None, backend=None,
                 require_tpu: bool = True):
        self.deadline = clock() + HUNG_S
        self.jax, self.devices = setup_jax(cell.chips, require_tpu)
        self.compiles = CompileCounter()
        from bench.feed import ClosedFeed, OpenLoop, QueueSource, Scatter

        self.cell = cell
        config = cell.config
        self.pats = patterns(config)
        self.note = self.jax.profiler.TraceAnnotation if trace else None
        self.horizon = int(cell.mix["prefill_windows"]) * config["window"]

        # a mix may fix the records, and their arrival times, for every
        # seed, so that the seed moves only which capture point each
        # record reaches and in what order it delivers them
        content = int(cell.mix.get("content_seed", seed))
        scatter = Scatter(make_traffic(config["traffic"], content),
                          config["sources"], seed)
        self.loop = None
        if rates is None:
            feed = ClosedFeed(scatter)
            queues, refill, release = feed.queues, feed.refill, None
            wait_s = 0.0
        else:
            self.loop = OpenLoop(scatter, rates,
                                 int(cell.mix.get("arrival_seed", seed)))
            self.loop.prefill(self.horizon)
            queues, refill = self.loop.queues, None
            release = self.loop.release
            wait_s = float(cell.mix["poll_wait_ms"]) / 1e3
            say(f"open loop: {rates} (edges/s, s), prefill "
                f"{self.loop.queued} edges")

        self.session = build_session(config, backend)
        self.svc = self.session.service
        say(f"platform {self.devices[0].platform} "
            f"({self.devices[0].device_kind}) x {len(self.devices)}, "
            f"join backend {self.svc.backend}")
        t = clock()
        self.tenants: list[tuple[int, list]] = []
        for pi, p in enumerate(self.pats):
            pattern = to_pattern(p)
            for _ in range(p["tenants"]):
                rec: list = []
                self.session.register(pattern, on_match=self._recorder(rec))
                self.tenants.append((pi, rec))
        if len(self.tenants) != config["n_tenants"]:
            raise ValueError(f"config has {len(self.tenants)} tenants, says "
                             f"{config['n_tenants']}")
        say(f"tenants {len(self.tenants)} over {len(self.pats)} patterns in "
            f"{len(self.svc._iter_groups())} groups, registered in "
            f"{clock() - t:.3f} s")

        self.sources = [QueueSource(f"cap{i}", q, refill=refill,
                                    release=release, wait_s=wait_s,
                                    annotate=self.note,
                                    alive=self.alive)
                        for i, q in enumerate(queues)]
        self.frontier = self.session.sources(
            {s.name: s for s in self.sources},
            **config["frontier"])
        self.served: list = []
        self.frontier.on("event", self.served.append)
        self.overflow = 0

    def alive(self) -> None:
        """Raise if the run has hung."""
        if clock() > self.deadline:
            raise RuntimeError(f"the run has not ended after {HUNG_S} s")

    def _recorder(self, rec: list):
        if self.note is None:
            return lambda m: rec.append((clock(), m))
        note = self.note

        def on_match(m):
            with note("bench.on_match"):
                rec.append((clock(), m))
        return on_match

    def serve(self, on_tick) -> None:
        """``serve_frontier`` at the configuration's fixed tick size until
        ``on_tick`` raises ``StopRun``."""
        def tick(info):
            self.overflow += info.n_overflow
            if self.note is None:
                on_tick(info)
            else:
                with self.note("bench.on_tick"):
                    on_tick(info)

        batch = int(self.cell.config["service"]["batch"])
        try:
            self.session.serve_frontier(
                self.frontier, batch_size=batch, min_batch=batch,
                max_batch=batch, on_tick=tick)
            raise RuntimeError("serve_frontier returned: the sources ran dry")
        except StopRun:
            pass

    def memory_peak(self) -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devices))

    def check(self, window_compiles: int) -> tuple[dict, int, int]:
        """The numbers compared against their limits, with the edges the
        frontier took in (``attempted``) and those it dropped, forced or
        overflowed (``failed``)."""
        fst = self.frontier.stats()
        polled = sum(s.n_polled for s in self.sources)
        unaccounted = polled - (len(self.served) + fst.n_late_dropped
                                + fst.n_dropped_forced_gap + fst.buffered)
        failed = (fst.n_late_dropped + fst.n_dropped_forced_gap
                  + fst.n_forced + self.overflow)
        say(f"frontier: {polled} polled, {len(self.served)} served, "
            f"{fst.n_late_dropped} late, {fst.n_dropped_forced_gap} "
            f"forced-gap drops, {fst.n_forced} forced, {fst.buffered} "
            f"buffered; overflow {self.overflow}")
        t = clock()
        edges = [Edge(e.src, e.dst, e.ts, e.src_label, e.dst_label,
                      e.edge_label) for e in self.served]
        refs = [find_matches(p, edges) for p in self.pats]
        missing = extra = dups = n_delivered = 0
        for pi, rec in self.tenants:
            got = collections.Counter(
                match_key(self.pats[pi], m.vertices, m.edges) for _, m in rec)
            n_delivered += len(rec)
            dups += sum(c - 1 for c in got.values())
            missing += len(refs[pi] - got.keys())
            extra += len(got.keys() - refs[pi])
        say(f"reference: {sum(map(len, refs))} distinct matches over "
            f"{len(self.pats)} patterns in {clock() - t:.3f} s; "
            f"{n_delivered} delivered to {len(self.tenants)} tenants")
        checks = {
            "missing_matches": (missing, 0),
            "extra_matches": (extra, 0),
            "duplicate_deliveries": (dups, 0),
            "dropped_or_overflowed_edges": (failed, 0),
            "unaccounted_edges": (abs(unaccounted), 0),
            "window_compiles": (window_compiles, 0),
        }
        return checks, polled, failed


@dataclass
class Window:
    """The measured window, as the tick callback saw it."""
    t_open: float = 0.0
    prefill_ticks: int = 0
    compiles_open: int = 0
    compiles_close: int = 0
    ticks: list = field(default_factory=list)   # (t, chunk, latency_ms)


@dataclass
class MetricContext:
    """What a per-layer metric reader gets: the reduced device trace of
    the window (``bench.devtrace.summarize``), the program's spans
    recorded in it, how many ticks both cover, and the tick latencies
    the program reported for the ticks completed inside the window."""
    trace: dict | None
    spans: list[dict]
    n_ticks: int
    tick_ms: list[float]


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, **run_kw) -> dict:
    """One run; returns the result line's object."""
    mix = cell.mix
    rates = None
    if "schedule" in mix:
        knee = float(cell.config["knee_edges_per_s"])
        rates = [(float(f) * knee, float(secs)) for f, secs in mix["schedule"]]
    run = Run(cell, seed, trace=trace, rates=rates, **run_kw)
    jax, loop, served = run.jax, run.loop, run.served
    from repro.obs import Tracer

    win = Window()
    state = {"phase": "prefill", "settle": 0}
    tracer_buf = io.StringIO()
    notes: list = []

    def open_window():
        win.compiles_open = run.compiles.n
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            # made after the trace starts, or it records nothing
            notes.append(jax.profiler.TraceAnnotation("bench.window"))
            notes[0].__enter__()
            run.svc.tracer = Tracer(tracer_buf)
        state["phase"] = "window"
        win.t_open = clock()

    def close_window():
        win.compiles_close = run.compiles.n
        if trace:
            run.svc.tracer = None
            notes[0].__exit__(None, None, None)
            jax.profiler.stop_trace()
        raise StopRun

    def on_tick(info):
        now = clock()
        if state["phase"] == "window":
            win.ticks.append((now, info.chunk, info.latency_ms))
            if now >= win.t_open + seconds:
                close_window()
            return
        win.prefill_ticks += 1
        if loop is None:
            if served and served[-1].ts - served[0].ts >= run.horizon:
                open_window()
        elif state["phase"] == "prefill":
            if loop.queued == 0:
                loop.start()
                state["phase"] = "settle"
        else:
            state["settle"] += 1
            if state["settle"] >= int(mix["settle_ticks"]):
                open_window()

    run.serve(on_tick)
    t_served = clock()
    mem = run.memory_peak()
    say(f"prefill ticks {win.prefill_ticks}, window ticks {len(win.ticks)}, "
        f"served {len(served)} edges, compiles {win.compiles_open} before "
        f"the window, {win.compiles_close - win.compiles_open} in it")
    checks, attempted, failed = run.check(
        win.compiles_close - win.compiles_open)

    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind,
              "count": len(run.devices), "memory_peak_bytes": mem}
    end = win.ticks[-1][0]
    extra: dict = {}
    if trace:
        t = clock()
        summary = devtrace.summarize(devtrace.from_xspace(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        say(f"device trace read in {clock() - t:.3f} s")
        device["busy_s"] = summary["busy_ns"] / 1e9
        device["window_s"] = summary["window_ns"] / 1e9
        ctx = MetricContext(
            trace=summary,
            spans=[json.loads(x) for x in tracer_buf.getvalue().splitlines()],
            n_ticks=len(win.ticks), tick_ms=[x[2] for x in win.ticks])
        metrics = {}
        for m in cell.per_layer:
            value = spec.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        extra["breakdown"] = {"device_ops": summary["device_ops"],
                              "idle_gaps": summary["idle_gaps"]}
    else:
        values = {"setup_s": win.t_open - t_start,
                  "edges_per_s": edges_per_s(win)}
        if loop is not None:
            lat = latencies_ms(run.pats, run.tenants, loop.created,
                               win.t_open, end)
            say(f"latency over {len(lat)} matches delivered in the window")
            values["match_latency_p50_ms"] = stats.percentile(lat, 0.5)
            values["match_latency_p99_ms"] = stats.percentile(lat, 0.99)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    say(f"setup {win.t_open - t_start:.3f} s, window {seconds} s, closed "
        f"at a tick's end {end - win.t_open:.3f} s after it opened, serving "
        f"ended {t_served - win.t_open:.3f} s after it opened")
    for k, (v, lim) in checks.items():
        say(f"check {k}: {v} (limit {lim})")
    return {"correct": all(v <= lim for v, lim in checks.values()),
            "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": device, **extra,
            "checks": {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}}


def edges_per_s(win: Window) -> float:
    """The edges of every tick of the window over its length: it opens
    and closes at the end of a tick, so that is all the work over all
    the time."""
    return sum(c for _, c, _ in win.ticks) / (win.ticks[-1][0] - win.t_open)


def latencies_ms(pats, tenants, created: dict, lo: float, hi: float):
    """Per match delivered inside ``[lo, hi]``: the time from the creation
    of its completing edge (its newest; among equals the latest created)
    to its delivery."""
    out = []
    for pi, rec in tenants:
        for t, m in rec:
            if not lo <= t <= hi:
                continue
            key = match_key(pats[pi], m.vertices, m.edges)
            newest = max(e[2] for e in key)
            made = max(created[e] for e in key if e[2] == newest)
            out.append((t - made) * 1e3)
    return out
