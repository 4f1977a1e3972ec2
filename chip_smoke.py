#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU: network-flow monitoring.

The deployment is the paper's own setting (§6.1, CAIDA traces; ROADMAP
W1): analyst patterns standing against one capture stream with
heavy-tailed endpoints and a few dominant ports, generated from
``--seed`` by ``repro.stream.generator.synth_traffic_stream`` and
delivered by 16 capture points with 1% bounded disorder.  Every tenant
is registered through ``repro.api.StreamSession`` and served by
``serve_frontier`` with event-time windows; the join backend is resolved
from the platform (the compiled Pallas kernels on a TPU).

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # replica-sharded serving, 4 chips

One chip: serves the deployment, checks that the served slot tick
contains the kernels (``tpu_custom_call``), that every tenant's matches
equal ``repro.core.oracle`` for its pattern, each exactly once, with no
overflow, no late drop and no compile after warm-up, and that a REF
session (the pure-jnp reference join) over the same stream delivers the
same matches.  ``--chips 4`` runs only the mesh phase: the same
deployment on ``ShardedSearchService(n_replicas=4)``, compared in this
process with the one-device service and the oracle, and a check that
each replica's slot block lives on its own device.

The last line of standard output is ``{"ok": true, "device": {...}}``;
any failed check exits non-zero without it, as does a host without a
TPU.  Times printed are smoke timings, not a benchmark.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# -- the deployment ------------------------------------------------------ #
SEED = 0
N_EDGES = 16384              # generated records (before duplicate removal)
N_VERTICES = 100_000         # hosts
N_PORTS = 8                  # destination ports (Zipf: top port ~60%)
N_SOURCES = 16               # capture points
DISORDER = 0.01              # delivered out of order (<= 8 positions)
LATENESS = 256               # allowed lateness, event-time units
WINDOW = 2048                # pattern window, event-time units
BATCH = 1024                 # edges per tick
CAP = dict(level_capacity=16384, l0_capacity=16384, max_new=2048)
SLOTS_PER_GROUP = 64

VICTIM, WEB, MAL, CC = 0, 1, 2, 3        # host roles (vertex labels)
Pattern = None               # repro.api.Pattern, bound by import_repro()


def c2_pattern(ports):
    """The 5-edge C2 exfiltration chain (``benchmarks/bench_serve.py``):
    web fetch, malware download, registration, command, exfiltration."""
    http, dl, reg, cmd, exfil = ports
    return (Pattern("c2")
            .edge("victim", "web", label=http, src_label=VICTIM,
                  dst_label=WEB)
            .edge("malware", "victim", label=dl, src_label=MAL)
            .edge("victim", "cc", label=reg, dst_label=CC)
            .edge("cc", "victim", label=cmd)
            .edge("victim", "drop", label=exfil, dst_label=CC)
            .before(0, 1).before(1, 2).before(2, 3).before(3, 4)
            .window(WINDOW))


def chain2_pattern(ports):
    """Fetch then download onto the same web host."""
    return (Pattern("chain2")
            .edge("client", "web", label=ports[0], src_label=VICTIM,
                  dst_label=WEB)
            .edge("malware", "web", label=ports[1], src_label=MAL)
            .before(0, 1)
            .window(WINDOW))


def chain3_pattern(ports):
    """Lateral movement: a -> b -> c -> d, each hop after the last."""
    return (Pattern("chain3")
            .edge("a", "b", label=ports[0], src_label=VICTIM, dst_label=WEB)
            .edge("b", "c", label=ports[1], dst_label=MAL)
            .edge("c", "d", label=ports[2], dst_label=CC)
            .before(0, 1).before(1, 2)
            .window(WINDOW))


def triangle_pattern(ports):
    """Beaconing triangle victim -> web -> malware -> victim, in order."""
    return (Pattern("triangle")
            .edge("u", "v", label=ports[0], src_label=VICTIM, dst_label=WEB)
            .edge("v", "w", label=ports[1], dst_label=MAL)
            .edge("w", "u", label=ports[2])
            .before(0, 1).before(1, 2)
            .window(WINDOW))


# (shape, tenants per port set, port sets): 1,024 tenants over 20
# distinct labeled patterns.  Port sets are ones the seed-0 traffic
# matches (tens to hundreds of matches each); port 0 is the dominant one.
CATALOG = [
    (c2_pattern, 64, [(2, 3, 3, 6, 1), (4, 2, 2, 2, 3), (5, 1, 5, 6, 3),
                      (7, 2, 2, 4, 2), (2, 3, 4, 1, 6), (3, 1, 4, 4, 2),
                      (5, 4, 3, 1, 1), (1, 4, 4, 3, 4)]),
    (chain3_pattern, 64, [(1, 1, 4), (1, 3, 6), (1, 3, 1), (2, 1, 1)]),
    (chain2_pattern, 32, [(1, 2), (1, 4), (4, 1), (3, 1)]),
    (triangle_pattern, 32, [(2, 3, 0), (5, 1, 0), (0, 1, 3), (4, 2, 1)]),
]


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


# -- set-up -------------------------------------------------------------- #
def import_repro():
    """Put the checkout's ``src`` on the path; fail without it."""
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}: run "
                           "chip_smoke.py from a checkout of the repo")
    sys.path.insert(0, src)
    global Pattern
    from repro.api import Pattern


def compile_cache_dir(jax) -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``
    (a fixed path: the directory is part of the cache key)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileCounter:
    """Counts XLA backend compiles (and their seconds) in this process."""

    def __init__(self):
        from jax import monitoring
        self.n, self.seconds = 0, 0.0
        event = "/jax/core/compile/backend_compile_duration"

        def on_event(name, secs, **_):
            if name == event:
                self.n += 1
                self.seconds += secs
        monitoring.register_event_duration_secs_listener(on_event)


def make_traffic(seed: int):
    """The capture stream, minus repeated (src, dst, ts) records: a
    repeated record is one flow seen twice, and would make each match's
    exactly-once delivery ambiguous."""
    from repro.stream.generator import StreamConfig, synth_traffic_stream
    raw = synth_traffic_stream(StreamConfig(
        n_edges=N_EDGES, n_vertices=N_VERTICES, n_vertex_labels=4,
        n_edge_labels=N_PORTS, ts_step_max=1, seed=seed))
    seen, stream = set(), []
    for e in raw:
        if (e.src, e.dst, e.ts) not in seen:
            seen.add((e.src, e.dst, e.ts))
            stream.append(e)
    return stream, len(raw) - len(stream)


def make_frontier(session, stream, seed: int):
    from repro.runtime.fault import RetryPolicy
    from repro.stream.generator import DisorderConfig, disordered_sources
    from repro.stream.ingest import ScriptedSource
    scripts = disordered_sources(stream, DisorderConfig(
        n_sources=N_SOURCES, disorder_frac=DISORDER, max_delay=8,
        seed=seed))
    return session.sources(
        {f"cap{i}": ScriptedSource(f"cap{i}", sc)
         for i, sc in enumerate(scripts)},
        allowed_lateness=LATENESS, sleep=lambda d: None,
        retry=RetryPolicy(base_delay_s=0.0, jitter_frac=0.0))


def distinct_patterns():
    """``[(pattern, n_tenants)]`` in catalog order."""
    return [(shape(ports), n) for shape, n, sets in CATALOG
            for ports in sets]


def register_all(session, patterns, per_pattern=None):
    """Register every tenant; returns ``[[sub, ...] per pattern]``."""
    return [[session.register(p) for _ in range(per_pattern or n)]
            for p, n in patterns]


def match_key(sub, m):
    """A delivered ``Match`` in the oracle's canonical frozenset form."""
    plan = sub.plan
    name_of = {c: n for n, c in zip(plan.vertex_names, plan.vertex_map)}
    bind, when = m.bindings, m.times
    out = []
    for j, ename in enumerate(plan.edge_names):
        ceid = plan.edge_map[j]
        u, v = plan.query.edges[ceid]
        out.append((ceid, (bind[name_of[u]], bind[name_of[v]], when[ename])))
    return frozenset(out)


def delivered(sub) -> collections.Counter:
    return collections.Counter(match_key(sub, m) for m in sub.drain())


def oracle_matches(query, window, stream):
    """Every match ``repro.core.oracle`` finds over the stream whose edges
    fit in one window span — what the served path must report, each
    exactly once.  The oracle enumerates 2-window segments (any span
    below one window lies inside one), over the edges that can match
    some query edge."""
    from repro.core.oracle import edge_matches, enumerate_matches
    rel = [e for e in stream
           if any(edge_matches(query, i, e) for i in range(query.n_edges))]
    out = set()
    if not rel:
        return out
    t0, t1 = rel[0].ts, max(e.ts for e in rel)
    for start in range(t0, t1 + 1, window):
        seg = [e for e in rel if start <= e.ts < start + 2 * window]
        for m in enumerate_matches(query, seg):
            ts = [t for _, (_, _, t) in m]
            if max(ts) - min(ts) < window:
                out.add(m)
    return out


def table_bytes(service) -> int:
    import jax
    return sum(x.nbytes for g in service._iter_groups()
               for x in jax.tree.leaves(g.sstate.engines))


def serve(session, stream, seed, on_tick=None):
    """Serve the whole stream through a fresh frontier; returns the
    per-tick ``ServeInfo`` records."""
    ticks = []

    def record(info):
        ticks.append(info)
        if on_tick is not None:
            on_tick(info)

    frontier = make_frontier(session, stream, seed)
    session.serve_frontier(frontier, batch_size=BATCH, min_batch=BATCH,
                           max_batch=BATCH, on_tick=record)
    st = frontier.stats()
    check(st.n_late_dropped == 0, f"{st.n_late_dropped} late drops")
    check(st.n_dropped_forced_gap == 0 and frontier.n_forced == 0,
          "reorder buffer forced events past the watermark")
    check(sum(t.chunk for t in ticks) == len(stream),
          "not every edge was served")
    return ticks


def check_tenants(subs_per_pattern, oracle, label):
    """Each tenant's delivered matches: exactly once, equal to the oracle
    of its pattern.  Returns the total delivered."""
    total = 0
    for i, subs in enumerate(subs_per_pattern):
        for sub in subs:
            got = delivered(sub)
            check(sub.n_dropped == 0, f"{label}: match queue overflowed")
            check(not got or max(got.values()) == 1,
                  f"{label}: pattern {i} delivered a match twice")
            check(set(got) == oracle[i],
                  f"{label}: pattern {i} (qid {sub.qid}) delivered "
                  f"{len(got)} matches, oracle has {len(oracle[i])}")
            check(sub.n_overflow == 0, f"{label}: qid {sub.qid} overflowed")
            total += len(got)
    return total


# -- phases -------------------------------------------------------------- #
def one_chip(args, jax, counter):
    from repro.api import StreamSession
    from repro.core.join import JoinBackend
    from repro.core.state import make_batch
    from repro.stream.generator import to_batches

    stream, n_dup = make_traffic(args.seed)
    span = stream[-1].ts - stream[0].ts
    say(f"traffic: {len(stream)} edges ({n_dup} repeated records "
        f"removed), event time {span} = {span / WINDOW:.2f} windows")
    check(span >= 3 * WINDOW, "stream spans fewer than three windows")

    session = StreamSession(slots_per_group=SLOTS_PER_GROUP, **CAP)
    svc = session.service
    say(f"backend: {svc.backend} (resolved from platform "
        f"{jax.devices()[0].platform})")
    check(svc.backend == JoinBackend.PALLAS, "TPU did not resolve PALLAS")
    patterns = distinct_patterns()
    t = time.perf_counter()
    subs = register_all(session, patterns)
    n_tenants = sum(len(s) for s in subs)
    say(f"tenants: {n_tenants} ({len(patterns)} distinct patterns), "
        f"groups: {len(svc._iter_groups())}, registered in "
        f"{time.perf_counter() - t:.1f} s")
    check(n_tenants >= 512, "fewer than 512 tenants")
    n_bytes = table_bytes(svc)
    say(f"table bytes: {n_bytes}")
    check(n_bytes >= 10**9, "under 1 GB of tables on the device")

    warm = {}

    def after_first(info):
        warm.setdefault("n", counter.n)
        warm.setdefault("s", counter.seconds)

    t = time.perf_counter()
    ticks = serve(session, stream, args.seed, after_first)
    wall = time.perf_counter() - t
    n_after = counter.n - warm["n"]
    say(f"ticks: {len(ticks)}, edges: {sum(x.chunk for x in ticks)}, "
        f"overflow: {sum(x.n_overflow for x in ticks)}")
    say(f"compiles: {warm['n']} in warm-up ({warm['s']:.1f} s), "
        f"{n_after} after")
    check(n_after == 0, f"{n_after} compiles after warm-up")
    check(sum(x.n_overflow for x in ticks) == 0, "tables overflowed")
    mem = jax.devices()[0].memory_stats() or {}
    say(f"peak_bytes_in_use: {mem.get('peak_bytes_in_use')}")
    say(f"serve wall seconds (smoke timing, not a benchmark): {wall:.1f}")

    # the served tick holds the kernels
    g = svc._iter_groups()[0]
    batch = make_batch(**to_batches(stream[:BATCH], BATCH)[0])
    text = g.tick.lower(g.sstate, batch,
                        jax.numpy.asarray(0, jax.numpy.int32)
                        ).compile().as_text()
    say(f"tpu_custom_call in served tick: {'tpu_custom_call' in text}")
    check("tpu_custom_call" in text, "served tick has no Pallas kernel")

    t = time.perf_counter()
    oracle = [oracle_matches(s[0].query, s[0].window, stream) for s in subs]
    say(f"oracle: {sum(len(o) for o in oracle)} distinct matches over "
        f"{len(oracle)} patterns in {time.perf_counter() - t:.1f} s")
    n_matches = check_tenants(subs, oracle, "pallas")
    say(f"matches delivered: {n_matches}, every tenant equal to the oracle")

    # the reference join on the same stream: one tenant per pattern
    ref = StreamSession(slots_per_group=1, backend=JoinBackend.REF, **CAP)
    ref_subs = register_all(ref, patterns, per_pattern=1)
    serve(ref, stream, args.seed)
    check_tenants(ref_subs, oracle, "ref")
    say("ref: every pattern equal to pallas and the oracle")


def four_chips(args, jax, counter):
    from repro.api import StreamSession

    check(len(jax.devices()) >= 4, "--chips 4 needs four devices")
    stream, n_dup = make_traffic(args.seed)
    say(f"traffic: {len(stream)} edges ({n_dup} repeated records removed)")
    patterns = distinct_patterns()
    spr = SLOTS_PER_GROUP // 4
    sharded = StreamSession(
        mesh={"n_replicas": 4, "slots_per_replica": spr}, **CAP)
    single = StreamSession(slots_per_group=SLOTS_PER_GROUP, **CAP)
    runs = {}
    for label, session in (("sharded", sharded), ("single", single)):
        subs = register_all(session, patterns)
        t = time.perf_counter()
        ticks = serve(session, stream, args.seed)
        check(sum(x.n_overflow for x in ticks) == 0,
              f"{label}: tables overflowed")
        say(f"{label}: {sum(len(s) for s in subs)} tenants, "
            f"{len(session.service._iter_groups())} groups, "
            f"{len(ticks)} ticks, served in {time.perf_counter() - t:.1f} s "
            "(smoke timing)")
        runs[label] = subs

    svc = sharded.service
    for g in svc._iter_groups():
        for leaf in jax.tree.leaves(g.sstate):
            owner = {}
            for sh in leaf.addressable_shards:
                start = sh.index[0].start or 0
                owner[start // spr] = sh.device
            check(sorted(owner) == [0, 1, 2, 3]
                  and len(set(owner.values())) == 4,
                  f"group {g.gid}: replica blocks not on four devices")
    say(f"replica blocks: every group's slot blocks on 4 distinct devices "
        f"{sorted(d.id for d in svc.mesh.devices.flat)}")
    for d in jax.devices()[:4]:
        say(f"device {d.id} peak_bytes_in_use: "
            f"{(d.memory_stats() or {}).get('peak_bytes_in_use')}")

    oracle = [oracle_matches(s[0].query, s[0].window, stream)
              for s in runs["single"]]
    n_sharded = check_tenants(runs["sharded"], oracle, "sharded")
    n_single = check_tenants(runs["single"], oracle, "single")
    check(n_sharded == n_single, "sharded and single totals differ")
    say(f"matches: sharded {n_sharded} == single {n_single} == oracle, "
        "per tenant")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)
    try:
        import_repro()
        import jax
        cache = compile_cache_dir(jax)
        counter = CompileCounter()
        devices = jax.devices()
        dev = devices[0]
        if dev.platform != "tpu":
            print(f"chip_smoke.py: no TPU (JAX platform {dev.platform!r})",
                  file=sys.stderr)
            return 2
        say(f"platform: {dev.platform}, device_kind: {dev.device_kind}, "
            f"devices: {len(devices)}")
        say(f"compile cache: {cache}")
        t = time.perf_counter()
        (four_chips if args.chips == 4 else one_chip)(args, jax, counter)
        say(f"compiles: {counter.n} in all, {counter.seconds:.1f} s")
        say(f"wall seconds (smoke timing, not a benchmark): "
            f"{time.perf_counter() - t:.1f}")
    except SmokeFailure as e:
        print(f"chip_smoke.py: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
