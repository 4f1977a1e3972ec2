"""Continuous-search service: the unified serving ENGINE for standing queries.

This is the internal engine room.  The public way to use the system is
``repro.api`` — a ``StreamSession`` facade (pattern DSL, canonicalizing
planner, typed Event/Match records, admission control) that drives this
class underneath; ``repro.launch.stream_serve.StreamServer`` is a thin
one-tenant wrapper over the same path.  Standing queries arrive and
leave while the edge stream flows; the service keeps the compile budget
fixed by bucketing queries into padded slot groups keyed by structural
signature, and owns the whole production loop: adaptive tick coalescing,
periodic async checkpoints, and fault-tolerant restore.

Registration / compile budget
-----------------------------
* ``register(query, window)`` compiles the query's ExecutionPlan (host-
  side numpy, cheap), looks up its structural signature
  (``repro.core.registry.plan_signature``), and arms a free slot in an
  existing group — a pure device-data write, **no XLA recompilation**.
  Compiled slot ticks live in a process-wide ``SlotTickCache`` keyed by
  signature, so even a never-seen *group* (overflow, or a restored
  server) only compiles when the *structure* is new to the process;
  ``n_compiles`` counts those builds for observability.
* ``unregister(qid)`` disarms the slot (again data-only).

Cross-tenant prefix sharing
---------------------------
With ``enable_sharing=True`` the service CSEs TC-subquery prefixes
across tenants (``repro.core.share``): each registration acquires a
refcounted chain of ``SharedPrefixForest`` nodes — one expansion-list
table per canonical prefix signature and registration epoch — and the
tenant's slot tick consumes the leaf's view, running only its suffix
joins.  The forest is advanced ONCE per tick by a dedicated prefix tick
regardless of how many tenants alias each node; slot groups gain a
prefix dimension (group key = structural signature × prefix node), and
checkpoints snapshot the forest (tables + refcounts + signatures) so a
restored service resumes sharing with zero warm recompiles.  Per-tenant
results are oracle-exact either way; see ``shared_prefix(qid)`` /
``forest_stats()`` and ``ServeInfo.n_shared_prefix_ticks``.

Serving
-------
* ``ingest(batch)`` advances every group's fused tick once and returns
  ``{qid: TickResult}`` — the low-level fixed-batch API.  Batches must
  keep a fixed shape (pad the tail; ``to_batches`` does) — a new batch
  size re-specializes the jitted ticks, as usual under JAX.
* ``serve_stream(edges, ...)`` is the production loop over a DataEdge
  list: a ``TickCoalescer`` adapts the chunk size to the measured
  per-tick barrier latency and queue depth (all groups dispatch
  asynchronously and meet at one barrier, so the slowest group
  inherently sets the pace — backpressure), chunks are padded to
  power-of-two shapes (``quantize_pow2``) to bound jit
  specializations, matches stream out through
  ``on_match(qid, bindings, ets)``, and every ``ckpt_every`` ticks the
  full service state is checkpointed asynchronously.
* With the default ``donate=True``, slot ticks are jitted with
  ``donate_argnums=(0,)``: each tick consumes the previous ``SlotState``
  buffers in place instead of copying the tables every tick.

Fault tolerance
---------------
``checkpoint()`` snapshots every group's ``SlotState`` pytree through
``repro.checkpoint.AsyncCheckpointer`` plus a JSON manifest of the whole
registry (qid -> query/window, slot layout, structural templates,
counters).  ``ContinuousSearchService.restore(ckpt_dir)`` rebuilds the
full multi-tenant server from the newest *usable* checkpoint — torn or
partial files are skipped — re-registering every query into the same
slot layout with the same qids, and re-arming the compiled ticks from
the ``SlotTickCache`` (zero recompiles for structures this process has
already served).  By the paper's timing-order semantics a restored
server misses nothing still inside the window: the differential test
(tests/test_service_restore.py) proves crash + restore reports exactly
the same match set as an uninterrupted run.

The compatibility-join implementation of every group's slot tick is
chosen by the platform (``repro.core.join.resolve_backend``): the fused
Pallas kernels (``PALLAS``) on a TPU, the pure-jnp reference (``REF``)
elsewhere.  Tests may pin ``REF`` or ``PALLAS_INTERPRET`` (the kernels
interpreted on the CPU); the compiled path is checked against REF on a
TPU v5e by ``chip_smoke.py``.

Example
-------
    svc = ContinuousSearchService(ckpt_dir="/ckpts")
    q1 = svc.register(chain_query, window=50)
    svc.serve_stream(edges, on_match=alert, ckpt_every=50)
    ...                                    # crash? restart:
    svc = ContinuousSearchService.restore("/ckpts")
    svc.serve_stream(edges[svc.n_edges_ingested:], on_match=alert)
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.checkpoint import (
    AsyncCheckpointer,
    CheckpointError,
    checkpoint_steps,
    dict_diff,
    load_resolved_manifest,
    restore_checkpoint,
    validate_checkpoint,
)
from repro.core import join as J
from repro.core.multi import (
    GLOBAL_SLOT_TICK_CACHE,
    SlotState,
    SlotTickCache,
    clear_slot,
    init_slot_state,
    read_slot,
    write_slot,
)
from repro.core.engine import TickLoad, TickResult, current_matches
from repro.core.plan import ExecutionPlan
from repro.core.query import QueryGraph
from repro.core.registry import (
    QueryRegistry,
    plan_decomposition,
    plan_signature,
)
from repro.core.share import (
    SharedPrefixForest,
    SharedPrefixInfo,
    shared_current_matches,
)
from repro.core.engine import NO_WATERMARK
from repro.core.state import EdgeBatch, EngineState, init_state, make_batch
from repro.obs import MetricsRegistry, Tracer, maybe_span
from repro.runtime.straggler import TickCoalescer, quantize_pow2
from repro.stream.generator import to_batches


def _serving_config(man: dict) -> dict:
    """A manifest's constructor config, minus what the restoring process
    decides for itself: checkpoints from before the backend left the
    manifest still carry one, and it must not pin the join kernels."""
    config = dict(man["config"])
    config.pop("backend", None)
    return config


class ServeInfo(NamedTuple):
    """Per-tick observability record passed to ``serve_stream``'s
    ``on_tick`` callback (after state update and any checkpoint)."""

    tick: int               # cumulative tick count (checkpoint step id)
    n_edges_ingested: int   # cumulative edges consumed after this tick
    chunk: int              # edges consumed by this tick
    latency_ms: float       # barrier latency of this tick (all groups)
    n_overflow: int = 0     # dropped appends this tick, summed over qids
                            # (shared-prefix drops attributed per tenant,
                            # matching the unshared engine's counters)
    n_shared_prefix_ticks: int = 0   # forest nodes advanced this tick
    # ingest-frontier observability (``serve_frontier`` only; the plain
    # ``serve_stream`` path leaves the defaults)
    watermark: int | None = None     # event-time watermark after this tick
    n_late_dropped: int = 0          # frontier late drops this tick
    n_duplicates: int = 0            # suppressed duplicate deliveries, tick
    n_reconnects: int = 0            # source reconnects this tick
    n_dropped_forced_gap: int = 0    # capacity-pressure drops this tick
    watermark_lag: int = 0           # freshest data ts − watermark
    window_staleness: int = 0        # emit floor − watermark (forced gap)
    # live-row counters of this tick (``repro.core.engine.TickLoad``),
    # summed over every slot the tick ran: live and allocated rows of
    # the level and L0 tables after expiry, and over every join call
    # Σ live_a·live_b and Σ ext_a·ext_b (the pairs below both sides'
    # live extents, which the pairs kernel sweeps) against Σ cap_a·cap_b
    live_rows: int = 0
    capacity_rows: int = 0
    live_pairs: int = 0
    capacity_pairs: int = 0
    swept_pairs: int = 0
    # ``serve_frontier`` only: each released record's hold in the
    # frontier's reorder buffer, ms (``IngestFrontier.take_ready``)
    hold_ms: tuple = ()


@dataclass(eq=False)       # identity semantics: fields hold device arrays
class _Group:
    """One slot group: compiled tick + device state + slot ownership."""

    gid: int                          # stable id (checkpoint manifest key)
    template: ExecutionPlan
    tick: object                      # jitted slot tick (SlotTickCache-shared)
    sstate: SlotState
    empty: EngineState                # cached init_state(template) for churn
    qids: list = field(default_factory=list)   # qid | None per slot
    prefix: object = None             # share.PrefixNode leaf | None
    prefix_depth: int = 0             # externalized subquery-0 levels

    def free_slot(self, lo: int = 0, hi: int | None = None) -> int | None:
        """First free slot in ``[lo, hi)`` (mesh placement restricts the
        search to one replica's contiguous slot block)."""
        hi = len(self.qids) if hi is None else hi
        for k in range(lo, hi):
            if self.qids[k] is None:
                return k
        return None

    @property
    def idle(self) -> bool:
        return all(q is None for q in self.qids)


class ContinuousSearchService:
    """Multi-tenant continuous subgraph search over one edge stream."""

    def __init__(
        self,
        slots_per_group: int = 4,
        level_capacity: int = 2048,
        l0_capacity: int = 2048,
        max_new: int = 512,
        backend: str | None = None,
        extract_matches: bool = True,
        max_out: int | None = None,
        jit: bool = True,
        donate: bool = True,
        ckpt_dir: str | None = None,
        keep_checkpoints: int = 8,
        tick_cache: SlotTickCache | None = None,
        enable_sharing: bool = False,
        compact_every: int = 1,
        obs: MetricsRegistry | None = None,
        tracer: Tracer | None = None,
    ):
        self.slots_per_group = slots_per_group
        self.backend = J.resolve_backend(backend)
        self.extract_matches = extract_matches
        self.max_out = max_out
        self._jit = jit
        self.donate = donate and jit
        self.tick_cache = (GLOBAL_SLOT_TICK_CACHE if tick_cache is None
                           else tick_cache)
        self.ckpt_dir = ckpt_dir
        self.keep_checkpoints = keep_checkpoints
        self.ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        self.registry = QueryRegistry(
            level_capacity=level_capacity, l0_capacity=l0_capacity,
            max_new=max_new)
        # group key: (plan_signature, prefix-leaf pid | None) — sharing
        # adds a prefix dimension to the slot-group layout, since every
        # slot of one group consumes ONE broadcast prefix view
        self._groups: dict[tuple, list[_Group]] = {}
        self._location: dict[int, tuple[_Group, int]] = {}
        self.forest = (SharedPrefixForest(
            self.tick_cache, backend=self.backend, jit=jit,
            donate=self.donate) if enable_sharing else None)
        self._prefix_of: dict[int, object] = {}   # qid -> leaf PrefixNode
        self._next_gid = 0
        self._frontier = None        # IngestFrontier bound by serve_frontier
        self.restored_ingest = None  # ingest manifest from restore()
        self._ckpt_step = 0          # last step id written (monotonic)
        # incremental manifests: with compact_every > 1 only every K-th
        # checkpoint re-serializes the whole registry; the steps between
        # write structural DELTAS against the previous step's manifest
        # (O(churn) bytes instead of O(total tenants) — see
        # repro.checkpoint.dict_diff / load_resolved_manifest)
        if compact_every < 1:
            raise ValueError("compact_every must be >= 1")
        self.compact_every = compact_every
        self._last_manifest: dict | None = None   # resolved, last written
        self._last_man_step: int | None = None
        self._chain_len = 0          # delta steps since last compacted base
        self.n_compiles = 0          # build_slot_tick cache misses (this service)
        self.n_edges_ingested = 0
        self.n_ticks = 0
        # caller state carried inside every checkpoint manifest (the api
        # layer persists its vocab/pattern plans here); a dict, or a
        # zero-arg callable evaluated at checkpoint time
        self.manifest_extra: dict = {}
        # observability (repro.obs): both OFF by default, and every hot-
        # path call site is guarded with an identity check so the
        # disabled service allocates nothing per tick and emits no spans.
        # Runtime knobs, deliberately NOT in the checkpoint config — a
        # restored service chooses its own instrumentation; the
        # registry's counter/histogram history rides in the manifest.
        self.obs = obs
        self.tracer = tracer
        if obs is not None:
            self._register_obs_gauges()

    def _register_obs_gauges(self) -> None:
        """Collect-time callback gauges (snapshot cost, zero tick cost)."""
        obs = self.obs
        obs.register_gauge("tick.n_active", lambda: self.n_active)
        obs.register_gauge(
            "tick.n_groups",
            lambda: sum(len(gs) for gs in self._groups.values()))
        obs.register_gauge("tick.n_compiles", lambda: self.n_compiles)
        if self.ckpt is not None:
            obs.register_gauge("ckpt.stall_s", lambda: self.ckpt.stall_s)
        if self.forest is not None:
            self.forest.register_obs(obs)

    # ------------------------------------------------------------------ #
    @property
    def n_active(self) -> int:
        return len(self._location)

    def _iter_groups(self) -> list[_Group]:
        """All groups in stable gid order (manifest / serving order)."""
        return sorted((g for gs in self._groups.values() for g in gs),
                      key=lambda g: g.gid)

    def _new_group(self, template: ExecutionPlan, leaf=None) -> _Group:
        depth = 0 if leaf is None else leaf.depth
        before = self.tick_cache.n_builds
        tick = self.tick_cache.get(
            template, backend=self.backend,
            extract_matches=self.extract_matches, max_out=self.max_out,
            jit=self._jit, donate=self.donate, prefix_depth=depth)
        self.n_compiles += self.tick_cache.n_builds - before
        g = _Group(
            gid=self._next_gid,
            template=template,
            tick=tick,
            sstate=init_slot_state(template, self.slots_per_group, depth),
            empty=init_state(template, depth),
            qids=[None] * self.slots_per_group,
            prefix=leaf,
            prefix_depth=depth,
        )
        self._next_gid += 1
        return g

    def _place(self, groups: list, plan: ExecutionPlan, leaf,
               signature) -> tuple[_Group, int]:
        """Pick ``(group, slot)`` for a new tenant of this group key,
        allocating a fresh group when none has a free slot.  The single
        placement hook: ``repro.runtime.mesh`` overrides it to route the
        choice through a replica ``PlacementPolicy`` and restrict the
        slot search to the chosen replica's block."""
        for g in groups:
            k = g.free_slot()
            if k is not None:
                return g, k
        g = self._new_group(plan, leaf)
        groups.append(g)
        return g, 0

    # ------------------------------------------------------------------ #
    def register(self, query: QueryGraph, window: int,
                 plan: ExecutionPlan | None = None) -> int:
        """Add a standing query; returns its qid.

        Always a pure data write when a group with the same structural
        signature has a free slot; an overflowing (or never-seen)
        structure allocates one new group, whose compiled tick comes
        from the process-wide ``SlotTickCache`` — only a structure new
        to the whole process actually compiles.  Pass ``plan`` to serve
        an exact pre-compiled plan (custom decomposition) instead of
        letting the registry compile one.
        """
        qid = self.registry.register(query, window, plan=plan)
        rq = self.registry.get(qid)
        leaf, gkey = None, None
        try:
            if self.forest is not None:
                # acquire the prefix chain at the CURRENT stream offset:
                # only tenants registered at the same offset may alias a
                # node, so shared tables hold exactly the history each
                # tenant would have built alone (oracle-exact under churn)
                leaf = self.forest.acquire(rq.plan,
                                           epoch=self.n_edges_ingested)
                self._prefix_of[qid] = leaf
            gkey = (rq.signature, None if leaf is None else leaf.pid)
            groups = self._groups.setdefault(gkey, [])
            group, k = self._place(groups, rq.plan, leaf, rq.signature)
            group.sstate = write_slot(group.sstate, group.template, k,
                                      rq.plan, empty=group.empty)
        except Exception:
            # no half-registered tenant: a failure anywhere (chain
            # acquisition, tick compile, slot write) rolls the qid, any
            # acquired prefix references, and an empty group-key entry
            # back out
            self.registry.unregister(qid)
            if self._prefix_of.pop(qid, None) is not None:
                self.forest.release(leaf)
            if gkey is not None and not self._groups.get(gkey):
                self._groups.pop(gkey, None)
            raise
        group.qids[k] = qid
        self._location[qid] = (group, k)
        return qid

    def unregister(self, qid: int) -> None:
        """Drop a standing query and its partial-match state (data-only).

        A group whose slots all become empty is released, except that one
        idle group per structural signature is kept warm so a tenant of a
        recently-seen structure can re-register without re-initializing
        device tables.  Use ``drop_idle_groups()`` to reclaim the warm
        groups too (the compiled tick itself stays in the SlotTickCache).
        Under prefix sharing idle groups are dropped immediately: their
        prefix node is released with the last tenant, and a later tenant
        of the same structure starts a fresh epoch (fresh node), so the
        warm group could never be re-armed.
        """
        group, k = self._location.pop(qid)
        group.sstate = clear_slot(group.sstate, group.template, k,
                                  empty=group.empty)
        group.qids[k] = None
        self.registry.unregister(qid)
        leaf = self._prefix_of.pop(qid, None)
        if leaf is not None:
            self.forest.release(leaf)
        if group.idle:
            gkey = next(
                key for key, gs in self._groups.items() if group in gs)
            siblings = self._groups[gkey]
            n_idle = sum(1 for g in siblings if g.idle)
            if group.prefix is not None or n_idle > 1:
                siblings.remove(group)
                if not siblings:
                    del self._groups[gkey]

    def overflow_pressure(self, signature=None) -> int:
        """Cumulative dropped appends across active tenants — of one
        structural ``plan_signature``, or the whole service.

        The engine counts per-slot overflow passively; this is the
        admission-control read: a structure under pressure (> 0) has
        already lost partial matches at the current capacities, so the
        api layer refuses to admit more tenants of that structure.
        ONE device read per group (the stacked ``[S]`` overflow counters
        come back in a single transfer; unarmed slots hold zeros) —
        call at admission/status time, not per tick.  Under prefix
        sharing the shared tables drop appends on behalf of every
        aliasing tenant, so each live group's prefix-chain overflow
        counts toward its structure's pressure too.
        """
        if signature is not None:
            groups = [g for (sig, _), gs in self._groups.items()
                      if sig == signature for g in gs]
        else:
            groups = self._iter_groups()
        live = [g for g in groups if not g.idle]
        total = sum(
            int(np.asarray(g.sstate.engines.stats.n_overflow).sum())
            for g in live)
        if self.forest is not None:
            seen = set()
            for g in live:
                node = g.prefix
                while node is not None and node.pid not in seen:
                    seen.add(node.pid)
                    total += int(np.asarray(node.state.n_overflow))
                    node = node.parent
        return total

    def drop_idle_groups(self) -> int:
        """Release all fully-empty slot groups (device tables); returns
        how many were dropped.  Compiled ticks stay cached, so
        re-registering a dropped structure re-allocates tables only."""
        dropped = 0
        for sig in list(self._groups):
            keep = [g for g in self._groups[sig] if not g.idle]
            dropped += len(self._groups[sig]) - len(keep)
            if keep:
                self._groups[sig] = keep
            else:
                del self._groups[sig]
        return dropped

    # ------------------------------------------------------------------ #
    def _advance_forest(self, batch: EdgeBatch, watermark=None):
        """The dedicated prefix tick: every live forest node advances
        once per service tick, no matter how many tenants alias it.
        Returns the per-node views consumed by the groups' suffix ticks
        plus the nodes' per-tick overflow scalars by pid (device)."""
        if self.forest is None or not len(self.forest):
            return {}, {}
        return self.forest.advance(batch, watermark)

    def _advance_group(self, g: _Group, batch: EdgeBatch, views=None,
                       forest_nds=None, watermark=None):
        """One fused tick for one group.  With ``donate`` the previous
        sstate buffers are consumed — ``g.sstate`` is rebound before this
        returns, so no caller can observe the donated state.

        A shared-prefix group's result comes back with each slot's
        ``n_overflow`` raised by its chain's drops this tick: the shared
        table drops on behalf of every aliasing tenant, and per-tenant
        counters must read as the unshared engine's would.

        ``watermark`` (None or a traced int32 scalar) is handed straight
        to the slot tick: one value per service tick drives every
        tenant's event-time clock (``serve_frontier`` feeds the
        frontier's watermark; the offline paths pass None and keep the
        legacy max-ts clock).
        """
        if g.prefix is not None:
            g.sstate, res = g.tick(g.sstate, batch, views[g.prefix.pid],
                                   watermark)
            chain_nd = self.forest.chain_tick_overflow(g.prefix, forest_nds)
            res = res._replace(
                n_overflow=res.n_overflow
                + jnp.where(g.sstate.params.active, chain_nd, 0))
        else:
            g.sstate, res = g.tick(g.sstate, batch, watermark)
        return res

    def ingest(self, batch, watermark=None) -> dict[int, TickResult]:
        """Advance all standing queries by one batch of stream edges.

        ``batch`` is an EdgeBatch or a dict of arrays (``to_batches``
        output).  Returns a per-qid TickResult (unstacked views of each
        group's fused result).  ``watermark`` switches the engines to
        event-time admission/expiry (see ``repro.core.engine``); None
        keeps the legacy max-ts clock.
        """
        if not isinstance(batch, EdgeBatch):
            batch = make_batch(**batch)
        views, forest_nds = self._advance_forest(batch, watermark)
        out: dict[int, TickResult] = {}
        for g in self._iter_groups():
            if g.idle:
                continue
            res = self._advance_group(g, batch, views, forest_nds,
                                      watermark)
            for k, qid in enumerate(g.qids):
                if qid is not None:
                    out[qid] = jax.tree.map(lambda x, k=k: x[k], res)
        self.n_ticks += 1
        # count on host: batch.valid is a concrete input array, so this
        # adds no sync point against the async tick dispatches above
        self.n_edges_ingested += int(np.asarray(batch.valid).sum())
        return out

    # ------------------------------------------------------------------ #
    def serve_stream(
        self,
        edges: list,
        on_match=None,
        on_tick=None,
        ckpt_every: int = 0,
        batch_size: int = 64,
        min_batch: int | None = None,
        max_batch: int | None = None,
        target_latency_ms: float = 50.0,
        coalescer: TickCoalescer | None = None,
        final_checkpoint: bool = True,
    ) -> dict[int, int]:
        """Drive the service over a DataEdge list (the production loop).

        One ``TickCoalescer`` adapts the chunk size to the measured tick
        latency and queue depth; chunks are padded to power-of-two
        shapes so the adaptive sizes produce a bounded set of jit
        specializations.  Group ticks dispatch asynchronously and the
        loop blocks ONCE per tick: the measured latency is the barrier
        every group experiences, so the slowest group inherently sets
        the pace (backpressure).  ``on_match(qid, bindings, ets)`` fires
        for each tenant's new matches; ``on_tick(ServeInfo)`` fires
        after each tick's state update (and checkpoint, if due) — an
        exception raised from it leaves the last checkpoint consistent,
        which is how the crash/restore tests inject failures.  With
        ``ckpt_dir`` set and ``ckpt_every > 0`` the full service state
        is checkpointed asynchronously every that-many ticks, plus once
        at the end of the call if ticks advanced past the last written
        step (so returning implies the served span is durable); pending
        writes are flushed before returning.  A consumer feeding the
        stream in many small calls can pass ``final_checkpoint=False``
        to keep strictly-every-``ckpt_every`` cadence.

        Pass ``coalescer`` to carry AIMD state across calls (a consumer
        feeding the stream in repeated ``serve_stream`` invocations
        keeps its converged batch size); the batch_size/bounds/latency
        arguments then have no effect.

        Returns ``{qid: total new matches}`` over the served span.
        """
        if on_match is not None and not self.extract_matches:
            raise ValueError(
                "on_match requires a service with extract_matches=True")
        if ckpt_every and self.ckpt is None:
            raise ValueError(
                "ckpt_every requires a service with ckpt_dir set — "
                "without it every checkpoint would be a silent no-op")
        if coalescer is None:
            coalescer = TickCoalescer.seeded(
                batch_size, min_batch, max_batch, target_latency_ms)

        totals: dict[int, int] = {}
        i, n = 0, len(edges)
        while i < n:
            tr = self.tracer
            if tr is not None:
                tr.next_tick()
            try:
                with maybe_span(tr, "serve.round"):
                    chunk = edges[i:i + coalescer.batch]
                    queue_depth = n - (i + len(chunk))
                    info = self._tick_chunk(
                        chunk, on_match, totals,
                        min_width=coalescer.min_batch)
                    # overflow joins latency and queue depth as a
                    # throttle input: dropped appends mean the tick was
                    # too big for the tables
                    coalescer.record(info.latency_ms, queue_depth,
                                     info.n_overflow)
                    if self.obs is not None:
                        self._observe_coalescer(coalescer)
                    i += len(chunk)
                    if self.ckpt and ckpt_every and \
                            self.n_ticks % ckpt_every == 0:
                        self.checkpoint()
                    if on_tick is not None:
                        with maybe_span(tr, "serve.on_tick"):
                            on_tick(info)
            finally:
                if tr is not None:
                    tr.flush()
        self._final_checkpoint(ckpt_every, final_checkpoint)
        return totals

    def _tick_chunk(self, chunk: list, on_match, totals: dict,
                    watermark=None, *, min_width: int) -> ServeInfo:
        """One production tick over ``chunk`` (a DataEdge list): batch
        padded to a power of two of at least ``min_width`` (the
        coalescer's floor, so a fixed batch size is one jit shape however
        ragged the released chunks are), async group dispatch, ONE
        barrier, match delivery (one host read per group, which also
        brings back the group's live-row counters).  Updates
        ``totals``/counters in place; returns the tick's ``ServeInfo``
        (frontier fields at their defaults).  Shared by ``serve_stream``
        (arrival-order chunks, ``watermark=None``) and ``serve_frontier``
        (watermark-order chunks with the frontier's traced event-time
        watermark).

        With a tracer the tick is the span ``tick``, holding
        ``tick.forest`` (only when a forest exists), ``tick.dispatch``,
        ``tick.barrier`` and ``tick.deliver``; the last holds, per group,
        ``tick.readback`` (the device reads) and ``tick.callbacks`` (the
        ``on_match`` calls, where the api layer builds its records)."""
        tr = self.tracer
        with maybe_span(tr, "tick") as tick_span:
            active = [g for g in self._iter_groups() if not g.idle]
            batch = make_batch(**to_batches(
                chunk, quantize_pow2(len(chunk), lo=min_width))[0])
            t0 = time.perf_counter()
            views, forest_nds = {}, {}
            if self.forest is not None and len(self.forest):
                with maybe_span(tr, "tick.forest"):
                    views, forest_nds = self._advance_forest(batch,
                                                             watermark)
            with maybe_span(tr, "tick.dispatch"):
                results = [(g, self._advance_group(g, batch, views,
                                                   forest_nds, watermark))
                           for g in active]
            with maybe_span(tr, "tick.barrier"):
                jax.block_until_ready(
                    [g.sstate for g in active]
                    + ([] if self.forest is None else self.forest.states()))
            lat_ms = (time.perf_counter() - t0) * 1e3
            with maybe_span(tr, "tick.deliver") as deliver:
                n_matches, tick_overflow, load, ticked = self._deliver(
                    results, on_match, totals)
            if tr is not None:
                deliver.set(n_matches=n_matches)
                tick_span.set(chunk=len(chunk), live_rows=load[0],
                              capacity_rows=load[1], live_pairs=load[2],
                              capacity_pairs=load[3], swept_pairs=load[4])
                self._trace_tick_extras(tr, tick_span, ticked)
        self.n_ticks += 1
        self.n_edges_ingested += len(chunk)
        obs = self.obs
        if obs is not None:
            obs.histogram("tick.latency_ms").observe(lat_ms)
            obs.counter("tick.n_ticks").inc()
            obs.counter("tick.n_edges").inc(len(chunk))
            obs.counter("tick.n_matches").inc(n_matches)
            obs.counter("tick.n_overflow").inc(tick_overflow)
            obs.gauge("tick.live_rows").set(load[0])
            obs.gauge("tick.capacity_rows").set(load[1])
            if views:
                obs.counter("share.n_prefix_ticks").inc(len(views))
        return ServeInfo(
            tick=self.n_ticks, n_edges_ingested=self.n_edges_ingested,
            chunk=len(chunk), latency_ms=lat_ms, n_overflow=tick_overflow,
            n_shared_prefix_ticks=len(views), live_rows=load[0],
            capacity_rows=load[1], live_pairs=load[2],
            capacity_pairs=load[3], swept_pairs=load[4])

    def _deliver(self, results, on_match, totals: dict
                 ) -> tuple[int, int, tuple[int, int, int, int, int], list]:
        """Read back each group's tick result and deliver its matches.
        Returns (new matches, overflow, ``TickLoad.totals()`` summed over
        the groups, and with a tracer each group with its ``TickLoad``:
        ``(group, load)`` pairs for ``_trace_tick_extras``)."""
        tr = self.tracer
        n_matches = tick_overflow = 0
        load = (0, 0, 0, 0, 0)
        ticked = []
        for g, res in results:
            armed = [(k, qid) for k, qid in enumerate(g.qids)
                     if qid is not None]
            with maybe_span(tr, "tick.readback"):
                # the group's [S] counters come back in one transfer;
                # match rows only when an armed slot has new matches
                n_new_s, n_ov_s, g_load = jax.device_get(
                    (res.n_new_matches, res.n_overflow, res.load))
                rows = None
                if on_match is not None and any(n_new_s[k] for k, _ in armed):
                    rows = jax.device_get((res.match_bindings,
                                           res.match_ets, res.match_valid))
            with maybe_span(tr, "tick.callbacks"):
                for k, qid in armed:
                    n_new = int(n_new_s[k])
                    tick_overflow += int(n_ov_s[k])
                    n_matches += n_new
                    totals[qid] = totals.get(qid, 0) + n_new
                    if n_new and rows is not None:
                        mb, me, mv = (x[k] for x in rows)
                        on_match(qid, mb[mv], me[mv])
            g_tick = TickLoad.unpack(g_load)
            load = tuple(a + b for a, b in zip(load, g_tick.totals()))
            if tr is not None:
                ticked.append((g, g_tick))
        return n_matches, tick_overflow, load, ticked

    def _trace_tick_extras(self, tr: Tracer, tick_span, ticked) -> None:
        """Tracer-on hook at the end of a tick, with the open ``tick``
        span and each ticked group with its ``TickLoad`` (as read back
        for delivery).  The mesh service adds its collective scalars and
        per-replica rows here; the base service has none."""

    def _observe_coalescer(self, coalescer: TickCoalescer) -> None:
        """Mirror the AIMD decision just taken into ``coalescer.*``
        (obs-on path only — callers guard on ``self.obs``)."""
        self.obs.counter(f"coalescer.{coalescer.last_action}").inc()
        self.obs.gauge("coalescer.batch").set(coalescer.batch)
        if self.tracer is not None:
            self.tracer.event("coalescer.decision",
                              action=coalescer.last_action,
                              batch=coalescer.batch)

    def _final_checkpoint(self, ckpt_every: int, final: bool) -> None:
        if self.ckpt:
            if ckpt_every and final and \
                    self.n_ticks % ckpt_every != 0 and \
                    self.n_ticks > self._ckpt_step:
                self.checkpoint()       # final end-of-call durability
            self.ckpt.wait()

    def serve_frontier(
        self,
        frontier,
        on_match=None,
        on_tick=None,
        ckpt_every: int = 0,
        batch_size: int = 64,
        min_batch: int | None = None,
        max_batch: int | None = None,
        target_latency_ms: float = 50.0,
        coalescer: TickCoalescer | None = None,
        final_checkpoint: bool = True,
        pump_size: int = 64,
        max_idle_rounds: int | None = None,
    ) -> dict[int, int]:
        """Drive the service from an ``IngestFrontier`` (the real-traffic
        production loop): sources -> retry/dedup -> k-way merge ->
        watermark -> tick.

        The coalescer ticks on WATERMARK ADVANCE, not arrival order:
        each round pumps every live source, takes the events the
        watermark has released (in deterministic merged event-time
        order, at most the coalescer's batch), and ticks only when
        something is ready — an all-sources stall is an idle round
        (``TickCoalescer.record_idle``), not a tick of garbage.  The
        frontier is bound to the service for the duration, so
        checkpoints written during the loop embed its resume state
        (per-source ack cursors + emit floor) in the manifest:
        ``ContinuousSearchService.restore`` surfaces it as
        ``restored_ingest`` and ``IngestFrontier.resume`` picks the
        stream back up exactly-once (replayed deliveries suppressed).

        Event-time end-to-end: each tick hands the frontier's
        ``watermark()`` to every engine as a traced scalar, so window
        admission and expiry follow EVENT time (what the sources
        produced) instead of processing order (what the reorder buffer
        happened to release) — a force-evicted straggler can no longer
        jump the window clock and prematurely expire every tenant's
        partials; ``allowed_lateness`` trades completeness against
        window staleness end-to-end.  The watermark rides in every
        checkpoint manifest, so a restored frontier + service resume the
        same clock (no re-expiry, no resurrection).

        ``ServeInfo`` gains the frontier fields: ``watermark``,
        ``watermark_lag`` / ``window_staleness`` gauges, and the
        per-tick ``n_late_dropped`` / ``n_dropped_forced_gap`` /
        ``n_duplicates`` / ``n_reconnects`` deltas — no event leaves the
        pipeline unaccounted.  ``max_idle_rounds`` bounds how many
        consecutive empty rounds to tolerate before returning (None:
        serve until every source is exhausted — a source whose retry
        budget is spent counts as exhausted, so a dead source can't spin
        this loop forever); the frontier stays resumable either way.
        Returns ``{qid: total new matches}``.
        """
        if on_match is not None and not self.extract_matches:
            raise ValueError(
                "on_match requires a service with extract_matches=True")
        if ckpt_every and self.ckpt is None:
            raise ValueError(
                "ckpt_every requires a service with ckpt_dir set — "
                "without it every checkpoint would be a silent no-op")
        if coalescer is None:
            coalescer = TickCoalescer.seeded(
                batch_size, min_batch, max_batch, target_latency_ms)
        totals: dict[int, int] = {}
        # stays bound after return, so later checkpoints (tenant churn,
        # shutdown) keep embedding the stream cursors — unbinding would
        # make a post-serve restore silently replay the whole stream
        self._frontier = frontier
        prev = frontier.stats()
        idle = 0
        new_tick = True        # the next traced round opens a tick id
        while not frontier.exhausted:
            tr = self.tracer
            if tr is not None and new_tick:
                # rounds that wait for data share the id of the tick
                # they lead to
                tr.next_tick()
                new_tick = False
            try:
                with maybe_span(tr, "serve.round"):
                    with maybe_span(tr, "ingest.pump"):
                        frontier.pump(pump_size)
                    with maybe_span(tr, "ingest.release") as rel:
                        chunk = frontier.take_ready(limit=coalescer.batch)
                    holds = frontier.last_holds_ms
                    if tr is not None:
                        rel.set(n_released=len(chunk),
                                hold_ms=[round(h, 3) for h in holds])
                    if not chunk:
                        idle += 1
                        coalescer.record_idle()
                        if self.obs is not None:
                            self._observe_coalescer(coalescer)
                        if max_idle_rounds is not None and \
                                idle > max_idle_rounds:
                            break
                        continue
                    idle = 0
                    # the frontier's event-time watermark drives every
                    # engine's admission/expiry clock this tick.  Traced
                    # scalar (one jit specialization for the whole
                    # event-time mode, not one per value); NO_WATERMARK
                    # is the traced "unknown yet" identity.
                    wm = frontier.watermark()
                    wm_in = jnp.asarray(
                        NO_WATERMARK if wm is None else wm, jnp.int32)
                    info = self._tick_chunk(
                        chunk, on_match, totals, wm_in,
                        min_width=coalescer.min_batch)
                    new_tick = True
                    coalescer.record(info.latency_ms, frontier.buffered,
                                     info.n_overflow)
                    if self.obs is not None:
                        self._observe_coalescer(coalescer)
                        frontier.publish_obs(self.obs)
                    if self.ckpt and ckpt_every and \
                            self.n_ticks % ckpt_every == 0:
                        self.checkpoint()
                    if on_tick is not None:
                        cur = frontier.stats()
                        info = info._replace(
                            watermark=cur.watermark,
                            n_late_dropped=cur.n_late_dropped
                            - prev.n_late_dropped,
                            n_duplicates=cur.n_duplicates
                            - prev.n_duplicates,
                            n_reconnects=cur.n_reconnects
                            - prev.n_reconnects,
                            n_dropped_forced_gap=cur.n_dropped_forced_gap
                            - prev.n_dropped_forced_gap,
                            watermark_lag=cur.watermark_lag,
                            window_staleness=cur.window_staleness,
                            hold_ms=tuple(holds))
                        prev = cur
                        with maybe_span(tr, "serve.on_tick"):
                            on_tick(info)
            finally:
                if tr is not None:
                    tr.flush()
        self._final_checkpoint(ckpt_every, final_checkpoint)
        return totals

    # ------------------------------------------------------------------ #
    # checkpoint / restore
    # ------------------------------------------------------------------ #
    def _manifest(self) -> dict:
        """JSON-serializable description of everything that is NOT a
        device array: config, registry, slot layout, counters."""
        extra = (self.manifest_extra() if callable(self.manifest_extra)
                 else self.manifest_extra)
        return {
            "extra": extra,
            "config": {
                "slots_per_group": self.slots_per_group,
                "level_capacity": self.registry.level_capacity,
                "l0_capacity": self.registry.l0_capacity,
                "max_new": self.registry.max_new,
                "extract_matches": self.extract_matches,
                "max_out": self.max_out,
                "jit": self._jit,
                "donate": self.donate,
                "keep_checkpoints": self.keep_checkpoints,
                "enable_sharing": self.forest is not None,
                "compact_every": self.compact_every,
            },
            "queries": {
                str(qid): {
                    "query": self.registry.get(qid).query.to_spec(),
                    "window": int(self.registry.get(qid).window),
                    # exact plan round-trip: restore bypasses the
                    # decomposition heuristics (custom plans survive)
                    "decomposition": [
                        list(seq) for seq in
                        plan_decomposition(self.registry.get(qid).plan)
                    ],
                }
                for qid in self.registry.qids()
            },
            # keyed by gid (not a list): stable keys make churn deltas
            # O(changed groups) under dict_diff instead of shifting every
            # downstream entry when a group is dropped
            "groups": {
                str(g.gid): {
                    "template_query": g.template.query.to_spec(),
                    "template_window": int(g.template.window),
                    "template_decomposition": [
                        list(seq) for seq in plan_decomposition(g.template)
                    ],
                    "qids": list(g.qids),
                    "prefix_pid": (None if g.prefix is None
                                   else g.prefix.pid),
                }
                for g in self._iter_groups()
            },
            "forest": (None if self.forest is None
                       else self.forest.to_manifest()),
            # ingest-frontier resume state (serve_frontier binds it):
            # per-source ack cursors + emit floor, so a restored service
            # can resume mid-stream exactly-once (IngestFrontier.resume)
            "ingest": (None if self._frontier is None
                       else self._frontier.to_manifest()),
            "counters": {
                "n_edges_ingested": int(self.n_edges_ingested),
                "n_ticks": int(self.n_ticks),
                "next_qid": int(self.registry.next_qid),
            },
            # obs registry history (counters + histogram buckets): a
            # restored service resumes its cumulative metrics, so e.g.
            # drop-driven health attribution survives restore
            "obs": (None if self.obs is None else self.obs.to_manifest()),
        }

    def _ckpt_tree(self) -> dict:
        tree = {str(g.gid): g.sstate for g in self._iter_groups()}
        if self.forest is not None:
            tree.update({f"prefix{n.pid}": n.state
                         for n in self.forest.nodes()})
        return tree

    def _ckpt_save_kwargs(self) -> dict:
        """Extra ``AsyncCheckpointer.save`` kwargs — the mesh service
        overrides this with per-replica shard splitting."""
        return {}

    def checkpoint(self, step: int | None = None):
        """Snapshot all groups' ``SlotState`` pytrees + the service
        manifest, asynchronously.  Returns the writer future (call
        ``self.ckpt.wait()`` to block on durability).

        Step ids are strictly monotonic even when the tick count has not
        advanced (e.g. a registry-only change checkpointed twice at the
        same tick): overwriting an existing step would put previously
        durable state at risk if a crash tore the rewrite.

        With ``compact_every > 1``, at most every K-th step carries the
        full manifest; the steps between write ``service_delta`` patches
        against the previous step (arrays are always complete — only the
        registry/layout metadata is incremental).  Restore replays the
        chain via ``load_resolved_manifest`` and falls back to the last
        compacted base if a link is torn.
        """
        if self.ckpt is None:
            raise ValueError("service was constructed without ckpt_dir")
        tr = self.tracer
        t0 = time.perf_counter() if self.obs is not None else 0.0
        with maybe_span(tr, "ckpt.publish") as publish:
            if step is None:
                step = max(self.n_ticks, self._ckpt_step + 1)
            self._ckpt_step = max(self._ckpt_step, step)
            man = self._manifest()
            if (self._last_manifest is not None
                    and self._chain_len + 1 < self.compact_every):
                extra = {"service_delta": {
                    "prev": self._last_man_step,
                    "patch": dict_diff(self._last_manifest, man)}}
                self._chain_len += 1
            else:
                extra = {"service": man}
                self._chain_len = 0
            self._last_manifest = man
            self._last_man_step = step
            fut = self.ckpt.save(step, self._ckpt_tree(), extra=extra,
                                 keep_last=self.keep_checkpoints,
                                 **self._ckpt_save_kwargs())
        # the span and the histogram time the synchronous publish:
        # manifest build + device_get snapshot (the async file write is
        # tracked by ckpt.stall_s)
        if tr is not None:
            publish.set(step=int(step))
            tr.flush()
        if self.obs is not None:
            self.obs.histogram("ckpt.publish_ms").observe(
                (time.perf_counter() - t0) * 1e3)
            self.obs.counter("ckpt.n_checkpoints").inc()
        return fut

    @classmethod
    def restore(
        cls,
        ckpt_dir: str,
        step: int | None = None,
        tick_cache: SlotTickCache | None = None,
        backend: str | None = None,
        extract_matches: bool | None = None,
        obs: MetricsRegistry | None = None,
        tracer: Tracer | None = None,
    ) -> "ContinuousSearchService":
        """Rebuild a full multi-tenant service from a checkpoint.

        Uses the newest *usable* checkpoint (or ``step`` if given) —
        torn/partial checkpoints are skipped, falling back to the
        previous one.  Every query is re-registered under its original
        qid into its original slot, the structural templates are
        recompiled host-side, and the compiled slot ticks come from the
        ``SlotTickCache``: a structure this process has already served
        restores with zero recompiles.

        ``extract_matches`` overrides the checkpointed config (a
        serving-behavior knob, independent of the persisted state
        layout).  The join ``backend`` is never read from a checkpoint:
        the restoring platform resolves it (``resolve_backend``), so a
        checkpoint written on one platform serves with the kernels of
        another; an explicit ``backend`` is honored like the
        constructor's.
        """
        candidates = ([step] if step is not None
                      else list(reversed(checkpoint_steps(ckpt_dir))))
        overrides = {}
        if backend is not None:
            overrides["backend"] = backend
        if extract_matches is not None:
            overrides["extract_matches"] = extract_matches
        # instrumentation is a runtime knob (never in the checkpointed
        # config): the restored service adopts the caller's registry/
        # tracer, then reloads counter/histogram history from the
        # manifest inside _restore_step
        if obs is not None:
            overrides["obs"] = obs
        if tracer is not None:
            overrides["tracer"] = tracer
        last_err: CheckpointError | None = None
        for s in candidates:
            try:
                return cls._restore_step(ckpt_dir, s, tick_cache, overrides)
            except CheckpointError as e:
                last_err = e
        raise CheckpointError(
            f"no usable service checkpoint under {ckpt_dir!r}") from last_err

    @classmethod
    def _restore_step(cls, ckpt_dir, step, tick_cache, overrides):
        validate_checkpoint(ckpt_dir, step)   # torn pair / file -> skip
        # Resolves incremental ``service_delta`` chains back to the last
        # full manifest; torn links raise CheckpointError so the restore
        # candidate loop falls back to an older step.
        man = load_resolved_manifest(ckpt_dir, step, "service")
        config = _serving_config(man)
        if "mesh" in config and not hasattr(cls, "_MESH_SERVICE"):
            # Checkpoint was written by a ShardedSearchService but
            # restore() was called on the base class: delegate.
            from repro.runtime.mesh import ShardedSearchService
            return ShardedSearchService._restore_step(
                ckpt_dir, step, tick_cache, overrides)
        svc = cls(ckpt_dir=ckpt_dir, tick_cache=tick_cache,
                  **{**config, **overrides})
        svc.manifest_extra = man.get("extra", {})
        svc.restored_ingest = man.get("ingest")
        for qid_s, ent in man["queries"].items():
            svc.registry.adopt(
                int(qid_s), QueryGraph.from_spec(ent["query"]),
                int(ent["window"]),
                decomposition=ent.get("decomposition"))
        by_pid = {}
        if svc.forest is not None and man.get("forest"):
            by_pid = svc.forest.restore_nodes(man["forest"])
        like = {}
        for gid_s, gspec in sorted(man["groups"].items(),
                                   key=lambda kv: int(kv[0])):
            template = svc.registry.compile(
                QueryGraph.from_spec(gspec["template_query"]),
                int(gspec["template_window"]),
                decomposition=gspec.get("template_decomposition"))
            pid = gspec.get("prefix_pid")
            leaf = None if pid is None else by_pid[int(pid)]
            g = svc._new_group(template, leaf)
            g.gid = int(gid_s)
            g.qids = [None if q is None else int(q) for q in gspec["qids"]]
            gkey = (plan_signature(template),
                    None if leaf is None else leaf.pid)
            svc._groups.setdefault(gkey, []).append(g)
            for k, qid in enumerate(g.qids):
                if qid is not None:
                    svc._location[qid] = (g, k)
                    if leaf is not None:
                        # one chain of references per restored tenant —
                        # refcounts are rebuilt, not trusted blindly
                        svc._prefix_of[qid] = svc.forest.adopt(leaf)
            like[str(g.gid)] = g.sstate
        if svc.forest is not None and man.get("forest"):
            want = {int(e["pid"]): int(e["refcount"])
                    for e in man["forest"]["nodes"]}
            got = {n.pid: n.refcount for n in svc.forest.nodes()}
            if want != got:
                raise CheckpointError(
                    f"step {step}: forest refcounts disagree with the "
                    f"manifest (manifest {want}, rebuilt {got})")
            for n in svc.forest.nodes():
                like[f"prefix{n.pid}"] = n.state
        svc._next_gid = 1 + max(
            (int(gid) for gid in man["groups"]), default=-1)
        restored = restore_checkpoint(ckpt_dir, step, like)
        for g in svc._iter_groups():
            g.sstate = jax.tree.map(jnp.asarray, restored[str(g.gid)])
        if svc.forest is not None:
            for n in svc.forest.nodes():
                n.state = jax.tree.map(jnp.asarray,
                                       restored[f"prefix{n.pid}"])
        counters = man["counters"]
        svc.n_edges_ingested = int(counters["n_edges_ingested"])
        svc.n_ticks = int(counters["n_ticks"])
        svc._ckpt_step = int(step)
        svc.registry._next_qid = max(
            svc.registry._next_qid, int(counters["next_qid"]))
        if svc.obs is not None and man.get("obs"):
            svc.obs.load_manifest(man["obs"])
        return svc

    # ------------------------------------------------------------------ #
    def state(self, qid: int) -> EngineState:
        """This query's (unstacked) engine state (under prefix sharing:
        the suffix levels only — the shared prefix lives in the forest)."""
        group, k = self._location[qid]
        return read_slot(group.sstate, k)

    def matches(self, qid: int):
        """All complete matches currently in the query's window."""
        group, _ = self._location[qid]
        plan = self.registry.get(qid).plan
        if group.prefix is None:
            return current_matches(plan, self.state(qid))
        return shared_current_matches(plan, group.prefix, self.forest,
                                      self.state(qid))

    def stats(self, qid: int):
        return self.state(qid).stats

    # ------------------------------------------------------------------ #
    # prefix-sharing observability
    # ------------------------------------------------------------------ #
    def shared_prefix(self, qid: int) -> SharedPrefixInfo | None:
        """Sharing stats for one tenant, or None when the service runs
        unshared (``enable_sharing=False``)."""
        leaf = self._prefix_of.get(qid)
        if leaf is None:
            return None
        return SharedPrefixInfo(depth=leaf.depth, n_tenants=leaf.refcount,
                                epoch=leaf.epoch)

    def forest_stats(self):
        """Aggregate ``ForestStats`` of the shared-prefix forest (None
        when sharing is disabled)."""
        return None if self.forest is None else self.forest.stats()

    def tenant_overflow(self, qid: int) -> int:
        """Cumulative dropped appends affecting this tenant: its own
        suffix/L0 tables plus (under sharing) its prefix chain."""
        total = int(np.asarray(self.stats(qid).n_overflow))
        leaf = self._prefix_of.get(qid)
        if leaf is not None:
            total += self.forest.chain_overflow(leaf)
        return total
