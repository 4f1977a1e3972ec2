"""Multi-query continuous search: one stream, many standing queries.

The paper evaluates one timing-constrained query against the stream; a
serving system holds *millions* of standing queries against the same
edges (cf. the multi-query framing of "Large-scale continuous subgraph
queries on streams" and StreamWorks, PAPERS.md).  Re-running the stream
once per query wastes the part of the work that is identical across
queries — the per-edge label scan — and pays one dispatch per query per
batch.  This module fuses N queries into one jit-able tick:

``build_multi_tick(plans)``
    Heterogeneous fusion.  All queries' label tables are concatenated so
    one ``edge_match_mask`` call produces a single ``[total_qedges, B]``
    mask per batch (instead of N separate scans); each query's slice
    feeds the shared tick body (``repro.core.engine.build_tick_body``).
    Per-query expansion-list state lives in one ``MultiEngineState``
    pytree and the tick returns one ``TickResult`` per query, so results
    are bit-identical to N independent ``build_tick`` runs (oracle
    cross-checked in tests/test_multi_query.py).

``build_slot_tick(template_plan, n_slots)``
    Homogeneous padded slots.  Every quantity the tick body closes over
    is *structural* (expansion-list layouts, REL/TREL matrices,
    capacities — see ``repro.core.registry.plan_signature``); the only
    per-query data are the three label arrays and the window span, which
    become runtime inputs stacked ``[n_slots, ...]``.  The body is
    ``jax.vmap``-ed over the slot axis, so registering / unregistering a
    query of an already-seen structure is a pure data update — **no
    recompilation** — which is what lets ``repro.runtime.service`` serve
    a changing query population at a fixed compile budget.

Backend note: both ticks accept the same ``backend`` as ``build_tick``
(``JoinBackend.REF`` / ``PALLAS`` / ``PALLAS_INTERPRET``; ``None`` lets
the platform choose — ``repro.core.join.resolve_backend``), and ALL
variants — including the slot tick's traced per-slot windows — are
served by every backend.  The Pallas kernels take ``window`` as a
scalar-prefetch input (not a specialization constant), and the vmapped
slot-group joins batch into ONE stacked 3-D-grid ``pallas_call`` per
join (slot, A-tile, B-tile) via the custom-vmap rule in
``repro.kernels.compat_join.ops`` — no per-slot dispatch, and
registering a query never recompiles.  Parity with REF is enforced on
the CPU by tests/test_slot_tick_pallas.py in interpret mode, the
compiled slot tick is compiled for a described TPU v5e by
tests/test_chip_compile.py, and ``chip_smoke.py`` checks on a TPU v5e
that the served PALLAS tick equals REF and the oracle.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import join as J
from repro.core.engine import (
    TickResult,
    build_tick_body,
    edge_match_mask,
)
from repro.core.plan import ExecutionPlan
from repro.core.state import EdgeBatch, EngineState, init_state

I32 = jnp.int32


# --------------------------------------------------------------------- #
# Heterogeneous fusion: build_multi_tick
# --------------------------------------------------------------------- #
class MultiEngineState(NamedTuple):
    """State for N fused queries: one pytree, jit/donate/shard friendly.

    ``queries`` holds one ``EngineState`` per plan (heterogeneous table
    shapes); ``active`` is a runtime bool per query — flipping it off
    stops a query's tables from growing without recompiling the tick.
    """

    queries: tuple          # tuple[EngineState, ...], parallel to plans
    active: jnp.ndarray     # bool [n_queries]


def init_multi_state(plans: Sequence[ExecutionPlan], active=None) -> MultiEngineState:
    if active is None:
        active = jnp.ones((len(plans),), jnp.bool_)
    return MultiEngineState(
        queries=tuple(init_state(p) for p in plans),
        active=jnp.asarray(active, jnp.bool_),
    )


def set_active(mstate: MultiEngineState, qi: int, value: bool) -> MultiEngineState:
    return mstate._replace(active=mstate.active.at[qi].set(value))


def reset_query(mstate: MultiEngineState, plans: Sequence[ExecutionPlan],
                qi: int) -> MultiEngineState:
    """Replace query ``qi``'s tables with empty ones (e.g. on re-arm)."""
    qs = list(mstate.queries)
    qs[qi] = init_state(plans[qi])
    return mstate._replace(queries=tuple(qs))


def build_multi_tick(
    plans: Sequence[ExecutionPlan],
    backend: str | None = None,
    extract_matches: bool = True,
    max_out: int | None = None,
):
    """Fuse ``plans`` into one ``tick(mstate, batch) -> (mstate, results)``.

    ``results`` is a tuple of per-query ``TickResult``s, index-parallel
    to ``plans``.  The per-edge label-match phase runs ONCE over the
    concatenated query-edge tables (one ``[total_qedges, B]`` mask);
    each query's expansion-list phase consumes its slice, multiplied by
    its ``active`` flag.  Semantics per query are exactly those of
    ``build_tick(plan)`` — same body, same mask slice.
    """
    plans = list(plans)
    if not plans:
        raise ValueError("build_multi_tick needs at least one plan")
    bodies = [
        build_tick_body(p, backend=backend, extract_matches=extract_matches,
                        max_out=max_out)
        for p in plans
    ]
    esl = jnp.concatenate([jnp.asarray(p.edge_src_label) for p in plans])
    edl = jnp.concatenate([jnp.asarray(p.edge_dst_label) for p in plans])
    eel = jnp.concatenate([jnp.asarray(p.edge_edge_label) for p in plans])
    offsets = np.cumsum([0] + [p.query.n_edges for p in plans])
    windows = [p.window for p in plans]

    def tick(mstate: MultiEngineState, batch: EdgeBatch, watermark=None):
        em_all = edge_match_mask(batch, esl, edl, eel)
        states, results = [], []
        for qi, body in enumerate(bodies):
            # an inactive query sees an all-invalid batch: no appends, no
            # stats drift (edges processed/discarded), frozen t_now —
            # which the watermark clock preserves by construction: an
            # all-invalid batch has max batch ts = INT32_MIN, and
            # min(watermark, that) never advances t_now
            act = mstate.active[qi]
            b_q = batch._replace(valid=batch.valid & act)
            em = em_all[offsets[qi]:offsets[qi + 1]] & act
            s, r = body(mstate.queries[qi], b_q, em, windows[qi],
                        watermark=watermark)
            states.append(s)
            results.append(r)
        return mstate._replace(queries=tuple(states)), tuple(results)

    return tick


# --------------------------------------------------------------------- #
# Homogeneous padded slots: build_slot_tick
# --------------------------------------------------------------------- #
class SlotParams(NamedTuple):
    """Runtime per-slot query data (everything non-structural)."""

    esl: jnp.ndarray     # int32 [S, n_qedges] query-edge src-vertex labels
    edl: jnp.ndarray     # int32 [S, n_qedges] dst-vertex labels
    eel: jnp.ndarray     # int32 [S, n_qedges] edge labels (-1 wildcard)
    window: jnp.ndarray  # int32 [S] sliding-window span per slot
    active: jnp.ndarray  # bool  [S]


class SlotState(NamedTuple):
    """State of one padded slot group: stacked engines + slot params."""

    engines: EngineState  # every leaf has a leading [S] slot axis
    params: SlotParams


def stack_states(states: Sequence[EngineState]) -> EngineState:
    """Stack homogeneous EngineStates along a new leading slot axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def init_slot_state(template_plan: ExecutionPlan, n_slots: int,
                    prefix_depth: int = 0) -> SlotState:
    nq = template_plan.query.n_edges
    return SlotState(
        engines=stack_states(
            [init_state(template_plan, prefix_depth)] * n_slots),
        params=SlotParams(
            esl=jnp.zeros((n_slots, nq), I32),
            edl=jnp.zeros((n_slots, nq), I32),
            eel=jnp.full((n_slots, nq), -1, I32),
            window=jnp.full((n_slots,), template_plan.window, I32),
            active=jnp.zeros((n_slots,), jnp.bool_),
        ),
    )


def _put_row(full, row, k):
    """``full[k] = row``: an update slice, each replica writing only its own block."""
    return jax.lax.dynamic_update_index_in_dim(full, jnp.asarray(row, full.dtype), k, 0)


@functools.partial(jax.jit, donate_argnums=(0,))
def _arm_slot(sstate: SlotState, k, engine: EngineState,
              params: SlotParams) -> SlotState:
    return SlotState(
        engines=jax.tree.map(lambda f, r: _put_row(f, r, k),
                             sstate.engines, engine),
        params=jax.tree.map(lambda f, r: _put_row(f, r, k),
                            sstate.params, params))


@functools.partial(jax.jit, donate_argnums=(0,))
def _disarm_slot(sstate: SlotState, k, engine: EngineState) -> SlotState:
    p = sstate.params
    return SlotState(
        engines=jax.tree.map(lambda f, r: _put_row(f, r, k),
                             sstate.engines, engine),
        params=p._replace(active=_put_row(p.active, False, k)))


def write_slot(sstate: SlotState, template_plan: ExecutionPlan, k: int,
               plan: ExecutionPlan,
               empty: EngineState | None = None) -> SlotState:
    """Arm slot ``k`` with ``plan``'s labels/window; reset its tables.

    ``plan`` must share ``template_plan``'s structural signature
    (``repro.core.registry.plan_signature``) — the caller (service)
    guarantees this by construction.  Pure data writes: no recompile
    (``k`` is traced).  Pass a cached ``empty = init_state(template_plan)``
    to avoid re-materializing the full-capacity empty tables per churn
    event, or another slot's engine rows to move a tenant with its state.

    The write runs under ``jit`` and DONATES ``sstate`` (callers must
    treat it as consumed); the result keeps ``sstate``'s sharding, so a
    replica-sharded group (``repro.runtime.mesh``) stays sharded.
    """
    if empty is None:
        empty = init_state(template_plan)
    params = SlotParams(
        esl=jnp.asarray(plan.edge_src_label, I32),
        edl=jnp.asarray(plan.edge_dst_label, I32),
        eel=jnp.asarray(plan.edge_edge_label, I32),
        window=jnp.asarray(plan.window, I32),
        active=jnp.asarray(True))
    return _arm_slot(sstate, k, empty, params)


def clear_slot(sstate: SlotState, template_plan: ExecutionPlan, k: int,
               empty: EngineState | None = None) -> SlotState:
    """Disarm slot ``k`` (unregister): deactivate + drop its tables.
    Donates ``sstate`` like ``write_slot``."""
    if empty is None:
        empty = init_state(template_plan)
    return _disarm_slot(sstate, k, empty)


def read_slot(sstate: SlotState, k: int) -> EngineState:
    """Unstack slot ``k``'s engine state (host-side result extraction)."""
    return jax.tree.map(lambda x: x[k], sstate.engines)


def build_slot_tick(
    template_plan: ExecutionPlan,
    backend: str | None = None,
    extract_matches: bool = True,
    max_out: int | None = None,
    prefix_depth: int = 0,
):
    """Compile a padded-slot tick for one structural template.

    Returns ``tick(sstate, batch) -> (sstate, results)`` where
    ``results`` is a ``TickResult`` whose leaves carry a leading slot
    axis.  The label-match phase evaluates all slots' masks in one shot
    from the stacked ``[S, n_qedges]`` label arrays; the structural body
    is vmapped over slots.  Inactive slots process nothing (their mask
    is zeroed) and their tables stay empty.

    With ``prefix_depth > 0`` (cross-tenant prefix sharing,
    ``repro.core.share``) the tick signature becomes ``tick(sstate,
    batch, prefix_view)``: every slot consumes the SAME shared prefix
    table view (vmap-broadcast), and the per-slot bodies run only the
    suffix joins.  Results and stats of unarmed slots are masked — the
    shared view is nonzero input even for slots that hold no tenant.

    Both variants accept a trailing ``watermark=None``: ``None`` keeps
    the legacy max-ts clock, a traced int32 scalar switches every slot
    to event-time admission/expiry (``repro.core.engine.NO_WATERMARK``
    is the traced "unknown" sentinel).  The watermark is vmap-broadcast;
    unarmed slots stay frozen because their all-invalid batch caps the
    clock advance at INT32_MIN.
    """
    body = build_tick_body(template_plan, backend=backend,
                           extract_matches=extract_matches, max_out=max_out,
                           prefix_depth=prefix_depth)

    if prefix_depth == 0:
        def one(engine, batch, esl, edl, eel, window, active, watermark):
            # unarmed slots see an all-invalid batch (no stats drift,
            # frozen t_now) in addition to the zeroed match mask; the
            # watermark clock keeps the freeze for free — an all-invalid
            # batch's max ts is INT32_MIN and min(watermark, ·) cannot
            # advance t_now, so no per-slot watermark masking is needed
            b_s = batch._replace(valid=batch.valid & active)
            em = edge_match_mask(b_s, esl, edl, eel) & active
            return body(engine, b_s, em, window, watermark=watermark)

        # a None watermark is an empty pytree, so the broadcast in_axes
        # serves both the legacy (None) and event-time (scalar) modes —
        # jit retraces once per mode, never per value
        vbody = jax.vmap(one, in_axes=(0, None, 0, 0, 0, 0, 0, None))

        def tick(sstate: SlotState, batch: EdgeBatch, watermark=None):
            p = sstate.params
            engines, results = vbody(
                sstate.engines, batch, p.esl, p.edl, p.eel, p.window,
                p.active, watermark)
            return sstate._replace(engines=engines), results

        return tick

    def one(engine, batch, esl, edl, eel, window, active, prefix_view,
            watermark):
        b_s = batch._replace(valid=batch.valid & active)
        em = edge_match_mask(b_s, esl, edl, eel) & active
        s, r = body(engine, b_s, em, window, prefix_view,
                    watermark=watermark)
        # a fully-shared subquery 0 feeds every slot the shared rows, so
        # unarmed slots must mask their outputs AND their stats (the
        # zeroed batch alone no longer freezes them)
        s = s._replace(stats=jax.tree.map(
            lambda new, old: jnp.where(active, new, old),
            s.stats, engine.stats))
        r = r._replace(
            n_new_matches=jnp.where(active, r.n_new_matches, 0),
            n_overflow=jnp.where(active, r.n_overflow, 0),
            match_valid=r.match_valid & active)
        return s, r

    vbody = jax.vmap(one, in_axes=(0, None, 0, 0, 0, 0, 0, None, None))

    def tick(sstate: SlotState, batch: EdgeBatch, prefix_view,
             watermark=None):
        p = sstate.params
        engines, results = vbody(
            sstate.engines, batch, p.esl, p.edl, p.eel, p.window,
            p.active, prefix_view, watermark)
        return sstate._replace(engines=engines), results

    return tick


# --------------------------------------------------------------------- #
# Compiled-tick cache: one build + jit per structural signature
# --------------------------------------------------------------------- #
class SlotTickCache:
    """Process-wide cache of compiled slot ticks, keyed by structure.

    ``build_slot_tick`` closes over only *structural* plan data (that is
    the whole point of ``plan_signature``), so ONE compiled — and, with
    ``jit=True``, jitted — tick can serve every slot group, in every
    ``ContinuousSearchService`` instance, whose template shares a
    signature.  Two consequences:

    * a group that overflows into a sibling group reuses the compiled
      tick instead of rebuilding an identical one;
    * a service restored after a crash (``ContinuousSearchService.
      restore``) re-arms all of its groups with cache *hits*: zero
      recompiles for structures this process has already served, and the
      shared jitted tick keeps its XLA trace cache, so the first
      post-restore batch of an already-seen shape does not retrace.

    ``donate=True`` jits with ``donate_argnums=(0,)``: the previous
    ``SlotState`` buffers are donated to each tick, so steady-state
    serving updates slot tables in place instead of copying them every
    tick (callers must treat the passed-in state as consumed — the
    service does).

    The cache is LRU-bounded (``max_entries``) so a long-lived server
    seeing many distinct structures over its lifetime does not leak
    compiled ticks without limit.  Eviction is always safe: live slot
    groups hold their own reference to their tick, so an evicted entry
    only means the NEXT group of that structure rebuilds.
    """

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self._ticks: dict[tuple, object] = {}   # insertion-ordered (LRU)
        self.n_builds = 0        # build_slot_tick invocations (cache misses)

    def __len__(self) -> int:
        return len(self._ticks)

    def ticks(self) -> list:
        """The cached (possibly jitted) tick callables."""
        return list(self._ticks.values())

    def _get(self, key, builder, jit: bool, donate: bool):
        tick = self._ticks.pop(key, None)
        if tick is None:
            tick = builder()
            if jit:
                tick = jax.jit(
                    tick, donate_argnums=(0,) if donate else ())
            self.n_builds += 1
        self._ticks[key] = tick                 # (re)insert at LRU tail
        while len(self._ticks) > self.max_entries:
            self._ticks.pop(next(iter(self._ticks)))
        return tick

    def get(
        self,
        template_plan: ExecutionPlan,
        backend: str | None = None,
        extract_matches: bool = True,
        max_out: int | None = None,
        jit: bool = True,
        donate: bool = False,
        prefix_depth: int = 0,
    ):
        from repro.core.registry import plan_signature

        key = (plan_signature(template_plan), backend, extract_matches,
               max_out, jit, donate, prefix_depth)
        return self._get(
            key,
            lambda: build_slot_tick(
                template_plan, backend=backend,
                extract_matches=extract_matches, max_out=max_out,
                prefix_depth=prefix_depth),
            jit, donate)

    def get_mesh(
        self,
        template_plan: ExecutionPlan,
        mesh,                                    # jax.sharding.Mesh
        slots_per_replica: int,
        backend: str | None = None,
        extract_matches: bool = True,
        max_out: int | None = None,
        donate: bool = True,
        prefix_depth: int = 0,
    ):
        """Compiled mesh slot tick (``repro.runtime.mesh``): the slot
        axis sharded over the mesh's replica axis.  Keyed by structure
        PLUS mesh identity (device ids + per-replica slot count), so a
        service restored onto the same mesh re-arms with cache hits —
        zero rebuilds, and the shared jitted tick keeps its XLA trace
        cache per replica."""
        from repro.core.registry import plan_signature
        from repro.runtime.mesh import build_mesh_slot_tick

        mesh_key = tuple(d.id for d in mesh.devices.flat)
        key = ("mesh", plan_signature(template_plan), mesh_key,
               slots_per_replica, backend, extract_matches, max_out,
               donate, prefix_depth)
        # the builder jits internally (one jit per watermark mode), so
        # _get must not wrap it again
        return self._get(
            key,
            lambda: build_mesh_slot_tick(
                template_plan, mesh, backend=backend,
                extract_matches=extract_matches, max_out=max_out,
                donate=donate, prefix_depth=prefix_depth),
            jit=False, donate=False)

    def get_node(
        self,
        spec,                                   # repro.core.share.NodeSpec
        backend: str | None = None,
        jit: bool = True,
        donate: bool = False,
    ):
        """Compiled prefix-node tick for one structural ``NodeSpec``
        (the forest's half of the cache's prefix dimension).  Labels and
        window are runtime inputs, so one entry serves every node of
        that structure — and restores re-arm forests with cache hits."""
        from repro.core.share import build_node_tick

        key = ("prefix_node", spec, backend, jit, donate)
        return self._get(
            key,
            lambda: build_node_tick(spec, backend=backend),
            jit, donate)

    def clear(self):
        self._ticks.clear()


GLOBAL_SLOT_TICK_CACHE = SlotTickCache()
