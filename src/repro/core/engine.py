"""The streaming match engine: ``tick()``.

One tick ingests a batch of stream edges and advances every expansion
list, with semantics *exactly equal* to processing the edges one-by-one
in timestamp order (streaming consistency, Definition 13).

How the paper's concurrency design maps to TPU dataflow
-------------------------------------------------------
The paper runs one thread per edge and serializes conflicting accesses to
expansion-list items with per-item lock wait-lists ordered by timestamp
(Section 5.2).  On a TPU there are no threads or locks; the equivalent
schedule is *level-ordered batched processing*:

 1. Edges that match ``ε_j`` only ever write item ``L_i^j`` (Theorem 1) —
    so items are the paper's "resources" and our loop over levels visits
    each resource once per tick, in timing-sequence order.
 2. Within a TC-subquery the timing sequence is a ≺-chain, so the strict
    ``ts_parent < ts_edge`` predicate *is* the lock wait-list: a batch
    edge joins a same-tick parent row if and only if the sequential
    schedule would have processed that parent first.  (Theorem: batched
    tick ≡ sequential replay; property-tested in tests/test_engine_props.)
 3. Cross-subquery joins into ``L_0`` use delta joins — ``Δ(A)⋈B ∪
    A_old⋈Δ(B)`` — the incremental-view form of Algorithm 1 lines 11-22.
 4. Deletion cascades run level-ordered top-down, which is the pure-
    functional image of the paper's two-phase "partial removal"
    (Section 5.3): no reader can ever observe a half-deleted path because
    the tick is a pure function from state to state.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import join as J
from repro.core.plan import ExecutionPlan
from repro.core.state import (
    EdgeBatch,
    EngineState,
    EngineStats,
    L0Table,
    LevelTable,
)

I32 = jnp.int32

# Traced "watermark unknown" sentinel for event-time ticks: composes as
# the identity through ``max(t_now, min(watermark, max_batch_ts))``, so a
# tick fed NO_WATERMARK behaves like the frozen/processing-time clock
# without retracing (the watermark stays a traced scalar either way).
NO_WATERMARK = int(np.iinfo(np.int32).min)


class TickLoad(NamedTuple):
    """Live-row counters of one tick, as the host reads them back.

    They are reductions of validity masks the tick already holds, beside
    the static row counts they are out of.  Tables are counted after
    expiry; each ``join_pairs`` call counts the rows it joins on side A
    and side B, the rows below each side's live extent (the last live
    row + 1), and the rows it holds there, so ``Σ live_a·live_b / Σ
    cap_a·cap_b`` is the share of a whole sweep's pairs that can match
    and ``Σ ext_a·ext_b / Σ cap_a·cap_b`` the share the pairs kernel
    sweeps.  The tick returns them packed in ONE int32 vector
    (``TickResult.load``, ``[..., 4 + 6 * n_joins]``), so a group's
    counters come back in one transfer; ``unpack`` reads it, with any
    leading slot axes.  Under a capacity-sharded tick (``axis_name``)
    the vector is psum'd over the shards."""

    level_live: np.ndarray    # live rows, all level tables
    level_cap: np.ndarray     # their capacity
    l0_live: np.ndarray       # live rows, all L0 tables
    l0_cap: np.ndarray        # their capacity
    join_live_a: np.ndarray   # [..., n_joins]: live rows, side A
    join_live_b: np.ndarray   # [..., n_joins]: live rows, side B
    join_ext_a: np.ndarray    # [..., n_joins]: rows below the extent, A
    join_ext_b: np.ndarray    # [..., n_joins]: rows below the extent, B
    join_cap_a: np.ndarray    # [..., n_joins]: rows held, side A
    join_cap_b: np.ndarray    # [..., n_joins]: rows held, side B

    @classmethod
    def unpack(cls, packed) -> "TickLoad":
        p = np.asarray(packed, np.int64)
        j = (p.shape[-1] - 4) // 6
        return cls(p[..., 0], p[..., 1], p[..., 2], p[..., 3],
                   *(p[..., 4 + k * j:4 + (k + 1) * j] for k in range(6)))

    def totals(self) -> tuple[int, int, int, int, int]:
        """(live rows, capacity rows, live pairs, capacity pairs, swept
        pairs), summed over every slot and join; the pair products are
        exact int64."""
        return (int(self.level_live.sum() + self.l0_live.sum()),
                int(self.level_cap.sum() + self.l0_cap.sum()),
                int((self.join_live_a * self.join_live_b).sum()),
                int((self.join_cap_a * self.join_cap_b).sum()),
                int((self.join_ext_a * self.join_ext_b).sum()))


class TickResult(NamedTuple):
    n_new_matches: jnp.ndarray     # int32 scalar
    n_overflow: jnp.ndarray       # int32 scalar (this tick)
    match_bindings: jnp.ndarray   # int32 [max_out, nv_total]
    match_ets: jnp.ndarray        # int32 [max_out, ne_total]
    match_valid: jnp.ndarray      # bool  [max_out]
    load: jnp.ndarray             # int32 [4 + 6*n_joins]: ``TickLoad``


class _View(NamedTuple):
    """Denormalized view of a table: what joins consume."""

    bind: jnp.ndarray   # int32 [C, nv]
    ets: jnp.ndarray    # int32 [C, ne]
    valid: jnp.ndarray  # bool [C]
    fresh: jnp.ndarray  # bool [C]


def _safe_slots(slots, ok, capacity):
    """Map ungranted slots to ``capacity`` so scatter mode='drop' skips them
    (negative indices would *wrap* in JAX)."""
    return jnp.where(ok, slots, capacity)


def _append_level(
    table: LevelTable,
    parent_idx,
    src,
    dst,
    ts,
    req_valid,
):
    """Scatter new MS-tree nodes into free slots; returns (table, n_drop)."""
    cap = table.valid.shape[0]
    slots, ok, n_drop = J.alloc_slots(table.valid, req_valid, req_valid.shape[0])
    s = _safe_slots(slots, ok, cap)
    return (
        LevelTable(
            src=table.src.at[s].set(src, mode="drop"),
            dst=table.dst.at[s].set(dst, mode="drop"),
            ts=table.ts.at[s].set(ts, mode="drop"),
            parent=table.parent.at[s].set(parent_idx, mode="drop"),
            valid=table.valid.at[s].set(True, mode="drop"),
            fresh=table.fresh.at[s].set(True, mode="drop"),
        ),
        n_drop,
    )


def _append_l0(table: L0Table, bindings, ets, req_valid):
    cap = table.valid.shape[0]
    slots, ok, n_drop = J.alloc_slots(table.valid, req_valid, req_valid.shape[0])
    s = _safe_slots(slots, ok, cap)
    return (
        L0Table(
            bindings=table.bindings.at[s].set(bindings, mode="drop"),
            ets=table.ets.at[s].set(ets, mode="drop"),
            valid=table.valid.at[s].set(True, mode="drop"),
            fresh=table.fresh.at[s].set(True, mode="drop"),
        ),
        n_drop,
    )


def _compact(view: _View, mask, size: int):
    """Gather up to ``size`` rows of ``view`` where ``mask``; returns a _View
    of static size plus the overflow count."""
    (idx,) = jnp.nonzero(mask, size=size, fill_value=-1)
    ok = idx >= 0
    safe = jnp.maximum(idx, 0)
    n_drop = jnp.maximum(jnp.sum(mask, dtype=I32) - size, 0)
    return (
        _View(
            bind=jnp.take(view.bind, safe, axis=0),
            ets=jnp.take(view.ets, safe, axis=0),
            valid=ok,
            fresh=ok,
        ),
        safe,
        n_drop,
    )


def _pack_load(levels, l0, joins: list[tuple]) -> jnp.ndarray:
    """The tick's packed ``TickLoad`` from its post-expiry tables and the
    ``(valid_a, valid_b)`` masks of each ``join_pairs`` call.  Masks of
    one length are reduced together: one sum, and one max of the live
    rows' positions + 1 (the extent)."""
    tables = [t.valid for sub in levels for t in sub]
    l0v = [t.valid for t in l0]
    masks = tables + l0v + [m for pair in joins for m in pair]
    sums: list = [None] * len(masks)
    exts: list = [None] * len(masks)
    by_len: dict[int, list[int]] = {}
    for i, m in enumerate(masks):
        by_len.setdefault(m.shape[-1], []).append(i)
    for n, idx in by_len.items():
        stacked = jnp.stack([masks[i] for i in idx])
        s = jnp.sum(stacked, axis=-1, dtype=I32)
        e = jnp.max(jnp.where(stacked, jnp.arange(1, n + 1, dtype=I32), 0),
                    axis=-1)
        for k, i in enumerate(idx):
            sums[i], exts[i] = s[k], e[k]
    zero = jnp.zeros((), I32)
    nt, nl = len(tables), len(l0v)
    head = [sum(sums[:nt], zero), sum(m.shape[-1] for m in tables),
            sum(sums[nt:nt + nl], zero), sum(m.shape[-1] for m in l0v)]
    pairs, ext = sums[nt + nl:], exts[nt + nl:]
    return jnp.stack(
        [jnp.asarray(x, I32) for x in head]
        + pairs[0::2] + pairs[1::2] + ext[0::2] + ext[1::2]
        + [jnp.asarray(a.shape[-1], I32) for a, _ in joins]
        + [jnp.asarray(b.shape[-1], I32) for _, b in joins])


def edge_match_mask(batch: EdgeBatch, esl, edl, eel) -> jnp.ndarray:
    """Per-query-edge label match mask ``[n_qedges, B]``.

    ``esl`` / ``edl`` / ``eel`` are the query's per-edge src-vertex,
    dst-vertex, and edge label arrays (``eel < 0`` = wildcard).  They may
    be compile-time constants (single-query ``build_tick``) or traced
    runtime arrays (the multi-query fused / slot ticks), which is what
    lets a service register a same-shaped query without recompiling.
    """
    with jax.named_scope("engine.label_scan"):
        no_selfloop = batch.src != batch.dst
        return (
            batch.valid[None, :]
            & no_selfloop[None, :]
            & (batch.src_label[None, :] == esl[:, None])
            & (batch.dst_label[None, :] == edl[:, None])
            & ((eel[:, None] < 0)
               | (batch.edge_label[None, :] == eel[:, None]))
        )


def build_tick_body(
    plan: ExecutionPlan,
    backend: str | None = None,
    extract_matches: bool = True,
    max_out: int | None = None,
    axis_name: str | None = None,
    n_shards: int = 1,
    prefix_depth: int = 0,
):
    """Compile the *structural* part of ``plan`` into a tick body.

    Returns ``body(state, batch, ematch, window) -> (state, TickResult)``
    where ``ematch`` is the ``[n_qedges, B]`` label-match mask (see
    ``edge_match_mask``) and ``window`` the sliding-window span.  Both are
    runtime inputs: everything the body closes over — expansion-list
    layouts, REL/TREL matrices, capacities — depends only on the query's
    *structure* (shape + timing order), not on its labels.  The
    single-query ``build_tick``, the fused ``build_multi_tick``, and the
    padded-slot ``build_slot_tick`` (repro.core.multi) all share this
    body, which is what makes the multi-query oracle equivalence hold by
    construction.

    With ``prefix_depth > 0`` (cross-tenant prefix sharing,
    ``repro.core.share``), the first ``prefix_depth`` levels of subquery
    0's expansion list live in a shared prefix table advanced elsewhere,
    and the body signature becomes ``body(state, batch, ematch, window,
    prefix_view)``: ``state`` holds only subquery 0's *suffix* levels
    (``init_state(plan, prefix_depth)``) and ``prefix_view`` is the
    shared table's post-append view for this tick (``repro.core.share.
    NodeView``: denormalized bind/ets plus pre- and post-expiry
    validity).  Semantics per tenant are exactly those of the unshared
    body — the view IS what the local level-``prefix_depth`` recon would
    have been.

    Sharding composes with sharing (``axis_name`` AND ``prefix_depth``
    both set): the prefix view is REPLICATED per shard — the forest node
    advances once and its tables are broadcast, never partitioned — so
    any join whose left side is the replicated prefix produces identical
    pairs on every shard.  Those pairs are round-robined over shards by
    pair index before appending (deterministic refcount/row
    partitioning), and their drop counts — computed redundantly on every
    shard — accumulate in a separate bucket psum'd then divided by
    ``n_shards``.  Deeper suffix levels inherit parent-locality as
    usual.
    """
    if prefix_depth:
        if not (0 < prefix_depth <= len(plan.subqueries[0].levels)):
            raise ValueError(
                f"prefix_depth {prefix_depth} out of range for subquery 0 "
                f"({len(plan.subqueries[0].levels)} levels)")
    max_out = max_out or max(js.max_new for js in plan.l0_joins) if plan.l0_joins \
        else (max_out or plan.subqueries[0].levels[-1].max_new)

    # per-(subquery, level>=1) REL for the edge join
    level_rel: dict[tuple[int, int], np.ndarray] = {}
    for si, s in enumerate(plan.subqueries):
        for li in range(1, len(s.levels)):
            lv = s.levels[li]
            nv_prev = len(s.levels[li - 1].vertex_layout)
            rel = np.zeros((nv_prev, 2), dtype=bool)
            if lv.src_slot >= 0:
                rel[lv.src_slot, 0] = True
            if lv.dst_slot >= 0:
                rel[lv.dst_slot, 1] = True
            level_rel[(si, li)] = rel
    def _trel_chain(nea: int) -> np.ndarray:
        """Chain timing spec: only A's last edge must precede the new edge —
        the ≺-chain of a TC timing sequence makes the rest transitive."""
        t = np.zeros((nea, 1), dtype=np.int8)
        t[nea - 1, 0] = -1
        return t

    nv_final = len(plan.final_vertex_layout)
    ne_final = len(plan.final_edge_layout)

    def _expire(levels, l0, lo, prefix_valid_after=None):
        """End-of-tick deletion (paper §4.2): level-ordered top-down cascade
        over MS-tree parent pointers; L0 rows checked directly on their
        denormalized per-edge timestamps.  With a shared prefix
        (``prefix_depth > 0``), subquery 0's first retained level cascades
        from the shared prefix table's post-expiry validity instead of a
        local parent level."""
        new_levels = []
        for si, sub in enumerate(levels):
            out = []
            prev_valid = prefix_valid_after if si == 0 else None
            for t in sub:
                v = t.valid & (t.ts > lo)
                if prev_valid is not None:
                    v = v & jnp.take(prev_valid, jnp.maximum(t.parent, 0),
                                     mode="clip")
                out.append(t._replace(valid=v))
                prev_valid = v
            new_levels.append(tuple(out))
        new_l0 = tuple(
            t._replace(valid=t.valid & jnp.all(t.ets > lo, axis=1))
            for t in l0
        )
        return tuple(new_levels), new_l0

    def body(state: EngineState, batch: EdgeBatch, ematch, window,
             prefix_view=None, watermark=None):
        # Every phase runs under a ``jax.named_scope`` (``engine.*``): the
        # name lands in each op's HLO metadata, so a device profile can
        # split the tick's time by phase.  Metadata only — no op, fusion
        # or result changes.
        #
        # -- 0. advance time; clear last tick's fresh marks ------------ #
        # NOTE: expiry is deferred to the END of the tick.  Mid-tick, the
        # window-span predicate inside every join plays the role of the
        # paper's two-phase partial removal (§5.3): a row that expires at
        # some intra-tick time is still joinable by earlier-timestamped
        # batch edges and already invisible to later ones.
        #
        # ``watermark=None`` (a Python-static choice, one trace each) is
        # the processing-time clock: t_now rides the max ts seen, so one
        # out-of-order edge jumps the window for everyone.  With a traced
        # ``watermark`` scalar (event-time mode, fed from the ingest
        # frontier), edges at-or-below the already-released floor are
        # rejected-and-counted before they can touch a table, and the
        # clock advances to min(watermark, max batch ts): bounded above
        # by the watermark so a force-evicted straggler cannot prematurely
        # expire partials still inside ``allowed_lateness``, and by the
        # batch max so release backlog (or an all-invalid batch — unarmed
        # slots, inactive queries) keeps the clock frozen exactly as the
        # sequential replay would.  INT32_MIN means "watermark unknown"
        # and degrades to the frozen/processing clock through the same
        # max/min composition — no branch on the traced value.
        with jax.named_scope("engine.label_scan"):
            rejected = jnp.zeros((), I32)
            if watermark is not None:
                late = batch.valid & (batch.ts <= state.t_now - window)
                rejected = jnp.sum(late, dtype=I32)
                keep = batch.valid & ~late
                batch = batch._replace(valid=keep)
                ematch = ematch & keep[None, :]
            bt = jnp.where(batch.valid, batch.ts, jnp.iinfo(jnp.int32).min)
            if watermark is None:
                t_now = jnp.maximum(state.t_now, jnp.max(bt))
            else:
                t_now = jnp.maximum(
                    state.t_now, jnp.minimum(watermark, jnp.max(bt)))
            levels = tuple(
                tuple(t._replace(fresh=jnp.zeros_like(t.fresh)) for t in sub)
                for sub in state.levels
            )
            l0 = tuple(t._replace(fresh=jnp.zeros_like(t.fresh)) for t in state.l0)

            n_overflow = jnp.zeros((), I32)
            # drops computed on REPLICATED inputs (prefix-view joins under
            # sharding): every shard counts the same drop, so this bucket is
            # psum'd then divided by n_shards at the end of the tick
            n_overflow_repl = jnp.zeros((), I32)

            def _own_rows(n):
                """Round-robin shard ownership mask over a row/pair index."""
                my_shard = jax.lax.axis_index(axis_name)
                return (jnp.arange(n) % n_shards) == my_shard

            # -- 1. per-query-edge label match mask [n_qedges, B] ---------- #
            edge_used = jnp.any(ematch, axis=0)
            n_discard = jnp.sum(batch.valid & ~edge_used, dtype=I32)

            bbind = jnp.stack([batch.src, batch.dst], axis=1)  # [B, 2]
            bets = batch.ts[:, None]

            # round-robin ownership of level-1 appends across shards
            if axis_name is not None:
                my = jax.lax.axis_index(axis_name)
                own1 = (jnp.arange(batch.src.shape[0]) % n_shards) == my
            else:
                own1 = jnp.ones(batch.src.shape, jnp.bool_)

        # both sides' validity of every join_pairs call (TickLoad)
        joins: list[tuple] = []

        def _join(bind_a, ets_a, valid_a, bind_b, ets_b, valid_b, rel,
                  trel, max_new):
            joins.append((valid_a, valid_b))
            return J.join_pairs(bind_a, ets_a, valid_a, bind_b, ets_b,
                                valid_b, rel, trel, max_new, window,
                                backend)

        # -- 2. subquery phase: level-ordered batched inserts ---------- #
        recons: list[list[_View]] = []
        new_levels = []
        for si, s in enumerate(plan.subqueries):
            sub = list(levels[si])
            sub_recons: list[_View] = []
            start = prefix_depth if si == 0 else 0
            if start:
                # subquery 0's first `prefix_depth` levels live in a
                # shared prefix table (repro.core.share); its post-append
                # view seeds the reconstruction chain exactly where the
                # local level-`start-1` recon would have
                sub_recons.append(_View(prefix_view.bind, prefix_view.ets,
                                        prefix_view.valid,
                                        prefix_view.fresh))
            for li in range(start, len(s.levels)):
                lv = s.levels[li]
                ti = li - start          # index into the (suffix) tables
                em = ematch[lv.qedge]
                if li == 0:
                    with jax.named_scope("engine.level_append"):
                        t, nd = _append_level(
                            sub[0], jnp.full_like(batch.src, -1),
                            batch.src, batch.dst, batch.ts, em & own1)
                    sub[0] = t
                    n_overflow += nd
                else:
                    prev = sub_recons[-1]
                    with jax.named_scope("engine.level_join"):
                        a_idx, b_idx, pv, nd1 = _join(
                            prev.bind, prev.ets, prev.valid,
                            bbind, bets, em,
                            level_rel[(si, li)],
                            _trel_chain(prev.ets.shape[1]), lv.max_new)
                    with jax.named_scope("engine.level_append"):
                        if axis_name is not None and li == start and start:
                            # left side is the replicated prefix view:
                            # every shard computed the same pairs —
                            # partition them deterministically so each
                            # lands exactly once
                            pv = pv & _own_rows(pv.shape[0])
                            n_overflow_repl += nd1
                        else:
                            n_overflow += nd1
                        t, nd2 = _append_level(
                            sub[ti], a_idx,
                            jnp.take(batch.src, b_idx, mode="clip"),
                            jnp.take(batch.dst, b_idx, mode="clip"),
                            jnp.take(batch.ts, b_idx, mode="clip"),
                            pv)
                    sub[ti] = t
                    n_overflow += nd2
                # reconstruct this level's denormalized view (post-append)
                with jax.named_scope("engine.level_recon"):
                    t = sub[ti]
                    if li == 0:
                        bind = jnp.stack([t.src, t.dst], axis=1)
                        ets = t.ts[:, None]
                    else:
                        p = jnp.maximum(t.parent, 0)
                        prevv = sub_recons[-1]
                        cols = [jnp.take(prevv.bind, p, axis=0)]
                        own = []
                        if lv.src_slot < 0:
                            own.append(t.src[:, None])
                        if lv.dst_slot < 0:
                            own.append(t.dst[:, None])
                        bind = jnp.concatenate(cols + own, axis=1)
                        ets = jnp.concatenate(
                            [jnp.take(prevv.ets, p, axis=0), t.ts[:, None]],
                            axis=1)
                sub_recons.append(_View(bind, ets, t.valid, t.fresh))
            recons.append(sub_recons)
            new_levels.append(tuple(sub))
        levels = tuple(new_levels)

        # -- 3. L_0 phase: delta joins across TC-subqueries ------------ #
        # When subquery 0 is FULLY prefixed its final view is the shared
        # (replicated) prefix table itself: its delta needs no gather,
        # and joins with it on the left produce replicated pairs that
        # must be ownership-partitioned before appending.
        a_repl = bool(prefix_depth) \
            and prefix_depth == len(plan.subqueries[0].levels)
        new_l0 = []
        a_view = recons[0][-1]  # L_0^1 ≡ P_1's final item (paper Fig. 8)
        for gi, js in enumerate(plan.l0_joins):
            b_view = recons[gi + 1][-1]
            tbl = l0[gi]
            d = js.max_new

            # J1: ΔA ⋈ B (old ∪ Δ)
            with jax.named_scope("engine.l0_compact"):
                da, _, nd0 = _compact(a_view, a_view.fresh & a_view.valid, d)
                if a_repl:
                    n_overflow_repl += nd0
                else:
                    n_overflow += nd0
                if axis_name is not None and not a_repl:
                    da = _View(*(
                        jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
                        for x in da))
            with jax.named_scope("engine.l0_join"):
                a1, b1, pv1, nd1 = _join(
                    da.bind, da.ets, da.valid,
                    b_view.bind, b_view.ets, b_view.valid,
                    js.rel, js.trel, d)
            with jax.named_scope("engine.l0_append"):
                nb = jnp.take(b_view.bind, b1, axis=0, mode="clip")
                out_bind1 = jnp.concatenate(
                    [jnp.take(da.bind, a1, axis=0, mode="clip")]
                    + ([nb[:, list(js.b_new_vertex_slots)]]
                       if js.b_new_vertex_slots else []),
                    axis=1)
                out_ets1 = jnp.concatenate(
                    [jnp.take(da.ets, a1, axis=0, mode="clip"),
                     jnp.take(b_view.ets, b1, axis=0, mode="clip")], axis=1)
                tbl, nd2 = _append_l0(tbl, out_bind1, out_ets1, pv1)

            # J2: A_old ⋈ ΔB
            with jax.named_scope("engine.l0_compact"):
                db, _, nd3 = _compact(b_view, b_view.fresh & b_view.valid, d)
                if axis_name is not None:
                    db = _View(*(
                        jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
                        for x in db))
            with jax.named_scope("engine.l0_join"):
                a2, b2, pv2, nd4 = _join(
                    a_view.bind, a_view.ets, a_view.valid & ~a_view.fresh,
                    db.bind, db.ets, db.valid,
                    js.rel, js.trel, d)
            with jax.named_scope("engine.l0_append"):
                if axis_name is not None and a_repl:
                    # replicated A × gathered (replicated) ΔB: identical
                    # pairs on every shard — partition before append
                    pv2 = pv2 & _own_rows(pv2.shape[0])
                    n_overflow_repl += nd4
                else:
                    n_overflow += nd4
                nb2 = jnp.take(db.bind, b2, axis=0, mode="clip")
                out_bind2 = jnp.concatenate(
                    [jnp.take(a_view.bind, a2, axis=0, mode="clip")]
                    + ([nb2[:, list(js.b_new_vertex_slots)]]
                       if js.b_new_vertex_slots else []),
                    axis=1)
                out_ets2 = jnp.concatenate(
                    [jnp.take(a_view.ets, a2, axis=0, mode="clip"),
                     jnp.take(db.ets, b2, axis=0, mode="clip")], axis=1)
                tbl, nd5 = _append_l0(tbl, out_bind2, out_ets2, pv2)

            n_overflow += nd1 + nd2 + nd3 + nd5
            new_l0.append(tbl)
            a_view = _View(tbl.bindings, tbl.ets, tbl.valid, tbl.fresh)
            a_repl = False  # the L0 table itself is always sharded
        l0 = tuple(new_l0)

        # -- 4. emit (before end-of-tick expiry: a match created mid-tick
        #       is reported even if it expires within the same tick,
        #       matching sequential replay) --------------------------- #
        with jax.named_scope("engine.emit"):
            final = a_view
            new_mask = final.fresh & final.valid
            if axis_name is not None and a_repl:
                # fully-prefixed chain query: the final view is
                # replicated — partition emission so each match is
                # reported exactly once
                new_mask = new_mask & _own_rows(new_mask.shape[0])
            n_new = jnp.sum(new_mask, dtype=I32)
            if axis_name is not None:
                n_new = jax.lax.psum(n_new, axis_name)
            if extract_matches:
                out, _, nd = _compact(final, new_mask, max_out)
                mb, me, mv = out.bind, out.ets, out.valid
                n_overflow += nd
            else:
                mb = jnp.zeros((max_out, nv_final), I32)
                me = jnp.zeros((max_out, ne_final), I32)
                mv = jnp.zeros((max_out,), jnp.bool_)

        # -- 5. end-of-tick expiry ------------------------------------- #
        with jax.named_scope("engine.expire"):
            levels, l0 = _expire(
                levels, l0, t_now - window,
                prefix_view.valid_after if prefix_depth else None)
            load = _pack_load(levels, l0, joins)

        if axis_name is not None:
            n_overflow = jax.lax.psum(n_overflow, axis_name) \
                + jax.lax.psum(n_overflow_repl, axis_name) // n_shards
            n_discard = jax.lax.psum(n_discard, axis_name) // n_shards
            load = jax.lax.psum(load, axis_name)
        else:
            n_overflow = n_overflow + n_overflow_repl

        stats = EngineStats(
            n_matches_total=state.stats.n_matches_total + n_new,
            n_overflow=state.stats.n_overflow + n_overflow,
            n_edges_processed=state.stats.n_edges_processed
            + jnp.sum(batch.valid, dtype=I32),
            n_edges_discarded=state.stats.n_edges_discarded + n_discard,
            n_edges_rejected=state.stats.n_edges_rejected + rejected,
        )
        new_state = EngineState(levels=levels, l0=l0, t_now=t_now, stats=stats)
        return new_state, TickResult(n_new, n_overflow, mb, me, mv, load)

    return body


def build_tick(
    plan: ExecutionPlan,
    backend: str | None = None,
    extract_matches: bool = True,
    max_out: int | None = None,
    axis_name: str | None = None,
    n_shards: int = 1,
    prefix_depth: int = 0,
):
    """Compile ``plan`` into a jit-able ``tick(state, batch) -> (state, res)``.

    ``backend`` selects the compatibility-join implementation
    (``JoinBackend.REF`` pure jnp reference, ``JoinBackend.PALLAS`` TPU
    kernel, ``JoinBackend.PALLAS_INTERPRET`` CPU-interpreted kernel).
    ``extract_matches=False`` skips materializing result bindings
    (throughput mode).

    Distribution (``axis_name`` set, run under shard_map): every table's
    capacity axis is sharded.  Three design rules keep almost all work
    local:
      * level-1 appends are round-robined over shards by batch position;
      * a level-j row lands on its parent's shard, so MS-tree parent
        chains NEVER cross shards and reconstruction is collective-free;
      * L0 delta joins all-gather only the (small) per-tick delta rows,
        never the tables.  Scalar stats/results are psum'd.

    For serving many standing queries against one stream, see
    ``repro.core.multi.build_multi_tick`` (fused label-match phase) and
    ``repro.runtime.service`` (recompile-free registration).
    """
    body = build_tick_body(
        plan,
        backend=backend,
        extract_matches=extract_matches,
        max_out=max_out,
        axis_name=axis_name,
        n_shards=n_shards,
        prefix_depth=prefix_depth,
    )
    esl = jnp.asarray(plan.edge_src_label)
    edl = jnp.asarray(plan.edge_dst_label)
    eel = jnp.asarray(plan.edge_edge_label)
    window = plan.window

    if prefix_depth:
        def tick(state: EngineState, batch: EdgeBatch, prefix_view,
                 watermark=None):
            return body(state, batch, edge_match_mask(batch, esl, edl, eel),
                        window, prefix_view, watermark=watermark)
    else:
        def tick(state: EngineState, batch: EdgeBatch, watermark=None):
            return body(state, batch, edge_match_mask(batch, esl, edl, eel),
                        window, watermark=watermark)

    return tick


def fold_level_host(acc, table, src_slot: int, dst_slot: int):
    """One step of the host-side MS-tree denormalization: fold a level
    table's (src, dst, ts, parent) onto its parent level's accumulated
    ``(bind, ets)`` (``acc=None`` for a root level).  Own columns are
    appended only for NEGATIVE slots, src before dst — the single
    layout rule every host-side reconstruction must agree on
    (``current_matches`` and the shared-prefix paths in
    ``repro.core.share`` all route through here)."""
    src = np.asarray(table.src)[:, None]
    dst = np.asarray(table.dst)[:, None]
    ts = np.asarray(table.ts)[:, None]
    if acc is None:
        return np.concatenate([src, dst], axis=1), ts
    bind, ets = acc
    p = np.maximum(np.asarray(table.parent), 0)
    own = []
    if src_slot < 0:
        own.append(src)
    if dst_slot < 0:
        own.append(dst)
    return (np.concatenate([bind[p]] + own, axis=1),
            np.concatenate([ets[p], ts], axis=1))


def current_matches(plan: ExecutionPlan, state: EngineState):
    """All complete matches in the current window (host-side; for tests).

    Returns a set of frozensets of ``(query_edge_id, (src, dst, ts))``.
    """
    if plan.l0_joins:
        tbl = state.l0[-1]
        bind = np.asarray(tbl.bindings)
        ets = np.asarray(tbl.ets)
        valid = np.asarray(tbl.valid)
    else:
        # reconstruct the single subquery's final level on host
        s = plan.subqueries[0]
        sub = state.levels[0]
        acc = None
        for li, lv in enumerate(s.levels):
            acc = fold_level_host(acc, sub[li], lv.src_slot, lv.dst_slot)
        bind, ets = acc
        valid = np.asarray(sub[-1].valid)

    return matches_from_rows(plan, bind, ets, valid)


def matches_from_rows(plan: ExecutionPlan, bind, ets, valid):
    """Convert final-layout match rows to the canonical frozenset form
    shared with the oracle (host-side helper for ``current_matches`` and
    the shared-prefix reconstruction in ``repro.core.share``)."""
    q = plan.query
    vlayout = plan.final_vertex_layout
    elayout = plan.final_edge_layout
    out = set()
    for r in np.nonzero(valid)[0]:
        v_of = {vl: int(bind[r, i]) for i, vl in enumerate(vlayout)}
        t_of = {el: int(ets[r, i]) for i, el in enumerate(elayout)}
        match = frozenset(
            (e, (v_of[q.edges[e][0]], v_of[q.edges[e][1]], t_of[e]))
            for e in range(q.n_edges)
        )
        out.add(match)
    return out


@functools.partial(jax.jit, static_argnums=(0,))
def _noop(x):  # pragma: no cover - placeholder to keep jax import warm
    return x
