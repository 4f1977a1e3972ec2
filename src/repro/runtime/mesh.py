"""Replica-sharded multi-tenant serving: the slot (tenant) axis on a mesh.

``repro.core.distributed`` shards ONE engine's capacity axis across
devices — the scale-up story for a single huge query.  This module is
the scale-OUT story for the serving layer: a ``ShardedSearchService``
keeps the whole ``ContinuousSearchService`` contract (register /
unregister / ingest / serve_stream / serve_frontier / checkpoint /
restore) but stacks each slot group ``n_replicas x slots_per_replica``
tenants high and shards the SLOT axis over a 1-D device mesh
``("replica",)`` via ``shard_map``:

* every ``SlotState`` leaf is partitioned ``P("replica")`` along its
  leading slot axis, so replica ``r`` owns the contiguous slot block
  ``[r*spr, (r+1)*spr)`` and materializes ONLY those tenants' tables;
* the edge batch is replicated (ingest bandwidth is tiny next to table
  state) and each replica's label scan covers only its own slots'
  ``[spr, n_qedges]`` label tables — the fan-out of the per-edge scan
  is the vmap over the local block, nothing crosses replicas;
* the tick body itself runs with ``axis_name=None`` — tenants are
  independent, so the hot loop has ZERO collectives; the only
  cross-replica traffic is five scalar reductions per tick
  (``MeshTickStats``: matched/overflow and live/allocated table-row
  psums + a pmax watermark clock);
* a ``PlacementPolicy`` decides which replica each newly registered
  tenant lands on (round-robin, or load-balanced by tenant count and
  ``overflow_pressure``); the slot search inside the chosen replica's
  block is the existing ``_Group.free_slot(lo, hi)``.

Prefix sharing composes: the ``SharedPrefixForest`` node tables are
advanced once OUTSIDE the shard_map and their views enter replicated
(``P()``), exactly like the replicated-view contract of
``build_sharded_tick`` — each replica's suffix joins read the same
shared prefix rows.  ``SharedPrefixForest.replica_refcounts`` splits
each node's refcount by owning replica so checkpoint manifests record
(and restore verifies) the partition.

Checkpoints are sharded: each step writes ``step_N.shard<r>of<R>.npz``
(slot-sharded keys split along axis 0; forest tables + scalars
replicated into shard 0) plus one manifest.  ``restore`` reassembles
host-side, so a checkpoint written on an 8-replica mesh restores onto a
2-replica mesh (or vice versa): same-size meshes re-arm the exact slot
layout with zero recompiles; a different ``n_replicas`` takes the
repack path — every tenant is re-placed by the policy and its engine
table rows are spliced into its new slot (oracle-exact either way,
tests/test_mesh.py).

CPU testing: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
before importing jax gives an 8-virtual-device host mesh.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import (
    CheckpointError,
    checkpoint_steps,
    load_resolved_manifest,
    restore_checkpoint,
    validate_checkpoint,
)
from repro.core import join as J
from repro.core.multi import (
    SlotTickCache,
    build_slot_tick,
    init_slot_state,
    read_slot,
    write_slot,
)
from repro.core.plan import ExecutionPlan
from repro.core.query import QueryGraph
from repro.core.state import init_state
from repro.runtime.service import (
    ContinuousSearchService,
    _Group,
    _serving_config,
)

I32 = jnp.int32


class MeshTickStats(NamedTuple):
    """Per-tick scalar reductions across the replica axis (the mesh
    tick's third output; all int32 scalars, replicated)."""

    n_matches: jnp.ndarray    # psum of new matches over all replicas
    n_overflow: jnp.ndarray   # psum of dropped appends over all replicas
    t_clock: jnp.ndarray      # pmax of every replica's engine clock
    live_rows: jnp.ndarray    # psum of live table rows (``TickLoad``)
    capacity_rows: jnp.ndarray  # psum of allocated table rows


# --------------------------------------------------------------------- #
# The sharded slot tick
# --------------------------------------------------------------------- #
def build_mesh_slot_tick(
    template_plan: ExecutionPlan,
    mesh,                                   # jax.sharding.Mesh, 1-D "replica"
    backend: str | None = None,
    extract_matches: bool = True,
    max_out: int | None = None,
    donate: bool = True,
    prefix_depth: int = 0,
):
    """Wrap ``build_slot_tick`` in ``shard_map`` over the replica axis.

    The returned callable keeps the slot tick's signature —
    ``tick(sstate, batch, watermark=None)``, or with ``prefix_depth``
    ``tick(sstate, batch, prefix_view, watermark=None)`` — but returns a
    THIRD output, ``MeshTickStats``.  ``sstate`` leaves are partitioned
    ``P("replica")`` along the leading slot axis (total slots =
    ``n_replicas * slots_per_replica``); batch, prefix view and
    watermark are replicated.  Inside the shard each replica runs the
    plain vmapped body over its local slot block — no collectives in the
    tick body, only the closing scalar psum/pmax.

    ``None`` vs traced watermark changes the argument pytree, so the two
    modes are two lazily-jitted shard_map programs behind one Python
    dispatcher (mirroring the single-device tick's one-retrace-per-mode
    behavior; a restored service re-arms with zero warm recompiles
    because ``SlotTickCache.get_mesh`` caches this whole dispatcher).
    """
    inner = build_slot_tick(
        template_plan, backend=backend, extract_matches=extract_matches,
        max_out=max_out, prefix_depth=prefix_depth)
    axis = "replica"
    donate_kw = {"donate_argnums": (0,)} if donate else {}
    compiled: dict[bool, object] = {}

    def _finish(sstate, res):
        stats = MeshTickStats(
            n_matches=jax.lax.psum(
                jnp.sum(res.n_new_matches).astype(I32), axis),
            n_overflow=jax.lax.psum(
                jnp.sum(res.n_overflow).astype(I32), axis),
            t_clock=jax.lax.pmax(jnp.max(sstate.engines.t_now), axis),
            live_rows=jax.lax.psum(
                jnp.sum(res.load[:, 0] + res.load[:, 2]), axis),
            capacity_rows=jax.lax.psum(
                jnp.sum(res.load[:, 1] + res.load[:, 3]), axis),
        )
        return sstate, res, stats

    def _build(has_wm: bool):
        # sstate/result specs are pytree prefixes: every leaf carries a
        # leading slot axis, partitioned over the replica axis
        state_spec, repl = P(axis), P()
        if prefix_depth == 0:
            if has_wm:
                def fn(sstate, batch, wm):
                    return _finish(*inner(sstate, batch, wm))
                in_specs = (state_spec, repl, repl)
            else:
                def fn(sstate, batch):
                    return _finish(*inner(sstate, batch))
                in_specs = (state_spec, repl)
        else:
            if has_wm:
                def fn(sstate, batch, view, wm):
                    return _finish(*inner(sstate, batch, view, wm))
                in_specs = (state_spec, repl, repl, repl)
            else:
                def fn(sstate, batch, view):
                    return _finish(*inner(sstate, batch, view))
                in_specs = (state_spec, repl, repl)
        out_specs = (state_spec, state_spec,
                     MeshTickStats(*(repl for _ in MeshTickStats._fields)))
        return jax.jit(
            jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False),
            **donate_kw)

    def _get(has_wm: bool):
        f = compiled.get(has_wm)
        if f is None:
            f = compiled[has_wm] = _build(has_wm)
        return f

    if prefix_depth == 0:
        def tick(sstate, batch, watermark=None):
            if watermark is None:
                return _get(False)(sstate, batch)
            return _get(True)(sstate, batch, watermark)
    else:
        def tick(sstate, batch, prefix_view, watermark=None):
            if watermark is None:
                return _get(False)(sstate, batch, prefix_view)
            return _get(True)(sstate, batch, prefix_view, watermark)

    return tick


# --------------------------------------------------------------------- #
# Placement policies
# --------------------------------------------------------------------- #
class PlacementPolicy:
    """Chooses the replica for each newly registered tenant.

    ``place`` returns a replica index in ``[0, svc.n_replicas)``; the
    service then searches that replica's slot block across the group
    list and opens a new group only when the block is full everywhere.
    Stateless policies restore trivially; ``RoundRobinPlacement``'s
    cursor is intentionally NOT persisted — post-restore placement
    starts fresh, which only affects future registrations.
    """

    name = "base"

    def place(self, svc: "ShardedSearchService", signature) -> int:
        raise NotImplementedError


class RoundRobinPlacement(PlacementPolicy):
    """Cycle through replicas in registration order."""

    name = "round_robin"

    def __init__(self):
        self._next = 0

    def place(self, svc, signature):
        r = self._next % svc.n_replicas
        self._next += 1
        return r


class LoadBalancedPlacement(PlacementPolicy):
    """Prefer the replica with the least overflow pressure, breaking
    ties by live tenant count then index.  Pressure is the cumulative
    dropped-append counter summed over the replica's slot block (one
    device read per live group — admission time, not per tick)."""

    name = "load_balanced"

    def place(self, svc, signature):
        pressure = svc.replica_pressure()
        load = svc.replica_load()
        return min(range(svc.n_replicas),
                   key=lambda r: (pressure[r], load[r], r))


_PLACEMENTS = {
    RoundRobinPlacement.name: RoundRobinPlacement,
    LoadBalancedPlacement.name: LoadBalancedPlacement,
}


def _resolve_placement(spec) -> PlacementPolicy:
    if spec is None:
        return RoundRobinPlacement()
    if isinstance(spec, PlacementPolicy):
        return spec
    try:
        return _PLACEMENTS[spec]()
    except KeyError:
        raise ValueError(
            f"unknown placement policy {spec!r} "
            f"(known: {sorted(_PLACEMENTS)})") from None


# --------------------------------------------------------------------- #
# The sharded service
# --------------------------------------------------------------------- #
class ShardedSearchService(ContinuousSearchService):
    """``ContinuousSearchService`` with the slot axis sharded on a mesh.

    Same API, same per-tenant semantics (differentially proven against
    the single-device service and the per-query oracle in
    tests/test_mesh.py); ``slots_per_group`` is derived as
    ``n_replicas * slots_per_replica`` and placement routes every
    registration to one replica's slot block.  Checkpoints are written
    as per-replica npz shards; ``restore(..., n_replicas=R')`` repacks
    onto a differently-sized mesh.
    """

    _MESH_SERVICE = True        # restore-dispatch marker (service.py)

    def __init__(
        self,
        n_replicas: int | None = None,
        slots_per_replica: int | None = None,
        placement=None,
        mesh: dict | None = None,
        **kw,
    ):
        # ``mesh`` is the manifest-config form (restore round-trip);
        # explicit arguments take precedence over it
        if mesh is not None:
            if n_replicas is None:
                n_replicas = mesh.get("n_replicas")
            if slots_per_replica is None:
                slots_per_replica = mesh.get("slots_per_replica")
            if placement is None:
                placement = mesh.get("placement")
        devices = jax.devices()
        if n_replicas is None:
            n_replicas = len(devices)
        if slots_per_replica is None:
            slots_per_replica = 4
        if not 1 <= n_replicas <= len(devices):
            raise ValueError(
                f"n_replicas={n_replicas} needs that many devices "
                f"(have {len(devices)}; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N before "
                f"importing jax)")
        kw.pop("slots_per_group", None)   # derived, not configurable
        self.n_replicas = int(n_replicas)
        self.slots_per_replica = int(slots_per_replica)
        self.placement = _resolve_placement(placement)
        # Auto axis: the slot axis is only *placed* on the mesh (tenants
        # are independent), so host-side per-slot reads and the slot
        # writes keep working on sharded state without per-op shardings.
        self.mesh = jax.make_mesh(
            (self.n_replicas,), ("replica",),
            devices=devices[:self.n_replicas],
            axis_types=(jax.sharding.AxisType.Auto,))
        self.mesh_stats: dict[int, MeshTickStats] = {}  # gid -> last tick
        super().__init__(
            slots_per_group=self.n_replicas * self.slots_per_replica, **kw)

    # -------------------------------------------------------------- #
    # placement
    # -------------------------------------------------------------- #
    def replica_load(self) -> list[int]:
        """Live tenants per replica (host-side bookkeeping, no sync)."""
        load = [0] * self.n_replicas
        for _, k in self._location.values():
            load[k // self.slots_per_replica] += 1
        return load

    def replica_pressure(self) -> list[int]:
        """Cumulative dropped appends per replica, summed over every
        live group's slot block (slot-table counters only — shared
        prefix-chain drops are not replica-attributable)."""
        spr = self.slots_per_replica
        pressure = [0] * self.n_replicas
        for g in self._iter_groups():
            if g.idle:
                continue
            ov = np.asarray(g.sstate.engines.stats.n_overflow)
            per = ov.reshape(self.n_replicas, spr, -1).sum(axis=(1, 2))
            pressure = [p + int(v) for p, v in zip(pressure, per)]
        return pressure

    def _place(self, groups, plan, leaf, signature):
        r = self.placement.place(self, signature)
        spr = self.slots_per_replica
        for g in groups:
            k = g.free_slot(r * spr, (r + 1) * spr)
            if k is not None:
                return g, k
        g = self._new_group(plan, leaf)
        groups.append(g)
        return g, r * spr

    # -------------------------------------------------------------- #
    # groups / ticking
    # -------------------------------------------------------------- #
    def _new_group(self, template: ExecutionPlan, leaf=None) -> _Group:
        depth = 0 if leaf is None else leaf.depth
        before = self.tick_cache.n_builds
        tick = self.tick_cache.get_mesh(
            template, self.mesh, self.slots_per_replica,
            backend=self.backend, extract_matches=self.extract_matches,
            max_out=self.max_out, donate=self.donate, prefix_depth=depth)
        self.n_compiles += self.tick_cache.n_builds - before
        sstate = self._shard_state(
            init_slot_state(template, self.slots_per_group, depth))
        g = _Group(
            gid=self._next_gid,
            template=template,
            tick=tick,
            sstate=sstate,
            empty=init_state(template, depth),
            qids=[None] * self.slots_per_group,
            prefix=leaf,
            prefix_depth=depth,
        )
        self._next_gid += 1
        return g

    def _shard_state(self, sstate):
        """Place a SlotState's leaves slot-sharded over the replica axis."""
        return jax.device_put(sstate, NamedSharding(self.mesh, P("replica")))

    def _advance_group(self, g: _Group, batch, views=None, forest_nds=None,
                       watermark=None):
        # same flow as the base class, with the mesh tick's third output
        # (the psum/pmax scalars) stashed per group for observability
        if g.prefix is not None:
            g.sstate, res, mstats = g.tick(
                g.sstate, batch, views[g.prefix.pid], watermark)
            chain_nd = self.forest.chain_tick_overflow(g.prefix, forest_nds)
            res = res._replace(
                n_overflow=res.n_overflow
                + jnp.where(g.sstate.params.active, chain_nd, 0))
        else:
            g.sstate, res, mstats = g.tick(g.sstate, batch, watermark)
        self.mesh_stats[g.gid] = mstats
        return res

    def last_mesh_stats(self) -> dict[int, dict]:
        """Host values of every group's last-tick ``MeshTickStats``, all
        read in one ``jax.device_get``."""
        return {gid: {k: int(v) for k, v in s._asdict().items()}
                for gid, s in jax.device_get(self.mesh_stats).items()}

    def _register_obs_gauges(self) -> None:
        super()._register_obs_gauges()
        obs = self.obs
        obs.gauge("mesh.n_replicas").set(self.n_replicas)
        obs.register_gauge(
            "mesh.replica_load_max", lambda: max(self.replica_load(),
                                                 default=0))
        obs.register_gauge(
            "mesh.replica_pressure_max",
            lambda: max(self.replica_pressure(), default=0))

    def _trace_tick_extras(self, tr, tick_span, ticked) -> None:
        """The tick's collective scalars and its table rows per replica.

        Every ticked group's ``MeshTickStats`` comes back in ONE
        ``jax.device_get`` (the tick has passed its barrier, so the read
        waits on nothing) and becomes an event ``mesh.collectives`` with
        the fields ``gid``; ``n_matches`` and ``n_overflow``, the psums
        of the group's new matches and dropped appends; ``t_clock``, the
        pmax of its replicas' engine clocks; and ``live_rows`` and
        ``capacity_rows``, the psums of its live and allocated table
        rows.  The ``tick`` span gets their sums over the groups,
        ``mesh_matches`` and ``mesh_live_rows`` (equal to the host's
        counts), and per replica the live and allocated rows of its slot
        block, ``replica_live_rows`` and ``replica_capacity_rows``, split
        from the per-slot ``TickLoad`` already read back for delivery.
        """
        stats = jax.device_get([self.mesh_stats[g.gid] for g, _ in ticked])
        live = np.zeros(self.n_replicas, np.int64)
        cap = np.zeros(self.n_replicas, np.int64)
        for (g, load), s in zip(ticked, stats):
            tr.event("mesh.collectives", gid=g.gid,
                     **{k: int(v) for k, v in s._asdict().items()})
            live += (load.level_live + load.l0_live).reshape(
                self.n_replicas, -1).sum(axis=1)
            cap += (load.level_cap + load.l0_cap).reshape(
                self.n_replicas, -1).sum(axis=1)
        tick_span.set(mesh_matches=sum(int(s.n_matches) for s in stats),
                      mesh_live_rows=sum(int(s.live_rows) for s in stats),
                      replica_live_rows=live.tolist(),
                      replica_capacity_rows=cap.tolist())

    # -------------------------------------------------------------- #
    # checkpoint / restore
    # -------------------------------------------------------------- #
    def _manifest(self) -> dict:
        man = super()._manifest()
        cfg = man["config"]
        del cfg["slots_per_group"]      # derived from the mesh config
        cfg["mesh"] = {
            "n_replicas": self.n_replicas,
            "slots_per_replica": self.slots_per_replica,
            "placement": self.placement.name,
        }
        if self.forest is not None:
            spr = self.slots_per_replica
            assignments = [
                (leaf, self._location[qid][1] // spr)
                for qid, leaf in self._prefix_of.items()
            ]
            man["replica_refcounts"] = {
                str(pid): counts
                for pid, counts in self.forest.replica_refcounts(
                    assignments, self.n_replicas).items()
            }
        return man

    def _ckpt_save_kwargs(self) -> dict:
        # slot-stacked group states split along axis 0 into one npz per
        # replica; forest node tables (replicated inputs) and scalars
        # ride in shard 0
        replicated = ()
        if self.forest is not None:
            replicated = tuple(
                f"prefix{n.pid}" for n in self.forest.nodes())
        return {"n_shards": self.n_replicas, "replicated": replicated}

    @classmethod
    def restore(
        cls,
        ckpt_dir: str,
        step: int | None = None,
        tick_cache: SlotTickCache | None = None,
        backend: str | None = None,
        extract_matches: bool | None = None,
        n_replicas: int | None = None,
        placement=None,
        obs=None,
        tracer=None,
    ) -> "ShardedSearchService":
        """Rebuild a sharded service from its newest usable checkpoint.

        With ``n_replicas`` equal to the checkpointed mesh size (or
        omitted) the exact slot layout is re-armed — zero recompiles for
        meshes this process has served.  A DIFFERENT ``n_replicas``
        triggers the repack path: queries keep their qids, the placement
        policy re-places every tenant onto the new mesh, and each
        tenant's engine-table rows are spliced from its old slot into
        its new one (host-side reassembly of the per-replica shards
        makes the npz layout mesh-agnostic).
        """
        overrides = {}
        if backend is not None:
            overrides["backend"] = backend
        if extract_matches is not None:
            overrides["extract_matches"] = extract_matches
        if placement is not None:
            overrides["placement"] = placement
        if obs is not None:
            overrides["obs"] = obs
        if tracer is not None:
            overrides["tracer"] = tracer
        candidates = ([step] if step is not None
                      else list(reversed(checkpoint_steps(ckpt_dir))))
        last_err: CheckpointError | None = None
        for s in candidates:
            try:
                validate_checkpoint(ckpt_dir, s)
                man = load_resolved_manifest(ckpt_dir, s, "service")
                mesh_cfg = man["config"].get("mesh")
                if mesh_cfg is None:
                    raise CheckpointError(
                        f"step {s}: not a ShardedSearchService checkpoint")
                if (n_replicas is None
                        or n_replicas == mesh_cfg["n_replicas"]):
                    return cls._restore_step(ckpt_dir, s, tick_cache,
                                             overrides)
                return cls._restore_reshard(ckpt_dir, s, man, tick_cache,
                                            overrides, n_replicas)
            except CheckpointError as e:
                last_err = e
        raise CheckpointError(
            f"no usable sharded checkpoint under {ckpt_dir!r}"
        ) from last_err

    @classmethod
    def _restore_step(cls, ckpt_dir, step, tick_cache, overrides):
        svc = super()._restore_step(ckpt_dir, step, tick_cache, overrides)
        svc._verify_replica_refcounts(
            load_resolved_manifest(ckpt_dir, step, "service"), step)
        for g in svc._iter_groups():
            g.sstate = svc._shard_state(g.sstate)
        return svc

    def _verify_replica_refcounts(self, man, step) -> None:
        """Refcounts are rebuilt, not trusted: re-derive the per-replica
        partition from the restored slot layout and compare with what
        the manifest recorded."""
        want = man.get("replica_refcounts")
        if want is None or self.forest is None:
            return
        spr = self.slots_per_replica
        assignments = [(leaf, self._location[qid][1] // spr)
                       for qid, leaf in self._prefix_of.items()]
        got = {str(pid): counts
               for pid, counts in self.forest.replica_refcounts(
                   assignments, self.n_replicas).items()}
        if want != got:
            raise CheckpointError(
                f"step {step}: per-replica refcount partition disagrees "
                f"with the manifest (manifest {want}, rebuilt {got})")

    @classmethod
    def _restore_reshard(cls, ckpt_dir, step, man, tick_cache, overrides,
                         n_replicas):
        """Restore onto a mesh of a different size: re-place and splice."""
        config = _serving_config(man)
        mesh_cfg = dict(config.pop("mesh"))
        mesh_cfg["n_replicas"] = n_replicas
        svc = cls(ckpt_dir=ckpt_dir, tick_cache=tick_cache,
                  mesh=mesh_cfg, **{**config, **overrides})
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

        # old-layout like-tree: one full-size SlotState per old group
        groups = sorted(man["groups"].items(), key=lambda kv: int(kv[0]))
        like, templates, leaves = {}, {}, {}
        for gid_s, gspec in groups:
            template = svc.registry.compile(
                QueryGraph.from_spec(gspec["template_query"]),
                int(gspec["template_window"]),
                decomposition=gspec.get("template_decomposition"))
            pid = gspec.get("prefix_pid")
            leaf = None if pid is None else by_pid[int(pid)]
            depth = 0 if leaf is None else leaf.depth
            templates[gid_s], leaves[gid_s] = template, leaf
            like[gid_s] = init_slot_state(
                template, len(gspec["qids"]), depth)
        if svc.forest is not None and man.get("forest"):
            for n in svc.forest.nodes():
                like[f"prefix{n.pid}"] = n.state
        restored = restore_checkpoint(ckpt_dir, step, like)

        # re-place every tenant on the new mesh and splice its engine
        # rows out of the old slot; params are rewritten from its plan
        for gid_s, gspec in groups:
            old = jax.tree.map(jnp.asarray, restored[gid_s])
            leaf = leaves[gid_s]
            for k, qid in enumerate(gspec["qids"]):
                if qid is None:
                    continue
                qid = int(qid)
                rq = svc.registry.get(qid)
                gkey = (rq.signature, None if leaf is None else leaf.pid)
                gs = svc._groups.setdefault(gkey, [])
                group, k2 = svc._place(gs, rq.plan, leaf, rq.signature)
                group.sstate = write_slot(
                    group.sstate, group.template, k2, rq.plan,
                    empty=read_slot(old, k))
                group.qids[k2] = qid
                svc._location[qid] = (group, k2)
                if leaf is not None:
                    svc._prefix_of[qid] = svc.forest.adopt(leaf)
        if svc.forest is not None and man.get("forest"):
            want = {int(e["pid"]): int(e["refcount"])
                    for e in man["forest"]["nodes"]}
            got = {n.pid: n.refcount for n in svc.forest.nodes()}
            if want != got:
                raise CheckpointError(
                    f"step {step}: forest refcounts disagree with the "
                    f"manifest after repack (manifest {want}, "
                    f"rebuilt {got})")
            for n in svc.forest.nodes():
                n.state = jax.tree.map(
                    jnp.asarray, restored[f"prefix{n.pid}"])
        for g in svc._iter_groups():
            g.sstate = svc._shard_state(g.sstate)
        counters = man["counters"]
        svc.n_edges_ingested = int(counters["n_edges_ingested"])
        svc.n_ticks = int(counters["n_ticks"])
        svc._ckpt_step = int(step)
        svc.registry._next_qid = max(
            svc.registry._next_qid, int(counters["next_qid"]))
        if svc.obs is not None and man.get("obs"):
            svc.obs.load_manifest(man["obs"])
        return svc
