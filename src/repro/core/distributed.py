"""Distributed execution of the streaming engine via shard_map.

Sharding model
--------------
Every partial-match table's capacity axis is sharded over the mesh's
engine axis (a flat view of ('pod','data') in production).  The edge
batch is replicated — ingest bandwidth is tiny next to table state.

Collectives per tick (the engine's roofline collective term):
  * 2·(k-1) all-gathers of compact delta rows (k = #TC-subqueries);
  * psums of scalar stats.
Everything else — label matching, expansion-list joins, MS-tree
reconstruction, expiry cascades — is shard-local by construction
(level-1 round-robin + parent-locality of appends).
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import join as J
from repro.core.engine import build_tick
from repro.core.plan import ExecutionPlan
from repro.core.state import EngineState, init_state


def _state_specs(state: EngineState, axes) -> EngineState:
    """PartitionSpec pytree: shard every capacity axis, replicate scalars."""
    shard = P(axes)

    def spec_leaf(x):
        return shard if x.ndim >= 1 else P()

    return jax.tree.map(spec_leaf, state)


def build_sharded_tick(
    plan: ExecutionPlan,
    mesh: Mesh,
    axes=("data",),
    backend: str | None = None,
    extract_matches: bool = False,
    prefix_depth: int = 0,
):
    """Returns ``(tick, state)`` with ``tick`` jit-compiled under shard_map
    and ``state`` placed according to the sharding spec.

    ``axes`` may name one or more mesh axes; the capacity dimension is
    sharded over their product (e.g. ``('pod', 'data')`` on the
    multi-pod production mesh).

    With ``prefix_depth > 0`` the tick takes a shared-prefix
    ``NodeView`` (``repro.core.share``) as a third argument; the view is
    REPLICATED across shards — the forest node advances once outside the
    shard_map — and the engine body partitions its join output
    deterministically (see ``build_tick_body``).
    """
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    axes = tuple(axes)
    axis_name = axes if len(axes) > 1 else axes[0]

    inner = build_tick(
        plan,
        backend=backend,
        extract_matches=extract_matches,
        axis_name=axis_name,
        n_shards=n_shards,
        prefix_depth=prefix_depth,
    )

    state0 = init_state(plan, prefix_depth)
    specs = _state_specs(state0, axes)

    from repro.core.engine import TickResult
    from repro.core.state import EdgeBatch

    batch_specs = EdgeBatch(*(P() for _ in range(7)))
    out_res_specs = TickResult(
        n_new_matches=P(),
        n_overflow=P(),
        match_bindings=P(axes),
        match_ets=P(axes),
        match_valid=P(axes),
        load=P(),                               # psum'd over shards
    )

    in_specs = (specs, batch_specs)
    if prefix_depth:
        from repro.core.share import NodeView
        in_specs = in_specs + (NodeView(P(), P(), P(), P(), P()),)

    tick = jax.jit(
        jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(specs, out_res_specs),
            check_vma=False,
        )
    )

    state = jax.device_put(
        state0, jax.tree.map(lambda s: NamedSharding(mesh, s), specs))
    return tick, state
