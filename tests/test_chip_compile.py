"""Compile the compat_join kernels and a whole PALLAS slot tick for a
described TPU v5e, without a chip.

The TPU compiler ships with the installed JAX and compiles for a chip
that is described, not attached.  It refuses what interpret mode and the
``repro.analysis`` KC rules cannot see (block shapes, layouts, scalar
stores to VMEM), so these compiles guard every change to the kernels and
the slot tick at the widths ``chip_smoke.py`` serves: tables of 16,384
rows joined against 1,024-edge batches, 64 slots per group; the L0
delta joins of the ``netflow-w1`` benchmark deployment, whose 16,384-row
sides the pairs kernel holds whole in VMEM; and the replica-sharded tick
of ``netflow-w1-x4``, the slot tick under ``shard_map`` over the four
chips of a described v5e host.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, so only the test worker that
runs this file touches it.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.join import JoinBackend
from repro.core.multi import _arm_slot, build_slot_tick, init_slot_state
from repro.core.plan import compile_plan
from repro.core.query import QueryGraph
from repro.core.state import EdgeBatch, init_state
from repro.kernels.compat_join import ops as cj_ops
from repro.runtime.mesh import build_mesh_slot_tick

CA, CB, SLOTS, MAX_NEW = 16384, 1024, 64, 2048
NVA, NEA, NVB, NEB = 4, 3, 2, 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compile can be written to the persistent cache
    # but not read back: keep the cache off around these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec():
    rel = np.zeros((NVA, NVB), bool)
    rel[0, 1] = True
    trel = np.zeros((NEA, NEB), np.int8)
    trel[-1, 0] = -1
    return rel, trel


def _tables(one_chip, slots=None):
    def s(*shape, dtype=jnp.int32):
        lead = () if slots is None else (slots,)
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)
    return (s(CA, NVA), s(CA, NEA), s(CA, dtype=jnp.bool_),
            s(CB, NVB), s(CB, NEB), s(CB, dtype=jnp.bool_))


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("op", ["compat_mask", "compat_join_pairs"])
def test_kernel_compiles_unbatched(one_chip, op):
    rel, trel = _spec()
    if op == "compat_mask":
        fn = lambda *t: cj_ops.compat_mask(*t, rel, trel, window=30)
    else:
        fn = lambda *t: cj_ops.compat_join_pairs(*t, rel, trel, MAX_NEW,
                                                 window=30)
    assert "tpu_custom_call" in _compiled_text(fn, *_tables(one_chip))


@pytest.mark.parametrize("op", ["compat_mask", "compat_join_pairs"])
def test_kernel_compiles_stacked(one_chip, op):
    """The vmapped form the slot tick runs: per-slot A side, per-slot
    windows, B bindings/timestamps shared by every slot (the batch)."""
    rel, trel = _spec()
    a = _tables(one_chip, SLOTS)[:3]
    b = _tables(one_chip)[3:5]
    vb = jax.ShapeDtypeStruct((SLOTS, CB), jnp.bool_, sharding=one_chip)
    win = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    if op == "compat_mask":
        one = lambda ba, ea, va, bb, eb, vb, w: cj_ops.compat_mask(
            ba, ea, va, bb, eb, vb, rel, trel, window=w)
    else:
        one = lambda ba, ea, va, bb, eb, vb, w: cj_ops.compat_join_pairs(
            ba, ea, va, bb, eb, vb, rel, trel, MAX_NEW, window=w)
    fn = jax.vmap(one, in_axes=(0, 0, 0, None, None, 0, 0))
    assert "tpu_custom_call" in _compiled_text(fn, *a, *b, vb, win)


@pytest.mark.parametrize("ca,cb", [(MAX_NEW, CA), (CA, MAX_NEW),
                                   (4 * CA, MAX_NEW), (MAX_NEW, 4 * CA)],
                         ids=["delta_a", "delta_b", "grid_a", "grid_both"])
def test_pairs_kernel_compiles_for_l0_joins(one_chip, ca, cb):
    """The L0 delta joins ΔA ⋈ B and A_old ⋈ ΔB, both sides per slot,
    at the widest operands of the ``netflow-w1`` walks (12 packed rows
    on A, 6 on B): each side is one whole-axis block of the kernel.  A
    65,536-row side exceeds ``kernel.BLOCK_BYTES`` and stays on the grid
    (a gridded B grids A too)."""
    nva, nea, nvb, neb = 6, 5, 3, 2
    rel = np.zeros((nva, nvb), bool)
    rel[0, 0] = True
    trel = np.zeros((nea, neb), np.int8)
    trel[-1, 0] = -1

    def s(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((SLOTS,) + shape, dtype,
                                    sharding=one_chip)

    one = lambda ba, ea, va, bb, eb, vb, w: cj_ops.compat_join_pairs(
        ba, ea, va, bb, eb, vb, rel, trel, MAX_NEW, window=w)
    fn = jax.vmap(one)
    text = _compiled_text(fn, s(ca, nva), s(ca, nea), s(ca, dtype=jnp.bool_),
                          s(cb, nvb), s(cb, neb), s(cb, dtype=jnp.bool_),
                          s())
    assert "tpu_custom_call" in text


def _c2_query() -> QueryGraph:
    """The 5-edge C2 exfiltration chain (vertex labels victim, web,
    malware, C&C, C&C; one port per edge; a total timing order)."""
    return QueryGraph(
        n_vertices=5, vertex_labels=(0, 1, 2, 3, 3),
        edges=((0, 1), (2, 0), (0, 3), (3, 0), (0, 4)),
        edge_labels=(1, 2, 3, 4, 5),
        prec=frozenset({(0, 1), (1, 2), (2, 3), (3, 4)}))


def test_pallas_slot_tick_compiles_with_kernels(one_chip):
    """One whole served slot tick (event-time mode) of the C2 template at
    deployment capacity compiles for the chip, with the kernels in it."""
    plan = compile_plan(_c2_query(), 600, level_capacity=CA,
                        l0_capacity=CA, max_new=MAX_NEW)
    tick = build_slot_tick(plan, backend=JoinBackend.PALLAS)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
    sstate = jax.tree.map(place, jax.eval_shape(
        lambda: init_slot_state(plan, SLOTS)))
    batch = EdgeBatch(*(
        [jax.ShapeDtypeStruct((CB,), jnp.int32, sharding=one_chip)] * 6
        + [jax.ShapeDtypeStruct((CB,), jnp.bool_, sharding=one_chip)]))
    wm = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = _compiled_text(tick, sstate, batch, wm)
    assert "tpu_custom_call" in text


def _mesh_group(topo):
    """A replica-sharded group at deployment capacity, 64 slots on each
    of the host's four chips: its mesh, plan, state shapes and the
    replicated sharding."""
    mesh = jax.make_mesh((4,), ("replica",), devices=topo.devices[:4],
                         axis_types=(jax.sharding.AxisType.Auto,))
    plan = compile_plan(_c2_query(), 600, level_capacity=CA,
                        l0_capacity=CA, max_new=MAX_NEW)
    sharded, repl = NamedSharding(mesh, P("replica")), NamedSharding(mesh, P())
    sstate = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharded),
        jax.eval_shape(lambda: init_slot_state(plan, 4 * SLOTS)))
    return mesh, plan, sstate, repl


def test_mesh_slot_tick_compiles_on_four_chips(topo):
    """The served tick of a replica-sharded group compiles for the host:
    the PALLAS kernels inside ``shard_map``, and the closing psum/pmax
    of ``MeshTickStats`` as all-reduces."""
    mesh, plan, sstate, repl = _mesh_group(topo)
    tick = build_mesh_slot_tick(plan, mesh, backend=JoinBackend.PALLAS)
    batch = EdgeBatch(*(
        [jax.ShapeDtypeStruct((CB,), jnp.int32, sharding=repl)] * 6
        + [jax.ShapeDtypeStruct((CB,), jnp.bool_, sharding=repl)]))
    wm = jax.ShapeDtypeStruct((), jnp.int32, sharding=repl)
    text = _compiled_text(tick, sstate, batch, wm)
    assert "tpu_custom_call" in text
    assert "all-reduce" in text


def test_mesh_slot_write_stays_on_its_replica(topo):
    """Registering a tenant writes one slot of a replica-sharded group in
    place on the replica that owns it: no collective moves the group's
    tables, and the state keeps its sharding."""
    _, plan, sstate, repl = _mesh_group(topo)
    empty = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(lambda: init_state(plan)))
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype, sharding=repl),
        sstate.params)
    k = jax.ShapeDtypeStruct((), jnp.int32, sharding=repl)
    compiled = _arm_slot.lower(sstate, k, empty, params).compile()
    text = compiled.as_text()
    assert not [op for op in ("all-gather", "all-reduce", "all-to-all",
                              "collective-permute") if op in text]
    assert {s.spec for s in jax.tree.leaves(compiled.output_shardings)} \
        == {P("replica")}
