"""compat_join Pallas kernels vs pure-jnp oracle: shape/dtype/spec sweep,
traced windows, vmapped slot-group batching, and the fused pair-extraction
op (interpret mode executes the kernel bodies on CPU).  The pairs kernel
sweeps only the tiles below each side's live extent: its output must
equal, element for element, a NumPy emulation of tile-order emission
over the whole padded grid, on sparse validity layouts, per-slot
extents, and with each side held whole in VMEM or kept on the grid."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import compile_plan
from repro.core.engine import build_tick, current_matches
from repro.core.join import JoinBackend, compat_mask_ref, extract_pairs
from repro.core.query import QueryGraph
from repro.core.state import init_state, make_batch
from repro.kernels.compat_join import ops as cj_ops
from repro.kernels.compat_join import kernel as cj_kernel
from repro.kernels.compat_join import ref as cj_ref
from repro.kernels.compat_join.kernel import TILE_A, TILE_B, choose_tiles
from repro.stream.generator import StreamConfig, synth_traffic_stream, to_batches


def rand_case(rng, ca, cb, nva, nvb, nea, neb, window):
    bind_a = rng.integers(0, 6, (ca, nva)).astype(np.int32)
    bind_b = rng.integers(0, 6, (cb, nvb)).astype(np.int32)
    ets_a = rng.integers(0, 30, (ca, nea)).astype(np.int32)
    ets_b = rng.integers(0, 30, (cb, neb)).astype(np.int32)
    valid_a = rng.random(ca) < 0.8
    valid_b = rng.random(cb) < 0.8
    rel = rng.random((nva, nvb)) < 0.3
    trel = rng.integers(-1, 2, (nea, neb)).astype(np.int8)
    return (jnp.asarray(bind_a), jnp.asarray(ets_a), jnp.asarray(valid_a),
            jnp.asarray(bind_b), jnp.asarray(ets_b), jnp.asarray(valid_b),
            rel, trel, window)


SHAPES = [
    (8, 8, 1, 1, 1, 1, None),
    (17, 33, 2, 2, 2, 1, None),
    (256, 256, 3, 2, 3, 1, 12),
    (300, 130, 4, 4, 4, 4, 20),
    (1, 512, 2, 2, 1, 1, 5),
    (512, 1, 5, 2, 5, 2, None),
]


@pytest.mark.parametrize("ca,cb,nva,nvb,nea,neb,window", SHAPES)
def test_kernel_matches_ref(ca, cb, nva, nvb, nea, neb, window):
    rng = np.random.default_rng(ca * 1000 + cb)
    args = rand_case(rng, ca, cb, nva, nvb, nea, neb, window)
    want = compat_mask_ref(*args[:6], args[6], args[7], args[8])
    got = cj_ops.compat_mask(*args[:6], args[6], args[7], args[8],
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kernel_random_specs(seed):
    rng = np.random.default_rng(seed)
    for _ in range(5):
        ca, cb = int(rng.integers(1, 400)), int(rng.integers(1, 400))
        nva, nvb = int(rng.integers(1, 5)), int(rng.integers(1, 5))
        nea, neb = int(rng.integers(1, 4)), int(rng.integers(1, 4))
        window = None if rng.random() < 0.5 else int(rng.integers(3, 25))
        args = rand_case(rng, ca, cb, nva, nvb, nea, neb, window)
        want = compat_mask_ref(*args[:6], args[6], args[7], args[8])
        got = cj_ops.compat_mask(*args[:6], args[6], args[7], args[8],
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------- #
# Adaptive tiling.
# --------------------------------------------------------------------- #
def test_choose_tiles_adapts_to_shape():
    assert choose_tiles(4096, 4096) == (TILE_A, TILE_B)
    # the common small-delta join no longer pads up to 256x256
    ta, tb = choose_tiles(64, 64)
    assert ta == 64 and tb == 128
    assert choose_tiles(1, 1) == (8, 128)
    assert choose_tiles(300, 130) == (256, 256)
    ta, tb = choose_tiles(9, 129)
    assert ta % 8 == 0 and tb % 128 == 0


# --------------------------------------------------------------------- #
# Traced windows.
# --------------------------------------------------------------------- #
def test_traced_window_parity_and_no_recompile():
    """``window`` is a scalar-prefetch input: changing it between calls
    produces oracle-exact masks from ONE jit trace (no recompile)."""
    rng = np.random.default_rng(7)
    args = rand_case(rng, 64, 48, 3, 2, 2, 1, None)
    f = jax.jit(lambda w: cj_ops.compat_mask(
        *args[:6], args[6], args[7], w, interpret=True))
    for w in (1, 7, 13, 29):
        want = compat_mask_ref(*args[:6], args[6], args[7], w)
        got = f(jnp.asarray(w, jnp.int32))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert f._cache_size() == 1


def test_traced_window_crossing_row_expiry_mid_tick():
    """Rows near expiry must stay joinable for earlier-timestamped B rows
    and be invisible to later ones (the paper's two-phase deletion as a
    window-span predicate): B timestamps straddle the A rows' expiry."""
    window = 10
    # A rows at ts 0, 5, 9; B rows at ts 8, 9, 12, 18: the (0, 12) pair
    # crosses expiry (span 12 >= 10) while (0, 9) does not.
    ets_a = jnp.asarray([[0], [5], [9]], jnp.int32)
    ets_b = jnp.asarray([[8], [9], [12], [18]], jnp.int32)
    bind_a = jnp.asarray([[1], [2], [3]], jnp.int32)
    bind_b = jnp.asarray([[4], [5], [6], [7]], jnp.int32)
    va = jnp.ones((3,), jnp.bool_)
    vb = jnp.ones((4,), jnp.bool_)
    rel = np.zeros((1, 1), bool)               # all-distinct vertices
    trel = np.full((1, 1), -1, np.int8)        # ts_a < ts_b
    want = compat_mask_ref(bind_a, ets_a, va, bind_b, ets_b, vb,
                           rel, trel, window)
    got = cj_ops.compat_mask(bind_a, ets_a, va, bind_b, ets_b, vb,
                             rel, trel, window, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    w = np.asarray(want)
    assert w[0, 1] and not w[0, 2] and not w[0, 3]   # crossing pairs drop
    assert w[2, 2] and w[2, 3]                       # late rows still join


# --------------------------------------------------------------------- #
# Batched (vmapped) slot-group joins -> stacked 3-D-grid kernel.
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mixed", [False, True])
def test_vmapped_slot_group_mask_matches_per_slot_ref(mixed):
    """jax.vmap over stacked tables + per-slot windows lowers to ONE
    stacked kernel and equals the per-slot reference masks.  ``mixed``
    leaves the B side unbatched (the slot tick's stream-edge operand)."""
    rng = np.random.default_rng(11)
    S, ca, cb = 3, 40, 24
    args = rand_case(rng, ca, cb, 3, 2, 2, 1, None)
    ba, ea, va, bb, eb, vb, rel, trel, _ = args
    bas = jnp.stack([ba, (ba + 1) % 6, ba[::-1]])
    ebs = jnp.stack([eb, eb + 1, eb])
    ws = jnp.asarray([4, 11, 25], jnp.int32)
    if mixed:   # B side (stream edges) shared across slots, A batched
        fn = jax.jit(jax.vmap(
            lambda xa, w: cj_ops.compat_mask(
                xa, ea, va, bb, eb, vb, rel, trel, w, interpret=True),
            in_axes=(0, 0)))
        got = fn(bas, ws)
    else:       # both sides batched
        fn = jax.jit(jax.vmap(
            lambda xa, xeb, w: cj_ops.compat_mask(
                xa, ea, va, bb, xeb, vb, rel, trel, w, interpret=True),
            in_axes=(0, 0, 0)))
        got = fn(bas, ebs, ws)
    for s in range(S):
        xeb = eb if mixed else ebs[s]
        want = compat_mask_ref(bas[s], ea, va, bb, xeb, vb, rel, trel,
                               int(ws[s]))
        np.testing.assert_array_equal(np.asarray(got[s]), np.asarray(want))


# --------------------------------------------------------------------- #
# Fused pair extraction (compat_join_pairs).
# --------------------------------------------------------------------- #
def _pair_set(a_idx, b_idx, valid):
    a, b, v = (np.asarray(x) for x in (a_idx, b_idx, valid))
    return set(zip(a[v].tolist(), b[v].tolist()))


def tile_order_pairs(mask, max_new):
    """NumPy emulation of the pairs kernel sweeping the WHOLE padded grid:
    every (A-tile, B-tile), A-tile major, row-major inside a tile, the
    first ``max_new`` pairs kept.  Returns ``(a_idx, b_idx, pair_valid,
    n_dropped)`` as ``compat_join_pairs`` does (unused entries 0)."""
    m = np.asarray(mask)
    ta, tb = choose_tiles(*m.shape)
    a, b = [], []
    for i in range(0, m.shape[0], ta):
        for j in range(0, m.shape[1], tb):
            r, c = np.nonzero(m[i:i + ta, j:j + tb])
            a += (r + i).tolist()
            b += (c + j).tolist()
    keep = min(len(a), max_new)
    out = np.zeros((3, max_new), np.int32)
    out[0, :keep], out[1, :keep], out[2, :keep] = a[:keep], b[:keep], 1
    return out[0], out[1], out[2].astype(bool), max(len(a) - max_new, 0)


def _assert_tile_order(got, mask, max_new):
    """The kernel's pairs equal the full sweep's, element for element."""
    want = tile_order_pairs(mask, max_new)
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(np.asarray(g), w)
    assert int(got[3]) == want[3]


def _check_pairs_vs_oracle(args, max_new):
    want_mask = compat_mask_ref(*args[:6], args[6], args[7], args[8])
    wa, wb, wv, wd = extract_pairs(want_mask, max_new)
    ga, gb, gv, gd = cj_ops.compat_join_pairs(
        *args[:6], args[6], args[7], max_new, args[8], interpret=True)
    assert int(gd) == int(wd), "n_dropped must be exact"
    _assert_tile_order((ga, gb, gv, gd), want_mask, max_new)
    want_set = _pair_set(wa, wb, wv)
    got_set = _pair_set(ga, gb, gv)
    if int(wd) == 0:
        assert got_set == want_set
    else:
        full = set(zip(*(x.tolist() for x in np.nonzero(np.asarray(want_mask)))))
        assert len(got_set) == max_new and got_set <= full
    # invalid entries are clamped to safe indices like extract_pairs
    assert int(jnp.min(ga)) >= 0 and int(jnp.min(gb)) >= 0


@pytest.mark.parametrize("ca,cb,nva,nvb,nea,neb,window", SHAPES)
def test_fused_pairs_match_mask_plus_extract(ca, cb, nva, nvb, nea, neb,
                                             window):
    rng = np.random.default_rng(ca * 31 + cb)
    args = rand_case(rng, ca, cb, nva, nvb, nea, neb, window)
    for max_new in (4, 64, 2048):
        _check_pairs_vs_oracle(args, max_new)


def test_fused_pairs_vmapped_slot_group():
    """Vmapped fused pairs (the PALLAS slot-tick join) == per-slot
    mask + extract_pairs, including per-slot n_dropped."""
    rng = np.random.default_rng(13)
    S, ca, cb, max_new = 3, 40, 24, 16
    args = rand_case(rng, ca, cb, 2, 2, 2, 1, None)
    ba, ea, va, bb, eb, vb, rel, trel, _ = args
    bas = jnp.stack([ba % 3, ba % 4, ba % 5])
    ws = jnp.asarray([6, 12, 29], jnp.int32)
    fn = jax.jit(jax.vmap(
        lambda xa, w: cj_ops.compat_join_pairs(
            xa, ea, va, bb, eb, vb, rel, trel, max_new, w, interpret=True),
        in_axes=(0, 0)))
    ga, gb, gv, gd = fn(bas, ws)
    assert fn._cache_size() == 1
    for s in range(S):
        mask = compat_mask_ref(bas[s], ea, va, bb, eb, vb, rel, trel,
                               int(ws[s]))
        wa, wb, wv, wd = extract_pairs(mask, max_new)
        assert int(gd[s]) == int(wd)
        if int(wd) == 0:
            assert _pair_set(ga[s], gb[s], gv[s]) == _pair_set(wa, wb, wv)
        else:
            full = set(zip(*(x.tolist()
                             for x in np.nonzero(np.asarray(mask)))))
            assert _pair_set(ga[s], gb[s], gv[s]) <= full


# --------------------------------------------------------------------- #
# Live extents: sparse validity layouts, per-slot extents, VMEM blocks.
# --------------------------------------------------------------------- #
def live_rows(rng, c, layout):
    """Validity of a ``c``-row side laid out as tables and deltas are:
    ``none`` (no live row), ``dense`` (80% live), ``prefix`` (all live
    below a random extent), ``holes`` (half live below it), ``last``
    (one live row, in the last tile), ``edge`` (rows 255 and 256, either
    side of a tile boundary)."""
    v = np.zeros(c, bool)
    ext = int(rng.integers(1, c + 1))
    if layout == "dense":
        v = rng.random(c) < 0.8
    elif layout == "prefix":
        v[:ext] = True
    elif layout == "holes":
        v[:ext] = rng.random(ext) < 0.5
        v[ext - 1] = True
    elif layout == "last":
        v[c - 1 - int(rng.integers(0, c % TILE_A or TILE_A))] = True
    elif layout == "edge":
        v[[255, 256]] = True
    return jnp.asarray(v)


def _sparse_case(seed, layout_a, layout_b, ca=600, cb=700):
    """600 x 700 rows: 3 x 3 tiles of 256, the last ones partial.  Vertex
    bindings are distinct, so most live pairs match: those whose A's
    last edge precedes B's edge (A's timestamps 0-29, B's 15-44)."""
    rng = np.random.default_rng(seed)
    args = list(rand_case(rng, ca, cb, 4, 2, 4, 1, None))
    args[0] = jnp.asarray(rng.permutation(ca * 4).reshape(ca, 4), jnp.int32)
    args[3] = jnp.asarray(ca * 4 + rng.permutation(cb * 2).reshape(cb, 2),
                          jnp.int32)
    args[4] = args[4] + 15
    args[2], args[5] = live_rows(rng, ca, layout_a), live_rows(rng, cb,
                                                               layout_b)
    args[6] = np.zeros((4, 2), bool)
    args[7] = np.zeros((4, 1), np.int8)
    args[7][-1, 0] = -1
    return tuple(args)


def _block_budget(mode, kb=4):
    """``kernel.BLOCK_BYTES`` that holds both 3-tile sides whole
    (``whole``), only B (``grid_a``: A stays on the grid), or neither
    (``grid_both``); B packs ``kb`` rows, padded to 8."""
    b_bytes = 8 * TILE_B * 3 * 4
    return {"whole": cj_kernel.BLOCK_BYTES, "grid_a": b_bytes,
            "grid_both": 0}[mode]


SPARSE = [("none", "dense"), ("dense", "none"), ("prefix", "prefix"),
          ("holes", "holes"), ("last", "dense"), ("dense", "last"),
          ("edge", "edge"), ("holes", "last")]


@pytest.mark.parametrize("mode", ["whole", "grid_a", "grid_both"])
@pytest.mark.parametrize("layout_a,layout_b", SPARSE)
def test_fused_pairs_sparse_layouts(monkeypatch, layout_a, layout_b, mode):
    """Pairs over tiles below the extents equal the full sweep's, bit
    for bit, with and without overflow (max_new 3 keeps a prefix)."""
    monkeypatch.setattr(cj_kernel, "BLOCK_BYTES", _block_budget(mode))
    args = _sparse_case(len(layout_a) * 7 + len(layout_b), layout_a,
                        layout_b)
    mask = compat_mask_ref(*args[:6], args[6], args[7], args[8])
    for max_new in (3, 4096):
        _check_pairs_vs_oracle(args, max_new)
    if layout_a != "none" and layout_b != "none":
        assert np.asarray(mask).any()


def test_extent_counts_tiles_to_the_last_live_one():
    """``ops._extent``: 0 for no live row, else the tiles up to and
    including the last tile with a valid row (padding never counts)."""
    rng = np.random.default_rng(0)
    for layout, want in (("none", 0), ("edge", 2), ("last", 3)):
        v = live_rows(rng, 600, layout)
        side, _ = cj_ops._pack_side(jnp.zeros((600, 2), jnp.int32),
                                    jnp.zeros((600, 1), jnp.int32), v,
                                    (False,) * 3, 1, TILE_A)
        assert side.shape == (3, 4, TILE_A)
        assert cj_ops._extent(side, 2).tolist() == [want, want]


@pytest.mark.parametrize("mode", ["whole", "grid_both"])
@pytest.mark.parametrize("shared", ["b", "a", "neither"])
def test_fused_pairs_vmapped_slot_extents(monkeypatch, shared, mode):
    """A vmapped slot group whose slots have different extents on the
    per-slot side (none, one tile, holes, all three tiles), against a
    side shared by every slot (2-D, one extent) or per slot too: each
    slot equals the full sweep of its own mask, overflow included."""
    monkeypatch.setattr(cj_kernel, "BLOCK_BYTES", _block_budget(mode))
    rng = np.random.default_rng(17)
    ca, cb, max_new = 600, 700, 5
    ba, ea, _, bb, eb, _, rel, trel, _ = _sparse_case(17, "none", "none")
    per_slot = [live_rows(rng, 600, x)
                for x in ("none", "edge", "holes", "dense")]
    va_s = jnp.stack(per_slot)
    vb_s = jnp.stack([live_rows(rng, cb, x)
                      for x in ("dense", "holes", "none", "last")])
    va1, vb1 = live_rows(rng, ca, "holes"), live_rows(rng, cb, "prefix")
    ws = jnp.asarray([30, 12, 30, 30], jnp.int32)
    in_a = None if shared == "a" else 0
    in_b = None if shared == "b" else 0
    va = va1 if shared == "a" else va_s
    vb = vb1 if shared == "b" else vb_s
    fn = jax.jit(jax.vmap(
        lambda xa, xb, w: cj_ops.compat_join_pairs(
            ba, ea, xa, bb, eb, xb, rel, trel, max_new, w, interpret=True),
        in_axes=(in_a, in_b, 0)))
    got = fn(va, vb, ws)
    for s in range(4):
        xa = va if in_a is None else va[s]
        xb = vb if in_b is None else vb[s]
        mask = compat_mask_ref(ba, ea, xa, bb, eb, xb, rel, trel,
                               int(ws[s]))
        _assert_tile_order([x[s] for x in got], mask, max_new)


def test_spec_normalization_is_cached():
    """Equal-content specs map to the identical cached tuple objects, so
    repeated joins reuse the same static kernel key per tick."""
    rng = np.random.default_rng(3)
    rel = rng.random((3, 2)) < 0.5
    trel = rng.integers(-1, 2, (2, 1)).astype(np.int8)
    k1 = cj_ops.normalize_spec(rel, trel)
    k2 = cj_ops.normalize_spec(rel.copy(), trel.copy())
    assert k1[0] is k2[0] and k1[1] is k2[1]
    k3 = cj_ops.normalize_spec(~rel, trel)
    assert k3[0] is not k1[0]


def test_ref_module_pairs_oracle():
    """The kernel package's own oracle (ref.py) agrees with core.join."""
    rng = np.random.default_rng(5)
    args = rand_case(rng, 30, 20, 2, 2, 2, 1, 9)
    wa, wb, wv, wd = cj_ref.compat_join_pairs(
        *args[:6], args[6], args[7], 16, args[8])
    mask = compat_mask_ref(*args[:6], args[6], args[7], args[8])
    ea_, eb_, ev_, ed_ = extract_pairs(mask, 16)
    np.testing.assert_array_equal(np.asarray(wa), np.asarray(ea_))
    np.testing.assert_array_equal(np.asarray(wv), np.asarray(ev_))
    assert int(wd) == int(ed_)


def test_engine_with_pallas_backend_matches_ref_backend():
    """Full engine equivalence with the Pallas join (interpret mode)."""
    q = QueryGraph(3, (0, 1, 0), ((0, 1), (1, 2)), prec=frozenset({(0, 1)}))
    stream = synth_traffic_stream(StreamConfig(
        n_edges=120, n_vertices=10, n_vertex_labels=2, n_edge_labels=2,
        seed=3, ts_step_max=2))
    window = 18
    finals = []
    for backend in (JoinBackend.REF, JoinBackend.PALLAS_INTERPRET):
        plan = compile_plan(q, window, level_capacity=512, max_new=256)
        tick = jax.jit(build_tick(plan, backend=backend))
        state = init_state(plan)
        for b in to_batches(stream, 16):
            state, _ = tick(state, make_batch(**b))
        finals.append((current_matches(plan, state),
                       int(state.stats.n_matches_total)))
    assert finals[0] == finals[1]
