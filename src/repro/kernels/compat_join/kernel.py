"""Pallas TPU kernels for the compatibility join (paper Definitions 7/8).

The join predicate between a partial-match row ``a`` and a candidate row
``b`` is a conjunction over a *static* spec:

  * vertex slot pairs: equality where both slots hold the same query
    vertex, inequality everywhere else (isomorphism injectivity);
  * edge slot pairs: strict timestamp order where ≺ relates the edges;
  * optional window-span predicate (sliding-window liveness at the time
    of the combined match's last edge).

TPU mapping
-----------
This is VPU (vector-unit) integer work, not MXU work: the arithmetic
intensity comes from the CA×CB blow-up, while the inputs are narrow
int32 tables.  The kernels tile the [CA, CB] pair space into (TA, TB)
VMEM blocks and perform all slot-pair compares in registers, so HBM
traffic is O(CA·K + CB·K + outputs) bytes instead of the O(CA·CB·K) a
naive broadcast materializes.

Operand layout
--------------
Each side's three column groups (vertex bindings, edge timestamps,
validity) travel as ONE packed, transposed int32 operand, so table rows
sit on lanes and the HBM array is dense (a ``[C, K]`` table with a
narrow ``K`` would be padded to 128 lanes, 16-32× its bytes):

  * ``a``: ``[S?, KA, CA]`` with ``KA = nva + nea + 1``, block
    ``(KA, TA)``; the kernel transposes it to ``[TA, KA]`` so that
    column ``k`` is a ``[TA, 1]`` value (A rows on sublanes).
  * ``b``: ``[S?, KB, CB]`` with ``KB = nvb + neb + 1``, block
    ``(KB, TB)``; row ``k`` is a ``[1, TB]`` value (B rows on lanes).

The last row of each operand is the validity flag.  ``KA``/``KB`` equal
the full array dim and TA/TB are either 256 or the whole padded axis,
which is what the TPU's (8, 128) block rule asks.  A leading slot axis,
when present, is a squeezed block dim; an operand shared by every slot
(the slot tick's stream-edge side, a shared prefix view) stays 2-D and
its index_map ignores the slot coordinate, so it is read once rather
than broadcast S× through HBM.

Both kernels run one 3-D grid ``(slot, A-tile, B-tile)``; an unbatched
call is the ``S = 1`` case.  ``window`` is a per-slot scalar-prefetch
input, so per-slot runtime windows never recompile.

  * ``compat_mask_kernel``       -> int8 ``[S, CA, CB]`` mask.
  * ``compat_join_pairs_kernel`` -> fused mask + on-chip pair
    extraction: compacted ``(a_idx, b_idx)`` pairs plus the total match
    count, with no [CA, CB] mask written to HBM.  An SMEM cursor carries
    the output position across the (sequential) grid steps of a slot;
    each tile emits its matches with a dynamic-trip ``fori_loop`` that
    takes the first set element (min over a linear iota) and writes it
    with a one-row vector read-modify-write into the ``[R, 128]`` output
    block (the TPU has no scalar stores to VMEM).  Pairs are emitted in
    tile order: the same pair set as mask + nonzero, the exact
    ``n_dropped``, and an unspecified keep-subset on overflow.

Tiling rules
------------
``choose_tiles(ca, cb)`` rounds CA up to the int32 sublane (8) and CB up
to the lane width (128), both capped at 256.  The int8 mask output
needs TA to be a multiple of 32 (its native sublane packing), which
``mask_tile_a`` adds on top.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Upper bounds for the adaptive tiles: (8, 128) is the int32 VREG tile
# on TPU; 256×256 keeps the live blocks well under 1 MB of VMEM while
# amortizing grid overhead on large tables.
TILE_A = 256
TILE_B = 256

_SUBLANE = 8   # int32 second-to-last dim granularity
_LANE = 128    # last dim granularity
_SUBLANE_I8 = 32  # int8 second-to-last dim granularity (mask output)


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def choose_tiles(ca: int, cb: int) -> tuple[int, int]:
    """Adaptive (TILE_A, TILE_B) from the actual table shapes.

    Rounds to hardware granularity ((8, 128) for int32) and caps at
    (TILE_A, TILE_B) so small deltas aren't padded up to a full
    256×256 tile.
    """
    ta = min(TILE_A, _ceil_to(max(ca, 1), _SUBLANE))
    tb = min(TILE_B, _ceil_to(max(cb, 1), _LANE))
    return ta, tb


def mask_tile_a(ta: int) -> int:
    """TA for the int8 mask output: a multiple of the int8 sublane."""
    return _ceil_to(ta, _SUBLANE_I8)


def out_rows(max_new: int) -> int:
    """Rows of the ``[R, 128]`` pair-output block holding ``max_new``."""
    return _ceil_to(max(max_new, 1), _LANE) // _LANE


def _tile_mask(a, b, w, *, rel, trel, widths):
    """The join predicate over one (TA, TB) tile, on register values.

    ``a`` is the packed A block transposed to ``[TA, KA]``, ``b`` the
    packed ``[KB, TB]`` B block.  ``rel``/``trel`` are static nested tuples ->
    the loops fully unroll.  ``w`` is a traced scalar (window span) or
    None (no window predicate).
    """
    nva, nea, nvb, neb = widths

    def col(k):                                  # [TA, 1]
        return a[:, k:k + 1]

    def row(k):                                  # [1, TB]
        return b[k:k + 1, :]

    m = (col(nva + nea) > 0) & (row(nvb + neb) > 0)
    for i in range(nva):
        for j in range(nvb):
            if rel[i][j]:
                m = m & (col(i) == row(j))
            else:
                m = m & (col(i) != row(j))

    for i in range(nea):
        for j in range(neb):
            if trel[i][j] == -1:
                m = m & (col(nva + i) < row(nvb + j))
            elif trel[i][j] == 1:
                m = m & (col(nva + i) > row(nvb + j))

    if w is not None:
        min_a = max_a = col(nva)
        for i in range(1, nea):
            min_a = jnp.minimum(min_a, col(nva + i))
            max_a = jnp.maximum(max_a, col(nva + i))
        min_b = max_b = row(nvb)
        for j in range(1, neb):
            min_b = jnp.minimum(min_b, row(nvb + j))
            max_b = jnp.maximum(max_b, row(nvb + j))
        span = jnp.maximum(max_a, max_b) - jnp.minimum(min_a, min_b)
        m = m & (span < w)
    return m


def _grid_spec(n_slots, ca, cb, ka, kb, tile_a, tile_b, a_batched,
               b_batched, out_specs, scratch_shapes=()):
    """3-D ``(slot, A-tile, B-tile)`` grid with the window prefetched.

    A per-slot operand has a squeezed leading block dim indexed by the
    slot coordinate; a shared one keeps its 2-D block and ignores it.
    """
    if a_batched:
        a_spec = pl.BlockSpec((None, ka, tile_a), lambda s, i, j, w: (s, 0, i))
    else:
        a_spec = pl.BlockSpec((ka, tile_a), lambda s, i, j, w: (0, i))
    if b_batched:
        b_spec = pl.BlockSpec((None, kb, tile_b), lambda s, i, j, w: (s, 0, j))
    else:
        b_spec = pl.BlockSpec((kb, tile_b), lambda s, i, j, w: (0, j))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_slots, ca // tile_a, cb // tile_b),
        in_specs=[a_spec, b_spec],
        out_specs=out_specs,
        scratch_shapes=list(scratch_shapes),
    )


# --------------------------------------------------------------------- #
# Compatibility mask kernel (int8 [S, CA, CB] output).
# --------------------------------------------------------------------- #
def _mask_body(w_ref, a_ref, b_ref, out_ref, *, rel, trel, widths,
               has_window):
    w = w_ref[pl.program_id(0)] if has_window else None
    m = _tile_mask(a_ref[...].T, b_ref[...], w, rel=rel, trel=trel,
                   widths=widths)
    out_ref[...] = jnp.where(m, 1, 0).astype(jnp.int8)


def compat_mask_kernel(
    window,                 # int32 [S] (scalar prefetch; dummy if !has_window)
    a,                      # int32 [S, KA, CA] or shared [KA, CA]
    b,                      # int32 [S, KB, CB] or shared [KB, CB]
    *,
    rel: tuple,             # static: nested tuples bool
    trel: tuple,            # static: nested tuples int
    widths: tuple,          # static: (nva, nea, nvb, neb)
    has_window: bool,
    tile_a: int,            # multiple of 32 (int8 output)
    tile_b: int,
    n_slots: int,
    a_batched: bool,
    b_batched: bool,
    interpret: bool = False,
):
    """Tiled mask; CA/CB must be multiples of tile_a/tile_b."""
    ka, ca = a.shape[-2:]
    kb, cb = b.shape[-2:]
    body = functools.partial(_mask_body, rel=rel, trel=trel, widths=widths,
                             has_window=has_window)
    grid_spec = _grid_spec(
        n_slots, ca, cb, ka, kb, tile_a, tile_b, a_batched, b_batched,
        out_specs=pl.BlockSpec((None, tile_a, tile_b),
                               lambda s, i, j, w: (s, i, j)))
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, ca, cb), jnp.int8),
        interpret=interpret,
        name="compat_mask",
    )(window, a, b)


# --------------------------------------------------------------------- #
# Fused mask + on-chip pair extraction kernel.
# --------------------------------------------------------------------- #
def _pairs_body(w_ref, a_ref, b_ref, a_out, b_out, n_out, cnt_ref, *,
                rel, trel, widths, has_window, tile_a, tile_b, max_new):
    s, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_i, n_j = pl.num_programs(1), pl.num_programs(2)

    # Grid steps are sequential; (i, j) == (0, 0) is each slot's first
    # visit — reset the running cursor and the (revisited) output block.
    @pl.when((i == 0) & (j == 0))
    def _init():
        cnt_ref[0] = 0
        a_out[...] = jnp.full(a_out.shape, -1, jnp.int32)
        b_out[...] = jnp.full(b_out.shape, -1, jnp.int32)

    w = w_ref[s] if has_window else None
    m = _tile_mask(a_ref[...].T, b_ref[...], w, rel=rel, trel=trel,
                   widths=widths)
    n_tile = jnp.sum(m.astype(jnp.int32))
    base = cnt_ref[0]

    # Emit this tile's matches at out[base:base+n_emit] by repeatedly
    # taking the first set element (min over a linear iota) and clearing
    # it.  Trip count is the tile's match count (sparse joins: usually
    # 0), clipped to the remaining output capacity.
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile_a, tile_b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile_a, tile_b), 1)
    sentinel = jnp.int32(tile_a * tile_b)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)
    n_emit = jnp.minimum(n_tile, jnp.maximum(max_new - base, 0))

    def put(ref, r, lane, value):
        cur = ref[pl.ds(r, 1), :]
        ref[pl.ds(r, 1), :] = jnp.where(lanes == lane, value, cur)

    def emit(k, lin):
        first = jnp.min(lin)
        r = jax.lax.div(first, jnp.int32(tile_b))
        c = first - r * tile_b
        p = base + k
        out_r = jax.lax.div(p, jnp.int32(_LANE))
        lane = p - out_r * _LANE
        put(a_out, out_r, lane, i * tile_a + r)
        put(b_out, out_r, lane, j * tile_b + c)
        return jnp.where(lin == first, sentinel, lin)

    jax.lax.fori_loop(0, n_emit, emit,
                      jnp.where(m, rows * tile_b + cols, sentinel))
    cnt_ref[0] = base + n_tile          # count ALL matches (overflow stat)

    @pl.when((i == n_i - 1) & (j == n_j - 1))
    def _fin():
        n_out[...] = jnp.full(n_out.shape, cnt_ref[0], jnp.int32)


def compat_join_pairs_kernel(
    window,                 # int32 [S] (scalar prefetch)
    a,                      # int32 [S, KA, CA] or shared [KA, CA]
    b,                      # int32 [S, KB, CB] or shared [KB, CB]
    *,
    rel: tuple,
    trel: tuple,
    widths: tuple,
    has_window: bool,
    tile_a: int,
    tile_b: int,
    max_new: int,
    n_slots: int,
    a_batched: bool,
    b_batched: bool,
    interpret: bool = False,
):
    """Fused join + compaction: returns ``(a_idx [S, R, 128], b_idx
    [S, R, 128], n_total [S, 1, 128])``; the first ``max_new`` entries
    of each flattened index row are the pairs, -1 filled."""
    ka, ca = a.shape[-2:]
    kb, cb = b.shape[-2:]
    r = out_rows(max_new)
    body = functools.partial(
        _pairs_body, rel=rel, trel=trel, widths=widths,
        has_window=has_window, tile_a=tile_a, tile_b=tile_b,
        max_new=max_new)
    idx_spec = pl.BlockSpec((None, r, _LANE), lambda s, i, j, w: (s, 0, 0))
    grid_spec = _grid_spec(
        n_slots, ca, cb, ka, kb, tile_a, tile_b, a_batched, b_batched,
        out_specs=[
            idx_spec, idx_spec,
            pl.BlockSpec((None, 1, _LANE), lambda s, i, j, w: (s, 0, 0)),
        ],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)])
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_slots, r, _LANE), jnp.int32),
            jax.ShapeDtypeStruct((n_slots, r, _LANE), jnp.int32),
            jax.ShapeDtypeStruct((n_slots, 1, _LANE), jnp.int32),
        ],
        interpret=interpret,
        name="compat_join_pairs",
    )(window, a, b)
