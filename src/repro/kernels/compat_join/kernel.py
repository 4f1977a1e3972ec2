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
validity) travel as ONE packed int32 operand, cut into tiles along the
table rows, with rows on lanes inside a tile, so the HBM array is dense
(a ``[C, K]`` table with a narrow ``K`` would be padded to 128 lanes,
16-32× its bytes):

  * ``a``: ``[S?, nA, KA, TA]`` with ``KA = nva + nea + 1``; a tile
    ``[KA, TA]`` is transposed in the kernel to ``[TA, KA]`` so that
    column ``k`` is a ``[TA, 1]`` value (A rows on sublanes).
  * ``b``: ``[S?, nB, KB, TB]`` with ``KB = nvb + neb + 1``; row ``k``
    of a tile is a ``[1, TB]`` value (B rows on lanes).

The last row of each tile is the validity flag.  A block holds whole
tiles, so its last two dims equal the array's, which is what the TPU's
(8, 128) block rule asks, and a tile is picked by a leading index.  A
leading slot axis, when present, is a squeezed block dim; an operand
shared by every slot (the slot tick's stream-edge side, a shared prefix
view) has no slot axis and its index_map ignores the slot coordinate,
so it is read once rather than broadcast S× through HBM.

Both kernels run a 3-D grid ``(slot, A-block, B-block)``; an unbatched
call is the ``S = 1`` case.  ``window`` is a per-slot scalar-prefetch
input, so per-slot runtime windows never recompile.

  * ``compat_mask_kernel``       -> int8 ``[S, CA, CB]`` mask, one
    (A-tile, B-tile) pair per grid step.
  * ``compat_join_pairs_kernel`` -> fused mask + on-chip pair
    extraction: compacted ``(a_idx, b_idx)`` pairs plus the total match
    count, with no [CA, CB] mask written to HBM.  It sweeps only the
    tiles below each side's live *extent*: ``ext_a``/``ext_b`` (scalar
    prefetch, per slot) count the tiles up to and including the last
    one holding a valid row.  Tables fill from their lowest free rows
    and deltas are compacted to the front, so the extent follows the
    live rows, not the capacity; holes inside it are still swept.
    When a side's whole padded axis fits ``BLOCK_BYTES`` of VMEM it is
    one block and its grid dim is 1, and the body walks its tiles with
    a dynamic-trip ``fori_loop`` up to the extent (A tiles outside,
    B tiles inside).  A side too large for that keeps one tile per
    block on the grid, with the index_map clamped to the last live
    block (no DMA) and the body skipping the step; a gridded B forces a
    gridded A, so pairs are visited A-tile major either way.  An SMEM
    cursor carries the output position across the tiles and grid steps
    of a slot; each tile emits its matches with a dynamic-trip
    ``fori_loop`` that takes the first set element (min over a linear
    iota) and writes it with a one-row vector read-modify-write into
    the ``[R, 128]`` output block (the TPU has no scalar stores to
    VMEM).  Pairs are emitted in tile order, A-tile major; a skipped
    tile holds no live row on one side and so no match: the output is
    the same pair sequence as the full sweep's, the exact
    ``n_dropped``, and the same keep-subset on overflow.

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
# on TPU; a 256×256 tile keeps the mask kernel's blocks well under 1 MB
# of VMEM while amortizing per-tile overhead on large tables.
TILE_A = 256
TILE_B = 256

_SUBLANE = 8   # int32 second-to-last dim granularity
_LANE = 128    # last dim granularity
_SUBLANE_I8 = 32  # int8 second-to-last dim granularity (mask output)

# VMEM a pairs-kernel side may take as one whole-axis block (double
# buffered: twice this per side).  A 16,384-row table of K <= 16 packed
# rows is 1 MiB.
BLOCK_BYTES = 2 * 1024 * 1024


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


def sweep_blocks(a_shape, b_shape) -> tuple[int, int]:
    """Tiles per pairs-kernel block of the packed sides ``[S?, n, K,
    tile]``: a side that fits ``BLOCK_BYTES`` of VMEM is one whole-axis
    block, whose tiles the body walks; else one tile per block, on the
    grid.  A gridded B grids A too, so tiles are visited A-tile major."""
    def fits(n, k, tile):
        return _ceil_to(k, _SUBLANE) * tile * n * 4 <= BLOCK_BYTES

    if not fits(*b_shape[-3:]):
        return 1, 1
    return (a_shape[-3] if fits(*a_shape[-3:]) else 1), b_shape[-3]


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


def _side_spec(k, tile, n_blk, batched, block_index):
    """BlockSpec of a packed ``[S?, n_tiles, K, tile]`` side, ``n_blk``
    tiles per block.  ``block_index(s, i, j, *prefetch)`` is the block's
    position on the tile axis.  A per-slot operand has a squeezed slot
    dim indexed by the slot coordinate; a shared one ignores it."""
    if batched:
        return pl.BlockSpec((None, n_blk, k, tile),
                            lambda s, i, j, *p: (s, block_index(s, i, j, *p),
                                                 0, 0))
    return pl.BlockSpec((n_blk, k, tile),
                        lambda s, i, j, *p: (block_index(s, i, j, *p), 0, 0))


# --------------------------------------------------------------------- #
# Compatibility mask kernel (int8 [S, CA, CB] output).
# --------------------------------------------------------------------- #
def _mask_body(w_ref, a_ref, b_ref, out_ref, *, rel, trel, widths,
               has_window):
    w = w_ref[pl.program_id(0)] if has_window else None
    m = _tile_mask(a_ref[0].T, b_ref[0], w, rel=rel, trel=trel,
                   widths=widths)
    out_ref[...] = jnp.where(m, 1, 0).astype(jnp.int8)


def compat_mask_kernel(
    window,                 # int32 [S] (scalar prefetch; dummy if !has_window)
    a,                      # int32 [S, nA, KA, TA] or shared [nA, KA, TA]
    b,                      # int32 [S, nB, KB, TB] or shared [nB, KB, TB]
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
    """Tiled mask, one (A-tile, B-tile) pair per grid step."""
    n_a, ka = a.shape[-3:-1]
    n_b, kb = b.shape[-3:-1]
    body = functools.partial(_mask_body, rel=rel, trel=trel, widths=widths,
                             has_window=has_window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_slots, n_a, n_b),
        in_specs=[
            _side_spec(ka, tile_a, 1, a_batched, lambda s, i, j, w: i),
            _side_spec(kb, tile_b, 1, b_batched, lambda s, i, j, w: j),
        ],
        out_specs=pl.BlockSpec((None, tile_a, tile_b),
                               lambda s, i, j, w: (s, i, j)))
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_slots, n_a * tile_a, n_b * tile_b), jnp.int8),
        interpret=interpret,
        name="compat_mask",
    )(window, a, b)


# --------------------------------------------------------------------- #
# Fused mask + on-chip pair extraction kernel.
# --------------------------------------------------------------------- #
def _pairs_body(w_ref, ea_ref, eb_ref, a_ref, b_ref, a_out, b_out, n_out,
                cnt_ref, *, rel, trel, widths, has_window, tile_a, tile_b,
                blk_a, blk_b, max_new):
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
    rows = jax.lax.broadcasted_iota(jnp.int32, (tile_a, tile_b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (tile_a, tile_b), 1)
    sentinel = jnp.int32(tile_a * tile_b)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, _LANE), 1)

    def put(ref, r, lane, value):
        cur = ref[pl.ds(r, 1), :]
        ref[pl.ds(r, 1), :] = jnp.where(lanes == lane, value, cur)

    # This step's tiles below the extents: [a_lo, a_hi) x [b_lo, b_hi),
    # empty for a gridded step past a side's extent.
    a_lo, b_lo = i * blk_a, j * blk_b
    a_hi = jnp.minimum(a_lo + blk_a, ea_ref[s])
    b_hi = jnp.minimum(b_lo + blk_b, eb_ref[s])

    def a_tile(ti, carry):
        a = a_ref[ti - a_lo].T

        def b_tile(tj, carry):
            m = _tile_mask(a, b_ref[tj - b_lo], w, rel=rel, trel=trel,
                           widths=widths)
            n_tile = jnp.sum(m.astype(jnp.int32))
            base = cnt_ref[0]

            # Emit this tile's matches at out[base:base+n_emit] by
            # repeatedly taking the first set element (min over a linear
            # iota) and clearing it.  Trip count is the tile's match
            # count (sparse joins: usually 0), clipped to the remaining
            # output capacity.
            n_emit = jnp.minimum(n_tile, jnp.maximum(max_new - base, 0))

            def emit(k, lin):
                first = jnp.min(lin)
                r = jax.lax.div(first, jnp.int32(tile_b))
                c = first - r * tile_b
                p = base + k
                out_r = jax.lax.div(p, jnp.int32(_LANE))
                lane = p - out_r * _LANE
                put(a_out, out_r, lane, ti * tile_a + r)
                put(b_out, out_r, lane, tj * tile_b + c)
                return jnp.where(lin == first, sentinel, lin)

            jax.lax.fori_loop(0, n_emit, emit,
                              jnp.where(m, rows * tile_b + cols, sentinel))
            cnt_ref[0] = base + n_tile     # count ALL matches (overflow)
            return carry

        return jax.lax.fori_loop(b_lo, b_hi, b_tile, carry)

    jax.lax.fori_loop(a_lo, a_hi, a_tile, 0)

    @pl.when((i == n_i - 1) & (j == n_j - 1))
    def _fin():
        n_out[...] = jnp.full(n_out.shape, cnt_ref[0], jnp.int32)


def _live_block(blk):
    """index_map of a pairs-kernel side: grid position ``g`` clamped to
    the last block holding a live tile, so a step past the extent keeps
    the block already in VMEM and no DMA is issued."""
    def index(s, g, ext_ref):
        last = jnp.maximum((ext_ref[s] + blk - 1) // blk - 1, 0)
        return jnp.minimum(g, last)
    return index


def compat_join_pairs_kernel(
    window,                 # int32 [S] (scalar prefetch)
    ext_a,                  # int32 [S]: live tiles of A (scalar prefetch)
    ext_b,                  # int32 [S]: live tiles of B (scalar prefetch)
    a,                      # int32 [S, nA, KA, TA] or shared [nA, KA, TA]
    b,                      # int32 [S, nB, KB, TB] or shared [nB, KB, TB]
    *,
    rel: tuple,
    trel: tuple,
    widths: tuple,
    has_window: bool,
    tile_a: int,
    tile_b: int,
    blk_a: int,             # A tiles per block (``sweep_blocks``)
    blk_b: int,             # B tiles per block; < nB forces blk_a = 1
    max_new: int,
    n_slots: int,
    a_batched: bool,
    b_batched: bool,
    interpret: bool = False,
):
    """Fused join + compaction over the tiles below each side's extent:
    returns ``(a_idx [S, R, 128], b_idx [S, R, 128], n_total [S, 1,
    128])``; the first ``max_new`` entries of each flattened index row
    are the pairs, -1 filled."""
    n_a, ka = a.shape[-3:-1]
    n_b, kb = b.shape[-3:-1]
    r = out_rows(max_new)
    body = functools.partial(
        _pairs_body, rel=rel, trel=trel, widths=widths,
        has_window=has_window, tile_a=tile_a, tile_b=tile_b,
        blk_a=blk_a, blk_b=blk_b, max_new=max_new)
    at_a, at_b = _live_block(blk_a), _live_block(blk_b)
    idx_spec = pl.BlockSpec((None, r, _LANE), lambda s, i, j, *p: (s, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_slots, n_a // blk_a, n_b // blk_b),
        in_specs=[
            _side_spec(ka, tile_a, blk_a, a_batched,
                       lambda s, i, j, w, ea, eb: at_a(s, i, ea)),
            _side_spec(kb, tile_b, blk_b, b_batched,
                       lambda s, i, j, w, ea, eb: at_b(s, j, eb)),
        ],
        out_specs=[
            idx_spec, idx_spec,
            pl.BlockSpec((None, 1, _LANE), lambda s, i, j, *p: (s, 0, 0)),
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
    )(window, ext_a, ext_b, a, b)
